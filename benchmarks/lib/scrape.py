"""Read what the agent serves over HTTP: ``/metrics`` (Prometheus text),
``/healthz`` and ``/debug/windows`` (the flight recorder's ring)."""

from __future__ import annotations

import json
import re
import urllib.error
import urllib.request

_SAMPLE = re.compile(r"^([a-zA-Z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def get(port: int, path: str, timeout: float = 10.0) -> bytes | None:
    """The body, whatever the status (``/healthz`` answers 503 while
    degraded); None while nothing listens."""
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/{path}",
                                    timeout=timeout) as r:
            return r.read()
    except urllib.error.HTTPError as e:
        return e.read()
    except (urllib.error.URLError, OSError):
        return None


def get_json(port: int, path: str) -> dict | None:
    body = get(port, path)
    if body is None:
        return None
    try:
        return json.loads(body)
    except ValueError:
        return None


class Metrics:
    """One ``/metrics`` scrape: [(name, labels, value)]."""

    def __init__(self, text: str):
        self.samples: list[tuple[str, dict, float]] = []
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            m = _SAMPLE.match(line)
            if m is None:
                continue
            try:
                value = float(m.group(3))
            except ValueError:
                continue
            self.samples.append(
                (m.group(1), dict(_LABEL.findall(m.group(2) or "")), value))

    def total(self, name: str, **labels) -> float:
        """Sum over the samples of ``name`` whose labels include
        ``labels``; 0.0 when there is none."""
        return sum(v for n, lab, v in self.samples if n == name
                   and all(lab.get(k) == w for k, w in labels.items()))

    def labels_of(self, name: str) -> dict | None:
        for n, lab, _v in self.samples:
            if n == name:
                return lab
        return None


def scrape_metrics(port: int) -> Metrics | None:
    body = get(port, "metrics")
    return None if body is None else Metrics(body.decode("utf-8", "replace"))


def window_rows(windows: dict) -> list[dict]:
    """``/debug/windows`` -> one row per trace, by seq: completeness,
    path, error, and every span's [start, end) in seconds from the
    trace's own start (a stage that ran twice keeps its last span)."""
    rows = []
    for t in windows.get("traces", []):
        spans = {}
        for s in t.get("spans", []):
            spans[s["stage"]] = (s["start_s"], s["start_s"] + s["duration_s"],
                                 s["duration_s"])
        meta = t.get("meta", {})
        rows.append({
            "seq": t["seq"], "complete": bool(t.get("complete")),
            "path": meta.get("path"), "samples": meta.get("samples"),
            "time_ns": meta.get("time_ns"),
            "error": t.get("error") or meta.get("iteration_error"),
            "lost": bool(meta.get("window_lost")), "spans": spans})
    rows.sort(key=lambda r: r["seq"])
    return rows
