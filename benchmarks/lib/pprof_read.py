"""Minimal readers for what the agent ships: the profilestore
``WriteRawRequest`` envelope and pprof ``profile.proto``.

The benchmark's own copies (the program's are ``agent/profilestore.py``
``decode_write_raw_request`` and ``pprof/builder.py`` ``parse_pprof``),
cut to what the comparison needs. Field numbers are the public schemas'.

  WriteRawRequest  { repeated RawProfileSeries series = 2; }
  RawProfileSeries { LabelSet labels = 1; repeated RawSample samples = 2; }
  LabelSet { repeated Label labels = 1; }   Label { name = 1; value = 2; }
  RawSample { bytes raw_profile = 1; }
"""

from __future__ import annotations

import dataclasses
import gzip

_MASK64 = (1 << 64) - 1


def _varint(data, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        b = data[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result & _MASK64, pos
        shift += 7
        if shift >= 70:
            raise ValueError("varint too long")


def fields(data):
    """(field number, wire type, value) over one serialised message:
    wire type 0 gives an int, 2 a bytes-like, 1 and 5 raw fixed bytes."""
    pos, n = 0, len(data)
    while pos < n:
        key, pos = _varint(data, pos)
        wt = key & 7
        if wt == 0:
            v, pos = _varint(data, pos)
        elif wt == 2:
            ln, pos = _varint(data, pos)
            if pos + ln > n:
                raise ValueError("truncated length-delimited field")
            v = data[pos:pos + ln]
            pos += ln
        elif wt in (1, 5):
            ln = 8 if wt == 1 else 4
            v = data[pos:pos + ln]
            pos += ln
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield key >> 3, wt, v


def _packed(v, acc: list) -> None:
    if isinstance(v, int):
        acc.append(v)
        return
    pos = 0
    while pos < len(v):
        x, pos = _varint(v, pos)
        acc.append(x)


def decode_write_raw(data: bytes) -> list[tuple[dict, list[bytes]]]:
    """One request -> [(labels, [gzipped pprof, ...]) per series]."""
    out = []
    for f, wt, series in fields(data):
        if f != 2 or wt != 2:
            continue
        labels: dict[str, str] = {}
        samples: list[bytes] = []
        for f2, w2, v2 in fields(series):
            if f2 == 1 and w2 == 2:
                for f3, w3, label in fields(v2):
                    if f3 != 1 or w3 != 2:
                        continue
                    name = value = ""
                    for f4, _w4, v4 in fields(label):
                        if f4 == 1:
                            name = bytes(v4).decode()
                        elif f4 == 2:
                            value = bytes(v4).decode()
                    labels[name] = value
            elif f2 == 2 and w2 == 2:
                for f3, w3, v3 in fields(v2):
                    if f3 == 1 and w3 == 2:
                        samples.append(bytes(v3))
        out.append((labels, samples))
    return out


@dataclasses.dataclass
class Profile:
    time_nanos: int
    duration_nanos: int
    period: int
    samples: list          # [(location ids leaf first, count)]
    locations: dict        # id -> (mapping id, address as written)
    mappings: dict         # id -> (start, limit, offset)

    def total(self) -> int:
        return sum(c for _ids, c in self.samples)

    def stacks_by_raw_address(self) -> dict[tuple, int]:
        """{leaf-first stack of process-space addresses: count}. A
        location's address is written normalised against its mapping
        (address - (start - offset)); adding the mapping's own base back
        recovers what was sampled, and a location without a mapping
        (kernel text) is written as sampled."""
        raw = {}
        for lid, (mid, addr) in self.locations.items():
            if mid:
                start, _limit, offset = self.mappings[mid]
                addr = (addr + start - offset) & _MASK64
            raw[lid] = addr
        out: dict[tuple, int] = {}
        for ids, c in self.samples:
            key = tuple(raw[i] for i in ids)
            out[key] = out.get(key, 0) + c
        return out


def read_profile(blob: bytes, totals_only: bool = False) -> Profile:
    """Parse one (optionally gzipped) pprof. ``totals_only`` skips the
    location and mapping tables."""
    if blob[:2] == b"\x1f\x8b":
        blob = gzip.decompress(blob)
    data = memoryview(blob)
    prof = Profile(0, 0, 0, [], {}, {})
    for f, wt, v in fields(data):
        if f == 2 and wt == 2:                      # Sample
            ids: list = []
            vals: list = []
            for f2, _w2, v2 in fields(v):
                if f2 == 1:
                    _packed(v2, ids)
                elif f2 == 2:
                    _packed(v2, vals)
            prof.samples.append((tuple(ids), vals[0] if vals else 0))
        elif f == 4 and wt == 2 and not totals_only:  # Location
            lid = mid = addr = 0
            for f2, w2, v2 in fields(v):
                if w2 != 0:
                    continue
                if f2 == 1:
                    lid = v2
                elif f2 == 2:
                    mid = v2
                elif f2 == 3:
                    addr = v2
            prof.locations[lid] = (mid, addr)
        elif f == 3 and wt == 2 and not totals_only:  # Mapping
            m = {f2: v2 for f2, w2, v2 in fields(v) if w2 == 0}
            prof.mappings[m.get(1, 0)] = (m.get(2, 0), m.get(3, 0),
                                          m.get(4, 0))
        elif wt == 0:
            if f == 9:
                prof.time_nanos = v
            elif f == 10:
                prof.duration_nanos = v
            elif f == 12:
                prof.period = v
    return prof
