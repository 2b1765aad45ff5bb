"""Roofline share of the programs whose XLA name matches ``pattern``:
the bytes a window needs, as ``bytes_fn`` of the module
``lib/<bytes_module>.py`` works them out from the configuration, over
the chip's peak bandwidth, over the programs' device time per traced
window. Percent."""

import importlib
import re


def read(ctx, pattern: str, bytes_module: str, bytes_fn: str):
    t = ctx.trace
    if not t or not ctx.trace_windows or not ctx.peaks:
        return None
    seconds = sum(p["seconds"] for name, p in t["programs"].items()
                  if re.search(pattern, name))
    if seconds <= 0:
        return None
    need = getattr(importlib.import_module(f"lib.{bytes_module}"),
                   bytes_fn)(ctx.cell.config)
    least = need / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / ctx.trace_windows)
