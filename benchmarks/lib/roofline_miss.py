"""Bytes the miss path's device program needs, from shapes.

``jit_miss_scatter`` writes a window's newly inserted stacks into the
device's dictionary table. Bound by memory bandwidth like the feed and
the close (``roofline.py``): per inserted stack one 4-byte slot index
read, one 16-byte dictionary row read (the row it replaces) and one
16-byte row written. How many stacks a window inserts is the
deployment's ``new_stacks_per_window``; for a configuration that states
none the bytes, and so the share, are 0.
"""

from __future__ import annotations

SCATTER_BYTES_PER_STACK = 4 + 16 + 16


def miss_scatter_bytes(config: dict) -> float:
    return float(config.get("new_stacks_per_window", 0)) * SCATTER_BYTES_PER_STACK
