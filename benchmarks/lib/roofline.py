"""Bytes the dict window path's device programs need, from shapes.

All three programs are bound by memory bandwidth, not by arithmetic
(integer compares and adds on words just read), so a program's least
time is its bytes over the chip's peak bytes per second
(``peaks.json``), and its roofline share is that over its measured
device time (``readers/trace_roofline.py``). The bytes are what the algorithm needs per window at the
deployment's sizes, not what a given lowering moves:

  feed    per fed row: the packed key and count (three 32-bit hash words
          and one count word, 16 bytes), one 16-byte dictionary row (a
          hit at the first probe), and one 4-byte accumulator read and
          one write.
  close   per dictionary id fetched: one 4-byte accumulator read and the
          packed count written, at the narrowest packing the close has
          (4 bits).
"""

from __future__ import annotations

FEED_BYTES_PER_ROW = 16 + 16 + 4 + 4
CLOSE_BYTES_PER_ID = 4 + 0.5


def feed_bytes(config: dict) -> float:
    """Every distinct stack of a window is one fed row."""
    return float(config["stacks"]) * FEED_BYTES_PER_ROW


def close_bytes(config: dict) -> float:
    """Every stack in the dictionary is one fetched id."""
    return float(config["stacks"]) * CLOSE_BYTES_PER_ID

