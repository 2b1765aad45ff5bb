"""A percentile, over the measured windows, of one span's duration in
``/debug/windows`` (milliseconds)."""

from .span_gap import percentile


def read(ctx, stage: str, q: float):
    vals = [1e3 * r["spans"][stage][2] for r in ctx.rows
            if r["complete"] and stage in r["spans"]]
    return percentile(vals, q)
