"""A percentile, over the measured windows, of one span's self time in
``/debug/windows``: its duration less the durations of its ``children``
(the stages the program records inside it), milliseconds. A child the
window did not run takes nothing away."""

from .span_gap import percentile


def read(ctx, stage: str, children: list[str], q: float):
    vals = [1e3 * (r["spans"][stage][2]
                   - sum(r["spans"][c][2] for c in children
                         if c in r["spans"]))
            for r in ctx.rows if r["complete"] and stage in r["spans"]]
    return percentile(vals, q)
