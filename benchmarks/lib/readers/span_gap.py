"""A percentile, over all the measured windows, of the time from the end
of one stage of a window to the end of another, by the harness's own
clock (``harness.EdgeClock``: read at the moment the program records the
span), in milliseconds.

``end`` lists stages in order of preference and a window ends at the
first it has: a window that the fast encoder did not take has no
``encode`` span, and its pprof bytes exist only when its scalar ``ship``
is over. Every measured window counts. One that lacks an edge (it never
got that far) has a time nobody knows, and then the metric is not
reported rather than taken over the windows that went well."""

from statistics import quantiles


def percentile(values: list[float], q: float) -> float | None:
    if not values:
        return None
    if len(values) == 1:
        return values[0]
    cuts = quantiles(values, n=100, method="inclusive")
    return cuts[min(98, max(0, round(q) - 1))]


def gaps_ms(ctx, start: str, end: list[str]) -> list[float] | None:
    gaps = []
    for r in ctx.rows:
        last = next((r["ended"][s] for s in end if s in r["ended"]), None)
        if last is None or start not in r["ended"]:
            return None
        gaps.append(1e3 * (last - r["ended"][start]))
    return gaps


def read(ctx, start: str, end: list[str], q: float):
    return percentile(gaps_ms(ctx, start, end) or [], q)
