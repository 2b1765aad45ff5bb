"""The mean, over all the measured windows, of the time from the end of
one stage of a window to the end of another, by the harness's own clock
(milliseconds); see ``span_gap`` for which windows count and where one
ends."""

from .span_gap import gaps_ms


def read(ctx, start: str, end: list[str]):
    vals = gaps_ms(ctx, start, end)
    return sum(vals) / len(vals) if vals else None
