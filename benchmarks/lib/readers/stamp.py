"""Seconds between two of the harness's own stamps (host clock)."""


def read(ctx, start: str, end: str):
    if start not in ctx.stamps or end not in ctx.stamps:
        return None
    return ctx.stamps[end] - ctx.stamps[start]
