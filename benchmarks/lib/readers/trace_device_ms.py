"""Summed device operation time per traced window, milliseconds."""


def read(ctx):
    t = ctx.trace
    if not t or not ctx.trace_windows:
        return None
    return 1e3 * t["busy_s"] / ctx.trace_windows
