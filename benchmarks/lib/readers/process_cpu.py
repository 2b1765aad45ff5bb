"""User+system CPU milliseconds of the agent's process (``os.times()`` at
both ends of the measured window) per window closed in it."""


def read(ctx):
    if not ctx.windows_closed or "t1" not in ctx.cpu:
        return None
    return 1e3 * (ctx.cpu["t1"] - ctx.cpu["t0"]) / ctx.windows_closed
