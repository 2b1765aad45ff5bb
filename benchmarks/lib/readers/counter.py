"""The rise of a ``/metrics`` counter over the measured window, per
window closed in it, times ``scale``. ``labels`` narrows the samples
summed. A counter that did not rise gives nothing."""


def read(ctx, name: str, labels: dict | None = None, scale: float = 1.0):
    if ctx.metrics0 is None or ctx.metrics1 is None or not ctx.windows_closed:
        return None
    rise = ctx.metrics1.total(name, **(labels or {})) \
        - ctx.metrics0.total(name, **(labels or {}))
    return scale * rise / ctx.windows_closed if rise else None
