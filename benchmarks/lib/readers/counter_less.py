"""The rise of one ``/metrics`` counter over the measured window less the
rises of a list of others, per window closed in it, times ``scale``: a
remainder (the process's CPU less the threads that have a name). ``less``
is a list of ``{"name": ..., "labels": {...}}``; one that is absent takes
nothing off. Nothing where the first counter is missing from the scrape."""


def read(ctx, name: str, less: list, labels: dict | None = None,
         scale: float = 1.0):
    if ctx.metrics0 is None or ctx.metrics1 is None or not ctx.windows_closed:
        return None
    labels = labels or {}
    if not any(n == name and all(lab.get(k) == v for k, v in labels.items())
               for n, lab, _v in ctx.metrics1.samples):
        return None

    def rise(name, labels):
        return ctx.metrics1.total(name, **labels) \
            - ctx.metrics0.total(name, **labels)

    left = rise(name, labels) - sum(
        rise(c["name"], c.get("labels") or {}) for c in less)
    return scale * left / ctx.windows_closed
