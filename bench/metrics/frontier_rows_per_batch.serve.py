"""Frontier rows the batched enumerator advanced per micro-batch in the
window: the ``ServeMetrics`` counters ``frontier_rows`` over ``batches``,
differenced across the window."""


def read(view):
    c = view.run.counters
    if not c.get("batches"):
        return None
    return c["frontier_rows"] / c["batches"]
