"""Mean wall time of one host swap iteration: ``invocation.swap`` spans
that lie inside the window, in s."""


def read(view):
    d = [s["duration_s"] for s in view.spans("invocation.swap")]
    return sum(d) / len(d) if d else None
