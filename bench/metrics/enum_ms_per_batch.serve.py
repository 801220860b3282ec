"""Mean wall time of one micro-batch's enumeration (drain to reply): the
durations of ``request.batch`` spans in the window, in ms."""


def read(view):
    d = [s["duration_s"] for s in view.spans("request.batch")]
    return 1e3 * sum(d) / len(d) if d else None
