"""Mean wall time of one field evaluation as the host sees it (upload of
the partition, the device call, the fetch of its outputs):
``invocation.field`` spans inside the window, in s."""


def read(view):
    d = [s["duration_s"] for s in view.spans("invocation.field")]
    return sum(d) / len(d) if d else None
