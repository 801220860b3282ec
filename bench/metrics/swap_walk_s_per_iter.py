"""Mean wall time of one swap iteration's sequential offer/receive walk:
``swap.walk`` spans inside the window, in s."""


def read(view):
    d = [s["duration_s"] for s in view.spans("swap.walk")]
    return sum(d) / len(d) if d else None
