"""Mean time one swap walk spends in its multi-member family branch (flood
fill and family gains): the ``family_s`` counter of ``swap.walk`` spans
inside the window, in s."""


def read(view):
    d = [s["attrs"]["family_s"] for s in view.spans("swap.walk")]
    return sum(d) / len(d) if d else None
