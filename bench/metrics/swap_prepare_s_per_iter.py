"""Mean wall time of one swap iteration's preparation (candidate queue,
whole-iteration precomputes, batched singleton rows and their ``.tolist()``
conversions): ``swap.prepare`` spans inside the window, in s."""


def read(view):
    d = [s["duration_s"] for s in view.spans("swap.prepare")]
    return sum(d) / len(d) if d else None
