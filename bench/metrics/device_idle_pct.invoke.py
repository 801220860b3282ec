"""Share of the traced window in which no operation ran on the device
(1 - busy share, averaged over the chips used), in %."""


def read(view):
    b = view.trace["busy"]
    if b["window_s"] <= 0 or b["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])
