"""Mean wall time of one field's device-to-host copy of its outputs, taken
after the device finished: ``field.fetch`` spans inside the window, in
ms."""


def read(view):
    d = [s["duration_s"] for s in view.spans("field.fetch")]
    return 1e3 * sum(d) / len(d) if d else None
