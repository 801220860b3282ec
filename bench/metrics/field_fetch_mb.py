"""Mean bytes one field copies from the device to the host (every output
array of the ``ExtroversionResult``): the ``bytes`` of ``field.fetch``
spans inside the window, in MB (1e6 bytes)."""


def read(view):
    b = [s["attrs"]["bytes"] for s in view.spans("field.fetch")]
    return sum(b) / len(b) / 1e6 if b else None
