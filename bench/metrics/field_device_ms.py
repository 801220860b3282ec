"""Mean device time of one execution of the field's jitted program
(``XLA Modules`` events whose name holds ``field_fn``) in the traced
window, in ms."""


def read(view):
    t = view.trace["field_module_s"]
    return 1e3 * sum(t) / len(t) if t else None
