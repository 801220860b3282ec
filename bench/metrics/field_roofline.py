"""The field program's share of its roofline: the least time the chip
could take for one evaluation (``bench/field_cost.py`` at the cell's
shapes over ``bench/peaks.json``; the bound that applies is in the result's
``notes``) over the mean device time of one execution, in %."""


def read(view):
    t = view.trace["field_module_s"]
    least = view.trace.get("field_least")
    if not t or least is None:
        return None
    return 100.0 * least["seconds"] / (sum(t) / len(t))
