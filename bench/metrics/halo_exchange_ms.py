"""Device time of the sharded field's halo exchange per field call: the
summed durations of the field program's ``all-reduce`` and
``collective-permute`` ops (async start and done included) in the traced
window, on the first device that ran it, over the field calls there, in
ms (the ``invoke_sharded`` driver's counters)."""


def read(view):
    c = view.run.counters
    if not c.get("field_calls") or not c.get("halo_ops"):
        return None
    return 1e3 * c["halo_collective_s"] / c["field_calls"]
