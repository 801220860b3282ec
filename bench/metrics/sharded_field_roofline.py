"""The sharded field program's share of its roofline: the least time one
chip could take for its part of an evaluation (``bench/sharded_field_cost.py``
at the run's shard shapes over ``bench/peaks.json``) over the mean device
time of one execution of the program (``XLA Modules`` events named
``*field_fn*``), in %."""
from field_cost import least_time
from sharded_field_cost import sharded_field_cost


def read(view):
    t = view.trace["field_module_s"]
    shapes = view.run.field_shapes
    if not t or view.peak is None or not shapes or "shards" not in shapes:
        return None
    least = least_time(sharded_field_cost(shapes), view.peak)
    return 100.0 * least["seconds"] / (sum(t) / len(t))
