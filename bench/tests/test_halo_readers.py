"""The halo exchange's readers, the sharded field's roofline reader and
its per-chip cost count, on hand-built views, synthetic traces and a
small packing."""
import pytest

import run as harness
from common import Run
from conftest import BENCH
from field_cost import field_cost, least_time
from sharded_field_cost import sharded_field_cost

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py",
                               "metric_" + name)


def driver_module():
    return harness.load_module(BENCH / "drivers" / "invoke_sharded.py",
                               "driver_invoke_sharded")


def span(name, t0, t1, **attrs):
    return {"name": name, "t0": t0, "t1": t1, "duration_s": t1 - t0,
            "attrs": attrs}


def view(spans=(), counters=None, trace=None, shapes=None, peak=None):
    run = Run(spans=list(spans), counters=dict(counters or {}),
              field_shapes=shapes,
              notes={"window_monotonic": [10.0, 100.0]})
    return harness.View(run, trace or {}, peak)


def test_halo_mb_per_call_reads_the_field_spans():
    spans = [span("invocation.field", 11.0, 12.0, halo_bytes=2_000_000,
                  halo_bytes_per_depth=500_000, depth_steps=4),
             span("invocation.field", 20.0, 21.0, halo_bytes=4_000_000,
                  halo_bytes_per_depth=1_000_000, depth_steps=4),
             # outside the window: never read
             span("invocation.field", 1.0, 2.0, halo_bytes=9e12)]
    assert reader("halo_mb_per_call").read(view(spans)) == pytest.approx(3.0)


def test_halo_mb_per_call_reads_nothing_without_a_sharded_field():
    spans = [span("invocation.field", 11.0, 12.0, backend="jnp"),
             span("field.fetch", 11.5, 12.0, bytes=10)]
    assert reader("halo_mb_per_call").read(view(spans)) is None


def test_halo_exchange_ms_reads_the_counters():
    c = {"halo_collective_s": 0.12, "halo_ops": 30, "field_calls": 3}
    assert reader("halo_exchange_ms").read(view(counters=c)) == \
        pytest.approx(40.0)


@pytest.mark.parametrize("counters", [
    {},                                                    # jnp field, CPU
    {"halo_collective_s": 0.0, "halo_ops": 0, "field_calls": 2},
    {"halo_collective_s": 0.0, "halo_ops": 0, "field_calls": 0},
])
def test_halo_exchange_ms_reads_nothing_without_collectives(counters):
    assert reader("halo_exchange_ms").read(view(counters=counters)) is None


def _events():
    """A window [100, 1000) ns; device 0 runs two field programs with a
    halo collective each, and an all-gather that is not the halo's."""
    dev0, dev1 = "/device:TPU:0", "/device:TPU:1"
    return [
        ("/host:CPU", "python", "bench.window", 100.0, 900.0),
        (dev0, "XLA Modules", "jit_sharded_field_fn(123)", 150.0, 300.0),
        (dev0, "XLA Modules", "jit_sharded_field_fn(123)", 600.0, 300.0),
        (dev0, "XLA Modules", "jit_other(1)", 950.0, 10.0),
        (dev0, "XLA Ops", "%collective-permute-start.1 = f32[16,8] "
         "collective-permute-start(f32[16,8] %fusion.2)", 160.0, 5.0),
        (dev0, "XLA Ops", "collective-permute-done.1", 170.0, 20.0),
        (dev0, "XLA Ops", "%psum.3 = f32[16,8]{1,0:T(8,128)} all-reduce("
         "%fusion.1), channel_id=1", 610.0, 7.0),
        (dev0, "XLA Ops", "all-gather.4", 700.0, 50.0),
        (dev0, "XLA Ops", "%fusion.9 = f32[8]{0} fusion(%psum.3)",
         720.0, 40.0),
        # outside the window
        (dev0, "XLA Ops", "all-reduce.5", 20.0, 5.0),
        (dev1, "XLA Modules", "jit_sharded_field_fn(123)", 150.0, 300.0),
        (dev1, "XLA Ops", "collective-permute-done.1", 170.0, 90.0),
    ]


def test_halo_counters_sum_the_halo_collectives_of_one_device():
    c = driver_module().halo_counters(_events())
    assert c["field_calls"] == 2
    assert c["halo_ops"] == 3
    assert c["halo_collective_s"] == pytest.approx(32e-9)


def test_halo_counters_of_a_trace_without_the_field_program():
    events = [e for e in _events() if "field_fn" not in e[2]]
    assert driver_module().halo_counters(events) == {}
    assert driver_module().halo_counters([]) == {}


def test_op_kind_reads_the_opcode_or_the_instruction_name():
    kind = driver_module().op_kind
    # as the chip's trace names them: the instruction's text, whose name
    # may be the JAX op's
    assert kind("%psum.28 = f32[16,8]{1,0:T(8,128)} all-reduce("
                "%multiply_bitcast_fusion.4), channel_id=1") == "all-reduce"
    assert kind("%collective-permute-start.2 = (f32[16,9]{1,0:T(8,128)S(1)}, "
                "f32[16,9]{1,0}, u32[]{:S(2)}) collective-permute-start("
                "%fusion.7)") == "collective-permute-start"
    assert kind("%fusion.9 = f32[8]{0:T(1024)} fusion(%psum.3), "
                "kind=kCustom") == "fusion"
    # a bare instruction name, plain or with its characters replaced
    assert kind("collective-permute-done.1") == "collective-permute-done.1"
    assert kind("_collective_permute_start.2___f32_16") == \
        "collective-permute-start.2"


SHAPES = {"n": 1000, "m": 6000, "k": 4, "n_labels": 3, "n_nodes": 6,
          "depth_nodes": [1, 2, 2, 1],
          "shards": [{"edges": 1500, "vertices": 250, "halo_rows": 400},
                     {"edges": 1600, "vertices": 250, "halo_rows": 100}]}


def test_sharded_cost_at_a_hand_count():
    c = sharded_field_cost(SHAPES)
    replicated = 1000 + 2 * 6 + 3 * 36 + 3
    # halo rows read the parent-depth columns of depths 1 and 2 (2 + 2)
    first = (7 * 1500 + 250 + replicated + 400 * 4
             + 250 * 6 + 3 * 250 + 1500 + 250 * 4)
    second = (7 * 1600 + 250 + replicated + 100 * 4
              + 250 * 6 + 3 * 250 + 1600 + 250 * 4)
    assert c["bytes"] == 4 * max(first, second)
    assert c["flops"] == 6 * 1600 * 3


def test_sharded_least_time_is_at_most_one_chips():
    """On a real packing of a small MusicBrainz graph, dealt along its
    hash partition into four shards, one chip's least time for its part
    is no more than one chip's for the whole field."""
    from repro.core.rpq import parse_rpq
    from repro.core.tpstry import TPSTry
    from repro.graphs.generators import musicbrainz_like
    from repro.graphs.partition import hash_partition
    from repro.graphs.sharded_packing import partition_shard_order

    g = musicbrainz_like(6000, seed=3)
    arrays = TPSTry.from_workload(
        [(parse_rpq("Area.Artist.(Artist|Label).Area"), 0.2),
         (parse_rpq("Artist.Credit.(Track|Recording).Credit.Artist"), 0.3),
         (parse_rpq("Artist.Credit.Track.Medium"), 0.5)]
    ).compile(g.label_names)
    part = hash_partition(g.n, 8, seed=1)
    sp = g.vm_packing_sharded(4, order=partition_shard_order(part, 4),
                              order_token="partition:0")
    shapes = {"n": g.n, "m": g.m, "k": 8, "n_labels": g.n_labels,
              "n_nodes": arrays.n_nodes,
              "depth_nodes": [int((arrays.depth == d).sum())
                              for d in range(arrays.max_depth + 1)],
              "shards": driver_module().shard_shapes(sp)}
    assert sum(s["edges"] for s in shapes["shards"]) == g.m
    assert sum(s["vertices"] for s in shapes["shards"]) == g.n
    assert all(s["halo_rows"] > 0 for s in shapes["shards"])
    one = least_time(field_cost(shapes), PEAK)["seconds"]
    per_chip = least_time(sharded_field_cost(shapes), PEAK)["seconds"]
    assert per_chip <= one


def test_sharded_roofline_reader():
    t = {"field_module_s": [0.5, 1.5]}
    least = least_time(sharded_field_cost(SHAPES), PEAK)["seconds"]
    v = view(trace=t, shapes=SHAPES, peak=PEAK)
    assert reader("sharded_field_roofline").read(v) == \
        pytest.approx(100.0 * least / 1.0)
    # a run of the one-chip field carries no shard shapes
    one_chip = {k: v for k, v in SHAPES.items() if k != "shards"}
    assert reader("sharded_field_roofline").read(
        view(trace=t, shapes=one_chip, peak=PEAK)) is None
    assert reader("sharded_field_roofline").read(
        view(trace={"field_module_s": []}, shapes=SHAPES, peak=PEAK)) is None
