"""Maintenance path of a partitioned store: back-to-back one-iteration
TAPER invocations with the extroversion field sharded over the cell's
chips.

The window and the check are ``invoke.py``'s (this driver subclasses its
``Driver``); set-up differs.  The configuration's ``field`` block picks the
field: ``backend``, ``shards`` (a mesh over the first that many devices),
``shard_map_source`` and ``halo_exchange``.  The shard map is dealt along
the start partition before the warm-up field, which evaluates a shifted
copy of the start, so that the shards follow the start and not the copy.

A traced run also reduces the profiler trace of the window (``run.py``
has stopped the profiler before ``finish``): the device seconds of the
halo exchange's collectives (``all-reduce`` and ``collective-permute``
ops, async start and done included) on the first device that ran the
field program, and the number of field calls there, go into
``Run.counters``.  The field's shard shapes go into ``Run.field_shapes``
for the per-chip cost count (``bench/sharded_field_cost.py``).
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path

from common import log, run_graph, workload_of

_HERE = Path(__file__).resolve().parent
_spec = importlib.util.spec_from_file_location("driver_invoke_base",
                                               _HERE / "invoke.py")
invoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(invoke)

#: where ``run.py`` writes the window's profiler trace
TRACE_DIR = _HERE.parents[1] / ".bench_trace"
#: kinds of XLA operation that make up the halo exchange
HALO_OPS = ("all-reduce", "collective-permute")


_OPCODE = re.compile(r"(?:^|[\s}])([a-z][a-z0-9-]*)\(")


def op_kind(event_name: str) -> str:
    """What an ``XLA Ops`` event is: for an event named by its instruction's
    text (``%psum.3 = f32[16,8]{1,0} all-reduce(...)``) the HLO opcode
    after the result shape (an instruction's name may be the JAX op's, as
    ``psum``), else the instruction name itself; underscores read as
    hyphens."""
    head, eq, rest = event_name.partition(" = ")
    if eq:
        m = _OPCODE.search(rest)
        if m:
            return m.group(1)
    name = head.lstrip("%_").split(" ")[0].split("___")[0]
    return name.replace("_", "-")


def halo_counters(events) -> dict:
    """``halo_collective_s``, ``halo_ops`` and ``field_calls`` of the
    window: on the first device plane that ran the field program, the
    summed device seconds of its halo collectives and the number of field
    program executions.  Empty when the trace holds no such plane."""
    import trace_reduce as tr

    win = tr.host_span(events, "bench.window")
    if win is None:
        return {}
    for plane in tr.device_planes(events):
        calls = sum(1 for p, line, name, t, d in events
                    if p == plane and line == tr.MODULES_LINE
                    and "field_fn" in name
                    and t >= win[0] and t + d <= win[1])
        if calls:
            break
    else:
        return {}
    halo = [d for p, line, name, t, d in events
            if p == plane and line == tr.OPS_LINE
            and t >= win[0] and t + d <= win[1]
            and any(k in op_kind(name) for k in HALO_OPS)]
    return {"halo_collective_s": sum(halo) * 1e-9, "halo_ops": len(halo),
            "field_calls": calls}


def shard_shapes(sp) -> list:
    """Per shard of a ``ShardedVMPacking``: its real edges, its owned
    vertices and the halo rows it reads."""
    return [{"edges": int((sp.slot_raw[s] >= 0).sum()),
             "vertices": int((sp.vlabels[s] >= 0).sum()),
             "halo_rows": int(sp.fr_reads[s, : sp.n_frontier].sum())}
            for s in range(sp.n_shards)]


class Driver(invoke.Driver):
    def setup(self) -> None:
        from repro.core.rpq import parse_rpq
        from repro.core.taper import Taper, TaperConfig
        from repro.graphs.graph import LabelledGraph
        from repro.launch.mesh import make_smoke_mesh

        # bound before the graph is made: a program whose Taper cannot deal
        # the shards along a given partition cannot run this cell
        seed_shard_order = Taper.seed_shard_order
        ctx, cfg = self.ctx, self.ctx.cfg
        fcfg = cfg["field"]
        self.labels, self.edges, self.part0 = run_graph(cfg, ctx.seed, ctx.n)
        n = self.labels.shape[0]
        g = LabelledGraph.from_undirected_edges(
            n, self.labels, self.edges, cfg["graph"]["labels"])
        self.tcfg = TaperConfig(field_backend=fcfg["backend"],
                                shard_map_source=fcfg["shard_map_source"],
                                halo_exchange=fcfg["halo_exchange"])
        self.taper = Taper(g, cfg["k"], self.tcfg)
        self.taper._pre["_mesh"] = make_smoke_mesh(int(fcfg["shards"]))
        seed_shard_order(self.taper, self.part0)
        self.tracer = None
        if ctx.trace:
            from repro.obs.trace import Tracer

            self.tracer = Tracer(capacity=1 << 16)
            self.taper.tracer = self.tracer
            self.taper.trace_ctx = self.tracer.new_trace(force=True)
        self.workload = workload_of(cfg)
        trie = self.taper.build_trie(
            [(parse_rpq(q), f) for q, f, _ in self.workload])
        self.arrays = trie.compile(g.label_names)
        self.per_start = int(ctx.traffic.get("iterations_per_start",
                                             self.tcfg.max_iterations))
        warm = (self.part0 if self.per_start > 1
                else (self.part0 + 1) % cfg["k"])
        with ctx.annotate("bench.warmup_field"):
            self.taper.field(warm, self.arrays)
        g.reverse_edge_index
        hs = self.taper._pre["_halo_stats"]
        log(f"graph n={g.n} m={g.m} trie_nodes={self.arrays.n_nodes} "
            f"max_depth={self.arrays.max_depth} k={cfg['k']}; field "
            f"{fcfg['backend']} over {hs['n_devices']} devices, "
            f"{hs['n_shards']} shards, halo {hs['halo_bytes_per_depth']} "
            f"bytes a depth step ({hs['halo_ratio']:.4f} of the full field)")

    def finish(self):
        shards = shard_shapes(self.taper._pre["_shard_dev"]["sp"])
        run = super().finish()
        run.field_shapes["shards"] = shards
        if self.ctx.trace:
            import trace_reduce as tr

            try:
                events = tr.load_events(str(TRACE_DIR))
            except FileNotFoundError:
                events = []
            run.counters.update(halo_counters(events))
            kinds = sorted({op_kind(name) for p, line, name, _, _ in events
                            if p.startswith(tr.DEVICE_PREFIX)
                            and line == tr.OPS_LINE
                            and any(c in op_kind(name) for c in (
                                "all-", "collective", "reduce-scatter"))})
            log(f"halo collectives: {run.counters}; collective ops in the "
                f"trace: {kinds[:40]}")
        return run
