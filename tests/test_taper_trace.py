"""Invocation tracing inside the swap and the field's fetch: the phase spans
nest under their parents, the walk's counters agree with themselves and
with the swap's decisions, tracing changes no decision, and the program's
spans reach the profiler's host plane."""
import glob
import os
import threading

import numpy as np
import pytest

import repro.obs.trace as trace_mod
from repro.core.rpq import parse_rpq
from repro.core.swap import SwapConfig, swap_iteration
from repro.core.taper import Taper, TaperConfig
from repro.core.tpstry import TPSTry
from repro.core.visitor import extroversion_field
from repro.graphs.generators import provgen_like
from repro.graphs.partition import hash_partition
from repro.obs.trace import Tracer

PROV = ["Entity.(Entity)*.Entity", "Agent.Activity.Entity",
        "Entity.Activity.(Agent)*"]


@pytest.fixture(scope="module")
def prov():
    g = provgen_like(1500, seed=3)
    w = [(parse_rpq(q), 1.0 / len(PROV)) for q in PROV]
    return g, TPSTry.from_workload(w).compile(g.label_names)


def traced_taper(g, k, **cfg):
    taper = Taper(g, k, TaperConfig(seed=0, **cfg))
    tracer = Tracer(capacity=1 << 12)
    taper.tracer = tracer
    taper.trace_ctx = tracer.new_trace(force=True)
    return taper, tracer


def by_name(tracer, name):
    return tracer.spans(name=name)


def test_phase_spans_nest_under_their_parents(prov):
    g, arrays = prov
    taper, tracer = traced_taper(g, 4)
    rep = taper.invoke(hash_partition(g.n, 4, seed=1), arrays,
                       max_iterations=2)
    swaps = {s["span_id"]: s for s in by_name(tracer, "invocation.swap")}
    fields = {s["span_id"] for s in by_name(tracer, "invocation.field")}
    prepares = by_name(tracer, "swap.prepare")
    walks = by_name(tracer, "swap.walk")
    fetches = by_name(tracer, "field.fetch")
    assert len(swaps) == len(prepares) == len(walks) >= 1
    assert sorted(s["parent_id"] for s in prepares) == sorted(swaps)
    assert sorted(s["parent_id"] for s in walks) == sorted(swaps)
    assert len(fetches) == len(fields) == rep.iterations + 1
    assert {s["parent_id"] for s in fetches} == fields
    for w in walks:
        sw = swaps[w["parent_id"]]
        pre = next(p for p in prepares if p["parent_id"] == w["parent_id"])
        # prepare then walk, both inside the swap
        assert sw["t0"] <= pre["t0"] <= pre["t1"] <= w["t0"] <= w["t1"] \
            <= sw["t1"]
    # no instant per-depth field events any more
    assert by_name(tracer, "field.depth") == []


CASES = {
    "prov-dense": (provgen_like, PROV, 4, True, SwapConfig()),
    "prov-two-phase": (provgen_like, PROV, 4, False, SwapConfig()),
    "prov-loose-families": (provgen_like, PROV, 3, True,
                            SwapConfig(family_threshold=0.2)),
    "prov-singleton-families": (provgen_like, PROV, 3, False,
                                SwapConfig(family_max_size=1)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_walk_counters_agree(case):
    gen, queries, k, dense, cfg = CASES[case]
    g = gen(1500, seed=5)
    w = [(parse_rpq(q), 1.0 / len(queries)) for q in queries]
    arrays = TPSTry.from_workload(w).compile(g.label_names)
    part = hash_partition(g.n, k, seed=2)
    tracer = Tracer()
    root = tracer.start("invocation.swap", tracer.new_trace(force=True))
    totals = dict(singles=0, stale=0, walks=0)
    for _ in range(3):
        fld = extroversion_field(g, arrays, part, k, dense_ext_to=dense)
        new, st = swap_iteration(g, part, fld, k, cfg,
                                 np.random.default_rng(0), parent=root)
        assert st.stale_rows <= st.singles_visited
        assert st.family_walks <= st.family_members \
            <= cfg.family_max_size * st.family_walks
        # a candidate the walk neither visited as a single nor walked as a
        # family was moved, before its turn, as a member of another's family
        skipped = st.candidates - st.singles_visited - st.family_walks
        assert 0 <= skipped <= st.moves - st.accepted_offers
        if cfg.family_max_size == 1:
            assert skipped == 0
            assert st.family_members == st.family_walks
        assert (st.family_s > 0) == (st.family_walks > 0)
        assert st.moves == int((new != part).sum())
        walk = tracer.spans(name="swap.walk")[-1]["attrs"]
        assert walk == dict(singles_visited=st.singles_visited,
                            stale_rows=st.stale_rows,
                            family_walks=st.family_walks,
                            family_members=st.family_members,
                            family_s=st.family_s)
        totals["singles"] += st.singles_visited
        totals["stale"] += st.stale_rows
        totals["walks"] += st.family_walks
        part = new
    # the case exercises the paths its counters count
    assert min(totals.values()) > 0


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "two-phase"])
def test_fetch_bytes_are_the_results_nbytes(prov, dense):
    g, arrays = prov
    taper, tracer = traced_taper(g, 4, dense_ext_to=dense)
    fld = taper.field(hash_partition(g.n, 4, seed=1), arrays)
    (fetch,) = by_name(tracer, "field.fetch")
    arrs = [fld.alpha, fld.pr, fld.edge_mass, fld.extro_mass,
            fld.extroversion] + ([fld.ext_to] if dense else [])
    assert fetch["attrs"]["bytes"] == sum(a.nbytes for a in arrs)
    assert (fld.ext_to is not None) == dense


def test_tracing_changes_no_decision(prov):
    g, arrays = prov
    part0 = hash_partition(g.n, 4, seed=1)
    plain = Taper(g, 4, TaperConfig(seed=0)).invoke(part0, arrays,
                                                    max_iterations=3)
    taper, tracer = traced_taper(g, 4)
    traced = taper.invoke(part0, arrays, max_iterations=3)
    assert traced.iterations == plain.iterations >= 1
    for a, b in zip(traced.parts, plain.parts):
        assert (a == b).all()
    assert [s.moves for s in traced.stats] == [s.moves for s in plain.stats]
    assert traced.stats == plain.stats
    assert traced.objective == plain.objective
    assert by_name(tracer, "swap.walk")


def test_untraced_taper_records_nothing(prov, monkeypatch):
    g, arrays = prov
    opened = []

    class Counting(trace_mod.TraceAnnotation):
        def __enter__(self):
            opened.append(1)
            return super().__enter__()

    monkeypatch.setattr(trace_mod, "TraceAnnotation", Counting)
    real_init = trace_mod.Span.__init__

    def counting_init(self, *a, **kw):
        opened.append(1)
        real_init(self, *a, **kw)

    monkeypatch.setattr(trace_mod.Span, "__init__", counting_init)
    rep = Taper(g, 4, TaperConfig(seed=0)).invoke(
        hash_partition(g.n, 4, seed=1), arrays, max_iterations=2)
    assert rep.iterations >= 1
    assert opened == []
    # an off tracer opens no span and no annotation either
    off = Tracer(enabled=False)
    taper = Taper(g, 4, TaperConfig(seed=0))
    taper.tracer, taper.trace_ctx = off, off.new_trace(force=True)
    taper.invoke(hash_partition(g.n, 4, seed=2), arrays, max_iterations=1)
    assert opened == [] and off.spans() == []


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    g = provgen_like(400, seed=1)
    w = [(parse_rpq(q), 1.0 / len(PROV)) for q in PROV]
    arrays = TPSTry.from_workload(w).compile(g.label_names)
    taper, tracer = traced_taper(g, 3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        taper.invoke(hash_partition(g.n, 3, seed=1), arrays,
                     max_iterations=1)
        cross = tracer.start("cross.thread", taper.trace_ctx)
        th = threading.Thread(target=cross.end)
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    host = [ev for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]
    names = {ev.name for ev in host}
    want = {"invocation.field", "field.fetch", "invocation.swap",
            "swap.prepare", "swap.walk", "cross.thread"}
    assert want <= names
    # one annotation per finished span, inside it: never longer
    for name in want:
        ann = sorted(e.duration_ns * 1e-9 for e in host if e.name == name)
        own = sorted(s["duration_s"] for s in tracer.spans(name=name))
        assert len(ann) == len(own)
        assert all(a <= b for a, b in zip(ann, own))
