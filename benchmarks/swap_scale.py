"""Swap-engine scaling: vectorised vs seed swap, per-phase invocation split.

Acceptance benchmark for the batched swap engine
(repro.core.swap): on a 50k-vertex, k=8 synthetic graph one internal
iteration's swap phase must be >= 5x faster than the seed per-vertex
implementation (repro.core.swap_ref), with bit-identical partitions.

Also reports the per-phase split of a full invocation — extroversion field
vs swap — and the resulting moves/sec, which is the number that governs how
far internal iterations scale (paper §5: iterations must stay inexpensive).

Scale via REPRO_SWAP_BENCH_N (default 50000); runs standalone or from
benchmarks/run.py.
"""
from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from benchmarks.common import Report, dataset, workload_for
from repro.core.swap import SwapConfig, swap_iteration
from repro.core.swap_ref import swap_iteration_reference
from repro.core.taper import Taper, TaperConfig
from repro.core.tpstry import TPSTry
from repro.core.visitor import extroversion_field
from repro.graphs.partition import hash_partition

BENCH_N = int(os.environ.get("REPRO_SWAP_BENCH_N", "50000"))
K = 8


def run(report: Optional[Report] = None, n: int = BENCH_N, k: int = K) -> Report:
    report = report or Report()
    g = dataset("musicbrainz", n=n)
    w = workload_for("musicbrainz")
    arrays = TPSTry.from_workload(w).compile(g.label_names)
    part = hash_partition(g.n, k, seed=1)

    # -- one-off graph caches (reverse index + kernel packing) --------------
    t0 = time.perf_counter()
    g.reverse_edge_index
    report.add("swap_scale/reverse_edge_index", time.perf_counter() - t0,
               f"m={g.m}")

    # -- field phase --------------------------------------------------------
    pre = {}
    t0 = time.perf_counter()
    fld = extroversion_field(g, arrays, part, k, _precomputed=pre)
    t_field_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    fld = extroversion_field(g, arrays, part, k, _precomputed=pre)
    t_field = time.perf_counter() - t0
    report.add("swap_scale/field_cold", t_field_cold, "jit compile + device put")
    report.add("swap_scale/field_warm", t_field, "device-resident inputs")

    # -- swap phase: vectorised vs seed ------------------------------------
    cfg = SwapConfig()
    t_new = []
    for _ in range(3):
        t0 = time.perf_counter()
        p_new, s_new = swap_iteration(g, part, fld, k, cfg,
                                      np.random.default_rng(0))
        t_new.append(time.perf_counter() - t0)
    t_new = min(t_new)
    t0 = time.perf_counter()
    p_ref, s_ref = swap_iteration_reference(g, part, fld, k, cfg,
                                            np.random.default_rng(0))
    t_ref = time.perf_counter() - t0

    identical = bool((p_new == p_ref).all()) and s_new == s_ref
    speedup = t_ref / max(t_new, 1e-9)
    report.add(
        "swap_scale/swap_vectorised", t_new,
        f"n={g.n} k={k} moves={s_new.moves} candidates={s_new.candidates} "
        f"moves_per_sec={s_new.moves / max(t_new, 1e-9):.0f}",
    )
    report.add("swap_scale/swap_seed", t_ref,
               f"moves_per_sec={s_ref.moves / max(t_ref, 1e-9):.0f}")
    report.add(
        "swap_scale/summary", t_new + t_field,
        f"speedup={speedup:.1f}x identical={identical} "
        f"field_frac={t_field / max(t_field + t_new, 1e-9):.2f} "
        f"swap_frac={t_new / max(t_field + t_new, 1e-9):.2f}",
    )

    # -- full-invocation per-phase split -----------------------------------
    taper = Taper(g, k, TaperConfig(max_iterations=3, seed=0))
    import repro.core.taper as taper_mod

    phase = {"field": 0.0, "swap": 0.0, "moves": 0}
    orig_swap = taper_mod.swap_iteration
    orig_field = taper_mod.extroversion_field

    def timed_swap(*a, **kw):
        t0 = time.perf_counter()
        out = orig_swap(*a, **kw)
        phase["swap"] += time.perf_counter() - t0
        phase["moves"] += out[1].moves
        return out

    def timed_field(*a, **kw):
        t0 = time.perf_counter()
        out = orig_field(*a, **kw)
        phase["field"] += time.perf_counter() - t0
        return out

    taper_mod.swap_iteration = timed_swap
    taper_mod.extroversion_field = timed_field
    try:
        rep = taper.invoke(part, arrays)
    finally:
        taper_mod.swap_iteration = orig_swap
        taper_mod.extroversion_field = orig_field
    total = phase["field"] + phase["swap"]
    report.add(
        "swap_scale/invoke_phases", total,
        f"iters={rep.iterations} field_s={phase['field']:.3f} "
        f"swap_s={phase['swap']:.3f} moves={phase['moves']} "
        f"moves_per_sec={phase['moves'] / max(phase['swap'], 1e-9):.0f}",
    )
    return report


if __name__ == "__main__":
    rep = run()
    rep.emit()
    summary = [r for r in rep.rows if r.name == "swap_scale/summary"][0]
    assert "identical=True" in summary.derived, summary.derived
    speedup = float(summary.derived.split("speedup=")[1].split("x")[0])
    assert speedup >= 5.0, f"swap speedup {speedup}x < 5x acceptance floor"
    print(f"\nACCEPTANCE OK: {summary.derived}")
