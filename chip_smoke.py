#!/usr/bin/env python3
"""Bring-up smoke run of the served TAPER path on a TPU.

    python chip_smoke.py             # one chip: field phase, serving phase
    python chip_smoke.py --chips 4   # four chips: the sharded field only

Everything runs in this one process; no child process is started.  The
graph is ProvGen at the paper's size: ``provgen_like(1_000_000,
avg_degree=6.0, seed=11)`` (``benchmarks/common.py``), hash-partitioned
into k = 8, under the PQ1-4 workload with its paper frequencies.  Nothing
is cut in width: labels, trie, k and balance are the paper's.

* field phase — ``extroversion_field`` with ``backend="jnp"`` (the
  one-chip program) against a float64 numpy transcription of the same
  recurrence, on the same graph and partition;
* serving phase — a ``ServingLoop`` with the default jnp field serves a few
  hundred sampled PQ requests until one overlapped TAPER invocation has
  committed; then health, ipt against the hash baseline, and served answers
  against the DFS oracle ``QueryExecutor.enumerate_paths_ref``;
* ``--chips 4`` — ``backend="pallas_sharded"`` over a 4-device
  ``make_smoke_mesh()`` with the ``"stripe"`` and ``"partition"`` shard
  maps and the sliced halo exchange, against the one-chip jnp field.

Any failed check or exception exits non-zero.  The last line of standard
output is ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": ...}}`` and is printed only when every phase passed on a TPU.
Times printed here are smoke timings of one run, not benchmark numbers.

``--rehearse N`` runs the same phases at N vertices on whatever backend
JAX has (the CPU, kernels interpreted) to rehearse the control flow; it
never prints the result line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
N_VERTICES = 1_000_000
K = 8
#: largest relative error (per trie column for alpha, normwise over vertices
#: otherwise) a float32 field may show against its reference
TOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"  check ok: {what}")


class CompileClock:
    """Seconds JAX spends in backend compiles (persistent-cache reads
    included) and the number of persistent-cache hits, since creation."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.seconds, self.hits


def rel_err(x, ref) -> float:
    """Largest error relative to the reference's magnitude: per column for
    a 2-D array, normwise for a vector or a scalar."""
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    if ref.ndim == 0:
        return float(abs(x - ref) / max(abs(ref), 1e-30))
    scale = np.maximum(np.abs(ref).max(axis=0), 1e-30)
    return float((np.abs(x - ref).max(axis=0) / scale).max())


def field_errors(fld, alpha, extroversion, total) -> dict:
    return {
        "alpha": rel_err(fld.alpha, alpha),
        "extroversion": rel_err(fld.extroversion, extroversion),
        "total_extroversion": rel_err(fld.total_extroversion, total),
    }


def reference_field(g, arrays, part):
    """Float64 numpy transcription of the extroversion-field recurrence
    (``repro.core.visitor`` module docstring), independent of JAX."""
    src = g.src.astype(np.int64)
    dst = g.dst.astype(np.int64)
    inv_cnt = 1.0 / np.maximum(g.neighbor_label_counts().astype(np.float64), 1.0)
    lab_vcount = g.label_counts()
    local = part[src] == part[dst]
    dst_lab = g.labels[dst]
    depth = arrays.depth.astype(np.int64)
    p = arrays.p.astype(np.float64)
    cond_p = arrays.cond_p.astype(np.float64)
    max_depth = int(arrays.max_depth)
    alpha = np.zeros((g.n, arrays.n_nodes))
    for i in np.nonzero(depth == 1)[0]:
        li = int(arrays.label[i])
        alpha[g.labels == li, i] = p[i] / max(int(lab_vcount[li]), 1)
    mass = np.zeros(g.m)
    steps = [c for c in np.argsort(depth, kind="stable")
             if 2 <= depth[c] <= max_depth]
    for c in steps:
        par, lc = int(arrays.parent[c]), int(arrays.label[c])
        contrib = np.where(dst_lab == lc,
                           alpha[src, par] * cond_p[c] * inv_cnt[src, lc], 0.0)
        mass += contrib
        alpha[:, c] += np.bincount(dst, weights=contrib * local, minlength=g.n)
    counted = [i for i in range(arrays.n_nodes)
               if 1 <= depth[i] < max_depth and not arrays.is_leaf[i]]
    pr = alpha[:, counted].sum(axis=1)
    extro_mass = np.bincount(src, weights=mass * ~local, minlength=g.n)
    extroversion = np.where(pr > 1e-30, extro_mass / np.maximum(pr, 1e-30), 0.0)
    return alpha, extroversion, float(extro_mass.sum())


def timed_field(clock, label, call):
    """First call (compile + run) and warm call of one field evaluation.
    The result is fetched to host numpy, which waits for the device."""
    c0, h0 = clock.mark()
    t0 = time.perf_counter()
    call()
    first = time.perf_counter() - t0
    c1, h1 = clock.mark()
    t0 = time.perf_counter()
    fld = call()
    warm = time.perf_counter() - t0
    log(f"  smoke timing {label}: backend compile {c1 - c0:.2f}s "
        f"(persistent-cache hits {h1 - h0}), first call {first:.2f}s, "
        f"warm call {warm:.3f}s (result fetched to host)")
    return fld


def field_phase(clock, g, arrays, part) -> None:
    from repro.core.visitor import extroversion_field

    log("field phase: jnp field vs float64 numpy reference")
    t0 = time.perf_counter()
    ref = reference_field(g, arrays, part)
    log(f"  reference field on host: {time.perf_counter() - t0:.1f}s, "
        f"total_extroversion={ref[2]:.9g}")
    pre = {}
    fld = timed_field(
        clock, "field[jnp]",
        lambda: extroversion_field(g, arrays, part, K, _precomputed=pre,
                                   backend="jnp"))
    errs = field_errors(fld, *ref)
    log("  field[jnp] largest relative error vs reference: "
        + ", ".join(f"{k}={v:.3e}" for k, v in errs.items()))
    check(max(errs.values()) <= TOL,
          f"field[jnp] within {TOL:g} of the float64 reference")


def serving_phase(g, part0, workload, seed) -> None:
    from repro.core.taper import TaperConfig
    from repro.serve.loop import ServeLoopConfig, ServingLoop
    from repro.workload.executor import QueryExecutor

    log("serving phase: ServingLoop, default jnp field, overlapped invocation")
    # one invocation: its first fit fires once 64 requests show the mix;
    # no second one may commit while the post-commit answers are checked
    cfg = ServeLoopConfig(first_invocation_after=64,
                          min_requests_between_invocations=10 ** 9)
    rng = np.random.default_rng(seed)
    queries = [q for q, _ in workload]
    freqs = np.asarray([f for _, f in workload], np.float64)
    loop = ServingLoop(g, K, part0.copy(), TaperConfig(), config=cfg)
    t_start = time.perf_counter()
    loop.start()

    def wave(count):
        picks = rng.choice(len(queries), size=count, p=freqs / freqs.sum())
        tickets = [loop.submit(queries[i]) for i in picks]
        check(all(t.accepted for t in tickets), f"{count} requests admitted")
        for t in tickets:
            if not t.wait(timeout=300.0):
                raise SmokeFailure("a request was not served within 300s")
        return list(zip(picks.tolist(), tickets))

    wave(128)
    deadline = time.perf_counter() + 900.0
    while loop.stats()["invocations"] < 1:
        if time.perf_counter() > deadline:
            raise SmokeFailure("no invocation committed within 900s")
        time.sleep(0.5)
    log(f"  smoke timing: first invocation committed "
        f"{time.perf_counter() - t_start:.1f}s after start")
    after = wave(128)
    stats = loop.stop()
    committed = loop.part
    log("  stats: " + ", ".join(
        f"{k}={stats[k]}" for k in (
            "completed", "invocations", "invocation_failures", "healthy",
            "degraded", "backend_fallbacks", "field_backend",
            "latency_p50_s", "latency_p99_s", "ipt_per_request")))
    check(stats["healthy"] == 1, "healthy == 1")
    check(stats["degraded"] == 0, "degraded == 0")
    check(stats["backend_fallbacks"] == 0, "backend_fallbacks == 0")
    check(stats["field_backend"] == "jnp", 'field_backend == "jnp"')
    check(stats["invocation_error"] == "" and stats["worker_error"] == "",
          "no invocation or worker error")
    check(stats["invocations"] >= 1, "at least one committed invocation")

    ex = QueryExecutor(g)
    ipt_hash = float(np.mean([ex.ipt(q, part0) for q in queries]))
    ipt_taper = float(np.mean([ex.ipt(q, committed) for q in queries]))
    log(f"  mean ipt per query over PQ1-4: hash {ipt_hash:.1f}, "
        f"committed {ipt_taper:.1f} ({1 - ipt_taper / ipt_hash:.1%} lower)")
    check(ipt_taper < ipt_hash, "committed ipt below the hash baseline")

    # one served answer per distinct query, plus a few repeats, all served
    # on the committed partition, against the DFS oracle
    sample, seen = [], set()
    for qi, ticket in after:
        if qi not in seen or len(sample) < 8:
            sample.append((qi, ticket))
            seen.add(qi)
    for qi, ticket in sample:
        paths, ipt = ex.enumerate_paths_ref(
            queries[qi], max_results=cfg.max_results_per_query, part=committed)
        if ticket.paths != paths or ticket.ipt != ipt:
            raise SmokeFailure(f"served answer for PQ{qi + 1} differs from "
                               "enumerate_paths_ref")
    check(True, f"{len(sample)} served answers equal enumerate_paths_ref")


def sharded_phase(clock, g, arrays, part, n_chips) -> None:
    import jax

    from repro.core.visitor import extroversion_field
    from repro.launch.mesh import make_smoke_mesh

    log(f"sharded phase: pallas_sharded over {n_chips} devices vs one-chip jnp")
    one = jax.devices()[0]
    with jax.default_device(one):
        base = timed_field(
            clock, "field[jnp, one chip]",
            lambda: extroversion_field(g, arrays, part, K, _precomputed={},
                                       backend="jnp"))
    mesh = make_smoke_mesh(n_chips)
    for source in ("stripe", "partition"):
        pre = {"_mesh": mesh}
        fld = timed_field(
            clock, f"field[pallas_sharded, {source}]",
            lambda: extroversion_field(
                g, arrays, part, K, _precomputed=pre,
                backend="pallas_sharded", shard_map_source=source,
                halo_exchange="sliced"))
        hs = pre["_halo_stats"]
        log(f"  {source}: shards={hs['n_shards']} devices={hs['n_devices']} "
            f"halo bytes/depth={hs['halo_bytes_per_depth']} "
            f"halo ratio={hs['halo_ratio']:.4f}")
        check(hs["n_devices"] == n_chips,
              f"sharded output spans {n_chips} devices ({source})")
        errs = field_errors(fld, base.alpha, base.extroversion,
                            base.total_extroversion)
        log(f"  pallas_sharded[{source}] largest relative error vs jnp: "
            + ", ".join(f"{k}={v:.3e}" for k, v in errs.items()))
        check(max(errs.values()) <= TOL,
              f"pallas_sharded[{source}] within {TOL:g} of the jnp field")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded field on four devices")
    ap.add_argument("--rehearse", type=int, default=None, metavar="N",
                    help="rehearse at N vertices on any backend; never "
                         "prints the result line")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    d0 = devs[0]
    log(f"devices: platform={d0.platform} device_kind={d0.device_kind} "
        f"count={len(devs)} {devs}")
    if d0.platform != "tpu" and args.rehearse is None:
        print(f"no TPU: JAX found platform {d0.platform!r}; this smoke run "
              "needs the chip", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock(jax)

    from benchmarks.common import provgen_workload
    from repro.core.taper import Taper
    from repro.graphs.generators import provgen_like
    from repro.graphs.partition import hash_partition

    n = args.rehearse or N_VERTICES
    t0 = time.perf_counter()
    g = provgen_like(n, avg_degree=6.0, seed=11)
    part = hash_partition(g.n, K, seed=1)
    workload = provgen_workload()
    arrays = Taper(g, K).build_trie(workload).compile(g.label_names)
    log(f"data: provgen n={g.n} m={g.m} labels={g.n_labels} k={K} "
        f"trie_nodes={arrays.n_nodes} max_depth={arrays.max_depth} "
        f"built in {time.perf_counter() - t0:.1f}s")

    t_run = time.perf_counter()
    if args.chips == 4:
        sharded_phase(clock, g, arrays, part, args.chips)
    else:
        field_phase(clock, g, arrays, part)
        serving_phase(g, part, workload, seed=11)
    log(f"smoke timing: phases took {time.perf_counter() - t_run:.1f}s, "
        f"backend compile {clock.seconds:.1f}s in all, "
        f"persistent-cache hits {clock.hits}")

    if args.rehearse is not None:
        log(f"rehearsal at n={n} on {d0.platform} passed; no result line")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
