import os
import sys

if "jax" not in sys.modules and "host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    # force the shard devices BEFORE jax's first init (it locks the device
    # count); standalone runs get an 8-way host mesh, run.py invocations
    # (jax already initialised by an earlier benchmark) keep what exists
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ.get("REPRO_FIELD_SHARD_DEVICES", "8")
        + " " + os.environ.get("XLA_FLAGS", "")).strip()

__doc__ = """Sharded multi-device extroversion field: scaling + halo traffic.

Acceptance benchmark for ``extroversion_field(backend="pallas_sharded")``:
on an 8-way (forced host device) mesh the sharded backend's warm
per-invocation field time is reported against the single-device ``jnp``
backend (not asserted), and the PR-5 claim: dealing shards along the live
TAPER partition vector (``shard_map_source="partition"``) with the two-tier
sliced halo exchange must cut the bytes moved per depth step by **>= 2x**
against the PR-3 baseline (id-striped shard map + psum'd union frontier,
halo ratio 0.876), at numerical parity with the jnp oracle on *both*
exchange backends.

Reported rows:

* ``field_shard/single_device_warm`` / ``field_shard/sharded_warm`` — warm
  per-invocation wall time of each backend (same graph, same trie), the
  sharded row on the PR-3 stripe+psum configuration;
* ``field_shard/speedup`` — single/sharded ratio on this host;
* ``field_shard/halo_exchange`` — per-shard bytes per depth step of the
  stripe+psum baseline vs a full-field exchange;
* ``field_shard/halo_sliced`` — the same graph under the partition shard
  map + sliced (hot union + ring pair slices) exchange: bytes per depth,
  the reduction factor vs the baseline (asserted >= 2x on an 8-way mesh,
  and partition-map halo ratio <= 0.5x the stripe baseline's — the CI
  bench-smoke gate), and the warm field time of the re-dealt layout;
* ``field_shard/patched_reinvoke`` — field time right after a *localized*
  mutation batch against the permuted packing, with how many of the S
  shards were re-uploaded (the delta-aware shard patching at work; a
  scratch re-pack would re-upload all of them).

Scale via ``REPRO_BENCH_N`` (default 50000) and
``REPRO_FIELD_SHARD_DEVICES`` (default 8; only effective standalone).
"""

import time
from typing import Optional

import numpy as np

from benchmarks.common import Report, workload_for
from repro.core.taper import Taper, TaperConfig
from repro.core.tpstry import TPSTry
from repro.core.visitor import extroversion_field
from repro.graphs.generators import musicbrainz_like
from repro.graphs.graph import MutationBatch
from repro.graphs.partition import metis_like_partition

BENCH_N = int(os.environ.get("REPRO_BENCH_N", "50000"))
K = 8
REPEATS = 3


def _time_invocations(fn, repeats=REPEATS) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(report: Optional[Report] = None, n: int = BENCH_N, k: int = K) -> Report:
    import jax

    report = report or Report()
    n_dev = len(jax.devices())
    g = musicbrainz_like(n, avg_degree=6.0, seed=13)
    w = workload_for("musicbrainz")
    arrays = TPSTry.from_workload(w).compile(g.label_names)
    # the live-TAPER scenario: a metis-like seed enhanced by a short
    # invocation — this is both the partition the field is evaluated on and
    # the vector the "partition" shard map deals vertices by
    part = metis_like_partition(g, k, seed=0)
    part = Taper(g, k, TaperConfig(max_iterations=2, seed=0)).invoke(
        part, w).final_part
    depths = max(arrays.max_depth - 1, 1)  # DP steps per invocation

    # -- single-device jnp baseline (also the parity oracle) ---------------
    pre_single = {}
    t0 = time.perf_counter()
    fld_ref = extroversion_field(g, arrays, part, k, _precomputed=pre_single,
                                 backend="jnp")
    t_single_cold = time.perf_counter() - t0
    t_single = _time_invocations(lambda: extroversion_field(
        g, arrays, part, k, _precomputed=pre_single, backend="jnp"))
    report.add("field_shard/single_device_warm", t_single,
               f"n={g.n} m={g.m} trie_N={arrays.n_nodes} "
               f"per_depth={1e3 * t_single / depths:.2f}ms",
               metrics={"n": g.n, "m": g.m, "trie_nodes": arrays.n_nodes})

    # -- sharded backend, PR-3 baseline configuration (stripe + psum) -------
    pre_shard = {}
    t0 = time.perf_counter()
    fld_sh = extroversion_field(g, arrays, part, k, _precomputed=pre_shard,
                                backend="pallas_sharded",
                                halo_exchange="psum")
    t_shard_cold = time.perf_counter() - t0
    t_shard = _time_invocations(lambda: extroversion_field(
        g, arrays, part, k, _precomputed=pre_shard,
        backend="pallas_sharded", halo_exchange="psum"))
    sp = g.vm_packing_sharded(n_dev)
    report.add("field_shard/sharded_warm", t_shard,
               f"devices={n_dev} shards={sp.n_shards} "
               f"per_depth={1e3 * t_shard / depths:.2f}ms "
               f"cold={t_shard_cold:.2f}s_vs_{t_single_cold:.2f}s",
               metrics={"devices": n_dev, "warm_s": t_shard,
                        "cold_s": t_shard_cold})

    speedup = t_single / max(t_shard, 1e-12)
    report.add("field_shard/speedup", t_single - t_shard,
               f"{speedup:.2f}x_single_over_sharded devices={n_dev} "
               f"target>=2x_at_8dev", metrics={"speedup": speedup})

    # -- parity guard (the speedup must be of the same answer) --------------
    err = float(np.abs(fld_ref.extroversion - fld_sh.extroversion).max())
    assert err < 1e-4, f"sharded field diverged from jnp oracle: {err}"

    # -- PR-3 baseline halo traffic vs full-field exchange ------------------
    halo_base = sp.halo_bytes_per_depth(arrays.n_nodes, exchange="psum")
    full = sp.full_field_bytes_per_depth(g.n, arrays.n_nodes)
    ratio_base = halo_base / full
    assert halo_base < full, "halo exchange must beat a full-field exchange"
    report.add("field_shard/halo_exchange", 0.0,
               f"halo_bytes={halo_base} full_field_bytes={full} "
               f"ratio={ratio_base:.3f} frontier={sp.n_frontier}/{g.n}",
               metrics={"halo_bytes_per_depth": halo_base,
                        "full_field_bytes_per_depth": full,
                        "halo_ratio": ratio_base,
                        "shard_map_source": "stripe",
                        "halo_exchange": "psum"})

    # -- PR-5: partition shard map + sliced exchange ------------------------
    pre_sliced = {}
    fld_sl = extroversion_field(g, arrays, part, k, _precomputed=pre_sliced,
                                backend="pallas_sharded",
                                shard_map_source="partition",
                                halo_exchange="sliced")
    err = float(np.abs(fld_ref.extroversion - fld_sl.extroversion).max())
    assert err < 1e-4, f"sliced-exchange field diverged from oracle: {err}"
    t_sliced = _time_invocations(lambda: extroversion_field(
        g, arrays, part, k, _precomputed=pre_sliced,
        backend="pallas_sharded", shard_map_source="partition",
        halo_exchange="sliced"))
    hs = pre_sliced["_halo_stats"]
    halo_sl, ratio_sl = hs["halo_bytes_per_depth"], hs["halo_ratio"]
    reduction = halo_base / max(halo_sl, 1)
    report.add("field_shard/halo_sliced", t_sliced,
               f"halo_bytes={halo_sl} ratio={ratio_sl:.3f} "
               f"reduction={reduction:.2f}x_vs_psum_union_baseline "
               f"hot_rows={hs['hot_rows']} sliced_rows={hs['sliced_rows']} "
               f"per_depth={1e3 * t_sliced / depths:.2f}ms target>=2x",
               metrics={"halo_bytes_per_depth": halo_sl,
                        "halo_ratio": ratio_sl,
                        "reduction_vs_baseline": reduction,
                        "shard_map_source": "partition",
                        "halo_exchange": "sliced",
                        "warm_s": t_sliced})
    if n_dev >= 8:
        # the PR-5 acceptance claim + the CI bench-smoke gate
        assert reduction >= 2.0, (
            f"partition shard map + sliced exchange must cut halo bytes per "
            f"depth >= 2x vs the psum'd-union baseline, got {reduction:.2f}x")
        assert ratio_sl <= 0.5 * ratio_base, (
            f"partition-map halo ratio {ratio_sl:.3f} must be <= 0.5x the "
            f"stripe baseline's {ratio_base:.3f}")

    # -- delta-aware shard patching on the permuted packing ----------------
    # a mutation localized to one shard's vertex range: the cached packing
    # is patched (dirty shards only), never re-packed from scratch
    token, order = pre_sliced["_shard_order"]
    sp_p = g.vm_packing_sharded(n_dev, order=order, order_token=token)
    owners = sp_p.owner_of(np.arange(g.n))
    shard0 = np.nonzero(owners == 0)[0]
    rng = np.random.default_rng(0)
    ends = shard0[rng.integers(0, shard0.size, (8, 2))]
    g.apply_mutations(MutationBatch(add_edges=ends))
    t0 = time.perf_counter()
    extroversion_field(g, arrays, part, k, _precomputed=pre_sliced,
                       backend="pallas_sharded",
                       shard_map_source="partition", halo_exchange="sliced")
    t_patched = time.perf_counter() - t0
    ups = pre_sliced["_shard_uploads"]
    report.add("field_shard/patched_reinvoke", t_patched,
               f"dirty_shards_uploaded={ups['last_shards']}/{sp_p.n_shards} "
               f"scratch_rebuilds={ups['rebuilds']}",
               metrics={"dirty_shards_uploaded": ups["last_shards"],
                        "n_shards": sp_p.n_shards,
                        "scratch_rebuilds": ups["rebuilds"]})
    return report


if __name__ == "__main__":
    run().emit()
