"""Benchmark runner: one module per paper figure/table.

Prints ``name,us_per_call,derived`` CSV (plus roofline/dry-run summaries if
artifacts exist).  Scale via REPRO_BENCH_N (default 20000 vertices).

``--json PATH`` additionally writes the full report machine-readable —
every row with its structured ``metrics`` dict (speedups, halo ratios,
throughputs) plus the run's scale/device context — so successive PRs leave
a comparable ``BENCH_*.json`` perf trajectory in the repo.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import traceback

from benchmarks.common import Report
from repro.launch.compile_cache import enable_compile_cache

CORE = [
    "fig7_convergence",
    "fig8_approaches",
    "fig9_queries",
    "fig10_drift",
    "fig11_online",
    "online_topology",
    "swap_scale",
    # multi-device field scaling; under run.py it inherits whatever device
    # count jax already initialised (run standalone for the 8-way mesh)
    "field_shard",
    # batched frontier enumeration vs the DFS oracle + multi-worker serving
    # scaling (pure numpy/threads; speedup gated at N>=20000, scaling gated
    # standalone on >=4-core hosts)
    "query_enum",
    # async serving loop: overlap win vs stop-the-world + warm dirty shards
    # (same device-count caveat as field_shard)
    "serve_loop",
    # crash-safe serving: snapshot cost, WAL replay catch-up, degraded floor
    "recovery",
    # replicated cluster: follower catch-up replay, fenced failover to
    # first answer, read throughput with one crashed replica
    "cluster_failover",
    # observability overhead: traced vs untraced serving throughput
    # (<=5% gated standalone), trace_sample_rate=0 ~free
    "obs_overhead",
    # closed-loop overload protection: flash-crowd brownout shedding
    # defends the hot-class SLO, goodput floor + hysteretic recovery
    "overload",
]

# integration benchmarks: skipped (by name) only when a genuinely optional
# third-party dependency is missing — an ImportError raised *inside* repro/
# benchmark code is a real bug and propagates
INTEGRATION = ["gnn_halo", "dlrm_span", "expert_placement"]

_FIRST_PARTY_PREFIXES = ("repro", "benchmarks")


def load_modules():
    modules = [(name, importlib.import_module(f"benchmarks.{name}"))
               for name in CORE]
    for name in INTEGRATION:
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
        except ImportError as e:
            missing = getattr(e, "name", None) or ""
            top = missing.split(".")[0]
            if top and top not in _FIRST_PARTY_PREFIXES:
                print(f"SKIP {name}: optional dependency {missing!r} "
                      "not installed", file=sys.stderr)
                continue
            raise  # ImportError from our own transitive code: surface it
        modules.append((name, mod))
    return modules


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the report (rows + per-row metrics "
                         "dicts + run context) as JSON to PATH")
    args = ap.parse_args(argv)
    enable_compile_cache()

    report = Report()
    failures = 0
    ran = []
    for name, mod in load_modules():
        try:
            mod.run(report)
            ran.append(name)
        except Exception:
            failures += 1
            print(f"BENCHMARK {name} FAILED:", file=sys.stderr)
            traceback.print_exc()
    report.emit()
    if args.json:
        doc = report.to_json()
        doc["modules"] = ran
        doc["failures"] = failures
        if "jax" in sys.modules:
            doc["devices"] = len(sys.modules["jax"].devices())
        with open(args.json, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=False)
            fh.write("\n")
        print(f"wrote {args.json}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
