"""Serving launcher: online RPQ query service with TAPER maintenance.

    PYTHONPATH=src python -m repro.launch.serve --dataset provgen --ticks 10
"""
from __future__ import annotations

import argparse

from repro.core.rpq import parse_rpq
from repro.graphs.generators import musicbrainz_like, provgen_like
from repro.graphs.partition import hash_partition
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.engine import GraphQueryEngine, ServeConfig
from repro.utils import get_logger
from repro.workload.stream import WorkloadStream

log = get_logger("launch.serve")

QUERIES = {
    "provgen": ["Entity.Entity.Entity", "Agent.Activity.Entity",
                "Entity.Activity.Agent"],
    "musicbrainz": ["Artist.Credit.Track.Medium",
                    "Artist.Credit.(Track|Recording).Credit.Artist",
                    "Area.Artist.(Artist|Label).Area"],
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", choices=["provgen", "musicbrainz"],
                    default="provgen")
    ap.add_argument("--n", type=int, default=8000)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--ticks", type=int, default=10)
    ap.add_argument("--batch", type=int, default=100)
    args = ap.parse_args()
    enable_compile_cache()

    g = (provgen_like if args.dataset == "provgen" else musicbrainz_like)(
        args.n, seed=3)
    queries = [parse_rpq(q) for q in QUERIES[args.dataset]]
    stream = WorkloadStream(queries, period=float(args.ticks), seed=0)
    engine = GraphQueryEngine(
        g, hash_partition(g.n, args.k, seed=1), args.k,
        ServeConfig(min_requests_between_invocations=3 * args.batch))

    for tick in range(args.ticks):
        results = engine.serve_batch(stream.sample(args.batch))
        ipt = sum(r.ipt for r in results) / len(results)
        s = engine.stats()
        log.info("tick %d: ipt/request=%.2f invocations=%d drift=%.3f",
                 tick, ipt, s["invocations"], s["drift"])
        stream.advance(1.0)
    log.info("served %d requests total, %.2f ipt/request",
             engine.stats()["requests"], engine.stats()["ipt_per_request"])


if __name__ == "__main__":
    main()
