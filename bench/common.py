"""Pieces shared by the harness and its drivers: the compile clock, the
start partition, the graph build, percentiles and the check list."""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

#: how long after a window's close a served request's answer is still
#: waited for; one that comes later counts as never answered
ANSWER_WAIT_S = 60.0


class CompileClock:
    """Seconds JAX spends in backend compiles (persistent-cache reads
    included), and the counts of compiles and of persistent-cache hits,
    since creation."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def hash_partition(n: int, k: int, seed: int) -> np.ndarray:
    """Balanced pseudo-random start partition by a mixed hash of the
    vertex id (splitmix64 finaliser)."""
    x = np.arange(n, dtype=np.uint64) + np.uint64(
        (seed * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF)
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return (x % np.uint64(k)).astype(np.int32)


def start_partition(cfg: Dict, n: int) -> np.ndarray:
    sp = cfg["start_partition"]
    if sp["kind"] != "hash":
        raise ValueError(f"unknown start partition {sp['kind']!r}")
    return hash_partition(n, cfg["k"], sp["seed"])


def run_graph(cfg: Dict, seed: int, n: int = None):
    """(labels, edges, start partition) of a run.  The configuration's graph
    is drawn once from its ``graph_seed``; ``seed`` renumbers its vertices
    (the start partition follows them) and reorders its edges.  Every seed
    so gets the same graph and start up to renumbering, the same work in
    another order.  ``n`` overrides the configured size (tests only)."""
    from generators import config_edges

    labels0, edges0 = config_edges(cfg, cfg["graph_seed"], n)
    size = labels0.shape[0]
    rng = np.random.default_rng(seed)
    perm = rng.permutation(size)
    labels = np.empty_like(labels0)
    labels[perm] = labels0
    start = np.empty(size, np.int32)
    start[perm] = start_partition(cfg, size)
    edges = perm[edges0][rng.permutation(edges0.shape[0])]
    return labels, edges, start


def balance_excess(part: np.ndarray, k: int, eps: float = 0.05) -> int:
    """Vertices by which the fullest or emptiest part leaves the balance
    band floor((1 + eps) n / k) .. ceil((1 - eps) n / k); 0 when inside."""
    sizes = np.bincount(part, minlength=k)
    ideal = part.shape[0] / k
    hi = int(np.floor((1.0 + eps) * ideal))
    lo = int(np.ceil((1.0 - eps) * ideal))
    return int(max(0, sizes.max() - hi, lo - sizes.min()))


def workload_of(cfg: Dict):
    """(rpq text, frequency, star_max) per query of the configuration."""
    return [(q["rpq"], float(q["freq"]), int(cfg["star_max"]))
            for q in cfg["queries"]]


def arrivals(seconds: float, count: int, freqs, rng: np.random.Generator):
    """(due offsets in [0, seconds), query index of each) of an open loop
    of ``count`` Poisson arrivals.  The gaps are one fixed draw and each
    query's count is its frequency's share of ``count``; ``rng`` only
    orders them, so that seeds differ in order and not in work."""
    gaps = np.diff(np.sort(np.random.default_rng(0).uniform(
        0.0, seconds, size=count)), prepend=0.0)
    offsets = np.cumsum(rng.permutation(gaps))
    share = np.asarray(freqs, np.float64) * count / np.sum(freqs)
    per = np.floor(share).astype(np.int64)
    per[np.argsort(per - share)[:count - per.sum()]] += 1
    picks = rng.permutation(np.repeat(np.arange(per.size), per))
    return offsets, picks


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def log(msg: str) -> None:
    import sys

    print(msg, file=sys.stderr, flush=True)


@dataclass
class Check:
    """One number compared for ``correct``, with its limit: the run is
    correct when ``value <= limit`` for every check."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return not math.isnan(self.value) and self.value <= self.limit


@dataclass
class Run:
    """What a driver hands back to the harness."""

    attempted: int = 0
    failed: int = 0
    end_to_end: Dict[str, float] = field(default_factory=dict)
    checks: List[Check] = field(default_factory=list)
    #: finished program spans (``Tracer.spans()`` dicts), traced runs only
    spans: List[Dict] = field(default_factory=list)
    #: program counters read after the window
    counters: Dict[str, float] = field(default_factory=dict)
    #: field program shapes for the byte/op count
    field_shapes: Optional[Dict] = None
    notes: Dict[str, object] = field(default_factory=dict)
