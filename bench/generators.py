"""Schema-constrained labelled-graph generator, kept with the benchmark.

A copy of the program's ``schema_graph`` arithmetic (same random draws in
the same order), so that the benchmark's data does not move when the
program's generator changes.  It returns plain arrays; the harness hands
them to the system under test, and the reference builds its own adjacency
from the same arrays.

The schema (labels, proportions, edge types with weight and community
layer, skew) comes from the configuration file, so a new deployment is a
new file, not new code.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np


def _zipf_pick(rng: np.random.Generator, n: int, size: int, skew: float) -> np.ndarray:
    """``size`` ranks in [0, n) with zipf-ish skew (0 = uniform)."""
    if n <= 0:
        raise ValueError("empty label class")
    u = rng.random(size)
    idx = np.floor(n * u ** (1.0 + skew)).astype(np.int64)
    return np.minimum(idx, n - 1)


def schema_edges(n: int, label_names: Sequence[str],
                 label_props: Sequence[float],
                 edge_schema: Sequence[Sequence], avg_degree: float,
                 skew: float, seed: int, p_intra: float = 0.9
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """(labels (n,) int32, undirected edges (e, 2) int64) of a random graph
    over the schema, with latent communities per edge layer: an edge's far
    endpoint stays in its near endpoint's community with probability
    ``p_intra`` (``n // 250`` communities, at least 8)."""
    rng = np.random.default_rng(seed)
    props = np.asarray(label_props, dtype=np.float64)
    props = props / props.sum()
    counts = np.maximum(1, np.round(props * n).astype(np.int64))
    counts[np.argmax(counts)] += n - counts.sum()
    name_to_id = {s: i for i, s in enumerate(label_names)}
    n_comm = max(8, n // 250)
    n_layers = 1 + max((e[3] if len(e) > 3 else 0) for e in edge_schema)

    labels = np.repeat(np.arange(len(label_names), dtype=np.int32), counts)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    comm = np.empty((n_layers, n), dtype=np.int64)
    for li in range(len(label_names)):
        lo, hi = offsets[li], offsets[li + 1]
        stripes = (np.arange(hi - lo) * n_comm) // max(hi - lo, 1)
        comm[0, lo:hi] = stripes
        for layer in range(1, n_layers):
            comm[layer, lo:hi] = stripes[rng.permutation(hi - lo)]
    # members of each (label, layer, community) cell in ascending id order
    # (one stable sort per label and layer, not one scan per community)
    cell_members: Dict[Tuple[int, int, int], np.ndarray] = {}
    for li in range(len(label_names)):
        lo, hi = offsets[li], offsets[li + 1]
        for layer in range(n_layers):
            cl = comm[layer, lo:hi]
            order = np.argsort(cl, kind="stable")
            cs, starts = np.unique(cl[order], return_index=True)
            for c, sel in zip(cs.tolist(), np.split(lo + order, starts[1:])):
                cell_members[(li, layer, c)] = sel

    target_edges = int(n * avg_degree / 2)
    weights = np.asarray([e[2] for e in edge_schema], dtype=np.float64)
    weights = weights / weights.sum()
    per_type = np.maximum(1, np.round(weights * target_edges).astype(np.int64))

    chunks = []
    for etype, cnt in zip(edge_schema, per_type):
        iu, iv = name_to_id[etype[0]], name_to_id[etype[1]]
        layer = etype[3] if len(etype) > 3 else 0
        cnt = int(cnt)
        us = offsets[iu] + _zipf_pick(rng, counts[iu], cnt, skew)
        intra = rng.random(cnt) < p_intra
        vs = offsets[iv] + _zipf_pick(rng, counts[iv], cnt, skew)
        uc = comm[layer, us]
        intra_idx = np.nonzero(intra)[0]
        if intra_idx.size:
            order = np.argsort(uc[intra_idx], kind="stable")
            sorted_idx = intra_idx[order]
            bounds = np.nonzero(np.diff(uc[sorted_idx]))[0] + 1
            for grp in np.split(sorted_idx, bounds):
                cell = cell_members.get((iv, layer, int(uc[grp[0]])))
                if cell is not None:
                    vs[grp] = cell[_zipf_pick(rng, cell.size, grp.size, skew)]
        chunks.append(np.stack([us, vs], axis=1))
    return labels, np.concatenate(chunks, axis=0)


def config_edges(cfg: Dict, seed: int, n: int = None):
    """The configuration's graph drawn from ``seed`` (``n`` overrides the
    configured size, for tests)."""
    gen = cfg["graph"]
    return schema_edges(
        n or cfg["n"], gen["labels"], gen["label_props"], gen["edge_schema"],
        gen["avg_degree"], gen["skew"], seed)
