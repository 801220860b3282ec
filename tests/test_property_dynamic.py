"""Hypothesis property test: after any random MutationBatch sequence the
graph's derived caches (reverse_edge_index and neighbour-label counts,
patched; in_edge_order; executor traversal counts) are bit-identical to
rebuilding from scratch.  The seeded numpy twin (always runnable) lives in
tests/test_dynamic_graph.py."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core.rpq import parse_rpq
from repro.graphs.generators import power_law_labelled
from repro.graphs.graph import MutationBatch
from repro.workload.executor import QueryExecutor
from test_dynamic_graph import (  # same-directory sibling (pytest sys.path)
    _assert_full_parity,
    _random_batch,
    _seed_caches,
)

SET = settings(
    max_examples=15,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
)


@st.composite
def mutation_scenario(draw):
    n = draw(st.integers(40, 250))
    seed = draw(st.integers(0, 2**16))
    specs = draw(st.lists(
        st.tuples(st.integers(0, 4), st.integers(0, 12), st.integers(0, 12),
                  st.booleans(), st.integers(0, 3)),
        min_size=1, max_size=3))
    return n, seed, specs


@given(mutation_scenario())
@SET
def test_random_mutation_batches_bitwise_parity(scenario):
    n, seed, specs = scenario
    g = power_law_labelled(n, n_labels=4, avg_degree=5.0, seed=seed)
    rng = np.random.default_rng(seed + 1)
    q = parse_rpq("L0.(L1|L2).L3")
    _seed_caches(g)
    ex = QueryExecutor(g)
    ex.traversals(q)
    for nv, na, nr, drop_vertex, nrl in specs:
        rem_v = [int(rng.integers(0, g.n))] if drop_vertex else []
        g.apply_mutations(_random_batch(g, rng, nv, na, nr, rem_v, nrl=nrl))
        g.validate()
        _assert_full_parity(g, queries=[(ex, q)])
