"""Dynamic-graph subsystem: mutation semantics + incremental-maintenance
parity (patched caches must be bit-identical to rebuild-from-scratch)."""
import numpy as np
import pytest

from repro.core.rpq import parse_rpq
from repro.graphs.generators import musicbrainz_like, power_law_labelled
from repro.graphs.graph import LabelledGraph, MutationBatch
from repro.workload.executor import QueryExecutor


def _rebuilt(g: LabelledGraph) -> LabelledGraph:
    """Fresh graph constructed from g's raw arrays (full re-sort path)."""
    return LabelledGraph(
        n=g.n, labels=g.labels.copy(), label_names=list(g.label_names),
        src=g.src.copy(), dst=g.dst.copy())


def _assert_full_parity(g: LabelledGraph, queries=()):
    """Every incrementally-maintained structure == scratch rebuild, bitwise."""
    g2 = _rebuilt(g)
    assert np.array_equal(g.src, g2.src)
    assert np.array_equal(g.dst, g2.dst)
    assert np.array_equal(g.row_ptr, g2.row_ptr)
    assert np.array_equal(g.reverse_edge_index, g2.reverse_edge_index)
    assert np.array_equal(
        g.cached_neighbor_label_counts(), g2.neighbor_label_counts())
    for a, b in zip(g.in_edge_order, g2.in_edge_order):
        assert np.array_equal(a, b)
    for ex, q in queries:
        assert np.array_equal(ex.traversals(q), QueryExecutor(g).traversals(q))


def _seed_caches(g: LabelledGraph):
    g.reverse_edge_index
    g.cached_neighbor_label_counts()
    g.in_edge_order


# ---------------------------------------------------------------------------
# mutation semantics
# ---------------------------------------------------------------------------


def test_add_and_remove_edges(paper_graph):
    g = paper_graph.subgraph_mask(np.ones(6, bool))  # private copy
    _seed_caches(g)
    m0, v0 = g.m, g.version
    applied = g.apply_mutations(MutationBatch(
        add_edges=[(0, 5)], remove_edges=[(1, 2)]))
    assert g.version == v0 + 1
    assert g.m == m0  # one undirected edge in, one out
    assert 5 in g.neighbors(0) and 2 not in g.neighbors(1)
    assert applied.added_src.size == 2 and applied.removed_src.size == 2
    _assert_full_parity(g)


def test_add_vertices_with_edges(paper_graph):
    g = paper_graph.subgraph_mask(np.ones(6, bool))
    _seed_caches(g)
    applied = g.apply_mutations(MutationBatch(
        add_vertex_labels=[2, 0], add_edges=[(6, 0), (6, 7), (7, 3)]))
    assert g.n == 8 and applied.n_after == 8
    assert sorted(g.neighbors(6).tolist()) == [0, 7]
    assert g.labels[6] == 2 and g.labels[7] == 0
    assert np.isin(np.arange(6, 8), applied.dirty_vertices()).all()
    _assert_full_parity(g)


def test_remove_vertex_isolates_tombstone(paper_graph):
    g = paper_graph.subgraph_mask(np.ones(6, bool))
    _seed_caches(g)
    lab = int(g.labels[1])
    g.apply_mutations(MutationBatch(remove_vertices=[1]))
    assert g.n == 6                       # slot remains
    assert g.neighbors(1).size == 0       # but isolated
    assert int(g.labels[1]) == lab        # label kept
    assert not np.isin(1, g.dst).any()
    _assert_full_parity(g)


def test_remove_vertex_drops_one_directional_in_arcs():
    """Asymmetric storage: a tombstoned vertex must lose in-arcs that have
    no stored reverse, not just its out-edges."""
    g = LabelledGraph(
        n=4, labels=[0, 0, 1, 1], label_names=["a", "b"],
        src=np.array([0, 1, 2], dtype=np.int32),
        dst=np.array([1, 2, 3], dtype=np.int32))
    g.apply_mutations(MutationBatch(remove_vertices=[1]))
    assert not np.isin(1, g.src).any() and not np.isin(1, g.dst).any()
    assert g.m == 1  # only (2, 3) survives


def test_noop_batch_does_not_bump_version(paper_graph):
    g = paper_graph.subgraph_mask(np.ones(6, bool))
    v0 = g.version
    applied = g.apply_mutations(MutationBatch(
        add_edges=[(0, 1)],          # already present
        remove_edges=[(0, 5)]))      # absent
    assert applied.is_noop and g.version == v0
    assert len(g.mutation_log) == 0


def test_out_of_range_add_edge_raises(paper_graph):
    g = paper_graph.subgraph_mask(np.ones(6, bool))
    with pytest.raises(ValueError, match="out of range"):
        # references vertex 6 without a matching add_vertex_labels entry
        g.apply_mutations(MutationBatch(add_edges=[(0, 6)]))


def test_duplicate_and_self_loop_additions_dropped(paper_graph):
    g = paper_graph.subgraph_mask(np.ones(6, bool))
    m0 = g.m
    g.apply_mutations(MutationBatch(add_edges=[(0, 0), (0, 5), (5, 0)]))
    assert g.m == m0 + 2  # one undirected edge, stored twice
    _assert_full_parity(g)


def test_stale_dst_order_not_served(paper_graph, paper_trie,
                                   paper_partition):
    """Stale derived caches must be refreshed, not silently reused: after a
    mutation the destination order is recomputed and the jnp field's device
    copy of it re-uploads."""
    from repro.core.visitor import extroversion_field

    g = paper_graph.subgraph_mask(np.ones(6, bool))
    _seed_caches(g)
    arrays = paper_trie.compile(g.label_names)
    part = np.asarray(paper_partition)
    pre = {}
    extroversion_field(g, arrays, part, 2, _precomputed=pre)
    before = pre["_dev"]["dst_order"]
    g.apply_mutations(MutationBatch(add_edges=[(0, 5)]))
    perm, in_ptr = g.in_edge_order
    assert perm.shape == (g.m,)
    fld = extroversion_field(g, arrays, part, 2, _precomputed=pre)
    after = pre["_dev"]["dst_order"]
    assert after is not before
    assert np.array_equal(np.asarray(after["src"]), g.src[perm])
    assert np.array_equal(np.asarray(after["in_ptr"]), in_ptr)
    fresh = extroversion_field(_rebuilt(g), arrays, part, 2)
    assert np.array_equal(fld.edge_mass, fresh.edge_mass)


# ---------------------------------------------------------------------------
# vertex re-labelling
# ---------------------------------------------------------------------------


def test_relabel_patches_caches(paper_graph):
    g = paper_graph.subgraph_mask(np.ones(6, bool))
    _seed_caches(g)
    v0 = g.version
    old = int(g.labels[2])
    new = (old + 1) % g.n_labels
    applied = g.apply_mutations(MutationBatch(relabel=[(2, new)]))
    assert g.version == v0 + 1
    assert int(g.labels[2]) == new
    assert np.array_equal(applied.relabel_v, [2])
    assert applied.relabel_old[0] == old and applied.relabel_new[0] == new
    assert 2 in applied.dirty_vertices()
    _assert_full_parity(g)


def test_relabel_same_label_is_noop(paper_graph):
    g = paper_graph.subgraph_mask(np.ones(6, bool))
    v0 = g.version
    applied = g.apply_mutations(
        MutationBatch(relabel=[(3, int(g.labels[3]))]))
    assert applied.is_noop and g.version == v0
    assert len(g.mutation_log) == 0


def test_relabel_last_entry_wins_and_validates(paper_graph):
    g = paper_graph.subgraph_mask(np.ones(6, bool))
    old = int(g.labels[1])
    new = (old + 1) % g.n_labels
    g.apply_mutations(MutationBatch(relabel=[(1, old), (1, new)]))
    assert int(g.labels[1]) == new
    with pytest.raises(ValueError, match="label range"):
        g.apply_mutations(MutationBatch(relabel=[(1, g.n_labels)]))
    with pytest.raises(ValueError, match="vertex id"):
        g.apply_mutations(MutationBatch(relabel=[(g.n, 0)]))


def test_relabel_mixed_with_structural_same_batch(paper_graph):
    g = paper_graph.subgraph_mask(np.ones(6, bool))
    _seed_caches(g)
    # add a vertex, rewire, and relabel both an old vertex and the new one
    g.apply_mutations(MutationBatch(
        add_vertex_labels=[0],
        add_edges=[(6, 1), (6, 4)],
        remove_edges=[(1, 2)],
        relabel=[(0, (int(g.labels[0]) + 1) % g.n_labels), (6, 1)]))
    assert int(g.labels[6]) == 1
    _assert_full_parity(g)


def test_relabel_executor_patch_matches_rebuild():
    g = musicbrainz_like(1200, seed=21)
    q = parse_rpq("Artist.Credit.Track.Medium")
    ex = QueryExecutor(g)
    ex.traversals(q)
    rng = np.random.default_rng(0)
    for _ in range(3):
        vs = rng.choice(g.n, size=5, replace=False)
        g.apply_mutations(MutationBatch(
            relabel=[(int(v), int(rng.integers(0, g.n_labels)))
                     for v in vs]))
        assert np.array_equal(ex.traversals(q), QueryExecutor(g).traversals(q))


def test_relabel_executor_patch_across_compacted_log():
    """Relabels compose across ring compaction like structural deltas."""
    g = musicbrainz_like(600, seed=22)
    q = parse_rpq("Area.Artist.(Artist|Label).Area")
    ex = QueryExecutor(g)
    ex.traversals(q)     # snapshot at version 0
    rng = np.random.default_rng(1)
    for _ in range(g.MUTATION_LOG_LIMIT + 4):
        v = int(rng.integers(0, g.n))
        g.apply_mutations(MutationBatch(
            relabel=[(v, int(rng.integers(0, g.n_labels)))],
            add_edges=[(int(rng.integers(0, g.n)),
                        int(rng.integers(0, g.n)))]))
    assert len(g.mutation_log) == g.MUTATION_LOG_LIMIT
    assert np.array_equal(ex.traversals(q), QueryExecutor(g).traversals(q))


# ---------------------------------------------------------------------------
# executor delta-aware cache
# ---------------------------------------------------------------------------


def test_executor_patch_matches_rebuild():
    g = musicbrainz_like(2000, seed=3)
    q = parse_rpq("Artist.Credit.Track.Medium")
    ex = QueryExecutor(g)
    ex.traversals(q)
    rng = np.random.default_rng(0)
    und = np.stack([g.src, g.dst], 1)
    und = und[und[:, 0] < und[:, 1]]
    g.apply_mutations(MutationBatch(
        add_vertex_labels=rng.integers(0, g.n_labels, 4),
        add_edges=np.stack([rng.integers(0, g.n + 4, 30),
                            rng.integers(0, g.n + 4, 30)], 1),
        remove_edges=und[rng.choice(len(und), 20, replace=False)]))
    patched = ex.traversals(q)
    scratch = QueryExecutor(g).traversals(q)
    assert np.array_equal(patched, scratch)


def test_executor_patch_across_multiple_batches():
    g = musicbrainz_like(1500, seed=4)
    q = parse_rpq("Area.Artist.(Artist|Label).Area")
    ex = QueryExecutor(g)
    ex.traversals(q)
    rng = np.random.default_rng(1)
    for _ in range(3):  # gap of 3 versions, patched in one composed hop
        g.apply_mutations(MutationBatch(
            add_edges=np.stack([rng.integers(0, g.n, 15),
                                rng.integers(0, g.n, 15)], 1)))
    assert np.array_equal(ex.traversals(q), QueryExecutor(g).traversals(q))


def test_executor_patches_across_compacted_log():
    """Overflowing the ring no longer strands a slow consumer: the two
    oldest records compose (``compose_mutations``), the log keeps reaching
    back to version 0, and the patch stays bit-identical to a rebuild."""
    g = musicbrainz_like(1000, seed=5)
    q = parse_rpq("Artist.Credit.Track.Medium")
    ex = QueryExecutor(g)
    ex.traversals(q)
    rng = np.random.default_rng(2)
    for _ in range(g.MUTATION_LOG_LIMIT + 2):  # overflow the ring
        g.apply_mutations(MutationBatch(
            add_edges=np.stack([rng.integers(0, g.n, 4),
                                rng.integers(0, g.n, 4)], 1)))
    assert len(g.mutation_log) == g.MUTATION_LOG_LIMIT
    assert g.mutation_log[0].version_base == 0  # history still rooted
    state = ex._cache[q.qhash]
    assert state.version == 0                   # consumer genuinely stale
    assert ex._covering_mutations(0) is not None
    assert np.array_equal(ex.traversals(q), QueryExecutor(g).traversals(q))


def test_mutation_log_compaction_ring_and_spans():
    g = musicbrainz_like(600, seed=6)
    rng = np.random.default_rng(3)
    total = g.MUTATION_LOG_LIMIT + 7
    for _ in range(total):
        g.apply_mutations(MutationBatch(
            add_edges=np.stack([rng.integers(0, g.n, 3),
                                rng.integers(0, g.n, 3)], 1)))
    log = g.mutation_log
    assert len(log) == g.MUTATION_LOG_LIMIT
    # spans are contiguous and cover version 0 .. current
    assert log[0].version_base == 0
    for a, b in zip(log, log[1:]):
        assert b.version_base == a.version
    assert log[-1].version == g.version == total
    # the head record absorbed all the overflow
    assert log[0].version - log[0].version_base == total - (
        g.MUTATION_LOG_LIMIT - 1)


def test_executor_rebuilds_when_inside_compacted_span():
    """A snapshot strictly inside a compacted span cannot be patched; the
    executor falls back to rebuild and still returns exact counts."""
    g = musicbrainz_like(800, seed=7)
    q = parse_rpq("Artist.Credit.Track.Medium")
    rng = np.random.default_rng(4)
    g.apply_mutations(MutationBatch(
        add_edges=np.stack([rng.integers(0, g.n, 3),
                            rng.integers(0, g.n, 3)], 1)))
    ex = QueryExecutor(g)
    ex.traversals(q)                           # snapshot at version 1
    for _ in range(g.MUTATION_LOG_LIMIT + 3):  # version 1 gets compacted over
        g.apply_mutations(MutationBatch(
            add_edges=np.stack([rng.integers(0, g.n, 3),
                                rng.integers(0, g.n, 3)], 1)))
    assert g.mutation_log[0].version_base == 0
    assert g.mutation_log[0].version > 1
    assert ex._covering_mutations(1) is None
    assert np.array_equal(ex.traversals(q), QueryExecutor(g).traversals(q))


def test_compacted_record_bounded_under_same_edge_churn():
    """Churning the same edges forever must not grow the head record: the
    compose step prunes span-transient edges, so list sizes are bounded by
    the distinct edge universe, not lifetime batch count."""
    g = musicbrainz_like(1000, seed=9)
    q = parse_rpq("Artist.Credit.Track.Medium")
    ex = QueryExecutor(g)
    ex.traversals(q)
    und = np.stack([g.src, g.dst], 1)
    fixed = und[und[:, 0] < und[:, 1]][:5]
    sizes = []
    for _ in range(3 * g.MUTATION_LOG_LIMIT):
        g.apply_mutations(MutationBatch(add_edges=fixed, remove_edges=fixed))
        sizes.append(int(g.mutation_log[0].removed_src.size))
    assert sizes[-1] == sizes[2 * g.MUTATION_LOG_LIMIT]  # plateaued
    assert sizes[-1] <= 2 * len(fixed) * 2
    assert np.array_equal(ex.traversals(q), QueryExecutor(g).traversals(q))


def test_compose_mutations_exact_roundtrip():
    """Composed old2new/new_edge_pos must agree with composing by hand."""
    from repro.graphs.graph import compose_mutations

    g = musicbrainz_like(500, seed=8)
    rng = np.random.default_rng(5)
    batches = []
    for _ in range(2):
        und = np.stack([g.src, g.dst], 1)
        und = und[und[:, 0] < und[:, 1]]
        batches.append(g.apply_mutations(MutationBatch(
            add_vertex_labels=rng.integers(0, g.n_labels, 2),
            add_edges=np.stack([rng.integers(0, g.n + 2, 8),
                                rng.integers(0, g.n + 2, 8)], 1),
            remove_edges=und[rng.choice(len(und), 5, replace=False)])))
    a, b = batches
    c = compose_mutations(a, b)
    assert c.version_base == a.version_base and c.version == b.version
    assert c.n_before == a.n_before and c.n_after == b.n_after
    valid = a.old2new >= 0
    expect = np.full(a.old2new.shape[0], -1, np.int64)
    expect[valid] = b.old2new[a.old2new[valid]]
    assert np.array_equal(c.old2new, expect)
    # every current edge is either mapped from the base or listed as added
    covered = np.zeros(g.m, bool)
    covered[c.old2new[c.old2new >= 0]] = True
    covered[c.new_edge_pos] = True
    assert covered.all()
    assert np.array_equal(g.src[c.new_edge_pos], c.added_src)
    assert np.array_equal(g.dst[c.new_edge_pos], c.added_dst)


# ---------------------------------------------------------------------------
# randomized MutationBatch parity (the acceptance gate); the hypothesis
# twin with minimisation lives in tests/test_property_dynamic.py
# ---------------------------------------------------------------------------


def _random_batch(g, rng, nv, na, nr, rem_v, nrl=0):
    und = np.stack([g.src, g.dst], 1)
    und = und[und[:, 0] < und[:, 1]]
    nr = min(nr, len(und))
    remove = (und[rng.choice(len(und), nr, replace=False)]
              if nr else np.zeros((0, 2), np.int64))
    hi = g.n + nv
    add = (np.stack([rng.integers(0, hi, na), rng.integers(0, hi, na)], 1)
           if na else np.zeros((0, 2), np.int64))
    relabel = (np.stack([rng.integers(0, hi, nrl),
                         rng.integers(0, g.n_labels, nrl)], 1)
               if nrl else np.zeros((0, 2), np.int64))
    return MutationBatch(
        add_vertex_labels=rng.integers(0, g.n_labels, nv),
        add_edges=add, remove_edges=remove, remove_vertices=rem_v,
        relabel=relabel)


@pytest.mark.parametrize("seed", range(8))
def test_random_mutation_batches_bitwise_parity(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(40, 250))
    g = power_law_labelled(n, n_labels=4, avg_degree=5.0, seed=seed)
    q = parse_rpq("L0.(L1|L2).L3")
    _seed_caches(g)
    ex = QueryExecutor(g)
    ex.traversals(q)
    for _ in range(int(rng.integers(1, 4))):
        rem_v = [int(rng.integers(0, g.n))] if rng.random() < 0.5 else []
        g.apply_mutations(_random_batch(
            g, rng,
            nv=int(rng.integers(0, 5)), na=int(rng.integers(0, 13)),
            nr=int(rng.integers(0, 13)), rem_v=rem_v,
            nrl=int(rng.integers(0, 4))))
        g.validate()
        _assert_full_parity(g, queries=[(ex, q)])
