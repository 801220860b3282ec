"""Visitor-Matrix / extroversion oracle tests.

Every expected number below appears verbatim in the paper (§4.2 example,
§5.2.1 safe-vertex example, §5.4 partial-extroversion example).  These pin
the vectorised DP to the paper's corecursive Alg. 1 semantics.
"""
import numpy as np
import pytest

from repro.core.visitor import extroversion_field, vm_cell

V1, V2, V3, V4, V5, V6 = 0, 1, 2, 3, 4, 5  # paper vertex ids 1..6


@pytest.fixture(scope="module")
def arrays(paper_trie, paper_graph):
    return paper_trie.compile(paper_graph.label_names)


@pytest.fixture(scope="module")
def field(paper_graph, arrays, paper_partition):
    return extroversion_field(paper_graph, arrays, paper_partition, k=2)


def test_vm_cell_paper_4_2(paper_graph, arrays):
    """§4.2: VM^(3)[1,2,*] = (0, 0, 0.25, 0.5, 0.25, 0)."""
    row = vm_cell(paper_graph, arrays, [V1, V2])
    np.testing.assert_allclose(row, [0, 0, 0.25, 0.5, 0.25, 0], atol=1e-7)


def test_vm_cell_unmatched_path(paper_graph, arrays):
    # a path whose label string is not a trie prefix has no transitions
    row = vm_cell(paper_graph, arrays, [V2])  # label 'b' is not a prefix
    np.testing.assert_allclose(row, np.zeros(6), atol=0)


def test_alpha_states_vertex3(paper_graph, arrays, field, paper_trie):
    """§5.2.1/§5.4 intermediate values for vertex 3, partition B={3,5,6}:
    alpha[(3)->'c']=0.125, alpha[(5,3)->'cc']=0.125, alpha[(6,3)->'ac']=0.25."""
    name_to = {
        tuple(): 0,
    }
    # locate trie nodes by path
    def node_of(path):
        cur = 0
        lbl = {s: i for i, s in enumerate(paper_graph.label_names)}
        for sym in path:
            cur = int(arrays.child_index[cur, lbl[sym]])
            assert cur >= 0
        return cur

    assert field.alpha[V3, node_of(["c"])] == pytest.approx(0.125, abs=1e-7)
    assert field.alpha[V3, node_of(["c", "c"])] == pytest.approx(0.125, abs=1e-7)
    assert field.alpha[V3, node_of(["a", "c"])] == pytest.approx(0.25, abs=1e-7)


def test_pr_vertex3(field):
    """§5.2.1: total traversal probability through v3, Pr(v3) = 0.5."""
    assert field.pr[V3] == pytest.approx(0.5, abs=1e-7)


def test_extroversion_vertex3(field):
    """§5.4: external transition probability 0.0625 ('0.06'); extroversion
    0.0625/0.5 = 0.125 ('0.12')."""
    assert field.extro_mass[V3] == pytest.approx(0.0625, abs=1e-7)
    assert field.extroversion[V3] == pytest.approx(0.125, abs=1e-7)


def test_introversion_vertex3(field):
    """§5.2.1: intra-partition traversal probability 0.44 (exactly 0.4375),
    introversion 0.4375/0.5 = 0.875 ('0.88') — v3 is 'safe' for any
    threshold below 0.875."""
    assert field.introversion[V3] == pytest.approx(0.875, abs=1e-7)


def test_ext_to_decomposition(field, paper_partition):
    """ext_to sums to extro_mass; v3's external mass all flows to A."""
    np.testing.assert_allclose(field.ext_to.sum(axis=1), field.extro_mass, atol=1e-6)
    assert field.ext_to[V3, 0] == pytest.approx(0.0625, abs=1e-7)
    assert field.ext_to[V3, 1] == pytest.approx(0.0, abs=1e-9)


def test_no_external_neighbours_is_safe(paper_graph, arrays, paper_partition):
    """§5.2.2: vertices without external neighbours have no extroversion."""
    fld = extroversion_field(paper_graph, arrays, paper_partition, k=2)
    # vertex 6's only neighbour is 3 (same partition B)
    assert fld.extro_mass[V6] == pytest.approx(0.0, abs=1e-9)
    assert fld.extroversion[V6] == pytest.approx(0.0, abs=1e-9)


def test_depth_cap_heuristic(paper_graph, arrays, paper_partition):
    """§5.2.2 time heuristic: capping path length k < t changes (only
    truncates) the field; with cap=1 there are no transitions at all."""
    fld_full = extroversion_field(paper_graph, arrays, paper_partition, k=2)
    fld_cap = extroversion_field(paper_graph, arrays, paper_partition, k=2, depth_cap=2)
    # with cap 2, only priors transition; v3 extroversion shrinks to the
    # depth-2 contribution (paths of length 1)
    assert fld_cap.extro_mass[V3] <= fld_full.extro_mass[V3] + 1e-9


def test_mass_conservation(paper_graph, arrays, paper_partition, field):
    """Per-vertex: edge mass out + termination mass == Pr(v)."""
    out_mass = np.zeros(paper_graph.n)
    np.add.at(out_mass, paper_graph.src, field.edge_mass)
    assert (out_mass <= field.pr + 1e-6).all()


# ---------------------------------------------------------------------------
# the fused program's destination-segment sums, against the float64 recurrence
# ---------------------------------------------------------------------------

SEG_LABELS = ["L0", "L1", "L2"]
SEG_QUERIES = [("L0.(L1|L2)*.L0", 0.5), ("L1.L0.L2.L1", 0.3),
               ("(L2|L0).L2*", 0.2)]


def _seg_trie(g):
    from repro.core.rpq import parse_rpq
    from repro.core.tpstry import TPSTry

    return TPSTry.from_workload(
        [(parse_rpq(q), f) for q, f in SEG_QUERIES],
        max_len=5).compile(g.label_names)


def _field_f64(g, t, part, k):
    """The recurrence of ``repro.core.visitor`` in float64 numpy, for any
    directed edge list: one ``bincount`` by ``dst`` per trie node."""
    n, m, L = g.n, g.m, g.n_labels
    src, dst = g.src.astype(np.int64), g.dst.astype(np.int64)
    dlab = g.labels[dst]
    cnt = np.bincount(src * L + dlab, minlength=n * L).reshape(n, L)
    inv = 1.0 / np.maximum(cnt, 1.0)
    nlab = np.bincount(g.labels, minlength=L)
    alpha = np.zeros((n, t.n_nodes))
    for i in np.flatnonzero(t.depth == 1):
        li = int(t.label[i])
        alpha[:, i] = np.where(g.labels == li,
                               float(t.p[i]) / max(nlab[li], 1), 0.0)
    local = part[src] == part[dst]
    mass = np.zeros(m)
    for d in range(2, t.max_depth + 1):
        for c in np.flatnonzero(t.depth == d):
            par, lc = int(t.parent[c]), int(t.label[c])
            contrib = (alpha[src, par] * float(t.cond_p[c]) * inv[src, lc]
                       * (dlab == lc))
            mass += contrib
            alpha[:, c] += np.bincount(dst, contrib * local, minlength=n)
    counted = [i for i in range(t.n_nodes)
               if 1 <= t.depth[i] < t.max_depth and not t.is_leaf[i]]
    pr = alpha[:, counted].sum(axis=1)
    ext = mass * ~local
    extro = np.bincount(src, ext, minlength=n)
    ext_to = np.bincount(src * k + part[dst], ext,
                         minlength=n * k).reshape(n, k)
    return {"alpha": alpha, "pr": pr, "edge_mass": mass, "extro_mass": extro,
            "extroversion": np.where(pr > 1e-30, extro / np.maximum(
                pr, 1e-30), 0.0), "ext_to": ext_to}


def _normwise(got, want):
    """Largest relative error of any column, each normwise."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if want.ndim == 1:
        got, want = got[:, None], want[:, None]
    num = np.linalg.norm(got - want, axis=0)
    den = np.linalg.norm(want, axis=0)
    return float(np.where(den > 0, num / np.maximum(den, 1e-300),
                          num).max())


def _hub_graph(directed: bool):
    """400 vertices: random edges, a hub that half of them point at, and
    vertices with no in-edge.  ``directed`` keeps the arcs one-way, so
    the reverse-edge index holds -1s."""
    from repro.graphs.graph import LabelledGraph

    rng = np.random.default_rng(7)
    n = 400
    labels = rng.integers(0, 3, size=n).astype(np.int32)
    e = rng.integers(10, n, size=(1200, 2))         # 0..9 take no random edge
    hub = np.stack([np.arange(12, n, 2), np.full((n - 12) // 2, 11)], 1)
    e = np.concatenate([e, hub])
    if not directed:
        return LabelledGraph.from_undirected_edges(n, labels, e, SEG_LABELS)
    e = e[e[:, 0] != e[:, 1]]
    e = np.unique(e, axis=0)
    return LabelledGraph(n=n, labels=labels, label_names=list(SEG_LABELS),
                         src=e[:, 0], dst=e[:, 1])


def _mutated_hub_graph(pre, t, part, k):
    """The symmetric hub graph after one field call with ``pre``, then a
    batch that swaps three edges for three others: ``n`` and ``m`` stay,
    so a stale device order would go unnoticed by the shapes."""
    from repro.graphs.graph import MutationBatch

    g = _hub_graph(directed=False)
    extroversion_field(g, t, part, k, _precomputed=pre)
    up = np.flatnonzero(g.src < g.dst)[:3]
    gone = np.stack([g.src[up], g.dst[up]], 1)
    new = np.array([[0, 1], [2, 3], [4, 11]])
    g.apply_mutations(MutationBatch(add_edges=new, remove_edges=gone))
    return g


@pytest.mark.parametrize("case", ["symmetric", "one_directional", "mutated"])
def test_fused_field_matches_float64_and_naive(case):
    """The fused program against :func:`_field_f64`, the naive
    transcription of the recurrence (one pass per trie node) in float64."""
    from repro.graphs.partition import hash_partition

    k = 4
    pre = {}
    g0 = _hub_graph(directed=case == "one_directional")
    t = _seg_trie(g0)
    part = hash_partition(g0.n, k, seed=3)
    if case == "mutated":
        g = _mutated_hub_graph(pre, t, part, k)
        old = pre["_dev"]["dst_order"]
        assert g.m == g0.m and g.n == g0.n and g.version == 1
    else:
        g = g0
    indeg = np.bincount(g.dst, minlength=g.n)
    assert indeg.max() > 64 and (indeg == 0).any()
    if case == "one_directional":
        assert (g.reverse_edge_index < 0).any()
    fused = extroversion_field(g, t, part, k, _precomputed=pre)
    if case == "mutated":
        # the version bump re-uploaded the order and its offsets
        assert pre["_dev"]["dst_order"] is not old
        assert pre["_dev_version"] == g.version
    want = _field_f64(g, t, part, k)
    for name, ref in want.items():
        assert _normwise(getattr(fused, name), ref) < 1e-5, name


@pytest.mark.parametrize("edges", [[], [[0, 1]], [[0, 1], [0, 2], [0, 3]]])
def test_fused_field_on_graphs_with_few_edges(edges):
    """No edge, and segments too short for a scan level."""
    from repro.graphs.graph import LabelledGraph

    g = LabelledGraph.from_undirected_edges(
        6, [0, 1, 2, 0, 1, 2], np.asarray(edges, np.int64).reshape(-1, 2),
        SEG_LABELS)
    t = _seg_trie(g)
    part = np.array([0, 1, 0, 1, 0, 1])
    fused = extroversion_field(g, t, part, 2)
    for name, ref in _field_f64(g, t, part, 2).items():
        assert _normwise(getattr(fused, name), ref) < 1e-6, name


@pytest.mark.parametrize("directed", [False, True])
def test_in_edge_order_is_the_stable_dst_order(directed):
    g = _hub_graph(directed)
    perm, in_ptr = g.in_edge_order
    np.testing.assert_array_equal(perm, np.argsort(g.dst, kind="stable"))
    np.testing.assert_array_equal(
        in_ptr, np.concatenate([[0], np.cumsum(np.bincount(
            g.dst, minlength=g.n))]))
    assert g.in_edge_order is g.in_edge_order       # cached


def test_fused_field_depth_steps_have_no_scatter():
    """Each DP depth sums contiguous destination segments: the program,
    lowered and compiled at a small size, has no scatter inside a
    ``field.depth<d>`` scope (a ``segment_sum`` by ``dst`` would show one
    a depth)."""
    import re

    import jax.numpy as jnp

    from repro.core.visitor import _build_field_fn

    g = _hub_graph(directed=False)
    t = _seg_trie(g)
    n, m, L, N = g.n, g.m, g.n_labels, t.n_nodes
    fn = _build_field_fn(None, t, 4, t.max_depth, dense_ext_to=True)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    f32 = lambda *shape: jnp.zeros(shape, jnp.float32)
    hlo = fn.lower(i32(m), i32(m), i32(n), i32(n, L), i32(L), i32(n),
                   f32(N), f32(N), i32(m), i32(m), i32(m), i32(n + 1),
                   i32(n + 1), n=n, m=m, levels=9).compile().as_text()
    depth_ops = [ln for ln in hlo.splitlines()
                 if re.search(r'op_name="[^"]*field\.depth\d', ln)]
    assert depth_ops
    assert not [ln for ln in depth_ops if " scatter(" in ln]
