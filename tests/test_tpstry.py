"""TPSTry construction and probability tests against the paper's §4.1
worked example (Fig. 3 / Fig. 4)."""
import numpy as np
import pytest

from repro.core.rpq import parse_rpq
from repro.core.tpstry import TPSTry


def test_paper_trie_probabilities(paper_trie):
    """Exact numbers from §4.1 and Fig. 4(right)."""
    t = paper_trie
    assert t.prob_of_path(["a"]) == pytest.approx(0.75)        # Pr(E->a) worked example
    assert t.prob_of_path(["c"]) == pytest.approx(0.25)
    assert t.prob_of_path(["a", "b"]) == pytest.approx(0.25)   # Pr(E->a->b)=.25...

    # Fig. 4: p(ab)=0.25? §4.1 computes Pr(E->a->b) = 0.25
    assert t.prob_of_path(["a", "c"]) == pytest.approx(0.5)
    assert t.prob_of_path(["c", "c"]) == pytest.approx(0.25)
    assert t.prob_of_path(["a", "b", "c"]) == pytest.approx(0.125)
    assert t.prob_of_path(["a", "b", "d"]) == pytest.approx(0.125)
    assert t.prob_of_path(["a", "c", "c"]) == pytest.approx(0.125)
    assert t.prob_of_path(["a", "c", "d"]) == pytest.approx(0.125)
    assert t.prob_of_path(["a", "c", "a"]) == pytest.approx(0.25)
    assert t.prob_of_path(["c", "c", "a"]) == pytest.approx(0.25)


def test_trie_structure(paper_trie):
    # Fig 3(b): merged trie with nodes for both queries
    t = paper_trie
    assert t.node_by_path(["a"]) is not None
    assert t.node_by_path(["c", "c", "a"]) is not None
    assert t.node_by_path(["b"]) is None
    assert t.max_depth == 3
    # node 'a' and 'ac' are labelled with both queries (paper fn. 4)
    q1, q2 = parse_rpq("a.(b|c).(c|d)"), parse_rpq("(c|a).c.a")
    assert t.node_by_path(["a"]).queries == {q1.qhash, q2.qhash}
    assert t.node_by_path(["a", "c"]).queries == {q1.qhash, q2.qhash}
    assert t.node_by_path(["a", "b"]).queries == {q1.qhash}


def test_frequency_zero_removes_query(paper_workload):
    """§4: an expression with frequency 0 has its labels (and orphaned
    nodes) removed and is treated as new in future."""
    trie = TPSTry.from_workload(paper_workload)
    n_before = trie.n_nodes
    (q1, _), (q2, _) = paper_workload
    trie.set_frequencies({q1.qhash: 1.0, q2.qhash: 0.0})
    assert trie.node_by_path(["c", "c"]) is None        # only Q2 used cc
    assert trie.node_by_path(["a", "c", "a"]) is None   # only Q2 used aca
    assert trie.node_by_path(["a", "b"]) is not None
    assert trie.n_nodes < n_before
    # with Q1 alone its conditionals renormalise
    assert trie.prob_of_path(["a"]) == pytest.approx(1.0)
    assert trie.prob_of_path(["a", "b"]) == pytest.approx(0.5)


def test_right_stochastic_children(paper_trie):
    """Children of any node sum to at most the node's probability (the
    shortfall is termination mass)."""
    t = paper_trie
    for node in t.nodes:
        p_children = sum(t.nodes[c].p for c in node.children.values())
        p_self = node.p if node.node_id != 0 else 1.0
        assert p_children <= p_self + 1e-9


def test_compile_arrays(paper_trie, paper_graph):
    arrays = paper_trie.compile(paper_graph.label_names)
    assert arrays.n_nodes == paper_trie.n_nodes
    assert arrays.max_depth == 3
    # depth ordering: parents precede children
    assert all(arrays.parent[i] < i for i in range(1, arrays.n_nodes))
    # cond_p of depth-1 node == p
    d1 = [i for i in range(arrays.n_nodes) if arrays.depth[i] == 1]
    np.testing.assert_allclose(arrays.cond_p[d1], arrays.p[d1], rtol=1e-6)


def test_compile_drops_unknown_symbols(paper_workload):
    trie = TPSTry.from_workload(paper_workload)
    arrays = trie.compile(["a", "b", "c"])  # no 'd' in this graph
    # abd / acd subtrees dropped
    assert arrays.n_nodes == trie.n_nodes - 2


def test_snapshot_change_detection(paper_workload):
    trie = TPSTry.from_workload(paper_workload)
    trie.snapshot()
    assert not trie.changed_since_snapshot().any()
    (q1, _), (q2, _) = paper_workload
    trie.set_frequencies({q1.qhash: 0.9, q2.qhash: 0.1})
    changed = trie.changed_since_snapshot()
    assert changed.any()


_TRIE_DIGEST = """
import hashlib
from repro.core.rpq import parse_rpq
from repro.core.tpstry import TPSTry

w = [(parse_rpq("Entity.(Entity)*.Entity"), 0.4),
     (parse_rpq("Agent.Activity.Entity.Entity.Activity.Agent"), 0.2),
     (parse_rpq("(Entity)*.Activity.Entity"), 0.2),
     (parse_rpq("Entity.Activity.(Agent)*"), 0.2)]
t = TPSTry.from_workload(w).compile(["Entity", "Activity", "Agent"])
h = hashlib.sha256()
for part in t.topology_signature():
    h.update(part if isinstance(part, bytes) else repr(part).encode())
h.update(t.p.tobytes())
print(h.hexdigest())
"""


def test_compiled_trie_independent_of_string_hashing():
    """Node ids follow insertion order, so the compiled trie — and with it
    the jitted field and its persistent-cache key — must not depend on the
    per-process string-hash seed that orders a query's string set."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    digests = set()
    for seed in ("1", "2", "3"):
        env = dict(os.environ, PYTHONHASHSEED=seed, PYTHONPATH=src)
        r = subprocess.run([sys.executable, "-c", _TRIE_DIGEST], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        digests.add(r.stdout.strip())
    assert len(digests) == 1
