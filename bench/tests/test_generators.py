"""The generator copy against the program's, and the MusicBrainz schema
against the query it must serve."""
import json

import numpy as np
import pytest

from conftest import BENCH
from generators import config_edges
from reference import Ref


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("n,seed", [(2000, 11), (5000, 3 * 2 ** 31 + 5), (60000, 17)])
def test_provgen_copy_is_byte_identical(n, seed):
    from repro.graphs.generators import provgen_like
    from repro.graphs.graph import LabelledGraph

    c = cfg("provgen-1m")
    labels, edges = config_edges(c, seed, n)
    ours = LabelledGraph.from_undirected_edges(n, labels, edges,
                                               c["graph"]["labels"])
    theirs = provgen_like(n, avg_degree=6.0, seed=seed)
    for key in ("labels", "src", "dst", "row_ptr"):
        a, b = getattr(ours, key), getattr(theirs, key)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), key
    assert ours.label_names == theirs.label_names


def test_mq1_matches_on_bench_musicbrainz():
    from repro.core.rpq import parse_rpq
    from repro.graphs.graph import LabelledGraph
    from repro.workload.executor import QueryExecutor

    c = cfg("musicbrainz-1m")
    n = 20000
    labels, edges = config_edges(c, 7, n)
    g = LabelledGraph.from_undirected_edges(n, labels, edges,
                                            c["graph"]["labels"])
    mq1 = next(q for q in c["queries"] if q["name"] == "MQ1")["rpq"]
    paths, _ = QueryExecutor(g).enumerate_paths(parse_rpq(mq1),
                                                max_results=32)
    assert len(paths) == 32
    assert Ref(n, labels, edges, c["graph"]["labels"]).top_paths(
        mq1, 3, 32) == paths


@pytest.mark.parametrize("name", ["provgen-1m", "musicbrainz-1m"])
def test_run_graph_renumbers_one_graph(name):
    """Two seeds give the same graph and start partition under a
    renumbering of the vertices; one seed gives the same arrays twice."""
    from common import run_graph

    c, n = cfg(name), 3000
    a, b = run_graph(c, 5, n), run_graph(c, 2 ** 33 + 3, n)
    for x, y in zip(a, run_graph(c, 5, n)):
        assert np.array_equal(x, y)
    assert not np.array_equal(a[1], b[1])

    def invariant(labels, edges, part):
        deg = np.bincount(edges.ravel(), minlength=n)
        verts = sorted(zip(labels.tolist(), part.tolist(), deg.tolist()))
        ends = np.stack([labels[edges], part[edges], deg[edges]], axis=-1)
        return verts, sorted(map(tuple, ends.reshape(-1, 6).tolist()))

    assert invariant(*a) == invariant(*b)
