"""The plain reference against the program at small sizes: it must agree
where the program is right, without sharing its code."""
import json

import numpy as np
import pytest

from common import start_partition, workload_of
from conftest import BENCH
from generators import config_edges
from reference import Ref, crossings, rpq_strings

CONFIGS = ["provgen-1m", "musicbrainz-1m"]


def setup(name, n=3000, seed=5):
    from repro.graphs.graph import LabelledGraph

    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    labels, edges = config_edges(c, seed, n)
    g = LabelledGraph.from_undirected_edges(n, labels, edges,
                                            c["graph"]["labels"])
    return c, g, Ref(n, labels, edges, c["graph"]["labels"])


@pytest.mark.parametrize("name", CONFIGS)
def test_rpq_strings_match_the_program(name):
    from repro.core.rpq import parse_rpq

    c, _, _ = setup(name, n=200)
    for q in c["queries"]:
        assert rpq_strings(q["rpq"], 3) == parse_rpq(q["rpq"]).strings(32, 3)


@pytest.mark.parametrize("name", CONFIGS)
def test_field_matches_the_program(name):
    from repro.core.rpq import parse_rpq
    from repro.core.taper import Taper

    c, g, ref = setup(name)
    wl = workload_of(c)
    part = start_partition(c, g.n)
    taper = Taper(g, c["k"])
    arrays = taper.build_trie(
        [(parse_rpq(q), f) for q, f, _ in wl]).compile(g.label_names)
    fld = taper.field(part, arrays)
    want = ref.field(part, wl, c["k"])
    scale = lambda a: np.abs(a).max()  # noqa: E731
    assert np.abs(fld.pr - want["pr"]).max() <= 1e-5 * scale(want["pr"])
    assert np.abs(fld.extroversion - want["extroversion"]).max() <= \
        1e-5 * scale(want["extroversion"])
    assert np.abs(fld.ext_to - want["ext_to"]).max() <= \
        1e-5 * scale(want["ext_to"])
    assert fld.total_extroversion == pytest.approx(want["total"], rel=1e-5)


@pytest.mark.parametrize("name", CONFIGS)
def test_ipt_matches_the_program(name):
    from repro.core.rpq import parse_rpq
    from repro.workload.executor import QueryExecutor

    c, g, ref = setup(name)
    wl = workload_of(c)
    part = start_partition(c, g.n)
    ex = QueryExecutor(g)
    prog = ex.workload_ipt([(parse_rpq(q), f) for q, f, _ in wl], part)
    assert ref.workload_ipt(part, wl) == pytest.approx(prog, rel=1e-12)


@pytest.mark.parametrize("name", CONFIGS)
def test_top_paths_match_the_serving_contract(name):
    from repro.core.rpq import parse_rpq
    from repro.workload.executor import QueryExecutor

    c, g, ref = setup(name)
    part = start_partition(c, g.n)
    ex = QueryExecutor(g)
    for q in c["queries"]:
        paths, ipt = ex.enumerate_paths_ref(parse_rpq(q["rpq"]),
                                            max_results=32, part=part)
        want = ref.top_paths(q["rpq"], 3, 32)
        assert want == paths
        assert crossings(want, part) == ipt
