"""A run with its timed path broken underneath must come out as not
correct, and the controls must fail their numbers.  The harness's look for
a chip is skipped (``run_cell`` directly), the rest of a run is driven at a
small size on the CPU."""
import json

import numpy as np
import pytest

import common
import run as harness
from common import start_partition, workload_of
from conftest import BENCH
from control import field_lowp, top_paths_ascending
from generators import config_edges
from reference import Ref, field_errors

N = 3000


@pytest.fixture(autouse=True)
def short_answer_wait(monkeypatch):
    monkeypatch.setattr(common, "ANSWER_WAIT_S", 3.0)


#: a serving mix as a traffic file gives it, at a rate this size sustains
SERVE = {"driver": "serve_invoke", "rate_rps": 30.0, "warmup_requests": 64,
         "micro_batch": 16, "max_results_per_query": 32, "n_workers": 1}
CELLS = {"musicbrainz-1m.invoke": ("musicbrainz-1m", "invoke"),
         "provgen-1m.invoke": ("provgen-1m", "invoke-fresh"),
         "provgen-1m.serve-invoke": ("provgen-1m", SERVE)}


def cell_run(name):
    """One run of a cell at n = N, from its configuration and traffic
    files (a serving mix inline)."""
    conf, traffic = CELLS[name]
    cfg = json.loads((BENCH / "configs" / f"{conf}.json").read_text())
    if isinstance(traffic, str):
        traffic = json.loads(
            (BENCH / "traffic" / f"{traffic}.json").read_text())
    cell = {"name": name, "config": conf, "chips": 1}
    return harness.run_cell(name, cell, cfg, traffic, seed=2 ** 33 + 1,
                            seconds=1.5, trace=False, peak=None, n=N)


@pytest.mark.parametrize("name", ["musicbrainz-1m.invoke",
                                  "provgen-1m.invoke",
                                  "provgen-1m.serve-invoke"])
def test_sound_run_is_correct(name):
    out = cell_run(name)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


def _swap_unchanged(monkeypatch):
    import repro.core.taper as taper_mod

    real = taper_mod.swap_iteration

    def unchanged(g, part, *a, **kw):
        _, stats = real(g, part, *a, **kw)
        return part.copy(), stats

    monkeypatch.setattr(taper_mod, "swap_iteration", unchanged)


def _field_half_edges(monkeypatch):
    import repro.core.taper as taper_mod
    from repro.graphs.graph import LabelledGraph

    real = taper_mod.extroversion_field

    def half(g, trie, part, k, **kw):
        keep = g.src < g.dst
        e = np.stack([g.src[keep], g.dst[keep]], axis=1)[::2]
        gh = LabelledGraph.from_undirected_edges(g.n, g.labels, e,
                                                 g.label_names)
        fld = real(gh, trie, part, k, **dict(kw, _precomputed={}))
        # the swap reads per-edge masses in the full graph's edge order
        fld.edge_mass = real(g, trie, part, k, **kw).edge_mass
        return fld

    monkeypatch.setattr(taper_mod, "extroversion_field", half)


def _field_altered(monkeypatch):
    import repro.core.taper as taper_mod

    real = taper_mod.extroversion_field

    def altered(*a, **kw):
        fld = real(*a, **kw)
        fld.extroversion = fld.extroversion.copy()
        fld.extroversion[int(np.argmax(fld.extroversion))] *= 1.01
        return fld

    monkeypatch.setattr(taper_mod, "extroversion_field", altered)


def _field_bf16_control(monkeypatch):
    """The control in the program's place: the reference recurrence in
    bfloat16, the precision below the float32 the configuration states."""
    import repro.core.taper as taper_mod

    real = taper_mod.extroversion_field
    cfg = json.loads((BENCH / "configs" / "musicbrainz-1m.json").read_text())

    def lowp(g, trie, part, k, **kw):
        fld = real(g, trie, part, k, **kw)
        keep = g.src < g.dst
        ref = Ref(g.n, np.asarray(g.labels),
                  np.stack([g.src[keep], g.dst[keep]], axis=1),
                  list(g.label_names))
        ctl = field_lowp(ref, part, workload_of(cfg), k)
        fld.pr, fld.extroversion = ctl["pr"], ctl["extroversion"]
        fld.ext_to, fld.total_extroversion = ctl["ext_to"], ctl["total"]
        return fld

    monkeypatch.setattr(taper_mod, "extroversion_field", lowp)


def _answer_altered(monkeypatch):
    from repro.workload.executor import QueryExecutor

    real = QueryExecutor.enumerate_paths_many

    def altered(self, *a, **kw):
        out = real(self, *a, **kw)
        paths, ipt = out[0]
        if paths:
            out[0] = ([paths[0][::-1]] + paths[1:], ipt)
        return out

    monkeypatch.setattr(QueryExecutor, "enumerate_paths_many", altered)


def _half_batch(monkeypatch):
    from repro.workload.executor import QueryExecutor

    real = QueryExecutor.enumerate_paths_many
    calls = []

    def half(self, queries, *a, **kw):
        # from the window on: the warm-up's batches are answered whole
        calls.append(1)
        keep = len(queries) if len(calls) <= 8 else max(1, len(queries) // 2)
        return real(self, queries[:keep], *a, **kw)

    monkeypatch.setattr(QueryExecutor, "enumerate_paths_many", half)


@pytest.mark.parametrize("name,fault", [
    ("musicbrainz-1m.invoke", _swap_unchanged),
    ("musicbrainz-1m.invoke", _field_half_edges),
    ("musicbrainz-1m.invoke", _field_altered),
    ("musicbrainz-1m.invoke", _field_bf16_control),
    ("provgen-1m.serve-invoke", _answer_altered),
    ("provgen-1m.serve-invoke", _half_batch),
])
def test_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    fault(monkeypatch)
    out = cell_run(name)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", ["provgen-1m", "musicbrainz-1m"])
def test_bf16_field_control_fails_the_field_limit(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    labels, edges = config_edges(cfg, 9, N)
    ref = Ref(N, labels, edges, cfg["graph"]["labels"])
    wl = workload_of(cfg)
    part = start_partition(cfg, N)
    want = ref.field(part, wl, cfg["k"])
    errs = field_errors(field_lowp(ref, part, wl, cfg["k"]), want)
    assert max(errs.values()) > cfg["limits"]["field_rel_err"]
    same = field_errors(field_lowp(ref, part, wl, cfg["k"], "float32"), want)
    assert max(same.values()) < cfg["limits"]["field_rel_err"]


@pytest.mark.parametrize("name", ["provgen-1m", "musicbrainz-1m"])
def test_ascending_answer_control_fails_wrong_paths(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    labels, edges = config_edges(cfg, 9, N)
    ref = Ref(N, labels, edges, cfg["graph"]["labels"])
    wrong = sum(top_paths_ascending(ref, q, sm, 32) != ref.top_paths(q, sm, 32)
                for q, _, sm in workload_of(cfg))
    assert wrong > 0


def test_no_tpu_exits_nonzero_with_no_result(capsys):
    rc = harness.main(["--workload", "musicbrainz-1m.invoke", "--seed",
                       str(2 ** 32 + 3), "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""
