"""The readers of the swap's phase spans and the field's fetch span, on a
hand-built view and on a traced run of an invoke cell at a small size."""
import json

import pytest

import run as harness
from common import Run
from conftest import BENCH

NEW = ["swap_prepare_s_per_iter", "swap_walk_s_per_iter",
       "swap_family_s_per_iter", "swap_stale_row_pct", "field_fetch_ms",
       "field_fetch_mb"]


def reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py",
                               "metric_" + name)


def span(name, t0, t1, **attrs):
    return {"name": name, "t0": t0, "t1": t1, "duration_s": t1 - t0,
            "attrs": attrs}


def walk(t0, t1, singles, stale, family_s):
    return span("swap.walk", t0, t1, singles_visited=singles,
                stale_rows=stale, family_walks=3, family_members=7,
                family_s=family_s)


def view(spans):
    run = Run(spans=spans, notes={"window_monotonic": [10.0, 100.0]})
    return harness.View(run, {}, None)


SPANS = [
    span("swap.prepare", 11.0, 12.0),
    walk(12.0, 15.0, singles=100, stale=20, family_s=0.5),
    span("swap.prepare", 20.0, 23.0),
    walk(23.0, 24.0, singles=300, stale=40, family_s=0.25),
    span("field.fetch", 30.0, 30.05, bytes=150_000_000),
    span("field.fetch", 40.0, 40.07, bytes=160_000_000),
    # outside the window: never read
    span("swap.prepare", 1.0, 9.0),
    walk(101.0, 190.0, singles=1, stale=1, family_s=80.0),
    span("field.fetch", 5.0, 9.0, bytes=1),
]
EXPECTED = {"swap_prepare_s_per_iter": 2.0, "swap_walk_s_per_iter": 2.0,
            "swap_family_s_per_iter": 0.375, "swap_stale_row_pct": 15.0,
            "field_fetch_ms": 60.0, "field_fetch_mb": 155.0}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_hand_built_view(name):
    assert reader(name).read(view(SPANS)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_without_its_spans(name):
    # the spans a program without the phase spans has
    old = [span("invocation.swap", 11.0, 15.0, moves=10),
           span("invocation.field", 16.0, 17.0, backend="jnp")]
    assert reader(name).read(view(old)) is None


def test_stale_share_reads_nothing_without_singles():
    spans = [walk(12.0, 15.0, singles=0, stale=0, family_s=0.5)]
    assert reader("swap_stale_row_pct").read(view(spans)) is None


def test_traced_invoke_run_reports_the_phase_metrics():
    """A whole traced run on the CPU at n = 3,000: the six metrics are in
    its line, and the phases lie inside the swap they split."""
    cfg = json.loads((BENCH / "configs" / "musicbrainz-1m.json").read_text())
    traffic = json.loads((BENCH / "traffic" / "invoke.json").read_text())
    cell = {"name": "musicbrainz-1m.invoke", "config": "musicbrainz-1m",
            "chips": 1}
    out = harness.run_cell(cell["name"], cell, cfg, traffic, seed=2 ** 33 + 7,
                           seconds=1.0, trace=True, peak=None, n=3000)
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW) <= set(m)
    assert m["swap_prepare_s_per_iter"] + m["swap_walk_s_per_iter"] \
        <= m["swap_s_per_iter"]
    assert m["swap_family_s_per_iter"] <= m["swap_walk_s_per_iter"]
    assert 0.0 <= m["swap_stale_row_pct"] <= 100.0
    assert m["field_fetch_ms"] <= 1e3 * m["field_s_per_iter"]
    assert m["field_fetch_mb"] > 0
