"""The field's byte/op count at known shapes."""
import pytest

from field_cost import field_cost, least_time


def test_counts_at_known_shapes():
    shapes = {"n": 10, "m": 40, "k": 2, "n_labels": 3, "n_nodes": 6,
              "depth_nodes": [1, 2, 2, 1]}
    c = field_cost(shapes)
    inputs = 2 * 40 + 10 + 30 + 3 + 10 + 12
    outputs = 60 + 10 + 40 + 10 + 10 + 20
    assert c["bytes"] == 4 * (inputs + outputs)
    assert c["flops"] == 6 * 40 * 3


def test_least_time_names_its_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert least_time({"bytes": 50.0, "flops": 100.0}, peak) == {
        "seconds": pytest.approx(5.0), "bound": "memory"}
    assert least_time({"bytes": 5.0, "flops": 1000.0}, peak) == {
        "seconds": pytest.approx(10.0), "bound": "compute"}


def test_provgen_paper_size_is_memory_bound_on_v5e():
    import json

    from conftest import BENCH

    peak = json.loads((BENCH / "peaks.json").read_text())["devices"][
        "TPU v5 lite"]
    shapes = {"n": 10 ** 6, "m": 11065708, "k": 8, "n_labels": 3,
              "n_nodes": 24, "depth_nodes": [1, 3, 5, 6, 5, 3, 1]}
    assert least_time(field_cost(shapes), peak)["bound"] == "memory"
