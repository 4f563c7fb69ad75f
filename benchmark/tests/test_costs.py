"""The kernel-cost functions against shapes worked by hand."""

import pytest

import kernel_costs


def test_als_iteration_by_hand():
    config = {"data": {"n_users": 3, "n_items": 2, "nnz": 5},
              "engine_params": {"rank": 2, "numIterations": 4}}
    c = kernel_costs.als_iteration(config)
    # Gram+rhs: 2 sides * 2 * (4+2) * 5 = 120; solves: 5 rows * (8/3 + 8)
    assert c["ops_per_call"] == pytest.approx(120 + 5 * (8 / 3 + 8))
    # 2 sides * 5 ratings * (8 + 4*2) B + factors written 4*2*5 B
    assert c["bytes_per_call"] == 2 * 5 * 16 + 40
    p = kernel_costs.als_program(config)
    assert p["ops_per_call"] == pytest.approx(4 * c["ops_per_call"])
    assert kernel_costs.als_jobs(config, {"traced_jobs": 3})["ops_total"] \
        == pytest.approx(3 * p["ops_per_call"])
    assert kernel_costs.als_jobs(config, {}) is None


def test_topk_flush_by_hand():
    config = {"model": {"n_items": 1000, "rank": 8}, "query": {"num": 10},
              "serving": {"bytes_per_element": 4}}
    c = kernel_costs.topk_flush(config, {"mean_flush_rows": 5.0})
    assert c["ops_per_call"] == 2 * 5 * 1000 * 8
    assert c["bytes_per_call"] == (1000 * 8 * 4 + 5 * 8 * 4
                                   + 2 * 4 * 5 * 1000 + 5 * 10 * 8)
    assert kernel_costs.topk_flush(config, {}) is None
    w = kernel_costs.topk_window(config, {"traced_queries": 7})
    assert w["ops_total"] == 2 * 7 * 1000 * 8


def test_ml20m_iteration_is_the_roadmap_figure():
    import json, os
    here = os.path.dirname(os.path.abspath(__file__))
    config = json.load(open(os.path.join(
        here, "..", "configs", "rec-als-ml20m-r10.json")))
    c = kernel_costs.als_iteration(config)
    # ROADMAP S4: 2*(r^2+r)*nnz a half-step = 4.4 GFLOP, 8.8 an iteration
    assert 8.8e9 < c["ops_per_call"] < 9.0e9
