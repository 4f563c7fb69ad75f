"""The cell `serve.simprod-amazon14-r128.closed128` beyond what
test_cells.py already holds every live cell to (a rehearsal through
every child with its control, `altered_answer`): the two faults its
rules can have, what it sends, pinned by hash, its cost function's
arithmetic, the model its adapter hands the deploy (what `train`
leaves, made without one Python object an item), and the layout it
holds the deploy to.

The pin is `tests/simprod_amazon14_pin.json` and not a file under
`pins/`, for the reason test_ecomm_cell.py gives: test_cells.py's
copy-with-an-overlay case counts the cases test_pins.py runs there
("9 passed"), and a file that is there is no PR's to edit but a
`benchmark` PR's. That PR moves this file to
`pins/serve.simprod-amazon14-r128.closed128.json` as it is (same keys)
and drops the second test below."""

import json
import os

import numpy as np
import pytest

import cell_serve
import harness
import reduce
import test_cells
from test_pins import bodies_sha

CELL = "serve.simprod-amazon14-r128.closed128"
PIN = harness.load_json(os.path.dirname(os.path.abspath(__file__)),
                        "simprod_amazon14_pin.json")


@pytest.mark.parametrize("fault,number", [
    ("ignored_categories", "filter_leaks"),
    ("own_items_served", "filter_leaks")])
def test_a_rule_ignored_is_not_correct(fault, number):
    proc, lines = test_cells.run("--workload", CELL, "--seed", "17",
                                 "--rehearse", "--fault", fault)
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert lines["rehearsal"]["ok"] is False, (fault, lines["rehearsal"])
    limits = test_cells.limits_of(CELL, test_cells.ROOT)
    assert lines["rehearsal"]["numbers"][number] > limits[number], fault


@pytest.mark.parametrize("size", ["rehearse", "full"])
def test_the_bodies_sent_are_the_pinned(size):
    spec = harness.load_cell(CELL)
    config, traffic = spec["config"], spec["traffic"]
    adapter = harness.adapter_of(config)
    model, seconds = config["model"], float(spec["run_seconds"])
    if size == "rehearse":
        model = adapter.rehearsal_model(model, traffic["rehearse_cut"])
        seconds = min(seconds, traffic["rehearse_seconds"])
    _due, asked, warm_up = cell_serve.offered(spec, adapter, model,
                                              PIN["seed"], seconds)
    wire = adapter.wire(spec)
    assert wire.body(asked[0]) == PIN[size]["first"]
    assert bodies_sha(wire, asked) == PIN[size]["window"]
    assert bodies_sha(wire, warm_up) == PIN[size]["warmup"]
    assert list(config["limits"]) == PIN["compared"]
    assert min(int(traffic["checked_replies"]), 256) == PIN["checked"]


def test_the_queries_are_the_mix_the_configuration_states():
    """1 item with probability 0.7, else 2 to 8, never a repeat, never
    more than the program's declared width; half with categories, a
    third with a black list, no white list."""
    spec = harness.load_cell(CELL)
    config = spec["config"]
    adapter = harness.adapter_of(config)
    q = config["query"]
    asked = [json.loads(text) for text in adapter.queries(
        spec, config["model"], 2**31 + 7, 20_000)]
    sizes = np.asarray([len(a["items"]) for a in asked])
    assert all(len(set(a["items"])) == len(a["items"]) for a in asked)
    assert sizes.min() == 1 and sizes.max() == q["items_max"] \
        == config["serving"]["query_width"]
    assert abs((sizes == 1).mean() - q["one_item_share"]) < 0.02
    assert abs(np.mean(["categories" in a for a in asked])
               - q["categories_share"]) < 0.02
    assert abs(np.mean(["blackList" in a for a in asked])
               - q["black_list_share"]) < 0.02
    assert not any("whiteList" in a for a in asked)
    assert all(a["num"] == q["num"] for a in asked)
    assert max(len(a.get("blackList", ())) for a in asked) \
        == q["black_list_max"]
    n = config["model"]["n_items"]
    assert all(0 <= int(i[1:]) < n for a in asked for i in a["items"])


def test_simprod_flush_cost_by_hand():
    config = {"model": {"n_items": 1000, "rank": 8, "n_categories": 40},
              "query": {"num": 10, "one_item_share": 0.5, "items_max": 6},
              "serving": {"bytes_per_element": 4}}
    cost = reduce.cost_function("simprod_flush")
    c = cost(config, {"mean_flush_rows": 5.0})
    assert c["ops_per_call"] == 2 * 5 * 1000 * 8
    # 41 bits of rules: two words an item; 0.5 + 0.5 * (2 + 6) / 2 = 2.5
    # query items a row
    assert c["bytes_per_call"] == (1000 * 8 * 4 + 2 * 4 * 5 * 1000
                                   + 1000 * (4 * 2 + 1)
                                   + 5 * 2.5 * 8 * 4 + 5 * 10 * 8)
    assert cost(config, {}) is None


def test_the_full_cells_least_flush_time_is_bytes_bound():
    """At 64 x 9,350,000 x 128 the flush moves 9.6 GB: 11.8 ms at the
    v5e's 819 GB/s, against 4.7 ms of float32 operations."""
    config = harness.load_cell(CELL)["config"]
    c = reduce.cost_function("simprod_flush")(config,
                                              {"mean_flush_rows": 64.0})
    peaks = harness.peaks_for("TPU v5 lite")
    t_bytes = c["bytes_per_call"] / peaks["hbm_bytes_per_s"]
    t_ops = c["ops_per_call"] / peaks["flops_fp32"]
    assert 0.0115 < t_bytes < 0.0120 and t_ops < t_bytes


def test_the_adapters_model_is_what_train_leaves(monkeypatch):
    """adapters/simprod_als.py models hands `pio deploy` an ALSModel in
    the one form `ALSAlgorithm.train` writes: unit rows, a vocabulary,
    and the items' categories as the rule words
    models/item_rules.py category_words makes of one Item an index,
    made here of one boolean vector a category. The same fields, the
    same bits, the same words."""
    import dataclasses

    from predictionio_tpu.models import item_rules
    from predictionio_tpu.models.similarproduct.als_algorithm import (
        ALSAlgorithm, ALSModel)
    from predictionio_tpu.models.similarproduct.engine import Item

    # `models` holds the child it runs in to the configuration's layout
    monkeypatch.setattr(ALSAlgorithm, "prepare_serving",
                        ALSAlgorithm.prepare_serving)
    adapter = harness.load_adapter("simprod_als")
    config = harness.load_cell(CELL)["config"]
    for n_categories in (24, 40):
        model = {**config["model"], "n_items": 3000,
                 "n_categories": n_categories}
        (made,) = adapter.models(config, model, 9, None, None)
        assert set(vars(made)) == {
            f.name for f in dataclasses.fields(ALSModel)}
        assert made.device is None and made.trained_mask.all()
        np.testing.assert_allclose(
            np.linalg.norm(made.product_features, axis=1), 1, rtol=1e-6)
        cats = adapter.item_categories(9, model)
        assert len(set(cats.tolist())) == n_categories  # none left out
        items = {i: Item(categories=(f"c{c}",)) for i, c in enumerate(cats)}
        due_bits, due_words = item_rules.category_words(items, 3000)
        assert made.category_bits == due_bits
        assert (made.rule_words == due_words).all()
        assert made.rule_words.dtype == due_words.dtype == np.uint32
        assert made.rule_words.shape == (-(-(n_categories + 1) // 32), 3000)


def test_a_deploy_on_another_layout_is_refused():
    """The configuration is the engine's device layout. A deploy that
    ends on the host arrays (on the CPU backend: a layout that cannot
    be placed) stops before `/readyz`, and nothing is measured."""
    proc, lines = test_cells.run("--workload", CELL, "--seed", "17",
                                 "--rehearse", "--fault", "host_layout")
    assert proc.returncode == 1, proc.stderr[-3000:]
    assert "batching.layout is 'host', not 'items+rules'" in proc.stderr
    assert "rehearsal" not in lines
