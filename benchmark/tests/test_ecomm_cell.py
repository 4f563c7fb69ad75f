"""The cell `serve.ecomm-amazon-r128.closed128` beyond what test_cells.py
and test_pins.py already hold every live cell to (a rehearsal through
every child with its control, `altered_answer`): the two faults its
rules can have, and what it sends, pinned by hash.

The pin is `tests/ecomm_amazon_pin.json` and not a file under `pins/`:
test_cells.py's copy-with-an-overlay case counts the cases test_pins.py
runs there ("9 passed"), so a fifth file under `pins/` fails it, and a
file that is there is no PR's to edit but a `benchmark` PR's. That PR
moves this file to `pins/serve.ecomm-amazon-r128.closed128.json` as it
is (same keys) and drops the second test below."""

import os

import pytest

import cell_serve
import harness
import test_cells
from test_pins import bodies_sha

CELL = "serve.ecomm-amazon-r128.closed128"
PIN = harness.load_json(os.path.dirname(os.path.abspath(__file__)),
                        "ecomm_amazon_pin.json")


@pytest.mark.parametrize("fault,number", [
    ("ignored_seen_filter", "filter_leaks"),
    ("ignored_constraint", "filter_leaks")])
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
    # the cut's `first_queries` is every query the cell can send
    assert config["seen_events_written"]["first_queries"] == \
        len(asked) + len(warm_up)
