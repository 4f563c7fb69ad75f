"""What a serving cell sends and what it compares, pinned a cell in a
file of its own, pins/<cell>.json: for the pin's seed the cell sends
byte-identical bodies in the same order and judges the same names
against its configuration's limits. The three cells of the adapter
seam's parent (02bbaeb, PR 33's tree, whose loadgen.py drew the users
and rendered the bodies itself) have theirs from that commit. A change
that moves these moves what every cell reads. A PR that adds a cell may
add the cell's pins as one more file; nothing here lists the cells."""

import hashlib
import os

import pytest

import cell_serve
import harness
import test_cells

PIN_DIR = os.path.join(harness.HERE, "pins")
PINS = {f[:-len(".json")]: harness.load_json(PIN_DIR, f)
        for f in sorted(os.listdir(PIN_DIR)) if f.endswith(".json")}
#: the cells BENCHMARK.json had at 02bbaeb
AT_THE_SEAM = ["serve.amazon-r128.closed128", "serve.amazon-r128.steady",
               "serve.amazon14-r128.sharded4.closed128"]


def bodies_sha(wire, queries):
    h = hashlib.sha256()
    for k in range(min(512, len(queries))):
        h.update(wire.body(queries[k]).encode() + b"\n")
    return h.hexdigest()


def test_the_seams_cells_keep_their_pins_and_every_pin_has_its_cell():
    live = [w["name"] for w in harness.benchmark_json()["workloads"]]
    assert set(AT_THE_SEAM) <= set(PINS)
    assert set(PINS) <= set(live), "a pin file of no cell"


@pytest.mark.parametrize("size", ["rehearse", "full"])
@pytest.mark.parametrize("cell", sorted(PINS))
def test_the_bodies_sent_are_the_pinned(cell, size):
    spec = harness.load_cell(cell)
    config, traffic = spec["config"], spec["traffic"]
    adapter = harness.adapter_of(config)
    model, seconds = config["model"], float(spec["run_seconds"])
    if size == "rehearse":
        model = adapter.rehearsal_model(model, traffic["rehearse_cut"])
        seconds = min(seconds, traffic["rehearse_seconds"])
    pin = PINS[cell]
    _due, asked, warm_up = cell_serve.offered(spec, adapter, model,
                                              pin["seed"], seconds)
    wire = adapter.wire(spec)
    assert wire.body(asked[0]) == pin[size]["first"]
    assert bodies_sha(wire, asked) == pin[size]["window"]
    assert bodies_sha(wire, warm_up) == pin[size]["warmup"]


def compared_as_pinned(cell, lines):
    """A rehearsal's log lines against the cell's pin: the names judged
    and their limits are the configuration's, in the pinned order."""
    pin = PINS[cell]
    compared = lines["rehearsal"]["compared"]
    assert list(compared) == pin["compared"]
    limits = harness.load_cell(cell)["config"]["limits"]
    assert {k: v[1] for k, v in compared.items()} == limits
    assert lines["rehearsal"]["ok"] is True
    assert lines["serve:checked"]["checked"] == pin["checked"]


@pytest.mark.parametrize("cell", sorted(PINS))
def test_the_names_compared_are_the_pinned(cell):
    """A rehearsal through every child: the records carry indices into
    the pinned queries."""
    proc, lines = test_cells.run("--workload", cell, "--seed",
                                 str(PINS[cell]["seed"]), "--rehearse",
                                 root=harness.ROOT)
    assert proc.returncode == 3, proc.stderr[-3000:]
    compared_as_pinned(cell, lines)
