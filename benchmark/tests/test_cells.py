"""Every cell end to end on the CPU at rehearsal size, through run.py's
own children: the last line's shape, the control (the reference in the
precision below, in the program's place, has to fail one of the cell's
numbers while the program passes them all), and the faults a cell can
have, planted under the timed path (`correct` has to come out false).

Slow for a unit test (about 20 s a case): each case is a whole run."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

import compare

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
LIVE = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]
# a parked cell's files are all here; only its entries are out of
# BENCHMARK.json (parked/<cell>.json holds them and says why)
PARKED = sorted(f[:-len(".json")]
                for f in os.listdir(os.path.join(BENCH, "parked")))
CELLS = LIVE + PARKED


def files_under(top):
    """{relative path: SHA-256} of every file under `top`."""
    out = {}
    for folder, _dirs, names in os.walk(top):
        if "__pycache__" in folder:
            continue
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def checkout_with(dest, entries, files=None, overlay=None):
    """A copy of the benchmark beside the program, with `entries` added
    to BENCHMARK.json, `files` written and the tree `overlay` laid over
    benchmark/: what a later PR's tree is. No file that was there is
    written to."""
    shutil.copytree(BENCH, dest / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "predictionio_tpu"),
               dest / "predictionio_tpu")
    new = {rel: json.dumps(content) for rel, content in (files or {}).items()}
    if overlay:
        top = os.path.join(ROOT, overlay)
        for rel in files_under(top):
            with open(os.path.join(top, rel)) as f:
                new[os.path.join("benchmark", rel)] = f.read()
    for rel, text in new.items():
        path = dest / rel
        assert not path.exists(), f"{rel} would edit a file that is there"
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)
    bj = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for key, more in entries.items():
        bj[key] += more
    (dest / "BENCHMARK.json").write_text(json.dumps(bj))
    return str(dest)


@pytest.fixture(scope="session")
def root_of(tmp_path_factory):
    """cell -> the checkout that holds it."""
    roots = {c: ROOT for c in LIVE}
    for c in PARKED:
        parked = json.load(open(os.path.join(BENCH, "parked", c + ".json")))
        roots[c] = checkout_with(tmp_path_factory.mktemp(c),
                                 parked["BENCHMARK.json"])
    return roots


def run(*argv, root=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, ".jax_cache"))
    env.pop("BENCH_REHEARSE", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *argv],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    lines = {}
    for line in proc.stderr.splitlines():
        if line.startswith("{"):
            obj = json.loads(line)
            lines[obj["phase"]] = obj
    return proc, lines


def limits_of(cell, root):
    bj = json.load(open(os.path.join(root, "BENCHMARK.json")))
    w = {x["name"]: x for x in bj["workloads"]}[cell]
    c = {x["name"]: x for x in bj["configs"]}[w["config"]]
    return json.load(open(os.path.join(root, c["file"])))["limits"]


HOST_TERMS = {"counter", "traced_counter", "span_s", "span_n"}


def metrics_a_rehearsal_reads(cell, root):
    """The cell's per-layer metrics that need no chip: sums whose terms
    are all counters of the program's status page or host spans of its
    capture. A traced rehearsal has both from a real deploy, so a
    counter's path or a span's name the program no longer has shows as
    a metric that found nothing to read."""
    bj = json.load(open(os.path.join(root, "BENCHMARK.json")))
    out = set()
    for m in bj["per_layer"]:
        if cell not in m.get("workloads", [cell]):
            continue
        spec = json.load(open(os.path.join(root, "benchmark", "metrics",
                                           m["name"] + ".json")))
        args = spec.get("args", {})
        terms = args.get("terms", []) + [args[k] for k in ("over",)
                                         if k in args]
        if spec["reducer"] == "sum" and terms and all(
                set(t) - {"sign"} <= HOST_TERMS for t in terms):
            out.add(m["name"])
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_without_a_chip_no_result(cell, root_of):
    proc, _ = run("--workload", cell, "--seed", "3", "--seconds", "1",
                  root=root_of[cell])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_passes_and_control_fails(cell, root_of):
    proc, lines = run("--workload", cell, "--seed", str(2**31 + 11),
                      "--trace", "1", "--rehearse", "--control",
                      root=root_of[cell])
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert proc.stdout.strip() == "", "a rehearsal printed a result"
    assert lines["rehearsal"]["ok"] is True, lines["rehearsal"]
    ref = lines["train:reference" if cell.startswith("train")
                else "serve:checked"]
    limits = limits_of(cell, root_of[cell])
    ok, _ = compare.judge({**ref["program"], "compiles_in_window": 0,
                           "bad_replies": ref["program"].get(
                               "bad_replies", 0)}, limits)
    assert ok
    ok, compared = compare.judge({**ref["control"], "compiles_in_window": 0},
                                 limits)
    assert not ok, f"the control passed every limit: {compared}"
    if not cell.startswith("train"):
        assert metrics_a_rehearsal_reads(cell, root_of[cell]) <= set(
            lines["rehearsal"]["layer_metrics"])


FAULTS = [(c, f) for c in CELLS for f in
          (("unchanged_state", "half_batch") if c.startswith("train")
           else ("altered_answer",))]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_under_the_timed_path_is_not_correct(cell, fault, root_of):
    proc, lines = run("--workload", cell, "--seed", "17", "--rehearse",
                      "--fault", fault, root=root_of[cell])
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert lines["rehearsal"]["ok"] is False, lines["rehearsal"]


def test_a_cell_is_added_with_new_files_only(tmp_path):
    """README's worked examples: a traffic mix, a configuration, a
    per-layer metric and the cell that uses them, as new files plus new
    entries of BENCHMARK.json; no file that was there is edited."""
    examples = json.load(open(os.path.join(BENCH, "README.examples.json")))
    root = checkout_with(tmp_path / "checkout", examples["BENCHMARK.json"],
                         examples["files"])
    cell = examples["BENCHMARK.json"]["workloads"][0]["name"]
    proc, lines = run("--workload", cell, "--seed", "5", "--trace", "1",
                      "--rehearse", root=root)
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert lines["rehearsal"]["ok"] is True


def test_a_cell_of_another_engine_is_added_with_new_files_only(tmp_path):
    """The seam's proof is the engine the room is for, at toy size:
    models/ecommerce with events and a constraint in the event store,
    its adapter, reference, cost function, configuration, traffic mix
    and three per-layer metrics laid over a copy as new files, plus new
    entries of BENCHMARK.json. It rehearses through every child, its
    control fails, and so do the two faults its rules can have."""
    example = json.load(open(os.path.join(
        BENCH, "README.examples.json")))["another_engine"]
    root = checkout_with(tmp_path / "checkout", example["BENCHMARK.json"],
                         overlay=example["overlay"])
    was, now = files_under(BENCH), files_under(os.path.join(root, "benchmark"))
    assert {rel: now[rel] for rel in was} == was, "a file that was there differs"
    added = sorted(set(now) - set(was))
    assert {rel.split(os.sep)[0] for rel in added} == {
        "adapters", "reference", "costs", "configs", "engines", "traffic",
        "metrics", "pins"}, added
    cell = example["BENCHMARK.json"]["workloads"][0]["name"]
    metrics = [m["name"] for m in example["BENCHMARK.json"]["per_layer"]]

    proc, lines = run("--workload", cell, "--seed", str(2**31 + 5),
                      "--trace", "1", "--rehearse", "--control", root=root)
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert proc.stdout.strip() == ""
    assert lines["rehearsal"]["ok"] is True, lines["rehearsal"]
    assert lines["serve:window"]["failed"] == 0
    limits = limits_of(cell, root)
    ref = lines["serve:checked"]
    assert ref["checked"] >= 64
    assert compare.judge({**ref["program"], "compiles_in_window": 0},
                         limits)[0]
    ok, compared = compare.judge({**ref["control"], "compiles_in_window": 0},
                                 limits)
    assert not ok, f"the control passed every limit: {compared}"
    # the counter terms and the span terms found something to read, in
    # the page and the capture of a real deploy; the third metric wants
    # a chip's peak, which a rehearsal has not
    assert set(lines["rehearsal"]["layer_metrics"]) == set(metrics[:2])
    assert metrics_a_rehearsal_reads(cell, root) == set(metrics[:2])

    # the tests that pin what the cells send and compare pass in the
    # copy as they are: the cells that were there, and the new cell by
    # the one file it brought (its compared names are read off the
    # rehearsal above; the other cells' rehearsals run in ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-k", "not names_compared",
         os.path.join(root, "benchmark", "tests", "test_pins.py")],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-3000:]
    assert "9 passed" in out.stdout, out.stdout[-500:]
    pin = json.load(open(os.path.join(root, "benchmark", "pins",
                                      cell + ".json")))
    assert pin["seed"] == 2**31 + 5
    assert list(lines["rehearsal"]["compared"]) == pin["compared"]
    assert ref["checked"] == pin["checked"]

    # the cost function is found by file, from the copy's own reduce.py
    facts = {"chips": 1, "traced_queries": 100, "peaks": {"flops_bf16": 1e9},
             "trace": {"spans": {"flush": [0.5, 20], "wake": [0.1, 20]},
                       "programs": {}}}
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, sys, harness, reduce\n"
         "spec = harness.load_cell(sys.argv[1])\n"
         "facts = dict(json.loads(sys.argv[2]), config=spec['config'])\n"
         "print(json.dumps(reduce.layer_metrics(spec['per_layer'], facts)))",
         cell, json.dumps(facts)],
        cwd=os.path.join(root, "benchmark"), capture_output=True, text=True,
        timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout)
    # 2 * 100 queries * 500 items * rank 8 over 0.5 s x 1e9/s
    assert got[metrics[2]]["value"] == pytest.approx(100 * 8e5 / 5e8)
    assert got[metrics[1]]["value"] == pytest.approx(25.0)
    assert metrics[0] not in got        # no page in these facts

    for fault, number in (("altered_answer", "rank_gap"),
                          ("ignored_seen_filter", "filter_leaks")):
        proc, lines = run("--workload", cell, "--seed", "17", "--rehearse",
                          "--fault", fault, root=root)
        assert proc.returncode == 3, proc.stderr[-3000:]
        assert lines["rehearsal"]["ok"] is False, (fault, lines["rehearsal"])
        assert lines["rehearsal"]["numbers"][number] > limits[number], fault
