"""Every cell end to end on the CPU at rehearsal size, through run.py's
own children: the last line's shape, the control (the reference in the
precision below, in the program's place, has to fail one of the cell's
numbers while the program passes them all), and the faults a cell can
have, planted under the timed path (`correct` has to come out false).

Slow for a unit test (about 20 s a case): each case is a whole run."""

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


def checkout_with(dest, entries, files=None):
    """A copy of the benchmark beside the program, with `entries` added
    to BENCHMARK.json and `files` written: what a later PR's tree is."""
    shutil.copytree(BENCH, dest / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "predictionio_tpu"),
               dest / "predictionio_tpu")
    for rel, content in (files or {}).items():
        path = dest / rel
        assert not path.exists(), f"{rel} would edit a file that is there"
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(content))
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
