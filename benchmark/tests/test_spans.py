"""The program's host spans under the unchanged reader (PR 27): the
batcher worker's stages, as `predictionio_tpu` emits them, come back from
`reduce.read_xplane`; `summarize_trace` puts every idle gap of the device
down to the stage it lies in, and `between_flushes` is left with what no
span covers; the two `batcher.flush_host_ms.*` metrics load by name and
reduce from the facts a serving cell offers."""

import glob
import json
import os
import sys
import time

import pytest

import harness
import reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
UNNAMED = "between_flushes"      # what reduce_child.py passes

# one worker's line over a 4 s window, three flushes: A and B back to
# back, then an idle wait, then C. (name, start, length), nested as the
# program nests them: flush > dispatch > execute > enqueue | device_get.
FLUSHES = {"A": 0.40, "B": 1.10, "C": 2.55}
DEVICE = [(0.50, 0.20), (0.72, 0.28),    # A: two ops, 20 ms apart
          (1.21, 0.39),                  # B
          (2.65, 0.45)]                  # C


def worker_spans():
    spans = [("window", 0.0, 4.0)]
    for t in FLUSHES.values():
        spans += [("form_batch", t - 0.02, 0.02), ("flush", t, 0.66),
                  ("supplement", t + 0.001, 0.009),
                  ("dispatch", t + 0.01, 0.63), ("pad", t + 0.011, 0.009),
                  ("execute", t + 0.02, 0.60), ("enqueue", t + 0.02, 0.07),
                  ("device_get", t + 0.09, 0.53),
                  ("unpack", t + 0.62, 0.02), ("merge", t + 0.64, 0.02),
                  ("wake", t + 0.66, 0.02)]
    # A's wake ends at 1.08 where B's form_batch starts; after B the
    # queue is empty until 2.50, then a head waits 30 ms for riders
    spans += [("idle_wait", 1.78, 0.72), ("fill_wait", 2.50, 0.03),
              ("idle_wait", 3.23, 0.60)]
    return sorted(spans, key=lambda s: s[1])


def trace():
    ops = [(f"%sort.{k} = f32[64,100]{{1,0}} sort(f32[64,100] %x)", s, d)
           for k, (s, d) in enumerate(DEVICE)]
    mods = [("jit_topk_for_users(7)", 0.50, 0.50),
            ("jit_topk_for_users(7)", 1.21, 0.39),
            ("jit_topk_for_users(7)", 2.65, 0.45)]
    return {"planes": {"/device:TPU:0": {reduce.OPS_LINE: ops,
                                         reduce.MODULES_LINE: mods}},
            "spans": worker_spans()}


def test_every_gap_lands_under_the_stage_it_lies_in():
    s = reduce.summarize_trace(trace(), unnamed_gap=UNNAMED)
    assert s["window_s"] == 4.0
    assert s["busy_s"] == pytest.approx(0.20 + 0.28 + 0.39 + 0.45)
    gaps = dict(s["gap_seconds_by_span"])
    # [0, 0.5]: the middle, 0.25, is before the first span of the
    # capture (the idle wait that began before it is lost): unnamed
    assert gaps.pop(UNNAMED) == pytest.approx(0.50)
    # [0.70, 0.72] inside program A: the worker is in device_get
    # [1.00, 1.21] between A and B: the middle, 1.105, is in B's flush,
    # and the innermost span around it is `supplement`
    assert gaps.pop("supplement") == pytest.approx(0.21)
    assert gaps.pop("device_get") == pytest.approx(0.02)
    # [1.60, 2.65]: the middle, 2.125, is the idle wait after B
    # [3.10, 4.00]: the middle, 3.55, is the next idle wait
    assert gaps.pop("idle_wait") == pytest.approx(1.05 + 0.90)
    assert gaps == {}
    b = reduce.breakdown(s)
    assert [n for n, _t in b["idle_gaps"]] == \
        ["idle_wait", UNNAMED, "supplement", "device_get"]
    assert s["programs"]["jit_topk_for_users"] == [pytest.approx(1.34), 3]


def test_without_the_programs_spans_one_line_is_left():
    """What the parent gives the same reader: every gap unnamed."""
    t = trace()
    t["spans"] = [sp for sp in t["spans"] if sp[0] == "window"]
    s = reduce.summarize_trace(t, unnamed_gap=UNNAMED)
    assert list(s["gap_seconds_by_span"]) == [UNNAMED]
    assert s["gap_seconds_by_span"][UNNAMED] == \
        pytest.approx(4.0 - s["busy_s"])


def test_the_reader_reads_what_the_program_emits(tmp_path):
    """A flush through the program's own batcher under a CPU capture with
    child_serve.py's profiler options: the unchanged read_xplane returns
    the worker's stages, nested, and nothing named `window`."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    from predictionio_tpu.common import profiling
    from predictionio_tpu.serving import MicroBatcher

    if not hasattr(profiling, "annotate"):
        pytest.skip("this program emits no host spans")
    assert profiling.DEVICE_TRACE_PREFIX == reduce.SPAN_PREFIX
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        def flush(items):
            time.sleep(0.003)
            return list(items)

        batcher = MicroBatcher(flush, max_batch_size=2, max_delay_ms=5.0)
        assert batcher.submit("q") == "q"
        assert batcher.submit("r") == "r"
    finally:
        jax.profiler.stop_trace()
        batcher.close()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    spans = reduce.read_xplane(path)["spans"]
    names = [n for n, _s, _d in spans]
    for stage in ("idle_wait", "fill_wait", "form_batch", "flush", "wake"):
        assert names.count(stage) >= 2, (stage, names)
    assert "window" not in names
    # in time order a cycle reads idle, fill, form, flush, wake
    first = names.index("form_batch")
    assert names[first:first + 3] == ["form_batch", "flush", "wake"]


NEW = {"batcher.flush_host_ms.rate": "serve.amazon-r128.closed128",
       "batcher.flush_host_ms.p95": "serve.amazon-r128.steady"}


@pytest.mark.parametrize("metric", sorted(NEW))
def test_flush_host_ms_loads_by_name_and_reduces(metric):
    spec = harness.load_cell(NEW[metric])
    mine = [m for m in spec["per_layer"] if m["name"] == metric]
    assert len(mine) == 1, "the cell does not list the metric"
    m = mine[0]
    assert (m["unit"], m["better"], m["source"], m["layer"]) == \
        ("ms/flush", "lower", "program_counter",
         "serving front serving/batcher.py")
    assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
    # the facts cell_serve.py offers, at the sizes of a chip run
    facts = {"flush_ms_total": 19931.5, "flushes": 40, "queries": 2497,
             "trace": None}
    out = reduce.layer_metrics([m], facts)
    assert out == {metric: {"value": pytest.approx(498.2875),
                            "unit": "ms/flush"}}
    # a window with no flush: nothing to read, left out, no error
    assert reduce.layer_metrics([m], {**facts, "flushes": None}) == {}
    # the other cell does not report it
    other = [c for c in NEW.values() if c != NEW[metric]][0]
    assert metric not in {x["name"]
                          for x in harness.load_cell(other)["per_layer"]}


def test_flush_host_ms_facts_are_what_a_rehearsal_offers():
    """cell_serve.py names its facts in a rehearsal's last log line."""
    import test_cells

    proc, lines = test_cells.run("--workload", NEW["batcher.flush_host_ms.rate"],
                                 "--seed", "27", "--rehearse")
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert {"flush_ms_total", "flushes"} <= \
        set(lines["rehearsal"]["facts_keys"])
    window = lines["serve:window"]["batching"]
    assert window["batches"] >= 1 and window["flush_s"] > 0


#: `per_layer` of BENCHMARK.json as accepted up to PR 33, in its order
ACCEPTED = [
    "deploy.ready_s",
    "batcher.flush_size.rate",
    "batcher.queue_wait_ms.rate",
    "batcher.flush_size.p95",
    "batcher.queue_wait_ms.p95",
    "topk.flush_device_ms.rate",
    "topk_flush_roofline",
    "topk.flush_device_ms.p95",
    "serve.flush_mfu.rate",
    "device.idle.rate",
    "device.idle.p95",
    "compile.in_window.rate",
    "compile.in_window.p95",
    "loadgen.late_ms.p95",
    "loadgen.offered_rate.p95",
    "batcher.flush_host_ms.rate",
    "batcher.flush_host_ms.p95",
    "sharded.flush_device_ms.rate"]


def test_benchmark_json_only_gained_entries():
    """Each accepted per-layer entry is where it was; what a later PR
    brings comes after them (the two of this file's PR, 27, came after
    `loadgen.offered_rate.p95`; PR 29's one after those)."""
    bj = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in bj["per_layer"]]
    assert names[:len(ACCEPTED)] == ACCEPTED
    at = ACCEPTED.index("loadgen.offered_rate.p95")
    assert ACCEPTED[at + 1:at + 3] == sorted(NEW, reverse=True)
    for n in names:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           n + ".json")), n
