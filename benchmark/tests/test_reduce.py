"""The trace reduction and the statistics, on hand-made intervals and on
the small recorded trace beside this file (one traced `pio train` job of
a rehearsal-sized run on the v5e, PR 26)."""

import math
import os

import pytest

import reduce

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded.xplane.pb")


def test_union_merges_nested_and_touching():
    assert reduce.union([(0, 2), (1, 3), (5, 6), (5.5, 5.7), (6, 7)]) == \
        [(0, 3), (5, 7)]
    assert reduce.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]


def hand_trace():
    ops = [("fusion.1", 1.0, 1.0), ("while.2", 3.0, 4.0),
           ("fusion.3", 3.5, 1.0), ("fusion.3", 5.0, 1.5),
           ("copy.4", 9.0, 0.5)]
    modules = [("jit_prep(11)", 1.0, 1.0), ("jit_train(12)", 3.0, 4.0),
               ("jit_train(12)", 9.0, 0.5)]
    return {"planes": {"/device:TPU:0": {reduce.OPS_LINE: ops,
                                         reduce.MODULES_LINE: modules}},
            "spans": [("window", 0.0, 10.0), ("job0", 0.9, 6.1),
                      ("job1", 8.0, 2.0)]}


def test_summary_busy_idle_programs_and_gaps():
    s = reduce.summarize_trace(hand_trace())
    assert s["window_s"] == 10.0
    # busy: [1,2] + [3,7] + [9,9.5]
    assert s["busy_s"] == pytest.approx(5.5)
    assert s["programs"]["jit_train"] == [pytest.approx(4.5), 2]
    assert s["programs"]["jit_prep"] == [pytest.approx(1.0), 1]
    # the while that contains ops is not ranked beside them
    assert not any("while.2" in k for k in s["ops"])
    assert s["ops"]["jit_train/fusion.3"] == pytest.approx(2.5)
    assert s["ops"]["jit_prep/fusion.1"] == pytest.approx(1.0)
    assert reduce.op_name(
        "%fusion.25 = s32[13369344]{0:T(1024)} fusion(s32[1]{0} %x), kind=kCustom"
    ) == "fusion.25 s32[13369344]"
    gaps = dict(s["gap_seconds_by_span"])
    assert gaps["job0"] == pytest.approx(1.0)            # [2, 3]
    assert gaps["outside_any_span"] == pytest.approx(1.0)  # [0, 1]
    # a gap goes whole to the span its middle lies in: [7, 9] and [9.5, 10]
    assert gaps["job1"] == pytest.approx(2.5)
    idle = reduce.reduce_busy_union({}, {"trace": s})
    assert idle == pytest.approx(45.0)


def test_reducers_leave_out_what_they_cannot_read():
    s = reduce.summarize_trace(hand_trace())
    facts = {"trace": s, "jobs": 2, "phase.persist_s": [0.1, 0.3],
             "iterations_per_call": 10}
    per_job = {"terms": [{"fact": "phase.persist_s"}],
               "over": {"fact": "jobs"}}
    assert reduce.reduce_sum(per_job, facts) == pytest.approx(0.2)
    per_iter = {"terms": [{"program_s": "train"}], "scale": 1000.0,
                "over": {"program_n": "train",
                         "times_fact": "iterations_per_call"}}
    assert reduce.reduce_sum(per_iter, facts) == pytest.approx(225.0)
    assert reduce.reduce_sum({"terms": [{"program_s": "nothing"}]},
                             facts) is None
    assert reduce.reduce_sum({"terms": [{"fact": "absent"}]}, facts) is None
    assert reduce.reduce_busy_union({}, {"trace": None}) is None
    specs = [{"name": "a", "unit": "s", "reducer": "sum", "args": per_job},
             {"name": "b", "unit": "%", "reducer": "sum",
              "args": {"terms": [{"fact": "absent"}]}}]
    assert reduce.layer_metrics(specs, facts) == \
        {"a": {"value": pytest.approx(0.2), "unit": "s"}}


def test_span_terms_read_the_programs_annotations():
    """seconds and count of the bench:<span> annotations that start in
    the window, whole, as a program's calls are; `window` is no span."""
    t = hand_trace()
    t["spans"] += [("flush", 1.0, 0.5), ("flush", 4.0, 1.5),
                   ("enqueue", 4.1, 0.2), ("flush", 9.9, 0.4),
                   ("flush", 10.0, 1.0), ("flush", -0.2, 0.5)]
    s = reduce.summarize_trace(t)
    assert s["spans"]["flush"] == [pytest.approx(2.4), 3]
    assert s["spans"]["enqueue"] == [pytest.approx(0.2), 1]
    assert "window" not in s["spans"] and s["spans"]["job0"][1] == 1
    facts = {"trace": s}
    per_flush = {"terms": [{"span_s": "^flush$"}], "scale": 1000.0,
                 "over": {"span_n": "^flush$"}}
    assert reduce.reduce_sum(per_flush, facts) == pytest.approx(800.0)
    outside = {"terms": [{"span_s": "^flush$"}, {"span_s": "enqueue",
                                                 "sign": -1}]}
    assert reduce.reduce_sum(outside, facts) == pytest.approx(2.2)
    assert reduce.reduce_sum({"terms": [{"span_s": "absent"}]}, facts) is None
    assert reduce.reduce_sum(per_flush, {"trace": None}) is None
    with pytest.raises(SystemExit):
        reduce.reduce_sum({"terms": [{"spam_s": "flush"}]}, facts)


def test_a_capture_with_no_device_plane_keeps_its_spans():
    """A rehearsal's capture on the CPU: the spans are read, and the
    busy time is 0, which a cell refuses from a chip."""
    t = {"planes": {}, "spans": [("window", 2.0, 4.0), ("flush", 3.0, 0.5)]}
    s = reduce.summarize_trace(t, unnamed_gap="between_flushes")
    assert (s["window_s"], s["busy_s"], s["devices"]) == (4.0, 0.0, 0)
    assert s["spans"] == {"flush": [0.5, 1]} and s["programs"] == {}
    assert reduce.summarize_trace({"planes": {}, "spans": []}) is None
    assert reduce.summarize_trace(
        {"planes": {}, "spans": [("flush", 3.0, 0.5)]}) is None


def test_counter_terms_difference_the_status_page():
    """`counter`: a dotted path into `GET /`, last reading of the window
    less the first; `traced_counter`: between the trace's marks."""
    def page(planned, queries, hist):
        return {"codec": {"plans": 3, "requests": {"planned": planned,
                                                   "reflected": 0}},
                "batching": {"enabled": True, "queries": queries,
                             "bucketHist": hist, "layout": "replicated"}}

    facts = {"counters": {
        "window": [page(100, 90, {"64": 2}), page(1100, 1090, {"64": 22})],
        "traced": [page(300, 290, {"64": 6}), page(500, 490, {"64": 10})]}}
    term = reduce._term
    assert term({"counter": "codec.requests.planned"}, facts) == 1000.0
    assert term({"traced_counter": "codec.requests.planned"}, facts) == 200.0
    assert term({"counter": "batching.bucketHist.64"}, facts) == 20.0
    assert term({"counter": "codec.requests.reflected"}, facts) == 0.0
    share = {"terms": [{"counter": "codec.requests.planned"}],
             "over": {"counter": "batching.queries"}}
    assert reduce.reduce_sum(share, facts) == pytest.approx(1.0)
    # nothing to read: no such path, not a number, a flag, no marks
    for path in ("codec.requests.absent", "batching.layout",
                 "batching.enabled", "batching", "codec.plans.deeper"):
        assert term({"counter": path}, facts) is None, path
    assert term({"traced_counter": "codec.plans"},
                {"counters": {"window": facts["counters"]["window"]}}) is None
    assert term({"counter": "codec.plans"}, {}) is None


def test_a_cost_function_is_found_in_kernel_costs_first_then_by_file():
    import kernel_costs

    assert reduce.cost_function("topk_flush") is kernel_costs.topk_flush
    # tests/test_cells.py has one found under costs/ in a copy that
    # brings the file; here there is none of that name
    with pytest.raises(SystemExit):
        reduce.cost_function("no_such_kernel")
    facts = {"trace": reduce.summarize_trace(hand_trace()),
             "peaks": {"flops_bf16": 1.0}, "chips": 1, "config": {}}
    with pytest.raises(SystemExit):
        reduce.reduce_mfu({"seconds": {"program_s": "train"},
                           "cost": "no_such_kernel"}, facts)
    # only a function kernel_costs.py defines is found there: a private
    # helper is one, a name it merely holds (a module, a constant a
    # later edit imports) would shadow costs/<name>.py and is passed by
    kernel_costs.shadow = math
    kernel_costs.borrowed = math.sqrt
    try:
        for name in ("shadow", "borrowed", "__doc__"):
            with pytest.raises(SystemExit):
                reduce.cost_function(name)
    finally:
        del kernel_costs.shadow, kernel_costs.borrowed


def test_a_share_of_a_peak_wants_the_peaks_or_a_rehearsals_word():
    """A run on a chip that forgot the table of peaks is an error, as it
    was; a rehearsal says `peaks` None and the share is left out."""
    facts = {"trace": reduce.summarize_trace(hand_trace()), "chips": 1,
             "config": {"data": {"n_users": 100, "n_items": 50, "nnz": 1000},
                        "engine_params": {"rank": 2, "numIterations": 10}}}
    roof = {"program": "train", "cost": "als_program", "peak": "flops_fp32"}
    mfu = {"seconds": {"program_s": "train"}, "cost": "als_program"}
    for reducer, args in ((reduce.reduce_roofline_share, roof),
                          (reduce.reduce_mfu, mfu)):
        with pytest.raises(KeyError):
            reducer(args, facts)
        assert reducer(args, {**facts, "peaks": None}) is None


def test_roofline_share_and_mfu_from_costs():
    s = reduce.summarize_trace(hand_trace())
    config = {"data": {"n_users": 100, "n_items": 50, "nnz": 1000},
              "engine_params": {"rank": 2, "numIterations": 10}}
    peaks = {"flops_fp32": 1e6, "flops_bf16": 6e6, "hbm_bytes_per_s": 1e6}
    facts = {"trace": s, "config": config, "peaks": peaks, "chips": 1,
             "traced_jobs": 2, "traced.job_s": [5.0, 5.0]}
    args = {"program": "train", "cost": "als_program", "peak": "flops_fp32"}
    # ops/call: 10 * (2*2*6*1000 + 150*(8/3+8)) = 256000; bytes/call:
    # 10 * (2*1000*16 + 8*150) = 332000 -> bytes bound, 0.332 s a call
    share = reduce.reduce_roofline_share(args, facts)
    assert share == pytest.approx(100 * 0.332 * 2 / 4.5)
    assert facts["bounds"]["train"] == "bytes"
    mfu = reduce.reduce_mfu({"seconds": {"fact": "traced.job_s"},
                             "cost": "als_jobs", "peak": "flops_bf16"}, facts)
    assert mfu == pytest.approx(100 * 2 * 256000 / (10.0 * 6e6))


def test_percentile_counts_failures_as_the_worst():
    lat = [10.0, 20.0, 30.0, 40.0]
    assert reduce.percentile_all(lat, 0, 50) == 20.0
    assert reduce.percentile_all(lat, 0, 95) == 40.0
    # one failure of five requests: it is the 5th of 5, so p95 is it
    assert math.isinf(reduce.percentile_all(lat, 1, 95))
    assert reduce.percentile_all(lat, 1, 80) == 40.0
    assert reduce.percentile_all([], 0, 95) is None


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace checked in")
def test_recorded_trace_reduces():
    trace = reduce.read_xplane(RECORDED)
    assert trace["planes"], "no device plane in the recorded trace"
    s = reduce.summarize_trace(trace)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert any("train" in name for name in s["programs"])
    total = sum(sec for sec, _n in s["programs"].values())
    assert total <= s["busy_s"] * 1.02 + 1e-6
    b = reduce.breakdown(s)
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
