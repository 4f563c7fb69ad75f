"""Bench-trajectory tracker tests (tools/benchtrend.py).

Acceptance: `python -m predictionio_tpu.tools.benchtrend BENCH_r*.json`
prints a trend table over the historical rounds and exits nonzero on an
injected regression fixture; the comparability rules (metric-name
match, warm-cache-only warmup comparisons) keep the gate honest.
"""

import json

import pytest

from predictionio_tpu.tools import benchtrend


def _write_round(tmp_path, n, value, detail=None, metric="m_steady_s",
                 wrapper=True):
    body = {"metric": metric, "value": value, "unit": "s",
            "detail": detail or {}}
    payload = {"n": n, "cmd": "python bench.py", "rc": 0,
               "tail": "...", "parsed": body} if wrapper else body
    path = tmp_path / f"BENCH_r{n:02d}.json"
    path.write_text(json.dumps(payload))
    return str(path)


def test_loads_both_wrapper_and_bare_formats(tmp_path):
    p1 = _write_round(tmp_path, 1, 2.0, wrapper=True)
    p2 = _write_round(tmp_path, 2, 1.5, wrapper=False)
    rounds, skipped = benchtrend.load_rounds([p1, p2])
    assert not skipped
    assert [r["label"] for r in rounds] == ["r01", "r02"]
    assert [r["value"] for r in rounds] == [2.0, 1.5]


def test_unparseable_files_skipped_not_fatal(tmp_path):
    good = _write_round(tmp_path, 1, 2.0)
    bad = tmp_path / "BENCH_r02.json"
    bad.write_text("{not json")
    rounds, skipped = benchtrend.load_rounds([good, str(bad)])
    assert len(rounds) == 1 and skipped == [str(bad)]


def test_improving_series_passes_gate(tmp_path):
    paths = [_write_round(tmp_path, n, v, {"serve_http_p99_ms": p})
             for n, (v, p) in enumerate(
                 [(10.0, 2.0), (8.0, 1.8), (7.5, 1.9)], start=1)]
    rounds, _ = benchtrend.load_rounds(paths)
    assert benchtrend.gate(rounds) == []
    assert benchtrend.main(paths) == 0
    assert benchtrend.main(["--gate", *paths]) == 0


def test_injected_regression_fixture_exits_nonzero(tmp_path, capsys):
    paths = [_write_round(tmp_path, n, v)
             for n, v in enumerate([10.0, 8.0, 7.5], start=1)]
    # injected regression: 3x the best prior run's headline
    paths.append(_write_round(tmp_path, 4, 22.5))
    assert benchtrend.main(["--gate", *paths]) == 1
    err = capsys.readouterr().err
    assert "BENCHTREND GATE FAILED" in err and "value" in err
    # report-only mode still prints the table and exits 0
    assert benchtrend.main(paths) == 0
    out = capsys.readouterr().out
    assert "m_steady_s" in out and "r04" in out


def test_gate_honored_via_strict_env(tmp_path, monkeypatch):
    paths = [_write_round(tmp_path, 1, 10.0),
             _write_round(tmp_path, 2, 30.0)]
    monkeypatch.setenv("BENCH_STRICT_EXTRAS", "1")
    assert benchtrend.main(paths) == 1


def test_headline_only_compares_same_metric_name(tmp_path):
    # r01 measured a DIFFERENT headline (wallclock); a later steady-state
    # round must not be compared against it
    p1 = _write_round(tmp_path, 1, 1.0, metric="m_wallclock_s")
    p2 = _write_round(tmp_path, 2, 9.0, metric="m_steady_s")
    rounds, _ = benchtrend.load_rounds([p1, p2])
    assert benchtrend.gate(rounds) == []


def test_warmup_compile_only_compared_warm_cache(tmp_path):
    warm = {"compile_cache": {"before": {"entries": 100, "bytes": 1}}}
    cold = {"compile_cache": {"before": {"entries": 0, "bytes": 0}}}
    # cold round pays the full remote compile: NOT a regression
    paths = [
        _write_round(tmp_path, 1, 1.0, {"warmup_compile_s": 30.0, **warm}),
        _write_round(tmp_path, 2, 1.0, {"warmup_compile_s": 400.0, **cold}),
    ]
    rounds, _ = benchtrend.load_rounds(paths)
    assert benchtrend.gate(rounds) == []
    # two WARM rounds with a blowup between them: that IS a regression
    paths.append(_write_round(
        tmp_path, 3, 1.0, {"warmup_compile_s": 400.0, **warm}))
    rounds, _ = benchtrend.load_rounds(paths)
    failures = benchtrend.gate(rounds)
    assert any("warmup_compile_s" in f for f in failures)


def test_threshold_is_configurable(tmp_path):
    paths = [_write_round(tmp_path, 1, 10.0),
             _write_round(tmp_path, 2, 11.5)]   # +15%
    rounds, _ = benchtrend.load_rounds(paths)
    assert benchtrend.gate(rounds, threshold=0.25) == []
    assert len(benchtrend.gate(rounds, threshold=0.10)) == 1


def test_up_metrics_gate_on_decreases(tmp_path):
    paths = [
        _write_round(tmp_path, 1, 1.0, {"serve_batched_qps_gain": 3.0}),
        _write_round(tmp_path, 2, 1.0, {"serve_batched_qps_gain": 1.2}),
    ]
    rounds, _ = benchtrend.load_rounds(paths)
    failures = benchtrend.gate(rounds)
    assert any("serve_batched_qps_gain" in f for f in failures)


def test_gate_current_for_bench_wiring(tmp_path):
    history = [_write_round(tmp_path, n, v)
               for n, v in enumerate([10.0, 8.0], start=1)]
    current = {"metric": "m_steady_s", "value": 8.2,
               "detail": {"serve_http_p99_ms": 1.0}}
    failures, brief = benchtrend.gate_current(current, history)
    assert failures == []
    assert brief["value"]["best_prior"] == 8.0
    assert brief["value"]["current"] == 8.2
    current["value"] = 30.0
    failures, _brief = benchtrend.gate_current(current, history)
    assert failures and "value" in failures[0]


# ---------------------------------------------------------------------------
# ABSOLUTE_GATES: the warm-cache-only availability ceilings
# ---------------------------------------------------------------------------

_WARM = {"compile_cache": {"before": {"entries": 100, "bytes": 1}}}
_COLD = {"compile_cache": {"before": {"entries": 0, "bytes": 0}}}


def test_absolute_gate_fires_on_first_ever_warm_round(tmp_path):
    """The ceiling needs NO prior round: the very first warm-cache round
    is already accountable for the < 10 s warm-replica promise."""
    p = _write_round(tmp_path, 1, 1.0,
                     {"time_to_ready_s": 42.0, **_WARM})
    rounds, _ = benchtrend.load_rounds([p])
    failures = benchtrend.gate(rounds)
    assert len(failures) == 1
    assert "time_to_ready_s" in failures[0] and "ceiling" in failures[0]
    # and a first warm round UNDER the ceiling gates nothing
    ok = _write_round(tmp_path, 1, 1.0,
                      {"time_to_ready_s": 3.0, **_WARM})
    rounds, _ = benchtrend.load_rounds([ok])
    assert benchtrend.gate(rounds) == []


def test_absolute_gate_skips_cold_and_unknown_cache_rounds(tmp_path):
    # cold cache: full compiles are legitimate, not an availability breach
    cold = _write_round(tmp_path, 1, 1.0,
                        {"time_to_ready_s": 400.0, **_COLD})
    rounds, _ = benchtrend.load_rounds([cold])
    assert benchtrend.gate(rounds) == []
    # no compile_cache detail at all (pre-r05 era): unknown, never gated
    unknown = _write_round(tmp_path, 1, 1.0, {"time_to_ready_s": 400.0})
    rounds, _ = benchtrend.load_rounds([unknown])
    assert benchtrend.gate(rounds) == []


def test_absolute_gate_mixed_history_judges_newest_round_only(tmp_path):
    """Mixed warm/cold history: only the NEWEST round's own cache state
    decides whether its ceiling applies — a breaching warm round fails
    even after a cold round, and a cold newest round passes even after
    warm priors."""
    paths = [
        _write_round(tmp_path, 1, 1.0, {"time_to_ready_s": 3.0, **_WARM}),
        _write_round(tmp_path, 2, 1.0,
                     {"time_to_ready_s": 400.0, **_COLD}),
        _write_round(tmp_path, 3, 1.0, {"time_to_ready_s": 12.0, **_WARM}),
    ]
    rounds, _ = benchtrend.load_rounds(paths)
    failures = benchtrend.gate(rounds)
    assert any("time_to_ready_s" in f and "ceiling" in f
               for f in failures)
    # newest cold round after warm priors: the ceiling stands down
    paths.append(_write_round(tmp_path, 4, 1.0,
                              {"time_to_ready_s": 400.0, **_COLD}))
    rounds, _ = benchtrend.load_rounds(paths)
    assert not any("ceiling" in f for f in benchtrend.gate(rounds))


def test_absolute_gate_breach_exits_nonzero_via_cli(tmp_path, capsys):
    paths = [
        _write_round(tmp_path, 1, 1.0, {"time_to_ready_s": 3.0, **_WARM}),
        _write_round(tmp_path, 2, 1.0, {"time_to_ready_s": 30.0, **_WARM}),
    ]
    assert benchtrend.main(["--gate", *paths]) == 1
    err = capsys.readouterr().err
    assert "BENCHTREND GATE FAILED" in err and "time_to_ready_s" in err
    # report-only mode still prints the table and exits 0
    assert benchtrend.main(paths) == 0


def test_lint_findings_gate_is_unconditional(tmp_path):
    """`lint_findings_total` gates at 0 on the NEWEST round regardless
    of cache state or history depth — static-analysis debt can't ride a
    cold-cache round in, and suppressed (baselined) findings don't
    trip it."""
    # a single COLD round with findings still fails
    p = _write_round(tmp_path, 1, 1.0,
                     {"lint_findings_total": 3, **_COLD})
    rounds, _ = benchtrend.load_rounds([p])
    failures = benchtrend.gate(rounds)
    assert len(failures) == 1 and "lint_findings_total" in failures[0]
    # clean lint with accepted baseline debt passes
    ok = _write_round(tmp_path, 1, 1.0,
                      {"lint_findings_total": 0,
                       "lint_suppressed_total": 5, **_COLD})
    rounds, _ = benchtrend.load_rounds([ok])
    assert benchtrend.gate(rounds) == []
    # rounds predating the lint leg (no key at all) are not judged
    legacy = _write_round(tmp_path, 1, 1.0, {})
    rounds, _ = benchtrend.load_rounds([legacy])
    assert benchtrend.gate(rounds) == []


@pytest.mark.parametrize("gate_flag", [False, True])
def test_five_round_history_renders_and_passes(gate_flag, tmp_path, capsys):
    """A five-round series shaped like the rounds the driver once
    archived (the headline metric renamed at round 4, warm-up compile
    growing, the compile-cache detail only from round 5): the table
    renders every round and the default-threshold gate passes — no
    >25% regression between rounds that measure the same thing."""
    rounds = [
        ("als_train_wallclock", 1.8, {}),
        ("als_train_wallclock", 22.7,
         {"warmup_compile_s": 27.5, "serve_http_p99_ms": 1.56,
          "steady_per_iter_ms": 1351.0}),
        ("als_train_wallclock", 18.1,
         {"warmup_compile_s": 136.1, "serve_http_p99_ms": 1.75,
          "steady_per_iter_ms": 96.0}),
        ("als_train_steady10_s", 0.88,
         {"warmup_compile_s": 419.2, "serve_http_p99_ms": 0.91,
          "steady_per_iter_ms": 87.8}),
        ("als_train_steady10_s", 0.94,
         {"warmup_compile_s": 398.8, "serve_http_p99_ms": 1.11,
          "steady_per_iter_ms": 94.3,
          "compile_cache": {"before": {"entries": 262, "bytes": 1}}}),
    ]
    paths = [_write_round(tmp_path, n, value, detail, metric=metric)
             for n, (metric, value, detail) in enumerate(rounds, start=1)]
    argv = (["--gate"] if gate_flag else []) + paths
    assert benchtrend.main(argv) == 0
    out = capsys.readouterr().out
    for label in ("r01", "r05", "steady_per_iter_ms", "warmup_compile_s"):
        assert label in out
