"""Bench-trajectory tracker: the BENCH_r*.json series, read and gated.

Five rounds of benchmarks exist as driver artifacts and nothing reads
them: a perf regression only gets caught if a human happens to diff two
JSON blobs. This module turns the series into (a) a per-metric trend
table an operator can read in one glance and (b) a regression gate the
bench wires in under ``BENCH_STRICT_EXTRAS=1`` — the newest run is
compared per metric against the BEST prior run and hard-fails beyond a
configurable threshold.

    python -m predictionio_tpu.tools.benchtrend BENCH_r*.json
    python -m predictionio_tpu.tools.benchtrend --gate --threshold 0.25 ...

File formats accepted: the driver wrapper (``{"n", "cmd", "rc", "tail",
"parsed": {...}}``) and the bare bench line (``{"metric", "value",
"unit", "detail"}``). Unparseable files are reported and skipped — a
corrupt round must not hide the trend of the others.

Comparability rules (the part that keeps the gate honest):

- The headline ``value`` only compares runs with the SAME ``metric``
  name (r01-r03 measured wall-clock, r04+ measure slope steady-state —
  numerically incomparable).
- ``warmup_compile_s`` only compares runs that BOTH ran against a warm
  persistent compile cache (``compile_cache.before.entries > 0``): a
  cold-cache round legitimately pays the full remote compile (~400 s in
  BENCH_r05) and must not read as a 14x regression against a warm one,
  nor set an impossible baseline for cold rounds. Rounds without
  compile-cache detail are treated as unknown and never compared.
- A lower bound of one prior comparable value: the first round of a new
  metric gates nothing.
- AOT era (serving/aot.py): ``warmup_compile_s`` stays train-compile-
  only (the bench subtracts the aot_export phase), so pre- and post-AOT
  rounds compare like with like; the serving-side cliff splits into
  ``aot_prebuild_s`` (deploy-time, off the request path) and
  ``first_query_compile_s`` (the lazy control). ``time_to_ready_s``
  additionally carries an ABSOLUTE ceiling (< 10 s, warm-cache rounds
  only) — the warm-replica availability contract, not a relative trend.
"""

from __future__ import annotations

import argparse
import glob as _glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

#: (detail key | "value", direction, gated) — direction "down" = lower
#: is better; gated metrics hard-fail the strict bench on regression.
#: "warm-cache" is the warmup_compile_s special: gated, but only across
#: warm-cache rounds (see module docstring).
METRICS: Tuple[Tuple[str, str, Any], ...] = (
    ("value", "down", True),
    ("steady_per_iter_ms", "down", True),
    ("cold_pio_train_total_s", "down", True),
    ("warm_pio_train_total_s", "down", False),
    ("serve_http_p50_ms", "down", True),
    ("serve_http_p99_ms", "down", True),
    ("ecom_unseen_p99_ms", "down", False),
    ("event_store_write_s", "down", False),
    ("phase_read_s", "down", False),
    ("phase_layout_s", "down", False),
    ("eval_grid_s", "down", False),
    ("read_parallel_speedup", "up", False),
    ("serve_batched_qps_gain", "up", True),
    ("warmup_compile_s", "down", "warm-cache"),
    ("serve_post_warmup_recompiles", "down", False),
    # AOT era (serving/aot.py): prebuild/first-query compile split so
    # pre- and post-AOT rounds compare like with like, plus the
    # warm-replica readiness record the absolute gate below enforces
    ("time_to_ready_s", "down", False),
    ("aot_prebuild_s", "down", False),
    ("first_query_compile_s", "down", False),
    # diagnosis era (common/waterfall.py): the stage-sampling path's p99
    # tax vs sampling off — trended here, hard-gated at <= 5% by the
    # bench's own waterfall leg under BENCH_STRICT_EXTRAS=1
    ("waterfall_overhead_p99_pct", "down", False),
    ("waterfall_on_p99_ms", "down", False),
    # flight-recorder era (common/journal.py + tracing tail retention):
    # the journal-on path's p99 tax (hard-gated at <= 5% by the bench's
    # own journal leg under BENCH_STRICT_EXTRAS=1), the event count,
    # and how many traces the tail ring pinned — trended so emitter
    # creep (a hot path that starts journaling) is visible per round
    ("journal_overhead_p99_pct", "down", False),
    ("journal_events_total", "up", False),
    ("trace_tail_retained", "up", False),
    # sharded-serving era (parallel/serve_dist.py): the row-sharded
    # top-k path's p99 and its overhead vs the replicated path —
    # hard-gated at <= 10% by the bench's serve-sharded leg under
    # BENCH_STRICT_EXTRAS=1, trended here
    ("serve_sharded_p99_ms", "down", False),
    ("serve_sharded_overhead_pct", "down", False),
    # quantized-serving era (ops/quant.py + ops/topk_pallas.py): the
    # int8(+fused) path's p99, its factor-matrix HBM ratio vs fp32, and
    # the wire-level recall@k — the strict gates (p99 <= fp32, ratio <=
    # 0.30, recall >= 0.99) live in the bench's serve-quant leg under
    # BENCH_STRICT_EXTRAS=1; trended here so drift is visible round
    # over round
    ("serve_quant_p99_ms", "down", False),
    ("serve_quant_hbm_ratio", "down", False),
    ("serve_quant_recall", "up", False),
    # realtime fold-in era (realtime/foldin.py): wire-level freshness
    # (event ack -> first personalized answer for an unseen user — the
    # speed-layer contract, hard-gated at <= 2 s by the bench's own
    # fold-in leg under BENCH_STRICT_EXTRAS=1), the worker's serve-p99
    # tax (hard-gated at <= 5% there), and the cursor lag at the end of
    # the leg — trended so speed-layer rot is visible round over round
    ("foldin_freshness_p99_s", "down", False),
    ("foldin_overhead_p99_pct", "down", False),
    ("foldin_cursor_lag_events", "down", False),
    # scale-out era (workflow/router.py): the fleet front door's added
    # p99 (hard-gated at <= 1 ms by the bench's router leg under
    # BENCH_STRICT_EXTRAS=1 on >= 4-core hosts) and the 1->2 replica
    # QPS scaling (>= 1.6x, same gate) — trended so front-door fat or a
    # scaling regression is visible round over round
    ("router_added_p99_ms", "down", False),
    ("router_qps_scaling_2", "up", False),
    # partition-routing + response-cache era (workflow/router.py
    # scatter/merge + _ResponseCache): the p99 the 1/N-catalog scatter
    # ADDS over one full replica, the zipfian hot-key hit ratio the
    # front-door cache absorbs, and the cached-path p99 itself —
    # trended so merge overhead growth or a cache-efficiency regression
    # is visible round over round
    ("router_partition_added_p99_ms", "down", False),
    ("router_cache_hit_ratio", "up", False),
    ("router_cache_p99_ms", "down", False),
    # multi-tenant era (serving/registry.py): noisy-neighbor isolation
    # — tenant B's p99 under tenant A's flood over B's solo p99
    # (hard-gated at <= 3x by the bench's multitenant leg under
    # BENCH_STRICT_EXTRAS=1 on >= 4-core hosts) — and the shared-AOT
    # compile count with 4 tenants (flat vs 1 tenant, strict-gated
    # everywhere: compiling is deterministic) — trended so isolation
    # rot or a compile-sharing regression is visible round over round
    ("mt_isolation_p99_ratio", "down", False),
    ("mt_compile_count_4t", "down", False),
    # static-analysis era (tools/analyze): `pio lint` runs inside the
    # bench's strict leg; findings are gated at 0 absolutely below,
    # suppressed counts are trended so baseline debt is visible per
    # round (it should only ever shrink)
    ("lint_findings_total", "down", False),
    ("lint_suppressed_total", "down", False),
    # ingest era (data/api/http.py + eventlog group commit): the two
    # transport modes' 32-connection throughput, their ratio (the >= 3x
    # contract is hard-gated by the bench's own ingest leg under
    # BENCH_STRICT_EXTRAS=1), and the async admission p99 — trended so
    # a transport regression is visible round over round
    ("ingest_threaded_eps_32", "up", False),
    ("ingest_async_eps_32", "up", False),
    ("ingest_async_speedup_32", "up", False),
    ("ingest_admission_p99_ms", "down", False),
    # out-of-core training era (data/store.py stream mode + data/
    # synthetic.py): the streamed pipeline's end-to-end ratings/s (the
    # >= 85%-of-in-core contract is hard-gated by the bench's own
    # train-stream leg under BENCH_STRICT_EXTRAS=1) and its peak host
    # RSS — trended so O(chunk) regressions (a host copy creeping back
    # into the streamed path) are visible round over round
    ("train_stream_ratings_per_s", "up", False),
    ("train_stream_peak_rss_mb", "down", False),
    # autopilot era (workflow/autopilot.py): seconds from a replica
    # SIGKILL to the fleet back at full rotation with the corpse
    # retired (the self-healing promise, strict-gated at <= 120 s on
    # capable hosts by the bench leg itself), and the total actions the
    # leg's control loops took — a creeping rise means the loop is
    # flapping where it used to converge
    ("autopilot_recovery_s", "down", False),
    ("autopilot_actions_total", "down", False),
    # continuous-training era (workflow/autotrain.py): seconds from the
    # trigger decision to the validated candidate live behind the
    # barrier (the closed-loop freshness promise — the cycle itself is
    # strict-gated to complete on capable hosts by the bench leg), and
    # the candidates the validation gate refused — a creeping rise
    # means retrains are regressing quality and the gate is doing the
    # serving path's job for it
    ("autotrain_cycle_s", "down", False),
    ("autotrain_candidates_rejected", "down", False),
    # metrics-flight-recorder era (common/history.py): the sampler's
    # serve-p99 tax with history on vs off (hard-gated at <= 5% by the
    # bench's history leg under BENCH_STRICT_EXTRAS=1 — the hot path
    # pays nothing) and the series the rings track — coverage of the
    # metric surface, bounded by PIO_HISTORY_MAX_SERIES (the bench leg
    # hard-fails if the cap is ever exceeded)
    ("history_overhead_p99_pct", "down", False),
    ("history_series_total", "up", False),
)

#: absolute ceilings (metric -> limit), enforced on the NEWEST round
#: regardless of history: some records are availability contracts, not
#: relative trends. time_to_ready_s < 10 s is the warm-replica promise
#: from ROADMAP Open item 2 — a deploy that pre-seeds its compile cache
#: from the model's artifact must be servable in seconds.
ABSOLUTE_GATES: Dict[str, float] = {
    "time_to_ready_s": 10.0,
}

#: absolute ceilings enforced UNCONDITIONALLY on the newest round (no
#: warm-cache precondition): `pio lint` findings are 0 on every round
#: or the round fails — new static-analysis debt can't ride a bench
#: artifact in. (The suppression baseline is how accepted debt is
#: recorded; it keeps findings at 0 without hiding NEW findings.)
ABSOLUTE_GATES_ALWAYS: Dict[str, float] = {
    "lint_findings_total": 1.0,
}

#: regression tolerance vs the best prior run; generous on purpose —
#: the r04->r05 history shows ~20% cross-round noise on serve p99
#: (shared hosts, device-link variance) that must not cry wolf
DEFAULT_THRESHOLD = 0.25


def _round_label(path: str) -> str:
    m = re.search(r"r(\d+)", os.path.basename(path))
    return f"r{int(m.group(1)):02d}" if m else os.path.basename(path)


def load_round(path: str) -> Optional[Dict[str, Any]]:
    """One bench artifact -> {label, metric, value, detail} or None."""
    try:
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return None
    body = raw.get("parsed") if isinstance(raw.get("parsed"), dict) else raw
    if not isinstance(body, dict) or "metric" not in body:
        return None
    value = body.get("value")
    if not isinstance(value, (int, float)):
        return None
    detail = body.get("detail")
    return {
        "label": _round_label(path),
        "path": path,
        "metric": str(body.get("metric")),
        "value": float(value),
        "detail": detail if isinstance(detail, dict) else {},
    }


def load_rounds(paths: Sequence[str]) -> Tuple[List[Dict[str, Any]],
                                               List[str]]:
    """(rounds sorted by label, skipped-path list)."""
    rounds, skipped = [], []
    for p in paths:
        r = load_round(p)
        if r is None:
            skipped.append(p)
        else:
            rounds.append(r)
    rounds.sort(key=lambda r: r["label"])
    return rounds, skipped


def metric_value(rnd: Dict[str, Any], key: str) -> Optional[float]:
    v = rnd["value"] if key == "value" else rnd["detail"].get(key)
    return float(v) if isinstance(v, (int, float)) else None


def _warm_cache(rnd: Dict[str, Any]) -> Optional[bool]:
    """True/False when the round recorded compile-cache state, None when
    unknown (pre-r05 rounds)."""
    cc = rnd["detail"].get("compile_cache")
    if not isinstance(cc, dict):
        return None
    before = cc.get("before")
    if not isinstance(before, dict):
        return None
    return int(before.get("entries", 0) or 0) > 0


def _comparable(key: str, gated: Any, a: Dict[str, Any],
                b: Dict[str, Any]) -> bool:
    if key == "value" and a["metric"] != b["metric"]:
        return False
    if gated == "warm-cache":
        return _warm_cache(a) is True and _warm_cache(b) is True
    return True


def best_prior(rounds: Sequence[Dict[str, Any]], key: str,
               direction: str, gated: Any,
               last: Dict[str, Any]) -> Optional[float]:
    vals = [metric_value(r, key) for r in rounds
            if r is not last and _comparable(key, gated, r, last)]
    vals = [v for v in vals if v is not None]
    if not vals:
        return None
    return min(vals) if direction == "down" else max(vals)


def regression_pct(last_v: float, best: float,
                   direction: str) -> Optional[float]:
    """Positive = worse than the best prior, as a fraction of it."""
    if best == 0:
        return None
    if direction == "down":
        return (last_v - best) / abs(best)
    return (best - last_v) / abs(best)


def gate(rounds: Sequence[Dict[str, Any]],
         threshold: float = DEFAULT_THRESHOLD) -> List[str]:
    """Regressions of the NEWEST round beyond threshold vs best prior,
    plus the ABSOLUTE_GATES ceilings (which need no prior round — the
    first AOT round is already accountable for the <10 s promise)."""
    if not rounds:
        return []
    last = rounds[-1]
    failures = []
    for key, limit in ABSOLUTE_GATES.items():
        v = metric_value(last, key)
        # warm-cache rounds only, like warmup_compile_s: a cold cache
        # legitimately pays full compiles and must not read as an
        # availability breach
        if v is not None and v >= limit and _warm_cache(last) is True:
            failures.append(
                f"{key}: {v:g} exceeds the absolute ceiling {limit:g} "
                "(warm-replica availability contract)")
    for key, limit in ABSOLUTE_GATES_ALWAYS.items():
        v = metric_value(last, key)
        if v is not None and v >= limit:
            failures.append(
                f"{key}: {v:g} must be 0 — fix the findings or accept "
                "them into conf/lint_baseline.json with a reason")
    if len(rounds) < 2:
        return failures
    for key, direction, gated in METRICS:
        if not gated:
            continue
        last_v = metric_value(last, key)
        if last_v is None:
            continue
        best = best_prior(rounds, key, direction, gated, last)
        if best is None:
            continue
        reg = regression_pct(last_v, best, direction)
        if reg is not None and reg > threshold:
            failures.append(
                f"{key}: {last_v:g} is {reg * 100:.1f}% worse than the "
                f"best prior run ({best:g}; threshold "
                f"{threshold * 100:.0f}%)")
    return failures


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

def _fmt(v: Optional[float]) -> str:
    if v is None:
        return "-"
    if v == int(v) and abs(v) < 1e9:
        return str(int(v))
    return f"{v:.3g}" if abs(v) >= 100 else f"{v:.4g}"


def render(rounds: Sequence[Dict[str, Any]],
           threshold: float = DEFAULT_THRESHOLD) -> str:
    if not rounds:
        return "benchtrend: no parseable bench rounds"
    labels = [r["label"] for r in rounds]
    last = rounds[-1]
    rows: List[Tuple[str, List[str], str]] = []

    # headline rows, one per distinct metric name in first-seen order
    seen_names: List[str] = []
    for r in rounds:
        if r["metric"] not in seen_names:
            seen_names.append(r["metric"])
    for name in seen_names:
        cells = [_fmt(r["value"]) if r["metric"] == name else "-"
                 for r in rounds]
        delta = ""
        if last["metric"] == name:
            best = best_prior(rounds, "value", "down", True, last)
            reg = (regression_pct(last["value"], best, "down")
                   if best is not None else None)
            if reg is not None:
                delta = f"{reg * +100:+.1f}% vs best"
        rows.append((name, cells, delta))

    for key, direction, gated in METRICS:
        if key == "value":
            continue
        vals = [metric_value(r, key) for r in rounds]
        if not any(v is not None for v in vals):
            continue
        best = best_prior(rounds, key, direction, gated, last)
        last_v = metric_value(last, key)
        delta = ""
        if best is not None and last_v is not None:
            reg = regression_pct(last_v, best, direction)
            if reg is not None:
                mark = " !" if (gated and reg > threshold) else ""
                delta = f"{reg * 100:+.1f}% vs best{mark}"
        elif gated == "warm-cache" and last_v is not None:
            delta = "(cold/unknown cache — not compared)"
        rows.append((key, [_fmt(v) for v in vals], delta))

    name_w = max(len(n) for n, _c, _d in rows)
    col_w = max(8, max((len(c) for _n, cells, _d in rows for c in cells),
                       default=8))
    head = ("metric".ljust(name_w) + "  "
            + "  ".join(lb.rjust(col_w) for lb in labels) + "  trend")
    lines = [head, "-" * len(head)]
    for name, cells, delta in rows:
        lines.append(name.ljust(name_w) + "  "
                     + "  ".join(c.rjust(col_w) for c in cells)
                     + ("  " + delta if delta else ""))
    return "\n".join(lines)


def trend_brief(rounds: Sequence[Dict[str, Any]],
                threshold: float = DEFAULT_THRESHOLD) -> Dict[str, Any]:
    """Compact per-metric {best_prior, current, delta_pct} for embedding
    in the bench JSON detail (the artifact should explain itself)."""
    out: Dict[str, Any] = {}
    if not rounds:
        return out
    last = rounds[-1]
    for key, direction, gated in METRICS:
        last_v = metric_value(last, key)
        if last_v is None:
            continue
        best = best_prior(rounds, key, direction, gated, last)
        if best is None:
            continue
        reg = regression_pct(last_v, best, direction)
        out[key] = {"best_prior": best, "current": last_v,
                    "delta_pct": (round(reg * 100, 2)
                                  if reg is not None else None)}
    return out


def gate_current(current: Dict[str, Any], history_paths: Sequence[str],
                 threshold: float = DEFAULT_THRESHOLD
                 ) -> Tuple[List[str], Dict[str, Any]]:
    """Gate an in-flight bench result (bench.py) against the historical
    series; returns (failures, trend_brief). `current` is the bench's
    own {"metric", "value", "detail"} dict."""
    rounds, _skipped = load_rounds(history_paths)
    cur = {
        "label": "now", "path": "<current>",
        "metric": str(current.get("metric", "")),
        "value": float(current.get("value", 0.0)),
        "detail": current.get("detail") or {},
    }
    rounds.append(cur)
    return gate(rounds, threshold), trend_brief(rounds, threshold)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m predictionio_tpu.tools.benchtrend",
        description="bench-trajectory trend table + regression gate")
    p.add_argument("files", nargs="+",
                   help="BENCH_r*.json artifacts (shell glob or literal)")
    p.add_argument("--gate", action="store_true",
                   help="exit nonzero when the newest round regresses "
                        "beyond --threshold vs the best prior run "
                        "(also enabled by BENCH_STRICT_EXTRAS=1)")
    p.add_argument("--threshold", type=float,
                   default=float(os.environ.get("BENCH_TREND_THRESHOLD",
                                                DEFAULT_THRESHOLD)),
                   help=f"regression tolerance (default "
                        f"{DEFAULT_THRESHOLD:g} = "
                        f"{DEFAULT_THRESHOLD:.0%})")
    args = p.parse_args(argv)

    paths: List[str] = []
    for pattern in args.files:
        hit = sorted(_glob.glob(pattern))
        paths.extend(hit if hit else [pattern])
    rounds, skipped = load_rounds(paths)
    for s in skipped:
        print(f"benchtrend: skipping unparseable {s}", file=sys.stderr)
    print(render(rounds, args.threshold))
    if not rounds:
        return 1
    gating = args.gate or os.environ.get("BENCH_STRICT_EXTRAS") == "1"
    if gating:
        failures = gate(rounds, args.threshold)
        if failures:
            print("\nBENCHTREND GATE FAILED:\n  "
                  + "\n  ".join(failures), file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
