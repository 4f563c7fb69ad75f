"""Per-request latency waterfalls: where did THIS request's 8 ms go?

PR 4's ``pio_serve_seconds`` says the p99 moved; nothing in the stack
says *which stage* moved it. This module decomposes every sampled
request's lifetime into explicit stages and keeps the evidence an
operator needs to go from "p99 is 8 ms" to "it's pad-to-bucket on
bucket=64" in one hop:

- **Stage histograms** — ``pio_serve_stage_seconds{stage}`` for each
  stage a request passes through. The serving stages, in request order:

      admission    enqueue -> batch formation (the batcher queue wait)
      supplement   serving.supplement over the flush
      dispatch     the whole predict_batch call (device path included)
      pad          pad-to-bucket index/buffer prep (a drill-down
                   INSIDE dispatch — stages may nest; sums of the
                   top-level stages approximate the total, drill-down
                   stages explain their parent)
      execute      the device dispatch ending in the host transfer of
                   the top-k result (inside dispatch; KNOWN_ISSUES #3 —
                   never block_until_ready, so the number is honest on
                   every backend). On the device paths it splits into
                   enqueue (argument transfer + launch: the call that
                   returns device arrays) and device_get (blocked until
                   the device is done, plus the copy back)
      unpack       rows -> PredictedResults through the item vocabulary
                   (inside dispatch, after execute)
      merge        per-query serve() over the flush results
      serialize    prediction -> JSON object on the request thread

- **Exemplars** — each stage-histogram bucket remembers the most recent
  trace id that landed in it, exposed on ``/metrics`` in OpenMetrics
  exemplar syntax (``... 42 # {trace_id="ab12"} 0.0034``) when the
  scraper negotiates ``Accept: application/openmetrics-text`` (classic
  0.0.4 scrapes stay exemplar-free — their parser would read the
  suffix as a timestamp), so an alerting threshold on a bucket leads
  straight to a concrete request.

- **Slow ring** — ``GET /debug/slow.json``: the N slowest sampled
  requests (``PIO_SLOW_RING``, default 32) with their full stage
  breakdown, trace id, and free-form details (e.g. the padding bucket
  that flush landed in).

Sampling: records, histograms and the slow ring gate on
``PIO_WATERFALL=1`` (default OFF — wire behavior, response bytes and
``/metrics`` series, stays byte-identical to the pre-waterfall code,
asserted by test). ``PIO_WATERFALL_SAMPLE=N`` samples every Nth request
(default 1 = all).

The same stages on the device trace's clock: on a thread that has
declared itself a device feeder (:func:`feeder` — the batcher's worker
calls it when it starts) ``stage(name)`` also opens
``profiling.annotate(name)``, whether or not sampling is on, so one
`with` at each call site serves both the per-request record and a
profiler capture. The annotation costs a flag test while no capture
runs. Request threads are not feeders and annotate nothing: their
stages (``serialize``, the inline path, ``admission``, which is
recorded after the fact) would be 128-256 threads opening spans
exactly when the device goes idle, and would pay per request what the
worker pays per flush. A multi-tenant deploy has one feeder per
batcher, and their spans interleave in a capture (one line per thread
in the xplane; a reader that attributes by innermost span alone cannot
tell them apart). The benchmark deploys one.

Cross-thread plumbing mirrors tracing.py: the record is born on the
request thread, rides the batcher's ``_Pending`` onto the worker
thread, and flush-level stages record into every record of the batch
(they are batch-level costs — each rider paid them).

Stdlib only (profiling.annotate finds jax in sys.modules, never
imports it); safe to import from any layer.
"""

from __future__ import annotations

import contextlib
import datetime as _dt
import itertools
import os
import threading
import time
import uuid
from typing import (Any, Dict, Iterator, List, NamedTuple, Optional, Sequence,
                    Tuple)

from predictionio_tpu.common import profiling, telemetry, tracing

#: stage latency buckets: tens of µs host stages through multi-second
#: device dispatches
STAGE_BUCKETS: Tuple[float, ...] = (
    0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
    0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0)

_override: Optional[bool] = None


def enabled() -> bool:
    """Is waterfall sampling on? ``PIO_WATERFALL=1`` turns it on;
    :func:`set_enabled` overrides for tests."""
    if _override is not None:
        return _override
    return os.environ.get("PIO_WATERFALL", "0") == "1"


def set_enabled(value: Optional[bool]) -> None:
    """Force sampling on/off regardless of env (None = back to env)."""
    global _override
    _override = value


def _sample_every() -> int:
    raw = os.environ.get("PIO_WATERFALL_SAMPLE", "")
    try:
        return max(1, int(raw)) if raw else 1
    except ValueError:
        return 1


def _ring_cap() -> int:
    raw = os.environ.get("PIO_SLOW_RING", "")
    try:
        return max(1, int(raw)) if raw else 32
    except ValueError:
        return 32


class RequestRecord:
    """One sampled request's stage breakdown. Stage adds are tiny and
    lock-free per record field (a record is written by at most one
    thread at a time: the request thread before submit and after the
    batch completes, the worker thread in between)."""

    __slots__ = ("trace_id", "mode", "stages", "details", "t0",
                 "started_at", "total_s")

    def __init__(self, mode: str, trace_id: str):
        self.trace_id = trace_id
        self.mode = mode
        self.stages: Dict[str, float] = {}
        self.details: Dict[str, Any] = {}
        self.t0 = time.perf_counter()
        # wall clock for display only; durations are perf_counter deltas
        self.started_at = _dt.datetime.now(
            _dt.timezone.utc).isoformat(timespec="milliseconds")
        self.total_s: float = 0.0

    def add(self, stage: str, duration_s: float) -> None:
        self.stages[stage] = self.stages.get(stage, 0.0) + duration_s

    def note(self, key: str, value: Any) -> None:
        """Attach free-form detail (e.g. the padding bucket this flush
        landed in) to the slow-ring entry."""
        self.details[key] = value

    def snapshot(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "traceId": self.trace_id,
            "mode": self.mode,
            "at": self.started_at,
            "totalMs": round(self.total_s * 1e3, 3),
            "stages": {k: round(v * 1e3, 3)
                       for k, v in self.stages.items()},
        }
        if self.details:
            out["details"] = dict(self.details)
        return out


# ---------------------------------------------------------------------------
# record lifecycle + thread-local activation
# ---------------------------------------------------------------------------

class _Active(NamedTuple):
    """What stage() finds on a thread: the records it times into and
    whether the thread also annotates (it feeds a device queue)."""

    recs: Tuple[RequestRecord, ...]
    feeder: bool


#: the state of a thread that never said anything: stage() hands back
#: the shared null context on identity, one getattr and no allocation
_OFF = _Active((), False)
_PASS_THROUGH = contextlib.nullcontext()

_tls = threading.local()
_sample_seq = itertools.count(1)


def feeder() -> None:
    """Declare the calling thread a device feeder, for its lifetime: its
    stages are annotated on the profiler's clock (module docstring).
    ``MicroBatcher._run`` is the one caller."""
    _tls.active = _Active(getattr(_tls, "active", _OFF).recs, True)


def begin(mode: str) -> Optional[RequestRecord]:
    """Start a record for this request, or None (sampling off / not this
    request's turn). Adopts the active trace id so the slow-ring entry,
    the /metrics exemplar, and /traces.json all name the same request;
    without tracing it mints its own id (still cross-referencable
    between slow.json and the exemplars)."""
    if not enabled():
        return None
    n = _sample_every()
    if n > 1 and next(_sample_seq) % n != 0:
        return None
    ctx = tracing.current()
    trace_id = ctx.trace_id if ctx is not None else uuid.uuid4().hex[:16]
    return RequestRecord(mode, trace_id)


@contextlib.contextmanager
def activate(records: Sequence[Optional[RequestRecord]]) -> Iterator[None]:
    """Install ``records`` as the calling thread's active set for the
    block — flush-level stages record into every record of the batch.
    Falsy/None entries are dropped; an empty set is a pure passthrough."""
    recs = tuple(r for r in records if r is not None)
    if not recs:
        yield
        return
    prev = getattr(_tls, "active", _OFF)
    _tls.active = _Active(recs, prev.feeder)
    try:
        yield
    finally:
        _tls.active = prev


def current() -> Optional[RequestRecord]:
    """The calling thread's primary active record (request threads have
    exactly one; the batcher captures it at submit like the trace)."""
    recs = getattr(_tls, "active", _OFF).recs
    return recs[0] if recs else None


def _stage_family():
    return telemetry.registry().histogram(
        "pio_serve_stage_seconds",
        "Per-request serve latency decomposed by stage (admission/"
        "supplement/dispatch/pad/execute/enqueue/device_get/unpack/"
        "merge/serialize); bucket exemplars carry the most recent "
        "trace id",
        labelnames=("stage",), buckets=STAGE_BUCKETS)


def observe_stage(stage: str, duration_s: float,
                  records: Sequence[Optional[RequestRecord]] = ()) -> None:
    """Record a completed stage with an explicit duration into
    ``records`` (cross-thread work, e.g. the batcher's admission wait)
    and into the stage histogram with the first record's trace id as
    the bucket exemplar. No-op when no record is live."""
    recs = tuple(r for r in records if r is not None)
    if not recs:
        return
    for r in recs:
        r.add(stage, duration_s)
    _stage_family().labels(stage=stage).observe(
        duration_s, exemplar=recs[0].trace_id)


def note(key: str, value: Any) -> None:
    """Attach free-form detail to every active record (e.g. the shard
    count a flush's sharded execute spanned). No-op when sampling is
    off — same one-getattr cost as stage()."""
    for r in getattr(_tls, "active", _OFF).recs:
        r.note(key, value)


def stage(name: str):
    """Time the block as stage ``name`` for every active record and, on
    a device feeder's thread, annotate it on the profiler's clock. With
    neither (waterfall off or an unsampled request, on a request
    thread) the block runs untouched — one getattr, the whole cost of
    sampling-off."""
    active = getattr(_tls, "active", _OFF)
    if active is _OFF:
        return _PASS_THROUGH
    return _stage(name, active)


@contextlib.contextmanager
def _stage(name: str, active: _Active) -> Iterator[None]:
    recs = active.recs
    with (profiling.annotate(name) if active.feeder else _PASS_THROUGH):
        if not recs:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            for r in recs:
                r.add(name, dt)
            _stage_family().labels(stage=name).observe(
                dt, exemplar=recs[0].trace_id)


# ---------------------------------------------------------------------------
# the slow ring (N slowest sampled requests)
# ---------------------------------------------------------------------------

class _SlowRing:
    """Bounded keep-the-slowest set. Insert is O(cap) over a small list
    and runs once per SAMPLED request, off the stage hot path."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: List[RequestRecord] = []

    def add(self, rec: RequestRecord) -> None:
        cap = _ring_cap()
        with self._lock:
            # evict the fastest entries until there is room under the
            # cap — one eviction in steady state, several when
            # PIO_SLOW_RING shrank between requests (always dropping by
            # total_s, never by insertion order)
            while len(self._entries) >= cap:
                fastest = min(self._entries, key=lambda r: r.total_s)
                if (len(self._entries) == cap
                        and rec.total_s <= fastest.total_s):
                    return
                self._entries.remove(fastest)
            self._entries.append(rec)

    def snapshot(self, limit: int) -> List[Dict[str, Any]]:
        with self._lock:
            entries = sorted(self._entries, key=lambda r: -r.total_s)
        return [r.snapshot() for r in entries[:max(1, limit)]]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


_ring = _SlowRing()


def end(rec: Optional[RequestRecord]) -> None:
    """Close the record (total = begin -> now) and offer it to the slow
    ring. None is allowed — callers never branch on sampling."""
    if rec is None:
        return
    rec.total_s = time.perf_counter() - rec.t0
    _ring.add(rec)


def clear() -> None:
    """Drop every slow-ring entry (tests)."""
    _ring.clear()


def slow_snapshot(limit: int = 32) -> Dict[str, Any]:
    """The ``GET /debug/slow.json`` payload: slowest first, each with
    its full stage breakdown and trace id (join against
    ``/traces.json?trace_id=`` and the /metrics exemplars)."""
    return {
        "enabled": enabled(),
        "capacity": _ring_cap(),
        "sampleEvery": _sample_every(),
        "requests": _ring.snapshot(limit),
    }
