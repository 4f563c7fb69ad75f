"""Request micro-batcher with admission control.

One worker thread owns a FIFO of pending items. A batch flushes when
either `max_batch_size` items are queued or the OLDEST item has waited
`max_delay_ms` (the timer is anchored on the head of the queue, so a
steady trickle cannot starve the first request). The flush callback
receives the whole batch and must return one result per item; request
threads block on their item's completion event, so the HTTP transport's
thread-per-connection model is preserved.

Admission control: when the queue already holds `max_queue` items,
`submit` raises :class:`ServerSaturated` instead of enqueueing — latency
stays bounded and the caller maps it to 503 with a Retry-After hint
derived from the observed drain rate.

Stats are REGISTRY-BACKED (common/telemetry.py): batch/query/reject
counts, batch-size and padding-bucket histograms, queue-wait totals and
flush (device) latency live as labeled instruments in the process-wide
metrics registry — `GET /metrics` scrapes them and the engine server's
`GET /` status route derives its byte-compatible legacy JSON from the
same instruments (single source of truth). Each batcher instance gets
its own label so a /reload's fresh batcher starts from zero exactly as
the old per-instance counters did. Updates stay a handful of scalar
bumps per BATCH, not per query.

AOT interplay (serving/aot.py): the deploy hands this batcher its
observation-pruned bucket set — the exact set whose programs were
AOT-prebuilt before /readyz flipped ready — and each flush installs it
thread-locally (``protocol.flush_buckets``) so predict_batch pads onto a
bucket whose program is already warm, never the process defaults. The
exact-flush-size counters this batcher records
(``pio_batcher_batch_size``) are the observed histogram the next
prebuild prunes against, and the recompile watchdog's warmup is marked
done by the AOT prebuild itself (an explicit mark, not a flush count),
making any serving-path compile after ready an alarm.

Tracing (common/tracing.py): when a submitting request carries a trace
context, the batch records an `admission` span per item (enqueue → batch
formation) and a `flush` span around the flush callback, parented on the
head item's trace so a propagated trace shows admission → flush →
dispatch → storage end to end. Flush timing honesty: the batched predict
path ends in a real host transfer (jax.device_get of the top-k result),
per KNOWN_ISSUES.md #3 — the flush span/histogram would under-report
where block_until_ready returns early if that ever regressed to it.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from predictionio_tpu.common import devicewatch, telemetry, tracing, waterfall
from predictionio_tpu.serving import protocol
from predictionio_tpu.serving.protocol import bucket_for, pad_buckets

#: distinguishes concurrently-live batchers (e.g. across /reload) in the
#: process-wide registry; the label value is f"{name}#{seq}"
_instance_seq = itertools.count()

#: flush latency buckets: sub-ms CPU flushes through multi-second
#: device dispatches
_FLUSH_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                  0.1, 0.25, 0.5, 1.0, 2.5, 5.0)


class ServerSaturated(Exception):
    """Queue depth hit max_queue; carries the 503 Retry-After hint."""

    def __init__(self, retry_after_s: int):
        super().__init__(
            f"serving queue saturated; retry after ~{retry_after_s}s")
        self.retry_after_s = retry_after_s


class _Pending:
    __slots__ = ("item", "t_enq", "done", "result", "error", "trace",
                 "rec")

    def __init__(self, item: Any, t_enq: float,
                 trace: Optional["tracing.TraceContext"] = None,
                 rec: Optional["waterfall.RequestRecord"] = None):
        self.item = item
        self.t_enq = t_enq
        self.done = threading.Event()
        self.result: Any = None
        self.error: Optional[BaseException] = None
        #: the submitting request's trace context: the worker thread
        #: records this item's admission span under it and parents the
        #: batch's flush span on the head item's
        self.trace = trace
        #: the submitting request's waterfall record (common/waterfall):
        #: the worker credits this item's admission wait to it and the
        #: flush-level stages record into every record of the batch
        self.rec = rec


class MicroBatcher:
    """Coalesces concurrent submit() calls into flush_fn(list) batches."""

    def __init__(self, flush_fn: Callable[[List[Any]], Sequence[Any]],
                 max_batch_size: int = 64,
                 max_delay_ms: float = 2.0,
                 max_queue: int = 256,
                 buckets: Optional[Tuple[int, ...]] = None,
                 name: str = "query-batcher"):
        if max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        if max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        self._flush_fn = flush_fn
        self.name = name
        self.max_batch_size = int(max_batch_size)
        self.max_delay_s = float(max_delay_ms) / 1e3
        self.max_queue = int(max_queue)
        self.buckets = pad_buckets(buckets)
        self._cond = threading.Condition()
        self._q: List[_Pending] = []
        self._closed = False
        # stats: registry-backed (single source of truth for BOTH
        # `GET /metrics` and the engine server's `GET /` legacy JSON).
        # One label per batcher instance so a fresh batcher — /reload, a
        # test — starts from zero like the old per-instance counters.
        reg = telemetry.registry()
        inst = {"batcher": f"{name}#{next(_instance_seq)}"}
        self._m_batches = reg.counter(
            "pio_batcher_batches_total", "Flushed batches",
            labelnames=("batcher",)).labels(**inst)
        self._m_queries = reg.counter(
            "pio_batcher_queries_total", "Queries admitted into batches",
            labelnames=("batcher",)).labels(**inst)
        self._m_rejected = reg.counter(
            "pio_batcher_rejected_total",
            "Queries rejected by admission control (503)",
            labelnames=("batcher",)).labels(**inst)
        self._m_queue_wait = reg.counter(
            "pio_batcher_queue_wait_seconds_total",
            "Summed per-query queue wait", labelnames=("batcher",)
        ).labels(**inst)
        self._m_flush = reg.histogram(
            "pio_batcher_flush_seconds",
            "Flush (device dispatch) latency per batch; the timed region "
            "ends in a real host transfer (KNOWN_ISSUES #3)",
            labelnames=("batcher",), buckets=_FLUSH_BUCKETS).labels(**inst)
        self._m_depth = reg.gauge(
            "pio_batcher_queue_depth", "Current admission queue depth",
            labelnames=("batcher",)).labels(**inst)
        self._size_fam = reg.counter(
            "pio_batcher_batch_size", "Batches by exact flush size",
            labelnames=("batcher", "size"))
        self._bucket_fam = reg.counter(
            "pio_batcher_bucket", "Batches by padding-bucket occupancy",
            labelnames=("batcher", "bucket"))
        self._inst = inst
        self._size_children: Dict[int, Any] = {}
        self._bucket_children: Dict[int, Any] = {}
        self._worker = threading.Thread(
            target=self._run, name=name, daemon=True)
        self._worker.start()

    # --------------------------------------------------------------- submit
    def submit(self, item: Any) -> Any:
        """Enqueue one item and block until its batch is served.

        Raises ServerSaturated when the queue is full and re-raises any
        exception the flush callback raised for this item's batch.
        """
        trace = tracing.current()
        rec = waterfall.current()
        with self._cond:
            if self._closed:
                raise RuntimeError("batcher is closed")
            if len(self._q) >= self.max_queue:
                self._m_rejected.inc()
                raise ServerSaturated(self._retry_after_locked())
            pending = _Pending(item, time.monotonic(), trace=trace,
                               rec=rec)
            self._q.append(pending)
            self._m_depth.set(len(self._q))
            self._cond.notify_all()
        pending.done.wait()
        if pending.error is not None:
            raise pending.error
        return pending.result

    def _retry_after_locked(self) -> int:
        """Drain-time estimate for the current backlog, floored at 1s."""
        batches = self._m_flush.count
        if batches:
            per_batch = self._m_flush.sum / batches
            est = (len(self._q) / self.max_batch_size + 1.0) * per_batch
        else:
            est = 1.0
        return max(1, int(est + 0.999))

    # --------------------------------------------------------------- worker
    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._q and not self._closed:
                    self._cond.wait()
                if not self._q:     # closed and drained
                    return
                # flush when the batch fills OR the head item's delay
                # budget is spent; new arrivals notify and re-check
                deadline = self._q[0].t_enq + self.max_delay_s
                while (len(self._q) < self.max_batch_size
                       and not self._closed):
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(remaining)
                batch = self._q[:self.max_batch_size]
                del self._q[:len(batch)]
                now = time.monotonic()
                self._m_batches.inc()
                self._m_queries.inc(len(batch))
                self._size_child(len(batch)).inc()
                bucket = bucket_for(len(batch), self.buckets)
                self._bucket_child(bucket).inc()
                self._m_queue_wait.inc(sum(now - p.t_enq for p in batch))
                self._m_depth.set(len(self._q))
            # per-item admission spans: enqueue -> batch formation, under
            # each submitter's own trace (the wait happened off-thread)
            head_ctx = None
            for p in batch:
                if p.trace is not None:
                    if head_ctx is None:
                        head_ctx = p.trace
                    tracing.record_span("admission", p.trace,
                                        now - p.t_enq, service=self.name)
                if p.rec is not None:
                    # waterfall: each item's own queue wait (off-thread,
                    # so explicit-duration like the span above)
                    waterfall.observe_stage("admission", now - p.t_enq,
                                            (p.rec,))
            recs = [p.rec for p in batch if p.rec is not None]
            if recs:
                # the bucket this flush pads onto — the detail that turns
                # "p99 is 8 ms" into "it's pad-to-bucket on bucket=64"
                for r in recs:
                    r.note("bucket", bucket)
                    r.note("batchSize", len(batch))
            t0 = time.monotonic()
            try:
                # recompile watchdog (common/devicewatch.py): any XLA
                # compile inside the flush is attributed to the serving
                # path; after warmup it is the padding-bucket alarm. The
                # signature names the batch size that broke the bucket
                # contract (the padded shape is the algorithm's concern,
                # but the admitted size is what the operator can act on).
                with devicewatch.serving_region(
                        "serve_flush",
                        signature=f"bucket={bucket},n={len(batch)}"):
                    # flush-scoped bucket set: predict_batch on this
                    # thread pads onto THIS batcher's (pruned, AOT-
                    # prebuilt) buckets, not the process defaults
                    with protocol.flush_buckets(self.buckets):
                        with tracing.activate(head_ctx):
                            with tracing.span("flush", service=self.name):
                                # flush-level waterfall stages
                                # (supplement/dispatch/pad/execute/merge
                                # inside the flush callback) record into
                                # every sampled rider of this batch
                                with waterfall.activate(recs):
                                    results = self._flush_fn(
                                        [p.item for p in batch])
                if len(results) != len(batch):
                    raise RuntimeError(
                        f"flush returned {len(results)} results for a "
                        f"batch of {len(batch)}")
                for p, r in zip(batch, results):
                    p.result = r
            except BaseException as e:  # propagate to every waiter
                for p in batch:
                    p.error = e
            self._m_flush.observe(time.monotonic() - t0)
            devicewatch.note_serving_flush()
            for p in batch:
                p.done.set()

    def _size_child(self, n: int):
        c = self._size_children.get(n)
        if c is None:
            c = self._size_fam.labels(size=str(n), **self._inst)
            self._size_children[n] = c
        return c

    def _bucket_child(self, b: int):
        c = self._bucket_children.get(b)
        if c is None:
            c = self._bucket_fam.labels(bucket=str(b), **self._inst)
            self._bucket_children[b] = c
        return c

    # ---------------------------------------------------------------- admin
    def depth(self) -> int:
        """Current queue depth (readiness probes)."""
        with self._cond:
            return len(self._q)

    def close(self, timeout: float = 5.0) -> None:
        """Stop accepting work; the worker drains the queue, then exits."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._worker.join(timeout)

    def stats(self) -> Dict[str, Any]:
        """The legacy `GET /` JSON shape, derived from the registry
        instruments (byte-compatible: same keys, same arithmetic)."""
        with self._cond:
            depth = len(self._q)
            size_hist = {k: int(c.value)
                         for k, c in self._size_children.items()}
            bucket_hist = {k: int(c.value)
                           for k, c in self._bucket_children.items()}
        batches = int(self._m_batches.value)
        queries = int(self._m_queries.value)
        flush_s = self._m_flush.sum
        return {
            "maxBatchSize": self.max_batch_size,
            "maxDelayMs": self.max_delay_s * 1e3,
            "maxQueue": self.max_queue,
            "buckets": list(self.buckets),
            "queueDepth": depth,
            "batches": batches,
            "queries": queries,
            "rejected": int(self._m_rejected.value),
            "batchSizeHist": {str(k): v for k, v in
                              sorted(size_hist.items())},
            "bucketHist": {str(k): v for k, v in
                           sorted(bucket_hist.items())},
            "avgQueueWaitMs": (self._m_queue_wait.value / queries * 1e3
                               if queries else 0.0),
            "avgFlushMs": (flush_s / batches * 1e3 if batches else 0.0),
        }
