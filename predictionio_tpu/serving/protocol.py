"""Batched-predict protocol + padding-bucket policy.

An algorithm opts into batched serving by overriding
``Algorithm.predict_batch(model, queries) -> [prediction]`` (see
controller/base.py). Everything else keeps working through the generic
fall-back that maps per-query ``predict`` — the batcher still amortizes
HTTP/queueing, just not the device dispatch.

Padding buckets: jitted batched kernels compile once per input SHAPE, so
flushing a 3-query batch as-is would compile a (3, r) program, a 5-query
batch a (5, r) one, and so on — an unbounded compile cache and a
recompile stall on the latency path. Batch-capable device paths instead
round the row count up to a small fixed set of bucket sizes and mask the
padding rows out, so at most len(buckets) programs exist per (k, shapes).
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Any, List, Optional, Sequence, Tuple

#: default padding buckets; override per-process with PIO_SERVE_BUCKETS
#: (comma-separated, e.g. "1,8,64").
DEFAULT_BUCKETS: Tuple[int, ...] = (1, 4, 16, 64)

#: FLUSH-SCOPED bucket set (serving/aot.py): the micro-batcher installs
#: its own — observation-pruned, AOT-prebuilt — bucket set on the
#: worker thread around each flush, so an algorithm's predict_batch
#: pads onto exactly the programs its deploy compiled. Thread-local
#: and context-managed: concurrent servers with different pruned sets
#: coexist, and nothing leaks past the flush (or the server) that
#: installed it.
_tls = threading.local()


@contextlib.contextmanager
def flush_buckets(buckets: Optional[Sequence[int]]):
    """Scope the calling thread's bucket resolution to ``buckets`` (the
    flushing batcher's set); None is a no-op passthrough."""
    if buckets is None:
        yield
        return
    prev = getattr(_tls, "buckets", None)
    _tls.buckets = pad_buckets(buckets)
    try:
        yield
    finally:
        _tls.buckets = prev


def active_buckets() -> Optional[Tuple[int, ...]]:
    """The calling thread's flush-scoped bucket set, if inside one."""
    return getattr(_tls, "buckets", None)


def pad_buckets(buckets: Optional[Sequence[int]] = None) -> Tuple[int, ...]:
    """Normalized, sorted bucket tuple (explicit arg > flush-scoped set
    > env > default)."""
    if buckets is None:
        active = active_buckets()
        if active is not None:
            return active
        env = os.environ.get("PIO_SERVE_BUCKETS")
        if env:
            buckets = [int(tok) for tok in env.split(",") if tok.strip()]
        else:
            buckets = DEFAULT_BUCKETS
    out = tuple(sorted({int(b) for b in buckets if int(b) >= 1}))
    if not out:
        raise ValueError(f"no usable padding buckets in {buckets!r}")
    return out


def bucket_for(n: int, buckets: Optional[Sequence[int]] = None) -> int:
    """Smallest bucket >= n; batches beyond the largest bucket compile at
    their exact size (the batcher's max_batch_size normally caps at the
    top bucket, so this is the overflow escape hatch, not the norm)."""
    for b in pad_buckets(buckets):
        if n <= b:
            return b
    return n


def batch_capable(algo: Any) -> bool:
    """True when the algorithm overrides the base predict_batch fallback
    (i.e. has a REAL batched implementation worth forming batches for)."""
    from predictionio_tpu.controller.base import Algorithm
    impl = getattr(type(algo), "predict_batch", None)
    return impl is not None and impl is not Algorithm.predict_batch


def predict_batch(algo: Any, model: Any, queries: Sequence[Any]) -> List[Any]:
    """Dispatch a batch through the algorithm's predict_batch (real or the
    base fallback). Non-Algorithm doers (duck-typed engines) without the
    method fall back to mapping predict."""
    impl = getattr(algo, "predict_batch", None)
    if impl is None:
        return [algo.predict(model, q) for q in queries]
    return list(impl(model, queries))


def host_serves_faster(dispatch, log) -> bool:
    """The CPU backend's layout rule, one for every engine: "device"
    arrays buy nothing for a tiny model there, so a real query
    (``dispatch()``, one call of the engine's single-query program) is
    compiled, then timed over three calls, and serving moves to host
    NumPy when a round trip is slower than PIO_SERVE_DEVICE_MS (default
    3 ms) or fails. Never asked on an accelerator, where the arrays stay
    on the device."""
    import time

    import jax

    try:
        jax.device_get(dispatch())      # warm the compile, then time
        t0 = time.perf_counter()
        for _ in range(3):
            jax.device_get(dispatch())
        per_query_ms = (time.perf_counter() - t0) / 3 * 1e3
    except Exception:
        per_query_ms = float("inf")
    threshold = float(os.environ.get("PIO_SERVE_DEVICE_MS", "3.0"))
    if per_query_ms > threshold:
        log.info("device round-trip %.2fms > %.1fms; serving from host "
                 "arrays", per_query_ms, threshold)
        return True
    return False


def device_layout_or_host(place, probe, log):
    """The rule engines' layout rule (models/ecommerce,
    models/similarproduct): ``place()`` builds the device layout. On an
    accelerator that is the layout, always, and a layout that fails
    RAISES: a deploy that quietly served some other way would pass
    every check without its layout ever having reached the chip. On the
    CPU backend, where a tiny model serves faster from host BLAS than
    through a dispatch, ``probe(layout)`` (one real bucket-1 query, a
    callable) is timed by :func:`host_serves_faster` and None (the
    host layout) returned when it is slow or placing failed."""
    import jax

    on_chip = jax.default_backend() != "cpu"
    try:
        dev = place()
        if not on_chip and host_serves_faster(probe(dev), log):
            dev = None
    except Exception:
        if on_chip:
            raise
        log.exception("device serving layout failed; serving from "
                      "host arrays")
        dev = None
    return dev


def device_rows(topk_fn, ixs, k: int, fill: int = 0):
    """The device half of a ``predict_batch``, the same for every device
    layout of every engine (recommendation: replicated, quantized,
    sharded; e-commerce: replicated with rules; similar product: item
    factors with rules): pad the batch's indices up to a serving
    bucket, make the ONE dispatch ``topk_fn(padded_ixs, k)``, fetch, and
    hand back the real rows of the ``(bucket, k)`` values and indices,
    still arrays: the `unpack` stage turns them into Python numbers,
    once a flush. ``ixs`` is (b,), a user a row, or (b, q), a padded
    list of items a row; a padding row holds ``fill``: index 0 where
    the program wants an in-bounds row (KNOWN_ISSUES #5), the list's
    own padding value where it takes one.
    Waterfall stages, drill-downs inside
    `dispatch` (and, on the batcher's worker, host spans in a profiler
    capture): `pad`; `execute` round `enqueue` (the call that returns the
    device arrays: argument transfer and launch) and `device_get`
    (blocked until the device is done, plus the copy back — the host
    transfer IS the clock stop, KNOWN_ISSUES #3, so the stage is honest
    on every backend)."""
    import jax
    import numpy as np

    from predictionio_tpu.common import waterfall

    with waterfall.stage("pad"):
        ixs = np.asarray(ixs)
        n = len(ixs)
        pix = np.full((bucket_for(n),) + ixs.shape[1:], fill, np.int32)
        pix[:n] = ixs
    with waterfall.stage("execute"):
        with waterfall.stage("enqueue"):
            on_device = topk_fn(pix, k)
        with waterfall.stage("device_get"):
            vals, idx = jax.device_get(on_device)
    return vals[:n], idx[:n]
