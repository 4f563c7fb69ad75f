"""Micro-batcher semantics (serving/batcher.py + serving/protocol.py):
flush-on-size, flush-on-timeout, admission control, error propagation,
padding-bucket policy, and the predict_batch protocol fallback."""

import threading
import time

import pytest

from predictionio_tpu.serving import (
    MicroBatcher, ServerSaturated, batch_capable, bucket_for, pad_buckets,
)


# ------------------------------------------------------------------ buckets
def test_bucket_for_rounds_up():
    assert bucket_for(1, (1, 4, 16, 64)) == 1
    assert bucket_for(2, (1, 4, 16, 64)) == 4
    assert bucket_for(4, (1, 4, 16, 64)) == 4
    assert bucket_for(17, (1, 4, 16, 64)) == 64
    # beyond the top bucket: exact size (overflow escape hatch)
    assert bucket_for(65, (1, 4, 16, 64)) == 65


def test_pad_buckets_env_override(monkeypatch):
    monkeypatch.setenv("PIO_SERVE_BUCKETS", "8, 2,32")
    assert pad_buckets() == (2, 8, 32)
    monkeypatch.setenv("PIO_SERVE_BUCKETS", "0,-3")
    with pytest.raises(ValueError):
        pad_buckets()
    monkeypatch.delenv("PIO_SERVE_BUCKETS")
    assert pad_buckets() == (1, 4, 16, 64)
    assert pad_buckets((16, 4, 4)) == (4, 16)


# ---------------------------------------------------------------- batching
def _collecting_batcher(**kw):
    batches = []

    def flush(items):
        batches.append(list(items))
        return [f"r:{x}" for x in items]

    return MicroBatcher(flush, **kw), batches


def test_flush_on_size():
    """A full batch flushes immediately, without waiting out the timer."""
    b, batches = _collecting_batcher(max_batch_size=4, max_delay_ms=60_000)
    try:
        results = [None] * 4

        def hit(k):
            results[k] = b.submit(k)

        threads = [threading.Thread(target=hit, args=(k,)) for k in range(4)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        assert time.monotonic() - t0 < 30  # far below the 60 s timer
        assert sorted(results) == ["r:0", "r:1", "r:2", "r:3"]
        assert len(batches) == 1 and sorted(batches[0]) == [0, 1, 2, 3]
        stats = b.stats()
        assert stats["batches"] == 1 and stats["queries"] == 4
        assert stats["batchSizeHist"] == {"4": 1}
        assert stats["bucketHist"] == {"4": 1}
    finally:
        b.close()


def test_flush_on_timeout():
    """A lone request is served after ~max_delay_ms, not held forever."""
    b, batches = _collecting_batcher(max_batch_size=64, max_delay_ms=30.0)
    try:
        t0 = time.monotonic()
        assert b.submit("only") == "r:only"
        dt = time.monotonic() - t0
        assert dt < 5.0            # seconds, not the 64-item wait
        assert batches == [["only"]]
    finally:
        b.close()


def test_timer_anchored_on_oldest():
    """A steady trickle of new arrivals must not starve the head request:
    the flush deadline comes from the FIRST enqueued item."""
    b, batches = _collecting_batcher(max_batch_size=64, max_delay_ms=120.0)
    try:
        done = threading.Event()
        out = []

        def first():
            out.append(b.submit("head"))
            done.set()

        threading.Thread(target=first).start()
        # trickle younger items in while the head waits
        trickle = []
        for k in range(3):
            time.sleep(0.03)
            t = threading.Thread(target=lambda k=k: b.submit(k))
            t.start()
            trickle.append(t)
        assert done.wait(10)
        assert out == ["r:head"]
        assert batches[0][0] == "head"
        for t in trickle:
            t.join(10)
    finally:
        b.close()


def test_greedy_mode_self_clocks():
    """max_delay_ms=0: a lone request flushes immediately, but arrivals
    during a busy flush still coalesce into the next batch."""
    gate = threading.Event()
    batches = []

    def flush(items):
        batches.append(list(items))
        if len(batches) == 1:
            gate.wait(30)    # hold the first batch on the "device"
        return list(items)

    b = MicroBatcher(flush, max_batch_size=64, max_delay_ms=0.0)
    try:
        threads = [threading.Thread(target=b.submit, args=("head",))]
        threads[0].start()
        while not batches:          # first batch is in flight
            time.sleep(0.005)
        for k in range(3):          # these arrive while the device is busy
            t = threading.Thread(target=b.submit, args=(k,))
            t.start()
            threads.append(t)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with b._cond:
                if len(b._q) == 3:
                    break
            time.sleep(0.005)
        gate.set()
        for t in threads:
            t.join(10)
        assert batches[0] == ["head"]
        assert len(batches) == 2 and sorted(batches[1]) == [0, 1, 2]
    finally:
        gate.set()
        b.close()


def test_admission_control_503():
    """Beyond max_queue pending items, submit raises ServerSaturated with
    a Retry-After hint >= 1s; the backlog still drains correctly."""
    entered = threading.Event()
    gate = threading.Event()

    def flush(items):
        entered.set()
        gate.wait(30)
        return list(items)

    b = MicroBatcher(flush, max_batch_size=1, max_delay_ms=1.0, max_queue=2)
    try:
        # 1 provably in-flight (the worker is inside flush) ...
        threads = [threading.Thread(target=b.submit, args=(0,))]
        threads[0].start()
        assert entered.wait(10)
        # ... + exactly max_queue queued behind it
        for k in (1, 2):
            t = threading.Thread(target=b.submit, args=(k,))
            t.start()
            threads.append(t)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with b._cond:
                depth = len(b._q)
            if depth >= b.max_queue:
                break
            time.sleep(0.01)
        assert depth == b.max_queue
        with pytest.raises(ServerSaturated) as ei:
            b.submit("overflow")
        assert ei.value.retry_after_s >= 1
        assert b.stats()["rejected"] == 1
        gate.set()
        for t in threads:
            t.join(10)
        assert b.stats()["queries"] == 3
    finally:
        gate.set()
        b.close()


def test_flush_error_propagates_to_every_waiter():
    def flush(items):
        raise RuntimeError("device fell over")

    b = MicroBatcher(flush, max_batch_size=8, max_delay_ms=1.0)
    try:
        errs = []

        def hit(k):
            try:
                b.submit(k)
            except RuntimeError as e:
                errs.append(str(e))

        threads = [threading.Thread(target=hit, args=(k,)) for k in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        assert errs == ["device fell over"] * 3
    finally:
        b.close()


def test_wrong_result_count_is_an_error():
    b = MicroBatcher(lambda items: [1, 2, 3], max_batch_size=1,
                     max_delay_ms=1.0)
    try:
        with pytest.raises(RuntimeError, match="flush returned"):
            b.submit("x")
    finally:
        b.close()


def test_close_drains_then_rejects():
    b, batches = _collecting_batcher(max_batch_size=8, max_delay_ms=50.0)
    results = []
    t = threading.Thread(target=lambda: results.append(b.submit("last")))
    t.start()
    time.sleep(0.05)
    b.close()
    t.join(10)
    assert results == ["r:last"]    # close() drained the pending item
    with pytest.raises(RuntimeError, match="closed"):
        b.submit("late")


# --------------------------------------------------------------- protocol
def test_batch_capable_detects_real_overrides():
    from predictionio_tpu.controller.base import Algorithm

    class Plain(Algorithm):
        def train(self, ctx, pd):
            return None

        def predict(self, model, q):
            return ("p", q)

    class Batched(Plain):
        def predict_batch(self, model, queries):
            return [("b", q) for q in queries]

    assert not batch_capable(Plain())
    assert batch_capable(Batched())
    # the base fallback maps predict, preserving order
    assert Plain().predict_batch(None, [1, 2]) == [("p", 1), ("p", 2)]
    assert Batched().predict_batch(None, [1, 2]) == [("b", 1), ("b", 2)]


# ------------------------------------------- predict_batch's unpack stage
class _FetchedRows:
    """In a sharded layout's place: fixed float32 / int32 (bucket, k)
    arrays for any flush. Items past ``n_real`` are fold-in headroom."""
    n_shards = 1

    def __init__(self, n_items, seed=11):
        import numpy as np
        rng = np.random.default_rng(seed)
        self.vals = -np.sort(-rng.standard_normal((64, 12)).astype(
            np.float32), axis=1)
        self.idx = rng.integers(0, n_items + 3, (64, 12)).astype(np.int32)
        self.asked = []

    def topk(self, pix, k):
        self.asked.append((len(pix), k))
        return self.vals[:len(pix), :k], self.idx[:len(pix), :k]


def _unpack_as_it_was(model, stub, queries, k):
    """The scalar-by-scalar loop `predict_batch` ran before `tolist()`."""
    from predictionio_tpu.models.recommendation.engine import (
        ItemScore, PredictedResult,
    )
    n_real, inv = len(model.item_vocab), model.item_vocab.inverse()
    out, row = [], 0
    for q in queries:
        if model.user_vocab.get(q.user) is None or min(q.num, n_real) <= 0:
            out.append(PredictedResult(()))
            continue
        n = min(q.num, k)
        out.append(PredictedResult(tuple(
            ItemScore(item=inv(int(i)), score=float(s))
            for s, i in zip(stub.vals[row, :n], stub.idx[row, :n])
            if int(i) < n_real)))
        row += 1
    return out


@pytest.mark.parametrize("nums,bucket", [
    ([10], 1), ([10] * 4, 4), ([10] * 3, 4), ([10] * 50, 64),
    ([10] * 64, 64), ([3, 10, 1, 0, 12, 40, -2, 7], 16),
], ids=["bucket1", "bucket4", "bucket4_padded", "bucket64_of_50",
        "bucket64_full", "mixed_num"])
def test_predict_batch_unpack_gives_the_same_results(nums, bucket):
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.recommendation.als_algorithm import (
        ALSAlgorithm, ALSAlgorithmParams, ALSModel,
    )
    from predictionio_tpu.models.recommendation.engine import Query

    n_items = 9    # under the stub's k of 12, so the headroom guard bites
    stub = _FetchedRows(n_items)
    model = ALSModel(
        rank=4, user_factors=None, item_factors=None,
        user_vocab=BiMap.string_int(f"u{j}" for j in range(80)),
        item_vocab=BiMap.string_int(f"i{j}" for j in range(n_items)),
        sharding=stub)
    queries = [Query(user=f"u{j}", num=n) for j, n in enumerate(nums)]
    queries.insert(len(queries) // 2, Query(user="nobody", num=5))
    got = ALSAlgorithm(ALSAlgorithmParams()).predict_batch(model, queries)
    valid = [n for n in nums if min(n, n_items) > 0]
    k = min(max(valid), n_items)
    assert stub.asked == [(bucket, k)]
    want = _unpack_as_it_was(model, stub, queries, k)
    assert got == want
    dropped = 0
    for g, w in zip(got, want):
        for a, b in zip(g.itemScores, w.itemScores):
            assert type(a.item) is str and type(a.score) is float
            assert (a.item, repr(a.score)) == (b.item, repr(b.score))
    for row, n in enumerate(valid):
        dropped += int((stub.idx[row, :min(n, k)] >= n_items).sum())
    assert dropped > 0   # pad rows were asked for, and none surfaced
    assert sum(len(g.itemScores) for g in got) == sum(
        min(n, k) for n in valid) - dropped
