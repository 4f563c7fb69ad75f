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
    """A lone request with nothing in flight is served after
    max_delay_ms, not held forever."""
    b, batches = _collecting_batcher(max_batch_size=64, max_delay_ms=30.0)
    try:
        t0 = time.monotonic()
        assert b.submit("only") == "r:only"
        dt = time.monotonic() - t0
        assert 0.03 <= dt < 5.0    # its delay, not the 64-item wait
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


class _Gated:
    """A flush callback that holds each batch until the test opens its
    gate, or every gate (``open_all``, from then on): ``entered`` lists
    the batches that reached the callback, in order, ``most`` the most
    callbacks that ran at once."""

    def __init__(self, fail=()):
        self.cond = threading.Condition()
        self.entered, self.gates = [], []
        self.running = self.most = 0
        self.fail = set(fail)      # batch ordinals that raise
        self.opened = False

    def __call__(self, items):
        gate = threading.Event()
        with self.cond:
            if self.opened:
                gate.set()
            n = len(self.entered)
            self.entered.append(list(items))
            self.gates.append(gate)
            self.running += 1
            self.most = max(self.most, self.running)
            self.cond.notify_all()
        try:
            assert gate.wait(30)
            if n in self.fail:
                raise RuntimeError(f"batch {n} fell over")
            return [f"r:{x}" for x in items]
        finally:
            with self.cond:
                self.running -= 1

    def wait_entered(self, n, timeout=10.0):
        with self.cond:
            assert self.cond.wait_for(lambda: len(self.entered) >= n,
                                      timeout), self.entered
        return self.entered[n - 1]

    def open_all(self):
        with self.cond:
            self.opened = True
            for g in self.gates:
                g.set()


def _submit_all(b, items, out):
    """One request thread an item; ``out[item]`` is its result or the
    error's text."""
    def ask(x):
        try:
            out[x] = b.submit(x)
        except RuntimeError as e:
            out[x] = str(e)

    threads = [threading.Thread(target=ask, args=(x,)) for x in items]
    for t in threads:
        t.start()
    return threads


def _queued(b, n, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with b._cond:
            if len(b._q) >= n:
                return len(b._q)
        time.sleep(0.005)
    with b._cond:
        return len(b._q)


def _join(threads):
    for t in threads:
        t.join(10)
        assert not t.is_alive()


def test_admission_control_503():
    """Beyond two flushes in flight plus max_queue pending items, submit
    raises ServerSaturated with a Retry-After hint >= 1s; the backlog
    still drains correctly."""
    flush = _Gated()
    b = MicroBatcher(flush, max_batch_size=1, max_delay_ms=1.0, max_queue=2)
    out = {}
    try:
        # 2 provably in flight (both lanes inside flush) ...
        threads = _submit_all(b, [0], out)
        flush.wait_entered(1)
        threads += _submit_all(b, [1], out)
        flush.wait_entered(2)
        # ... + exactly max_queue queued behind them
        threads += _submit_all(b, [2, 3], out)
        assert _queued(b, b.max_queue) == b.max_queue
        with pytest.raises(ServerSaturated) as ei:
            b.submit("overflow")
        assert ei.value.retry_after_s >= 1
        assert b.stats()["rejected"] == 1
        flush.open_all()
        _join(threads)
        assert out == {k: f"r:{k}" for k in range(4)}
        assert b.stats()["queries"] == 4
        assert flush.most == 2
    finally:
        flush.open_all()
        b.close()


def test_full_batch_launches_while_one_is_in_flight():
    """A full batch goes while the other lane's flush is in flight: two
    callbacks at once, never three; `overlapped` counts that flush."""
    flush = _Gated()
    b = MicroBatcher(flush, max_batch_size=2, max_delay_ms=60_000)
    out = {}
    try:
        threads = _submit_all(b, ["a0", "a1"], out)
        assert sorted(flush.wait_entered(1)) == ["a0", "a1"]
        threads += _submit_all(b, ["b0", "b1"], out)
        assert sorted(flush.wait_entered(2)) == ["b0", "b1"]
        # a third full batch queues: both lanes are inside a flush
        threads += _submit_all(b, ["c0", "c1"], out)
        assert _queued(b, 2) == 2
        time.sleep(0.05)
        assert len(flush.entered) == 2 and flush.running == 2
        assert b.stats()["overlapped"] == 1
        flush.gates[0].set()
        assert sorted(flush.wait_entered(3)) == ["c0", "c1"]
        flush.open_all()
        _join(threads)
        assert out == {x: f"r:{x}" for x in
                       ("a0", "a1", "b0", "b1", "c0", "c1")}
        assert flush.most == 2
        stats = b.stats()
        # the third began while the second was still in flight
        assert stats["batches"] == 3 and stats["overlapped"] == 2
    finally:
        flush.open_all()
        b.close()


def test_partial_batch_waits_for_the_flush_in_flight():
    """While a flush is in flight a partial batch does not go, however
    long its head has waited; it flushes when that flush returns."""
    flush = _Gated()
    b = MicroBatcher(flush, max_batch_size=4, max_delay_ms=1.0)
    out = {}
    try:
        threads = _submit_all(b, ["head"], out)
        assert flush.wait_entered(1) == ["head"]
        threads += _submit_all(b, ["x", "y"], out)
        assert _queued(b, 2) == 2
        time.sleep(0.1)        # a hundred times the head's delay
        assert len(flush.entered) == 1
        flush.gates[0].set()
        assert sorted(flush.wait_entered(2)) == ["x", "y"]
        flush.open_all()
        _join(threads)
        assert out == {"head": "r:head", "x": "r:x", "y": "r:y"}
        assert b.stats()["overlapped"] == 0
    finally:
        flush.open_all()
        b.close()


def test_two_flushes_in_flight_answer_only_their_own_waiters():
    """With two flushes in flight, one batch's error and the other's
    results reach only their own waiters, whichever returns first."""
    flush = _Gated(fail={0})
    b = MicroBatcher(flush, max_batch_size=2, max_delay_ms=60_000)
    out = {}
    try:
        threads = _submit_all(b, ["a0", "a1"], out)
        flush.wait_entered(1)
        threads += _submit_all(b, ["b0", "b1"], out)
        flush.wait_entered(2)
        flush.gates[1].set()       # the second returns first
        _join(threads[2:])
        assert out == {"b0": "r:b0", "b1": "r:b1"}
        flush.gates[0].set()
        _join(threads[:2])
        assert out["a0"] == out["a1"] == "batch 0 fell over"
    finally:
        flush.open_all()
        b.close()


def test_close_drains_both_lanes():
    """close() lets both flushes in flight and everything queued behind
    them finish, then both lanes exit."""
    flush = _Gated()
    b = MicroBatcher(flush, max_batch_size=2, max_delay_ms=60_000)
    out = {}
    items = ["a0", "a1", "b0", "b1", "c0"]
    threads = _submit_all(b, items[:2], out)
    flush.wait_entered(1)
    threads += _submit_all(b, items[2:4], out)
    flush.wait_entered(2)
    threads += _submit_all(b, items[4:], out)
    assert _queued(b, 1) == 1
    closer = threading.Thread(target=b.close, kwargs={"timeout": 10})
    closer.start()
    time.sleep(0.02)
    flush.open_all()
    assert flush.wait_entered(3) == ["c0"]    # closed: a partial goes
    flush.open_all()
    _join(threads + [closer])
    assert out == {x: f"r:{x}" for x in items}
    assert not any(lane.is_alive() for lane in b._lanes)
    assert len(b._lanes) == 2
    with pytest.raises(RuntimeError, match="closed"):
        b.submit("late")


def test_lanes_under_many_submitters_lose_nothing():
    """Stress: many more submitters than cores, a short switch interval:
    every item gets its own answer, never more than two flushes run at
    once, and the counters add up."""
    import sys

    running = [0, 0]          # now, most
    lock = threading.Lock()

    def flush(items):
        with lock:
            running[0] += 1
            running[1] = max(running[1], running[0])
        time.sleep(0.001)
        with lock:
            running[0] -= 1
        return [("r", x) for x in items]

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    b = MicroBatcher(flush, max_batch_size=8, max_delay_ms=0.5,
                     max_queue=10_000)
    try:
        out = {}
        threads = []
        for x in range(400):
            t = threading.Thread(
                target=lambda x=x: out.__setitem__(x, b.submit(x)))
            t.start()
            threads.append(t)
        _join(threads)
        assert out == {x: ("r", x) for x in range(400)}
        assert running[1] <= 2
        stats = b.stats()
        assert stats["queries"] == 400
        assert sum(stats["batchSizeHist"].values()) == stats["batches"]
        assert 0 <= stats["overlapped"] < stats["batches"]
    finally:
        sys.setswitchinterval(interval)
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


# ------------------------------------------------ a flush on two lanes
def at_once(fn, batches, rounds=4):
    """``fn(batch)`` for every batch, each on its own thread, the
    threads let go together ``rounds`` times: what the batcher's two
    lanes do to a flush callback. -> one list of answers a batch."""
    barrier = threading.Barrier(len(batches))
    out = [[] for _ in batches]

    def run(i):
        for _ in range(rounds):
            barrier.wait(30)
            out[i].append(fn(batches[i]))

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(batches))]
    for t in threads:
        t.start()
    _join(threads)
    assert all(len(o) == rounds for o in out)
    return out


@pytest.mark.parametrize("branch", ["replicated", "quant", "sharded"])
def test_recommendation_predict_batch_is_reentrant(branch):
    """The recommendation engine's device layouts: two flushes at once,
    at two buckets, answer as each does alone."""
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.recommendation.als_algorithm import (
        ALSAlgorithm, ALSAlgorithmParams, ALSModel,
    )
    from predictionio_tpu.models.recommendation.engine import Query
    from predictionio_tpu.ops import quant
    from predictionio_tpu.parallel import serve_dist

    rng = np.random.default_rng(5)
    U = rng.normal(size=(40, 8)).astype(np.float32)
    V = rng.normal(size=(300, 8)).astype(np.float32)
    vocabs = dict(user_vocab=BiMap({f"u{k}": k for k in range(40)}),
                  item_vocab=BiMap({f"i{k}": k for k in range(300)}))
    if branch == "replicated":
        model = ALSModel(rank=8, user_factors=jnp.asarray(U),
                         item_factors=jnp.asarray(V), **vocabs)
    elif branch == "quant":
        model = ALSModel(rank=8, user_factors=U, item_factors=V,
                         quant=quant.QuantizedServing.build(
                             quant.QuantizedFactors.from_factors(U, V)),
                         **vocabs)
    else:
        sharded = serve_dist.shard_factors(U, V)
        model = ALSModel(rank=8, user_factors=sharded.user_shards,
                         item_factors=sharded.item_shards,
                         sharding=sharded, **vocabs)
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=8))
    batches = [[Query(user=f"u{(7 * j + r) % 41}", num=1 + j % 12)
                for j in range(n)] for r, n in enumerate((3, 20))]
    alone = [algo.predict_batch(model, b) for b in batches]
    assert any(r.itemScores for r in alone[1])
    for got, due in zip(at_once(lambda b: algo.predict_batch(model, b),
                                batches), alone):
        assert all(g == due for g in got)


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
