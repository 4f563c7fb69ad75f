"""Where a query's host seconds go, counted in every run: the counted
spans of common/profiling.py (calls and exclusive wall seconds a span,
per-thread tables summed on read) and its thread groups' CPU clocks,
read when asked, the batcher's `lanes` and
`wakeSeconds`, the transports' `cpuSeconds`, the process's `host` block,
their `/metrics` families, and the benchmark's five metric files reduced
on two `GET /` pages of a live deploy."""

import http.client
import importlib.util
import json
import math
import os
import sys
import threading
import time

import pytest

from predictionio_tpu.common import declarations, profiling
from predictionio_tpu.data.api.http import serve_background
from predictionio_tpu.serving import MicroBatcher
from predictionio_tpu.serving import batcher as batcher_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the benchmark's five readers of these counters; none reads
#: `host.runQueueSeconds`, which a kernel without schedstat leaves out
FIVE = ("serve.host_cpu_us.rate", "transport.cpu_us.rate",
        "batcher.wake_ms.rate", "batcher.lane_cpu_ms.rate",
        "batcher.lane_offcpu_ms.rate")


def _burn(seconds):
    """Spin on this thread's CPU for `seconds` of thread time."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def _in_thread(fn):
    """Run `fn` on a fresh thread (a table of its own) -> its result."""
    out = {}
    t = threading.Thread(target=lambda: out.setdefault("r", fn()))
    t.start()
    t.join()
    return out["r"]


# ------------------------------------------------------------ counted spans
def test_exclusive_wall_under_nesting_and_cpu_against_wall():
    """A child's time is taken out of its parent's; a thread that sleeps
    inside a span runs up wall and no CPU on its clock, one that burns
    runs up both."""
    group = profiling.ThreadCPU()

    def run():
        group.join()
        t0 = time.perf_counter()
        with profiling.annotate("outer"):
            _burn(0.02)
            with profiling.annotate("inner"):
                time.sleep(0.1)
        whole = time.perf_counter() - t0
        got = profiling.span_totals([profiling.thread_spans()])
        return got, whole, group.seconds()

    (got, whole, cpu) = _in_thread(run)
    outer, inner = got["outer"], got["inner"]
    assert outer["n"] == inner["n"] == 1
    assert set(outer) == {"n", "wallSeconds"}
    # the sleep is the inner span's alone, the burn the outer one's, and
    # the two exclusive times add up to the whole
    assert 0.1 <= inner["wallSeconds"] and 0.02 <= outer["wallSeconds"]
    assert outer["wallSeconds"] + inner["wallSeconds"] == pytest.approx(
        whole, abs=0.02)            # counted twice, the sum would be 0.1 over
    # on the clock: the burn, not the sleep
    assert 0.02 <= cpu < 0.06
    # a thread that ended without leave() keeps its last reading
    assert group.seconds() == pytest.approx(cpu)


def test_thread_cpu_counts_each_thread_once_and_never_falls():
    """Two threads burn on one group's clocks: a reading while they run
    sees their CPU so far; one that leaves is counted once; a second
    join is no change; the sum never falls."""
    group = profiling.ThreadCPU()
    go, readings = threading.Event(), []
    burnt = threading.Barrier(3, timeout=10)

    def worker(leave):
        group.join()
        group.join()
        _burn(0.03)
        burnt.wait()
        go.wait(10)
        if leave:
            group.leave()

    threads = [threading.Thread(target=worker, args=(k == 0,))
               for k in range(2)]
    for t in threads:
        t.start()
    burnt.wait()
    readings.append(group.seconds())
    go.set()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    time.sleep(0.1)                     # the OS threads finish exiting
    readings += [group.seconds(), group.seconds()]
    assert 0.06 <= readings[0] < 0.12
    assert readings[0] <= readings[1] <= readings[2] < 0.12
    assert readings[2] == pytest.approx(readings[1], abs=1e-3)


def test_per_thread_tables_are_summed_on_read():
    """Two threads open the same span, each in its own table; the whole
    process's totals are their sum and a thread's own are its alone;
    a thread that ended keeps its counts."""
    def run(k):
        for _ in range(k):
            with profiling.annotate("summed_on_read"):
                time.sleep(0.002)
        return profiling.thread_spans()

    a, b = _in_thread(lambda: run(2)), _in_thread(lambda: run(3))
    assert a is not b
    mine = {t: profiling.span_totals([t])["summed_on_read"] for t in (a, b)}
    assert (mine[a]["n"], mine[b]["n"]) == (2, 3)
    whole = profiling.span_totals()["summed_on_read"]
    assert whole["n"] >= 5      # other tests' threads may have added more
    assert whole["wallSeconds"] >= (mine[a]["wallSeconds"]
                                    + mine[b]["wallSeconds"] - 1e-12)
    assert mine[a]["wallSeconds"] >= 2 * 0.002


def test_counts_lose_nothing_under_thread_switches():
    """More threads than cores open spans with the switch interval
    shortened, while a reader sums the tables: every call is counted
    once, and no reading runs backwards."""
    workers, calls = 2 * (os.cpu_count() or 1) + 2, 300
    name = "stress_switches"
    readings, done = [], threading.Event()

    def worker():
        for _ in range(calls):
            with profiling.annotate(name):
                with profiling.annotate(name + ".inner"):
                    pass

    def reader():
        while not done.is_set():
            got = profiling.span_totals().get(name)
            if got is not None:
                readings.append((got["n"], got["wallSeconds"]))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        watcher = threading.Thread(target=reader)
        watcher.start()
        threads = [threading.Thread(target=worker) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        done.set()
        watcher.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads + [watcher])
    got = profiling.span_totals()
    assert got[name]["n"] == got[name + ".inner"]["n"] == workers * calls
    assert readings and all(a[0] <= b[0] and a[1] <= b[1] + 1e-12
                            for a, b in zip(readings, readings[1:]))


def test_lanes_leave_out_exactly_the_three_waits():
    """`batching.lanes.workWallSeconds` sums every span of the two lanes
    but `idle_wait`, `fill_wait` and `device_get`: a flush's 100 ms in
    `device_get` and an idle lane's 300 ms stay out, its 20 ms of `pad`
    are in; `workCpuSeconds` is the lanes' clocks: a burn in `unpack`
    shows there, the sleeps do not."""
    from predictionio_tpu.common import waterfall

    assert batcher_mod.WAITS == {"idle_wait", "fill_wait", "device_get"}

    def flush(items):
        with waterfall.stage("pad"):
            time.sleep(0.02)
        with waterfall.stage("device_get"):
            time.sleep(0.1)
        with waterfall.stage("unpack"):
            _burn(0.01)
        return items

    b = MicroBatcher(flush, max_batch_size=1, max_delay_ms=0.0)
    try:
        for k in range(3):
            assert b.submit(k) == k
        time.sleep(0.3)             # a lane waits in idle_wait meanwhile
        assert b.submit(3) == 3
        time.sleep(0.05)            # the last wake closes
        lanes = b.stats()["lanes"]
        spans = profiling.span_totals(b._lane_spans)
    finally:
        b.close()
    assert set(lanes) == {"workWallSeconds", "workCpuSeconds"}
    assert spans["pad"]["n"] == spans["device_get"]["n"] == 4
    assert spans["idle_wait"]["wallSeconds"] >= 0.3
    work = {n: t for n, t in spans.items() if n not in batcher_mod.WAITS}
    assert lanes["workWallSeconds"] == pytest.approx(
        sum(t["wallSeconds"] for t in work.values()))
    # in: four pads' sleeps (off the CPU) and four burns; out: device_get
    # (0.4 s) and idle_wait (0.3 s)
    assert 4 * 0.03 <= lanes["workWallSeconds"] < 4 * 0.03 + 0.25
    assert 4 * 0.01 <= lanes["workCpuSeconds"] < 4 * 0.01 + 0.05


def test_wake_seconds_grow_by_one_stamp_a_query(monkeypatch):
    """`batching.wakeSeconds` adds, per query, the submitting thread's
    clock when its wait returns less the lane's stamp before
    `done.set()`: a request thread held 100 ms after its wake adds
    100 ms, once."""
    class SlowEvent(threading.Event):
        def wait(self, timeout=None):
            got = super().wait(timeout)
            time.sleep(0.1)         # what a thread in line for the GIL sees
            return got

    class Pending(batcher_mod._Pending):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.done = SlowEvent()

    monkeypatch.setattr(batcher_mod, "_Pending", Pending)
    b = MicroBatcher(lambda items: items, max_batch_size=1, max_delay_ms=0.0)
    try:
        assert b.stats()["wakeSeconds"] == 0.0
        for k in range(5):
            b.submit(k)
        st = b.stats()
    finally:
        b.close()
    assert st["queries"] == 5
    assert 5 * 0.1 <= st["wakeSeconds"] < 5 * 0.1 + 0.4


# ------------------------------------------------------------ the transports
class _WorkAPI:
    """`/burn` spends 5 ms of CPU, `/sleep` 50 ms off it."""

    def handle(self, method, path, query=None, body=b"", headers=None):
        if path == "/burn":
            _burn(0.005)
        elif path == "/sleep":
            time.sleep(0.05)
        return 200, {"ok": True}


@pytest.mark.parametrize("transport", ["threaded", "async"])
def test_transport_cpu_seconds_on_both_transports(transport):
    """`transport.cpuSeconds` is the request threads' CPU, read from
    their clocks: ten 5 ms burns add at least 50 ms; ten 50 ms sleeps
    add far less than their 500 ms of wall; it never falls once the
    server and its threads are gone."""
    from predictionio_tpu.data.api.http import transport_status

    server, port = serve_background(_WorkAPI(), transport=transport)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)

        def ask(path, n):
            c0 = transport_status()["cpuSeconds"]
            for _ in range(n):
                conn.request("GET", path)
                resp = conn.getresponse()
                assert resp.status == 200 and resp.read()
            time.sleep(0.05)        # the last reply's count lands after it
            return transport_status()["cpuSeconds"] - c0

        burnt = ask("/burn", 10)
        slept = ask("/sleep", 10)
        conn.close()
        before_close = transport_status()["cpuSeconds"]
    finally:
        server.shutdown()
        server.server_close()
    assert burnt >= 10 * 0.005
    assert slept < 0.25
    time.sleep(0.1)
    assert transport_status()["cpuSeconds"] >= before_close


# ---------------------------------------------------------- the process
def _fake_tasks(tmp_path, readings):
    """A /proc/self/task of our own: tid -> schedstat text."""
    for tid, text in readings.items():
        (tmp_path / str(tid)).mkdir()
        (tmp_path / str(tid) / "schedstat").write_text(text)
    return str(tmp_path)


@pytest.fixture()
def exited(monkeypatch):
    monkeypatch.setattr(profiling, "_run_queue_last", {})
    monkeypatch.setattr(profiling, "_run_queue_gone", 0.0)


def test_host_block_leaves_run_queue_out_where_schedstat_reads_zero(
        tmp_path, monkeypatch, exited):
    monkeypatch.setattr(profiling, "_TASKS", _fake_tasks(
        tmp_path, {101: "0 0 0\n", 102: "0 0 0\n"}))
    got = profiling.host_status()
    assert set(got) == {"cpuSeconds", "threads"}
    assert got["threads"] == 2 and got["cpuSeconds"] > 0


def test_host_block_sums_run_queue_and_keeps_ended_threads(
        tmp_path, monkeypatch, exited):
    """Field 2 of every task's schedstat, in seconds; a task gone since
    the last page keeps its last reading, so the sum never falls."""
    root = _fake_tasks(tmp_path, {101: "9 2000000000 1\n",
                                  102: "9 500000000 1\n",
                                  103: "9 250000000 1\n"})
    monkeypatch.setattr(profiling, "_TASKS", root)

    def gone(tid):
        os.remove(os.path.join(root, str(tid), "schedstat"))
        os.rmdir(os.path.join(root, str(tid)))

    assert profiling.host_status()["runQueueSeconds"] == pytest.approx(2.75)
    gone(101)
    gone(103)
    got = profiling.host_status()
    assert got["runQueueSeconds"] == pytest.approx(2.75)
    assert got["threads"] == 1
    with open(os.path.join(root, "102", "schedstat"), "w") as f:
        f.write("9 600000000 1\n")
    assert profiling.host_status()["runQueueSeconds"] == pytest.approx(2.85)


# ------------------------------------------- the pages, /metrics, the readers
def _load_reduce():
    path = os.path.join(ROOT, "benchmark", "reduce.py")
    spec = importlib.util.spec_from_file_location("bench_reduce_counters", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _metric_specs():
    bj = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    out = []
    for m in bj["per_layer"]:
        if m["name"] in FIVE:
            with open(os.path.join(ROOT, "benchmark", "metrics",
                                   m["name"] + ".json")) as f:
                out.append({**m, **json.load(f)})
    return out


@pytest.fixture()
def live_pages(memory_storage):
    """Two `GET /` pages of a batching deploy round 60 queries over 8
    connections, and its `/metrics` text after them."""
    from test_telemetry import _trained_query_api

    api, _ = _trained_query_api(memory_storage)
    server, port = serve_background(api)

    def client(k):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        for j in range(k, 60, 8):
            conn.request("POST", "/queries.json",
                         json.dumps({"user": f"u{j % 4}", "num": 2}))
            resp = conn.getresponse()
            assert resp.status == 200 and resp.read()
        conn.close()

    try:
        before = api.handle("GET", "/")[1]
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        time.sleep(0.05)
        after = api.handle("GET", "/")[1]
        metrics = api.handle("GET", "/metrics")[1]
    finally:
        server.shutdown()
        server.server_close()
        api.close()
    yield before, after, metrics


def test_pages_carry_the_counters_and_metrics_names_them(live_pages):
    before, after, metrics = live_pages
    for page in (before, after):
        assert {"hostSpans", "host"} <= set(page)
    assert after["batching"]["queries"] - before["batching"]["queries"] == 60
    assert after["batching"]["wakeSeconds"] > before["batching"]["wakeSeconds"]
    assert after["transport"]["cpuSeconds"] > before["transport"]["cpuSeconds"]
    spans = after["hostSpans"]
    assert {"idle_wait", "form_batch", "flush", "wake"} <= set(spans)
    assert set(spans["flush"]) == {"n", "wallSeconds"}
    lines = metrics.splitlines()
    for family in ("pio_host_span_seconds_total", "pio_host_spans_total",
                   "pio_transport_cpu_seconds_total",
                   "pio_batcher_wake_seconds_total"):
        assert family in declarations.METRICS
        assert any(ln.startswith(family) for ln in lines), family
    assert 'pio_host_span_seconds_total{span="flush"}' in metrics
    assert 'pio_host_spans_total{span="wake"}' in metrics


def test_the_six_metric_files_reduce_to_finite_values(live_pages):
    """benchmark/reduce.py, loaded in place, reads each of the five from
    the two pages' `counter` terms, whatever the kernel gives of
    run-queue time."""
    before, after, _ = live_pages
    reduce = _load_reduce()
    specs = _metric_specs()
    assert sorted(s["name"] for s in specs) == sorted(FIVE)
    for s in specs:
        assert s["source"] == "program_counter" and s["moves"] == "query_rate"
    got = reduce.layer_metrics(specs, {"counters": {"window": [before, after]}})
    assert set(got) == set(FIVE)
    for name, m in got.items():
        assert math.isfinite(m["value"]) and m["value"] >= 0, (name, m)
    # the parent's pages: none of the counters, so none of the five
    strip = [{k: v for k, v in p.items() if k not in ("host", "hostSpans")}
             for p in (before, after)]
    for p in strip:
        p["batching"] = {k: v for k, v in p["batching"].items()
                         if k not in ("lanes", "wakeSeconds")}
        p["transport"] = {k: v for k, v in p["transport"].items()
                          if k != "cpuSeconds"}
    assert reduce.layer_metrics(specs, {"counters": {"window": strip}}) == {}


@pytest.mark.parametrize("name", FIVE)
def test_a_metric_of_the_host_counters_reads_without_schedstat(
        name, live_pages, tmp_path, monkeypatch, exited):
    """A metric that lists the closed-loop cells has to read in each of
    them, and the chip's host gives no schedstat: with every task's
    reading 0 the `host` block loses `runQueueSeconds`, and each of the
    five still reduces to a finite value on pages built so."""
    before, after, _ = live_pages
    monkeypatch.setattr(profiling, "_TASKS", _fake_tasks(
        tmp_path, {101: "0 0 0\n"}))
    host = profiling.host_status()
    assert "runQueueSeconds" not in host
    pages = []
    for page, cpu in ((before, 0.0), (after, 0.5)):
        pages.append({**page, "host": {**host, "cpuSeconds": cpu}})
    spec = [s for s in _metric_specs() if s["name"] == name]
    got = _load_reduce().layer_metrics(spec, {"counters": {"window": pages}})
    assert set(got) == {name}
    assert math.isfinite(got[name]["value"]) and got[name]["value"] >= 0


# ------------------------------------- tracing and sampling off: null context
def test_tracing_off_hands_back_the_shared_null_context():
    """No context on the thread: `activate(None)` and `span()` are the one
    shared null context; with one, `activate(None)` still clears it for
    the block and `span()` still records a child under it."""
    from predictionio_tpu.common import tracing

    assert tracing.current() is None
    off = tracing.activate(None)
    assert off is tracing.span("flush") is tracing._PASS_THROUGH
    with off as got:
        assert got is None and tracing.current() is None
    tracing.clear()
    ctx = tracing.new_context("feedc0de00000001")
    with tracing.activate(ctx) as active:
        assert active is ctx and tracing.current() is ctx
        with tracing.activate(None) as cleared:
            assert cleared is None and tracing.current() is None
        assert tracing.current() is ctx
        with tracing.span("flush", service="s") as child:
            assert child.trace_id == ctx.trace_id
            assert tracing.current() is child
        assert tracing.current() is ctx
    assert tracing.current() is None
    spans = tracing.snapshot(trace_id=ctx.trace_id)["traces"][0]["spans"]
    assert [(s["name"], s["parentId"]) for s in spans] == [
        ("flush", ctx.span_id)]


def test_sampling_off_hands_back_the_shared_null_context():
    """`waterfall.activate` with no record is the shared null context;
    with records it installs them for the block (stages record into
    each, the histogram gets the first's id) and restores after."""
    from predictionio_tpu.common import waterfall

    assert waterfall.activate([]) is waterfall._PASS_THROUGH
    assert waterfall.activate([None, None]) is waterfall._PASS_THROUGH
    a, b = (waterfall.RequestRecord("batched", f"{k:016x}") for k in (1, 2))
    with waterfall.activate([a, None, b]):
        assert waterfall.current() is a
        with waterfall.stage("merge"):
            time.sleep(0.002)
    assert waterfall.current() is None
    assert a.stages["merge"] >= 0.002 and b.stages["merge"] >= 0.002
