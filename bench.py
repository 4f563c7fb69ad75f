"""Headline benchmark: the full `pio train` + `pio deploy` user experience
at MovieLens-20M scale, through the framework's front door.

The reference's north-star workload (BASELINE.json): `pio train` on the
Recommendation template — MLlib ALS, rank=10, 10 iterations, lambda=0.01
(tests/pio_tests/engines/recommendation-engine/engine.json:14-17). The
reference publishes no numbers (SURVEY.md §6), so `vs_baseline` is reported
against a published figure only when BASELINE.json carries one; otherwise
null.

Methodology (the round-3 verdict's failing test case was a 20% r02->r03
swing with zero train-path code change; this design removes each cause):

- FRESH DATA SEED per invocation (os.urandom unless BENCH_DATA_SEED set):
  no cross-run caching of identical inputs can fake a win.
- STEADY STATE BY SLOPE: the headline number is 10x the per-iteration
  slope (t(I2) - t(I1)) / (I2 - I1) between two full front-door `pio
  train` runs that differ only in numIterations (the iteration count is a
  traced scalar, so both share one compiled program). The slope is taken
  over the TRAIN PHASE alone (minus the nested device-layout phase):
  measured in the early rounds, the iteration-independent ETL baseline (event
  read + in-HBM sort) varies by +-4 s run to run, and a whole-wall-clock
  slope would launder that variance into the per-iteration number.
- CONSUMED CHECKSUMS: every timed region ends by summing the persisted
  factor matrices on host. On the early rounds' remote backend
  jax.block_until_ready returned before results landed (measured; the
  r02/r03 phase tables were distorted by exactly this), so nothing short
  of a host transfer is trusted as a barrier.
- REPRODUCIBILITY IS PART OF THE OUTPUT: the slope is measured twice with
  different factor seeds; `steady_rel_spread` reports their relative gap.

What runs (nothing is short-circuited):
1. 20M synthetic ratings are written to the COLUMNAR EVENT LOG backend
   (data/storage/eventlog.py) — the framework's own scalable event store —
   and a 20k-event sample is pushed through the real HTTP
   `POST /batch/events.json` route (batch cap 50, EventServer.scala:70
   parity) to measure front-door ingestion.
2. `run_train` executes the real Recommendation engine: DataSource →
   find_columnar (store→host) → Preparator → ALSAlgorithm (device layout +
   csrb ALS in HBM) → model persist (pickle forces host materialization).
3. The trained instance is deployed behind QueryAPI + the stdlib HTTP
   server; p50/p99 of `POST /queries.json` round-trips are measured.

Data is synthetic at ML-20M scale (138k users x 27k items x 20M ratings;
zero-egress environment) with a power-law profile. Prints ONE JSON line.

Correctness is gated, not just printed (round-4 postmortem): non-finite
model checksums, an at-scale hybrid-vs-csrb RMSE parity gap > 1%, or an
inverted eval-grid ordering exit nonzero so the driver records a FAILED
bench instead of a garbage headline.

Env knobs: BENCH_NNZ / BENCH_USERS / BENCH_ITEMS / BENCH_ITERS /
BENCH_DATA_SEED override the workload (smoke-testing on CPU);
BENCH_SKIP_HTTP=1 skips the ingestion sample; BENCH_SKIP_PARITY=1 skips
the dual-kernel parity leg; BENCH_SKIP_THROUGHPUT=1 skips the
concurrent-client QPS leg (micro-batcher off vs on);
BENCH_STRICT_EXTRAS=1 turns a crashed eval-grid leg (eval_error) into a
hard failure instead of a recorded skip; BENCH_SHARD_BUDGET_MB (64)
sizes the sharded-serving leg's HBM-ceiling demonstration budget.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def synth_codes(n_users: int, n_items: int, nnz: int, seed: int):
    """Zipf-ish popularity for items, log-normal activity for users.
    Inverse-CDF sampling (searchsorted) instead of rng.choice(p=...):
    ~40x faster at 20M draws, same distribution family."""
    rng = np.random.default_rng(seed)
    user_w = rng.lognormal(0.0, 1.2, n_users)
    item_w = 1.0 / np.arange(1, n_items + 1) ** 0.8
    u_cdf = np.cumsum(user_w / user_w.sum())
    i_cdf = np.cumsum(item_w / item_w.sum())
    u = np.searchsorted(u_cdf, rng.random(nnz)).astype(np.int32)
    i = np.searchsorted(i_cdf, rng.random(nnz)).astype(np.int32)
    np.clip(u, 0, n_users - 1, out=u)
    np.clip(i, 0, n_items - 1, out=i)
    r = np.clip(np.round(rng.normal(3.5, 1.1, nnz) * 2) / 2, 0.5, 5.0
                ).astype(np.float32)
    return u, i, r


def seed_event_store(storage, app_id, u, i, r, n_users):
    """Write the ratings as real `rate` events into the columnar event log
    (bulk import path, reference PEvents.write)."""
    nnz = len(u)
    pool = (["rate", "user", "item"]
            + [f"u{x}" for x in range(n_users)]
            + [f"i{x}" for x in range(np.max(i) + 1 if nnz else 1)])
    ev = storage.get_events()
    ev.init(app_id)
    t0 = time.perf_counter()
    base_ms = 1_600_000_000_000
    step = 4_000_000
    for lo in range(0, nnz, step):
        hi = min(nnz, lo + step)
        n = hi - lo
        ev.append_encoded(
            app_id, None, pool,
            event=np.zeros(n, np.int32),
            entity_type=np.full(n, 1, np.int32),
            entity_id=u[lo:hi] + 3,
            time_ms=np.arange(lo, hi, dtype=np.int64) + base_ms,
            target_type=np.full(n, 2, np.int32),
            target_id=i[lo:hi] + 3 + n_users,
            numeric={"rating": r[lo:hi]},
        )
    return time.perf_counter() - t0


def measure_read_modes(storage, app_id):
    """Serial-vs-parallel bulk read leg: the SAME read_columns scan with 1
    decode worker vs the default pool, checksummed. Records the speedup in
    the JSON so the parallel path's win (ISSUE 2: 6.46 s of chunk I/O on
    one thread) is attributable from the artifact alone; a checksum
    disagreement between the legs is a correctness bug and hard-fails
    under BENCH_STRICT_EXTRAS=1."""
    import hashlib

    from predictionio_tpu.data.storage.eventlog import _read_thread_count

    ev = storage.get_events()
    kw = dict(event_names=["rate"], entity_type="user",
              target_entity_type="item")

    def leg(threads):
        t0 = time.perf_counter()
        cols = ev.read_columns(app_id, read_threads=threads, **kw)
        dt = time.perf_counter() - t0
        h = hashlib.blake2b(digest_size=16)
        for k in ("entity_code", "target_code", "event_code", "rating",
                  "time_ms"):
            h.update(np.ascontiguousarray(cols[k]).view(np.uint8))
        return dt, h.hexdigest()

    serial_s, serial_ck = leg(1)
    n_threads = _read_thread_count(None)
    parallel_s, parallel_ck = leg(n_threads)
    return {
        "phase_read_serial_s": round(serial_s, 3),
        "phase_read_parallel_s": round(parallel_s, 3),
        "read_threads": n_threads,
        "read_parallel_speedup": round(serial_s / max(parallel_s, 1e-9), 2),
        "read_checksums_match": serial_ck == parallel_ck,
    }


def measure_robustness(workdir, n_calls: int = 300,
                       fault_rate: float = 0.01):
    """Serving-under-faults leg: p50/p99 and error rate of storage RPCs
    with 1% injected storage faults (synthetic 503s at the client
    transport boundary), circuit breaker OFF vs ON, retries configured
    in both legs (3 attempts, 2 ms full-jitter backoff).

    The signal: bounded retries absorb a 1% fault rate completely
    (surfaced error rate 0) while the breaker — correctly — stays closed
    and adds no fast-fail noise at this rate. Under BENCH_STRICT_EXTRAS=1
    a surfaced error or a spuriously-opened breaker hard-fails the run."""
    from predictionio_tpu.common import resilience
    from predictionio_tpu.common.resilience import CircuitBreaker
    from predictionio_tpu.data.storage import App, Storage
    from predictionio_tpu.data.storage.remote import serve_storage

    backing = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": os.path.join(workdir, "robust_el"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    app_id = backing.get_meta_data_apps().insert(App(0, "RobustApp"))
    ev_b = backing.get_events()
    ev_b.init(app_id)
    import datetime as dt

    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.event import Event
    t0 = dt.datetime(2021, 1, 1, tzinfo=dt.timezone.utc)
    ids = ev_b.insert_batch(
        [Event(event="rate", entity_type="user", entity_id=f"u{k % 97}",
               target_entity_type="item", target_entity_id=f"i{k % 53}",
               properties=DataMap({"rating": float(k % 5) + 1.0}),
               event_time=t0 + dt.timedelta(seconds=k))
         for k in range(2000)], app_id)
    server = serve_storage(backing, host="127.0.0.1", port=0)
    port = server.server_address[1]

    def leg(breaker_on: bool):
        prior = os.environ.get("PIO_BREAKER_ENABLED")
        os.environ["PIO_BREAKER_ENABLED"] = "1" if breaker_on else "0"
        CircuitBreaker.reset_registry()
        try:
            remote = Storage(env={
                "PIO_STORAGE_SOURCES_R_TYPE": "remote",
                "PIO_STORAGE_SOURCES_R_URL": f"http://127.0.0.1:{port}",
                "PIO_STORAGE_SOURCES_R_RETRIES": "3",
                "PIO_STORAGE_SOURCES_R_BACKOFF_MS": "2",
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "R",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "R",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "R",
            })
            ev = remote.get_events()
            inj = resilience.install(
                f"error:{fault_rate}:503@client", seed=1234)
            lat, errors = [], 0
            for k in range(n_calls):
                t = time.perf_counter()
                try:
                    got = ev.get(ids[k % len(ids)], app_id)
                    assert got is not None
                except Exception:
                    errors += 1
                lat.append((time.perf_counter() - t) * 1e3)
            resilience.clear()
            opened = 0
            if breaker_on:
                br = CircuitBreaker.for_endpoint(f"127.0.0.1:{port}")
                opened = br.stats()["opened"] if br else 0
            return {
                "p50_ms": round(float(np.percentile(lat, 50)), 3),
                "p99_ms": round(float(np.percentile(lat, 99)), 3),
                "err": errors,
                "err_rate": round(errors / n_calls, 4),
                "faults_injected": inj.fired.get("error", 0),
                "breaker_opened": opened,
            }
        finally:
            resilience.clear()
            CircuitBreaker.reset_registry()
            if prior is None:
                os.environ.pop("PIO_BREAKER_ENABLED", None)
            else:
                os.environ["PIO_BREAKER_ENABLED"] = prior

    try:
        off = leg(False)
        on = leg(True)
    finally:
        server.shutdown()
        server.server_close()
        try:
            ev_b.close()   # flush before the workdir vanishes
        except Exception:
            pass
    return {
        "robust_fault_rate": fault_rate,
        "robust_calls_per_leg": n_calls,
        "robust_breaker_off": off,
        "robust_breaker_on": on,
    }


def _pipelined_ingest_pump(port, path_qs, my_batches, depth,
                           latencies, errors):
    """One ingest client connection: HTTP/1.1 keep-alive with up to
    ``depth`` pipelined requests in flight (depth=1 = plain
    request/response — the admission-latency probe). Responses are
    parsed by Content-Length; per-request round-trip times land in
    ``latencies``. No blind resend anywhere: a failed connection fails
    the leg rather than double-ingesting events the throughput figure
    doesn't count."""
    import socket as _socket
    try:
        # request bytes prebuilt outside the pump loop: the client and
        # server share the host, so client-side string work would tax
        # the measured server throughput (most visibly on small hosts)
        requests = [
            (f"POST {path_qs} HTTP/1.1\r\nHost: bench\r\n"
             "Content-Type: application/json\r\n"
             f"Content-Length: {len(body)}\r\n\r\n").encode() + body
            for body in my_batches]
        sock = _socket.create_connection(("127.0.0.1", port))
        sock.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
        rfile = sock.makefile("rb")
        n = len(requests)
        t_sent = [0.0] * n
        sent = recvd = 0
        while recvd < n:
            while sent < n and sent - recvd < depth:
                sock.sendall(requests[sent])
                t_sent[sent] = time.perf_counter()
                sent += 1
            status_line = rfile.readline()
            if not status_line:
                raise ConnectionError("server closed mid-pipeline")
            clen = 0
            while True:
                h = rfile.readline()
                if h in (b"\r\n", b"\n", b""):
                    break
                if h.lower().startswith(b"content-length:"):
                    clen = int(h.split(b":", 1)[1])
            payload = rfile.read(clen) if clen else b""
            latencies.append(time.perf_counter() - t_sent[recvd])
            recvd += 1
            code = int(status_line.split()[1])
            if code != 200:
                raise RuntimeError(f"ingest got {code}: {payload[:200]!r}")
        rfile.close()
        sock.close()
    except Exception as e:   # surfaced after join
        errors.append(e)


def _ingest_sweep(port, key, batches, n_events, conn_counts, depth):
    """{n_conns: (events_per_s, p99_round_trip_ms)} for one server."""
    import threading
    out = {}
    path_qs = f"/batch/events.json?accessKey={key}"
    for n_conns in conn_counts:
        errors: list = []
        latencies: list = []
        slices = [batches[k::n_conns] for k in range(n_conns)]
        threads = [threading.Thread(
            target=_pipelined_ingest_pump,
            args=(port, path_qs, s, depth, latencies, errors))
            for s in slices if s]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        if errors:
            raise errors[0]
        p99 = float(np.percentile(np.asarray(latencies), 99) * 1e3)
        out[n_conns] = (n_events / dt, p99)
    return out


def measure_http_ingest(storage, n_users, n_items,
                        n_events: int = 20_000,
                        conn_counts=(1, 8, 32, 128)):
    """Front-door ingestion in BOTH transport modes: POST
    /batch/events.json in cap-50 batches against throwaway apps
    (EventServer.scala:70 parity), pumped by a pipelined keep-alive
    client over a {1, 8, 32, 128} connection sweep.

    The two legs are the two production configurations, A/B'd on the
    same host and data:

    - **threaded**: the BENCH_r05 stack — `PIO_TRANSPORT=threaded` with
      per-append WAL writes (`PIO_WAL_GROUP_MS=0`, no fsync), so the
      `http_ingest_events_per_s` figure stays comparable with the
      recorded history;
    - **async**: `PIO_TRANSPORT=async` + group-commit WAL at its
      defaults (2 ms window, fsync-per-group) — stronger durability AND
      the throughput headline; `wal_group_commit_{size,flush_ms}`
      record what the coalescing actually did.

    Admission latency is probed separately at pipeline depth 1 (a
    depth-N client measures queueing, not admission): async at 32
    connections vs threaded at 8 — the acceptance pair.
    """
    from predictionio_tpu.data.api.http import make_server
    from predictionio_tpu.data.api.service import EventAPI
    from predictionio_tpu.data.storage import AccessKey, App
    from predictionio_tpu.data.storage import eventlog

    apps = storage.get_meta_data_apps()
    keys = storage.get_meta_data_access_keys()
    depth = int(os.environ.get("BENCH_INGEST_DEPTH", "8"))
    rng = np.random.default_rng(0)
    uu = rng.integers(0, n_users, n_events)
    ii = rng.integers(0, n_items, n_events)
    rr = rng.integers(1, 11, n_events) / 2.0
    batches = []
    for lo in range(0, n_events, 50):
        hi = min(n_events, lo + 50)
        batches.append(json.dumps([
            {"event": "rate", "entityType": "user", "entityId": f"u{uu[k]}",
             "targetEntityType": "item", "targetEntityId": f"i{ii[k]}",
             "properties": {"rating": float(rr[k])}}
            for k in range(lo, hi)]).encode())
    lat_events = min(n_events, 8_000)
    lat_batches = batches[: (lat_events + 49) // 50]

    modes = {
        # the r05 production stack, exactly: thread-per-connection
        # transport, per-item inserts, per-append WAL, no fsync — keeps
        # the http_ingest_events_per_s trend key apples-to-apples
        "threaded": {"PIO_TRANSPORT": "threaded",
                     "PIO_BATCH_BULK_INSERT": "0",
                     "PIO_WAL_GROUP_MS": "0", "PIO_WAL_FSYNC": "off"},
        # today's default stack: event loop, bulk batch insert,
        # group-commit WAL with fsync-per-group
        "async": {"PIO_TRANSPORT": "async",
                  "PIO_BATCH_BULK_INSERT": None,
                  "PIO_WAL_GROUP_MS": None, "PIO_WAL_FSYNC": None},
    }
    eps: dict = {}
    adm: dict = {}
    wal_before = dict(eventlog.WAL_GROUP_STATS)
    for mode, overrides in modes.items():
        ing_app = apps.insert(App(0, f"BenchIngest_{mode}"))
        key = f"benchingestkey{mode}"
        keys.insert(AccessKey(key=key, appid=ing_app, events=[]))
        storage.get_events().init(ing_app)
        saved = {k: os.environ.get(k) for k in overrides}
        for k, v in overrides.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        api = EventAPI(storage=storage)
        server = make_server(api, "127.0.0.1", 0)
        port = server.server_address[1]
        import threading
        threading.Thread(target=server.serve_forever, daemon=True).start()
        try:
            eps[mode] = _ingest_sweep(port, key, batches, n_events,
                                      conn_counts, depth)
            # depth-1 admission-latency probe at the acceptance pair's
            # connection count for this mode
            probe_conns = 32 if mode == "async" else 8
            adm[mode] = _ingest_sweep(port, key, lat_batches, lat_events,
                                      (probe_conns,), 1)[probe_conns][1]
        finally:
            server.shutdown()
            server.server_close()
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
    wal_after = dict(eventlog.WAL_GROUP_STATS)
    commits = wal_after["commits"] - wal_before["commits"]
    group_events = wal_after["events"] - wal_before["events"]
    flush_s = wal_after["flush_s"] - wal_before["flush_s"]

    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:   # pragma: no cover - non-linux
        cores = os.cpu_count() or 1
    out = {
        # legacy-shaped record (threaded mode) so the BENCH_r* trend on
        # this key stays apples-to-apples with r05's threaded figures
        "http_ingest_events_per_s": {
            str(c): round(v[0]) for c, v in eps["threaded"].items()},
        "ingest_pipeline_depth": depth,
        # the >= 3x strict gate needs the client off the server's core:
        # on a 1-2 core host the pump threads, the event loop and the
        # handler executor all share one GIL core, which deflates the
        # async figure (measured ~2.2x there vs the same code's >= 3x
        # shape on unshared hosts) — mirror the HBM-ceiling demo's
        # "skip honestly" pattern and record capability with the data
        "ingest_gate_capable": cores >= 4,
        "ingest_host_cores": cores,
        "ingest_admission_p99_ms": round(adm["async"], 3),
        "ingest_threaded_admission_p99_ms_8": round(adm["threaded"], 3),
        "wal_group_commit_size": (round(group_events / commits, 1)
                                  if commits else None),
        "wal_group_commit_flush_ms": (round(flush_s / commits * 1e3, 3)
                                      if commits else None),
    }
    for mode in modes:
        for c, (v, _p99) in eps[mode].items():
            out[f"ingest_{mode}_eps_{c}"] = round(v)
    if 32 in eps["threaded"] and eps["threaded"][32][0] > 0:
        out["ingest_async_speedup_32"] = round(
            eps["async"][32][0] / eps["threaded"][32][0], 2)
    return out


def measure_kernel_parity(u, i, r, n_users, n_items, iters: int = 10):
    """Hybrid-vs-csrb numerical parity AT SCALE on the attached device
    (round-4 postmortem: the 296-test CPU suite never trains >500k nnz, so
    a kernel that diverged only at 20M shipped a NaN headline). Trains
    both kernels on the bench data, same seed, in BOTH feedback modes
    (the similarproduct/ecommerce families ride the implicit path), and
    compares training RMSE. Returns a dict of per-mode numbers + rel
    diffs; non-finite results or a rel diff above 1% must fail the run.
    BENCH_PARITY_IMPLICIT=0 skips the implicit legs."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import als

    data = als.prepare_ratings(u, i, r, n_users, n_items, device=True)
    bu = data.by_user
    mask = (bu.self_idx < n_users).astype(jnp.float32)
    out = {}
    modes = [("explicit", als.train_explicit, {})]
    if os.environ.get("BENCH_PARITY_IMPLICIT", "1") != "0":
        modes.append(("implicit", als.train_implicit, {"alpha": 1.0}))
    for mode, train, kw in modes:
        for kern in ("hybrid", "csrb"):
            U, V = train(data, rank=10, iterations=iters, lambda_=0.01,
                         seed=11, kernel=kern, **kw)
            out[f"{mode}_{kern}"] = float(als.rmse(
                U, V, bu.self_idx, bu.other_idx, bu.rating, mask))
        ref = out[f"{mode}_csrb"]
        out[f"{mode}_rel"] = abs(out[f"{mode}_hybrid"] - ref) \
            / max(abs(ref), 1e-9)
    out["ok"] = all(
        np.isfinite(v) for v in out.values()) and all(
        out[k] < 0.01 for k in out if k.endswith("_rel"))
    return out


def measure_eval_grid(storage, n_events: int = 100_000, n_users: int = 943,
                      n_items: int = 1_682):
    """The reference's default eval workload (Evaluation.scala:90-106 +
    BASELINE.md): rank {5,10,20} x iterations {1,5,10}, 5-fold CV,
    Precision@10, at MovieLens-100K scale, through run_evaluation with
    FastEval memoization. Returns (wall_s, best_score, n_variants,
    ordering_ok, layout_reuse_hits) — the hits count how many variant
    trains served their device layout from the shared fold layout the
    grid hoists out of the per-variant loop (fast_eval.py)."""
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.models.recommendation import als_algorithm
    from predictionio_tpu.models.recommendation.evaluation import (
        RecommendationEvaluation, engine_params_list,
    )
    from predictionio_tpu.workflow import run_evaluation
    from predictionio_tpu.workflow.context import WorkflowContext

    app_id = storage.get_meta_data_apps().insert(App(0, "BenchEval"))
    # latent low-rank structure (not iid noise) so Precision@10 measures
    # something: a learnable signal exists and the grid's better variants
    # visibly beat the random baseline
    rng = np.random.default_rng(100)
    Ut = rng.normal(0, 1, (n_users, 6))
    Vt = rng.normal(0, 1, (n_items, 6))
    u, i, _ = synth_codes(n_users, n_items, n_events, seed=100)
    scores = np.einsum("ij,ij->i", Ut[u], Vt[i]) / np.sqrt(6)
    scores += rng.normal(0, 0.5, n_events)
    r = np.clip(np.round((3.0 + 1.2 * scores) * 2) / 2, 0.5, 5.0
                ).astype(np.float32)
    seed_event_store(storage, app_id, u, i, r, n_users)

    params = engine_params_list("BenchEval", k_fold=5, query_num=10)
    ctx = WorkflowContext(storage=storage)
    hits0 = als_algorithm.LAYOUT_STATS["hits"]
    t0 = time.perf_counter()
    result = run_evaluation(
        ctx, RecommendationEvaluation(), params,
        evaluation_class="RecommendationEvaluation")
    wall = time.perf_counter() - t0
    reuse_hits = als_algorithm.LAYOUT_STATS["hits"] - hits0
    # ordering assert (round-4 Weak #6): with a PLANTED low-rank signal,
    # a correct trainer must order the grid sensibly — 2.4x random for the
    # best variant alone proves wiring, not training. Converged variants
    # (max iters in the grid) must beat the 1-iteration ones on average,
    # and the weakest variant (min rank, min iters) must not win. Variant
    # params are read from each score's own engine_params so grid edits
    # cannot silently misalign the gate.
    def variant(s):
        ap = dict(s.engine_params.algorithm_params_list)["als"]
        return ap.rank, ap.numIterations, float(s.score)

    rows = [variant(s) for s in result.engine_params_scores]
    max_iters = max(it for _r, it, _s in rows)
    min_iters = min(it for _r, it, _s in rows)
    mean_hi = np.mean([s for _r, it, s in rows if it == max_iters])
    mean_lo = np.mean([s for _r, it, s in rows if it == min_iters])
    weakest = min(rows, key=lambda t: (t[0], t[1]))[2]
    ordering_ok = (mean_hi > mean_lo
                   and float(result.best_score.score) > weakest)
    return (wall, float(result.best_score.score), len(params), ordering_ok,
            reuse_hits)


def measure_ecom_serving(storage, big_app_users: int, n_queries: int = 200):
    """E-commerce serving with unseenOnly=true against the 20M-event log:
    every query does LIVE seen-events + similar-events lookups
    (ecommerce/als_algorithm.py _seen_items / predict) through the event
    store's postings index + chunk cache. Returns (p50_ms, p99_ms)."""
    import http.client
    import socket
    import threading

    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.data.api.http import make_server
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.models.ecommerce import (
        DataSourceParams, ECommAlgorithmParams, ECommerceEngine,
    )
    from predictionio_tpu.workflow import run_train
    from predictionio_tpu.workflow.create_server import QueryAPI
    from predictionio_tpu.workflow.context import WorkflowContext

    # small TRAINING app sharing the big log's user/item id space; the
    # algorithm's appName points at the 20M log so serve-time lookups pay
    # the real cost
    app_id = storage.get_meta_data_apps().insert(App(0, "BenchEcom"))
    ev = storage.get_events()
    ev.init(app_id)
    rng = np.random.default_rng(7)
    n_tu, n_ti = 1_000, 400
    evs = [Event(event="$set", entity_type="user", entity_id=f"u{k}",
                 properties=DataMap({})) for k in range(n_tu)]
    evs += [Event(event="$set", entity_type="item", entity_id=f"i{k}",
                  properties=DataMap({"categories": ["c"]}))
            for k in range(n_ti)]
    ev.insert_batch(evs, app_id)
    uu = rng.integers(0, n_tu, 30_000)
    ii = rng.integers(0, n_ti, 30_000)
    rr = rng.integers(1, 11, 30_000) / 2.0
    evs = [Event(event="rate", entity_type="user", entity_id=f"u{a}",
                 target_entity_type="item", target_entity_id=f"i{b}",
                 properties=DataMap({"rating": float(c)}))
           for a, b, c in zip(uu, ii, rr)]
    for lo in range(0, len(evs), 10_000):
        ev.insert_batch(evs[lo:lo + 10_000], app_id)

    engine = ECommerceEngine()
    algo_params = ECommAlgorithmParams(
        appName="BenchApp", unseenOnly=True, seenEvents=("rate",),
        similarEvents=("rate",), rank=8, numIterations=3, lambda_=0.05,
        seed=3)
    ep = EngineParams(
        data_source_params=DataSourceParams(appName="BenchEcom"),
        algorithm_params_list=(("ecomm", algo_params),))
    run_train(WorkflowContext(storage=storage), engine, ep,
              engine_factory="bench-ecom",
              params_json={
                  "datasource": {"params": {"appName": "BenchEcom"}},
                  "algorithms": [{"name": "ecomm", "params": {
                      "appName": "BenchApp", "unseenOnly": True,
                      "seenEvents": ["rate"], "similarEvents": ["rate"],
                      "rank": 8, "numIterations": 3, "lambda": 0.05,
                      "seed": 3}}]})

    api = QueryAPI(storage=storage, engine=engine)
    server = make_server(api, "127.0.0.1", 0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port)
        conn.connect()
        conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        lat = []
        for q in range(n_queries):
            # users drawn from the BIG log's id space: live lookups hit it
            body = json.dumps(
                {"user": f"u{q * 131 % min(big_app_users, n_tu)}",
                 "num": 5})
            t0 = time.perf_counter()
            conn.request("POST", "/queries.json", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = resp.read()
            lat.append(time.perf_counter() - t0)
            assert resp.status == 200, payload[:200]
        lat_ms = np.asarray(lat) * 1e3
        return (float(np.percentile(lat_ms, 50)),
                float(np.percentile(lat_ms, 99)))
    finally:
        server.shutdown()


def measure_concurrent_qps(storage, engine, batching: str,
                           conc_levels=(1, 4, 16, 64),
                           queries_per_client: int = 100):
    """Throughput leg: C concurrent keep-alive clients hammering
    `POST /queries.json`, with the micro-batcher on or off (serving/
    batcher.py — concurrent queries coalesce into one batched device
    dispatch per flush). Returns {C: {"qps", "p50_ms", "p99_ms"}} plus
    the server's final batch-size histogram so the recorded QPS is
    attributable to actual coalescing, not luck. Latency percentiles are
    honest per workaround #3 (KNOWN_ISSUES.md): the batched predict path
    ends in a jax.device_get, a REAL host transfer, so response times
    cannot under-report by racing an early block_until_ready."""
    import http.client
    import socket
    import threading

    from predictionio_tpu.data.api.http import make_server
    from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig

    api = QueryAPI(storage=storage, engine=engine,
                   config=ServerConfig(batching=batching))
    server = make_server(api, "127.0.0.1", 0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    out = {}
    try:
        for n_conns in conc_levels:
            lat_lock = threading.Lock()
            lat: list = []
            errors: list = []
            barrier = threading.Barrier(n_conns + 1)

            def client(cx):
                try:
                    conn = http.client.HTTPConnection("127.0.0.1", port)
                    conn.connect()
                    conn.sock.setsockopt(
                        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    my = []
                    barrier.wait()
                    for q in range(queries_per_client):
                        body = json.dumps(
                            {"user": f"u{(cx * 997 + q * 37) % 1000}",
                             "num": 10})
                        t0 = time.perf_counter()
                        conn.request(
                            "POST", "/queries.json", body=body,
                            headers={"Content-Type": "application/json"})
                        resp = conn.getresponse()
                        payload = resp.read()
                        my.append(time.perf_counter() - t0)
                        assert resp.status == 200, payload[:200]
                    conn.close()
                    with lat_lock:
                        lat.extend(my)
                except Exception as e:
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(cx,))
                       for cx in range(n_conns)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if errors:
                raise errors[0]
            lat_ms = np.asarray(lat) * 1e3
            out[n_conns] = {
                "qps": round(n_conns * queries_per_client / wall, 1),
                "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
            }
        status = api.handle("GET", "/")[1]
        out["batch_size_hist"] = status["batching"].get("batchSizeHist") \
            if status["batching"]["enabled"] else None
    finally:
        server.shutdown()
        api.close()
    return out


def measure_telemetry(storage, engine, n_conns: int = 8,
                      queries_per_client: int = 100):
    """Telemetry leg (run after the concurrent-QPS leg): the same batched
    serving path with PIO_TELEMETRY off vs on, then a real HTTP
    `GET /metrics` scrape whose parsed counters land in the JSON detail
    (padding-waste ratio, flush-size histogram, retry counts).

    The off leg is the overhead baseline; under BENCH_STRICT_EXTRAS=1 a
    failed/unparseable scrape, or a metrics-on p99 more than 5% AND
    0.2 ms above metrics-off (the absolute floor keeps sub-noise deltas
    from tripping the ratio on a fast CPU path), hard-fails the run."""
    import http.client
    import re
    import socket
    import threading

    from predictionio_tpu.data.api.http import make_server
    from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig

    def leg(telemetry_on: bool):
        prior = os.environ.get("PIO_TELEMETRY")
        os.environ["PIO_TELEMETRY"] = "1" if telemetry_on else "0"
        try:
            api = QueryAPI(storage=storage, engine=engine,
                           config=ServerConfig(batching="on"))
            server = make_server(api, "127.0.0.1", 0)
            port = server.server_address[1]
            threading.Thread(target=server.serve_forever,
                             daemon=True).start()
            lat_lock = threading.Lock()
            lat: list = []
            errors: list = []
            barrier = threading.Barrier(n_conns + 1)

            def client(cx):
                try:
                    conn = http.client.HTTPConnection("127.0.0.1", port)
                    conn.connect()
                    conn.sock.setsockopt(
                        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    my = []
                    barrier.wait()
                    for q in range(queries_per_client):
                        body = json.dumps(
                            {"user": f"u{(cx * 131 + q * 17) % 1000}",
                             "num": 10})
                        t0 = time.perf_counter()
                        conn.request(
                            "POST", "/queries.json", body=body,
                            headers={"Content-Type": "application/json"})
                        resp = conn.getresponse()
                        payload = resp.read()
                        my.append(time.perf_counter() - t0)
                        assert resp.status == 200, payload[:200]
                    conn.close()
                    with lat_lock:
                        lat.extend(my)
                except Exception as e:
                    errors.append(e)

            scrape = None
            try:
                threads = [threading.Thread(target=client, args=(cx,))
                           for cx in range(n_conns)]
                for t in threads:
                    t.start()
                barrier.wait()
                for t in threads:
                    t.join()
                if errors:
                    raise errors[0]
                if telemetry_on:
                    conn = http.client.HTTPConnection("127.0.0.1", port)
                    conn.request("GET", "/metrics")
                    resp = conn.getresponse()
                    text = resp.read().decode("utf-8")
                    assert resp.status == 200, "scrape failed"
                    assert resp.getheader("Content-Type", "").startswith(
                        "text/plain"), "scrape content type"
                    conn.close()
                    inst = api._batcher._inst["batcher"]
                    scrape = (text, inst)
            finally:
                server.shutdown()
                api.close()
            lat_ms = np.asarray(lat) * 1e3
            return {"p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                    "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                    }, scrape
        finally:
            if prior is None:
                os.environ.pop("PIO_TELEMETRY", None)
            else:
                os.environ["PIO_TELEMETRY"] = prior

    off, _ = leg(False)
    on, scrape = leg(True)
    text, inst = scrape

    def samples(family):
        out = {}
        for m in re.finditer(
                rf'^{family}\{{([^}}]*)\}}\s(\S+)$', text, re.M):
            labels, value = m.groups()
            if f'batcher="{inst}"' in labels or "batcher" not in labels:
                out[labels] = float(value)
        return out

    def label(labels, key):
        m = re.search(rf'{key}="([^"]+)"', labels)
        return m.group(1) if m else None

    queries = sum(samples("pio_batcher_queries_total").values())
    flush_hist = {label(k, "size"): int(v)
                  for k, v in samples("pio_batcher_batch_size").items()}
    padded = sum(int(label(k, "bucket")) * v
                 for k, v in samples("pio_batcher_bucket").items())
    if queries <= 0 or padded <= 0 or not flush_hist:
        raise RuntimeError("metrics scrape parsed but the telemetry leg's "
                           "batcher series are missing")
    retries = {label(k, "kind"): int(v)
               for k, v in samples("pio_rpc_retries_total").items()}
    # overhead gate: relative AND absolute (p99 noise floor)
    overhead_ok = (on["p99_ms"] <= off["p99_ms"] * 1.05
                   or on["p99_ms"] - off["p99_ms"] <= 0.2)
    return {
        "telemetry_off": off,
        "telemetry_on": on,
        "telemetry_overhead_p99_pct": round(
            (on["p99_ms"] / max(off["p99_ms"], 1e-9) - 1.0) * 100, 2),
        "telemetry_overhead_ok": bool(overhead_ok),
        "telemetry_scrape_ok": True,
        "telemetry_flush_size_hist": dict(sorted(flush_hist.items(),
                                                 key=lambda kv: int(kv[0]))),
        "telemetry_padding_waste_ratio": round(1.0 - queries / padded, 4),
        "telemetry_rpc_retries": retries,
    }


def measure_waterfall(storage, engine, n_conns: int = 8,
                      queries_per_client: int = 100):
    """Waterfall leg (common/waterfall.py): the same batched serving
    path with PIO_WATERFALL off vs on (telemetry ON in both legs — the
    realistic production baseline), then a /debug/slow.json read whose
    stage breakdown lands in the JSON detail.

    The acceptance gate: stage sampling must cost <= 5% p99 versus
    sampling off (absolute floor 0.2 ms, like the telemetry leg — CPU
    sub-noise deltas must not trip the ratio). Hard-fails under
    BENCH_STRICT_EXTRAS=1."""
    import http.client
    import socket
    import threading

    from predictionio_tpu.common import telemetry as _telemetry
    from predictionio_tpu.common import waterfall
    from predictionio_tpu.data.api.http import make_server
    from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig

    def leg(waterfall_on: bool):
        _telemetry.set_enabled(True)
        waterfall.set_enabled(waterfall_on)
        waterfall.clear()
        try:
            api = QueryAPI(storage=storage, engine=engine,
                           config=ServerConfig(batching="on"))
            server = make_server(api, "127.0.0.1", 0)
            port = server.server_address[1]
            threading.Thread(target=server.serve_forever,
                             daemon=True).start()
            lat_lock = threading.Lock()
            lat: list = []
            errors: list = []
            barrier = threading.Barrier(n_conns + 1)

            def client(cx):
                try:
                    conn = http.client.HTTPConnection("127.0.0.1", port)
                    conn.connect()
                    conn.sock.setsockopt(
                        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    my = []
                    barrier.wait()
                    for q in range(queries_per_client):
                        body = json.dumps(
                            {"user": f"u{(cx * 131 + q * 17) % 1000}",
                             "num": 10})
                        t0 = time.perf_counter()
                        conn.request(
                            "POST", "/queries.json", body=body,
                            headers={"Content-Type": "application/json"})
                        resp = conn.getresponse()
                        payload = resp.read()
                        my.append(time.perf_counter() - t0)
                        assert resp.status == 200, payload[:200]
                    conn.close()
                    with lat_lock:
                        lat.extend(my)
                except Exception as e:
                    errors.append(e)

            slow = None
            try:
                threads = [threading.Thread(target=client, args=(cx,))
                           for cx in range(n_conns)]
                for t in threads:
                    t.start()
                barrier.wait()
                for t in threads:
                    t.join()
                if errors:
                    raise errors[0]
                if waterfall_on:
                    conn = http.client.HTTPConnection("127.0.0.1", port)
                    conn.request("GET", "/debug/slow.json?limit=8")
                    resp = conn.getresponse()
                    assert resp.status == 200, "slow.json read failed"
                    slow = json.loads(resp.read().decode("utf-8"))
                    conn.close()
            finally:
                server.shutdown()
                api.close()
            lat_ms = np.asarray(lat) * 1e3
            return {"p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                    "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                    }, slow
        finally:
            _telemetry.set_enabled(None)
            waterfall.set_enabled(None)

    off, _ = leg(False)
    on, slow = leg(True)
    reqs = (slow or {}).get("requests") or []
    if not reqs:
        raise RuntimeError("waterfall leg served traffic but "
                           "/debug/slow.json recorded no requests")
    slowest = reqs[0]
    stages = slowest.get("stages") or {}
    expected = {"admission", "supplement", "dispatch", "merge",
                "serialize"}
    if not expected <= set(stages):
        raise RuntimeError(
            f"slow.json stage breakdown incomplete: {sorted(stages)}")
    overhead_ok = (on["p99_ms"] <= off["p99_ms"] * 1.05
                   or on["p99_ms"] - off["p99_ms"] <= 0.2)
    return {
        "waterfall_off": off,
        "waterfall_on": on,
        "waterfall_on_p99_ms": on["p99_ms"],
        "waterfall_overhead_p99_pct": round(
            (on["p99_ms"] / max(off["p99_ms"], 1e-9) - 1.0) * 100, 2),
        "waterfall_overhead_ok": bool(overhead_ok),
        "waterfall_slow_ring": len(reqs),
        "waterfall_slowest": {
            "total_ms": slowest.get("totalMs"),
            "trace_id": slowest.get("traceId"),
            "stages_ms": stages,
            "details": slowest.get("details"),
        },
    }


def measure_journal(storage, engine, n_conns: int = 8,
                    queries_per_client: int = 100):
    """Flight-recorder leg (common/journal.py): the same batched serving
    path with PIO_JOURNAL off vs on (telemetry ON in both legs), then a
    /debug/events.json read whose event counts land in the JSON detail.

    The journal's cost model is "operational events are rare, requests
    never emit" — so journal-on p99 must sit within 5% of journal-off
    (absolute floor 0.2 ms, like the telemetry/waterfall legs). The on
    leg must also actually RECORD something: the deploy's lifecycle
    event (model generation live) proves the emitters are wired.
    Hard-fails under BENCH_STRICT_EXTRAS=1."""
    import http.client
    import socket
    import threading

    from predictionio_tpu.common import journal
    from predictionio_tpu.common import telemetry as _telemetry
    from predictionio_tpu.common import tracing
    from predictionio_tpu.data.api.http import make_server
    from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig

    def leg(journal_on: bool):
        _telemetry.set_enabled(True)
        journal.set_enabled(journal_on)
        try:
            api = QueryAPI(storage=storage, engine=engine,
                           config=ServerConfig(batching="on"))
            server = make_server(api, "127.0.0.1", 0)
            port = server.server_address[1]
            threading.Thread(target=server.serve_forever,
                             daemon=True).start()
            lat_lock = threading.Lock()
            lat: list = []
            errors: list = []
            barrier = threading.Barrier(n_conns + 1)

            def client(cx):
                try:
                    conn = http.client.HTTPConnection("127.0.0.1", port)
                    conn.connect()
                    conn.sock.setsockopt(
                        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    my = []
                    barrier.wait()
                    for q in range(queries_per_client):
                        body = json.dumps(
                            {"user": f"u{(cx * 131 + q * 17) % 1000}",
                             "num": 10})
                        t0 = time.perf_counter()
                        conn.request(
                            "POST", "/queries.json", body=body,
                            headers={"Content-Type": "application/json"})
                        resp = conn.getresponse()
                        payload = resp.read()
                        my.append(time.perf_counter() - t0)
                        assert resp.status == 200, payload[:200]
                    conn.close()
                    with lat_lock:
                        lat.extend(my)
                except Exception as e:
                    errors.append(e)

            events = None
            try:
                threads = [threading.Thread(target=client, args=(cx,))
                           for cx in range(n_conns)]
                for t in threads:
                    t.start()
                barrier.wait()
                for t in threads:
                    t.join()
                if errors:
                    raise errors[0]
                conn = http.client.HTTPConnection("127.0.0.1", port)
                conn.request("GET", "/debug/events.json?limit=16")
                resp = conn.getresponse()
                assert resp.status == 200, "events.json read failed"
                events = json.loads(resp.read().decode("utf-8"))
                conn.close()
            finally:
                server.shutdown()
                api.close()
            lat_ms = np.asarray(lat) * 1e3
            return {"p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                    "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                    }, events
        finally:
            _telemetry.set_enabled(None)
            journal.set_enabled(None)

    off, off_events = leg(False)
    on, on_events = leg(True)
    if off_events is None or off_events.get("enabled") is not False:
        raise RuntimeError("journal-off leg still reports an enabled "
                           f"journal: {off_events}")
    recorded = (on_events or {}).get("events") or []
    if not any(e.get("category") == "lifecycle" for e in recorded):
        raise RuntimeError(
            "journal-on leg recorded no lifecycle deploy event — the "
            f"emitters are not wired ({recorded})")
    overhead_ok = (on["p99_ms"] <= off["p99_ms"] * 1.05
                   or on["p99_ms"] - off["p99_ms"] <= 0.2)
    return {
        "journal_off": off,
        "journal_on": on,
        "journal_on_p99_ms": on["p99_ms"],
        "journal_overhead_p99_pct": round(
            (on["p99_ms"] / max(off["p99_ms"], 1e-9) - 1.0) * 100, 2),
        "journal_overhead_ok": bool(overhead_ok),
        "journal_events_total": int(journal.events_total()),
        "journal_events_buffered": len(recorded),
        "trace_tail_retained": int(tracing.tail_retained()),
    }


def measure_history(storage, engine, n_conns: int = 8,
                    queries_per_client: int = 100):
    """Metrics-flight-recorder leg (common/history.py): the same
    batched serving path with PIO_HISTORY off vs on (telemetry ON in
    both legs, sampler ticking at a bench-fast cadence in the on leg),
    plus a /debug/history.json read taken WHILE the burst is running.

    The recorder's cost model is "the hot path pays nothing" — sampling
    runs on its own thread at scrape cadence — so history-on p99 must
    sit within 5% of history-off (absolute floor 0.2 ms, like the
    telemetry/journal legs). The on leg must also actually RECORD: the
    mid-burst read must answer 200 with >= 1 sample carrying
    pio_serve_seconds bucket deltas, and the ring must stay bounded
    (seriesTotal <= the PIO_HISTORY_MAX_SERIES cap). Hard-fails under
    BENCH_STRICT_EXTRAS=1."""
    import http.client
    import socket
    import threading

    from predictionio_tpu.common import history
    from predictionio_tpu.common import telemetry as _telemetry
    from predictionio_tpu.data.api.http import make_server
    from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig

    def leg(history_on: bool):
        _telemetry.set_enabled(True)
        history.set_enabled(history_on)
        history.reset()
        # bench-fast sampler cadence so a sub-minute burst still lands
        # several ring entries (production default is 5 s)
        history.install(history.HistoryConfig(tick_s=0.1))
        try:
            api = QueryAPI(storage=storage, engine=engine,
                           config=ServerConfig(batching="on"))
            server = make_server(api, "127.0.0.1", 0)
            port = server.server_address[1]
            threading.Thread(target=server.serve_forever,
                             daemon=True).start()
            lat_lock = threading.Lock()
            lat: list = []
            errors: list = []
            barrier = threading.Barrier(n_conns + 1)

            def client(cx):
                try:
                    conn = http.client.HTTPConnection("127.0.0.1", port)
                    conn.connect()
                    conn.sock.setsockopt(
                        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    my = []
                    barrier.wait()
                    for q in range(queries_per_client):
                        body = json.dumps(
                            {"user": f"u{(cx * 131 + q * 17) % 1000}",
                             "num": 10})
                        t0 = time.perf_counter()
                        conn.request(
                            "POST", "/queries.json", body=body,
                            headers={"Content-Type": "application/json"})
                        resp = conn.getresponse()
                        payload = resp.read()
                        my.append(time.perf_counter() - t0)
                        assert resp.status == 200, payload[:200]
                    conn.close()
                    with lat_lock:
                        lat.extend(my)
                except Exception as e:
                    errors.append(e)

            hist_body = None
            try:
                threads = [threading.Thread(target=client, args=(cx,))
                           for cx in range(n_conns)]
                for t in threads:
                    t.start()
                barrier.wait()
                # the mid-burst read: the endpoint must answer while
                # the serving path is under load and the sampler ticks
                time.sleep(0.3)
                conn = http.client.HTTPConnection("127.0.0.1", port)
                conn.request("GET", "/debug/history.json?limit=64")
                resp = conn.getresponse()
                assert resp.status == 200, "history.json read failed"
                hist_body = json.loads(resp.read().decode("utf-8"))
                conn.close()
                for t in threads:
                    t.join()
                if errors:
                    raise errors[0]
            finally:
                server.shutdown()
                api.close()
            lat_ms = np.asarray(lat) * 1e3
            return {"p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                    "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                    }, hist_body
        finally:
            _telemetry.set_enabled(None)
            history.set_enabled(None)
            history.reset()

    off, off_hist = leg(False)
    on, on_hist = leg(True)
    if off_hist is None or off_hist.get("enabled") is not False:
        raise RuntimeError("history-off leg still reports an enabled "
                           f"recorder: {off_hist}")
    samples = (on_hist or {}).get("samples") or []
    served = [
        e for e in samples
        if any(history.series_family(k) == "pio_serve_seconds"
               and isinstance(v, dict) and v.get("count", 0) > 0
               for k, v in (e.get("series") or {}).items())]
    if not served:
        raise RuntimeError(
            "history-on leg's mid-burst /debug/history.json carried no "
            f"pio_serve_seconds deltas ({len(samples)} sample(s))")
    series_total = int(on_hist.get("seriesTotal") or 0)
    max_series = history.HistoryConfig.from_env().max_series
    if series_total > max_series:
        raise RuntimeError(
            f"recorder tracks {series_total} series, over the "
            f"PIO_HISTORY_MAX_SERIES cap {max_series} — unbounded")
    overhead_ok = (on["p99_ms"] <= off["p99_ms"] * 1.05
                   or on["p99_ms"] - off["p99_ms"] <= 0.2)
    return {
        "history_off": off,
        "history_on": on,
        "history_on_p99_ms": on["p99_ms"],
        "history_overhead_p99_pct": round(
            (on["p99_ms"] / max(off["p99_ms"], 1e-9) - 1.0) * 100, 2),
        "history_overhead_ok": bool(overhead_ok),
        "history_series_total": series_total,
        "history_midburst_samples": len(samples),
        "history_dropped_series": int(on_hist.get("droppedSeries") or 0),
    }


def measure_foldin(storage, engine, n_conns: int = 8,
                   queries_per_client: int = 60, n_fresh_users: int = 12):
    """Realtime fold-in leg (realtime/foldin.py): the same batched
    serving path under the same live event stream, with the fold-in
    worker off vs on (25 ms tick — the on leg's p99 includes live
    solve + publication), plus the wire-level freshness measurement:
    brand-new users (unseen at train time) post events and the leg
    polls /queries.json until each answers personalized top-k. Under
    BENCH_STRICT_EXTRAS=1: freshness p99 <= 2 s always (the e-commerce
    "signed up 10 seconds ago" contract, with margin); worker-on p99
    within 5% of off (floor 0.2 ms) only on >= 4-core hosts — on a
    shared-core container the solver and the serving threads fight for
    one GIL core and the ratio measures the host, not the subsystem
    (`foldin_gate_capable` in the artifact says which case this round
    was)."""
    import http.client
    import socket
    import tempfile
    import threading

    from predictionio_tpu.data.api.http import make_server
    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.event import Event, utcnow
    from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig

    app = storage.get_meta_data_apps().get_by_name("BenchApp")
    cursor_dir = tempfile.mkdtemp(prefix="pio_foldin_cursor_")
    prev_env = {k: os.environ.get(k) for k in
                ("PIO_FOLDIN", "PIO_FOLDIN_CURSOR_DIR")}
    os.environ["PIO_FOLDIN_CURSOR_DIR"] = cursor_dir
    os.environ.pop("PIO_FOLDIN", None)

    def rate_events(uid, n=6, base=0):
        now = utcnow()
        return [Event(
            event="rate", entity_type="user", entity_id=uid,
            target_entity_type="item", target_entity_id=f"i{base + j}",
            properties=DataMap({"rating": 5.0 - 0.4 * j}),
            event_time=now) for j in range(n)]

    def leg(foldin_on: bool):
        api = QueryAPI(storage=storage, engine=engine,
                       config=ServerConfig(
                           batching="on",
                           foldin="on" if foldin_on else "off",
                           foldin_tick_ms=25.0))
        server = make_server(api, "127.0.0.1", 0)
        port = server.server_address[1]
        threading.Thread(target=server.serve_forever, daemon=True).start()
        lat_lock = threading.Lock()
        lat: list = []
        errors: list = []
        stop_posting = threading.Event()
        barrier = threading.Barrier(n_conns + 1)

        def poster():
            # a live event stream for the worker to chew on during the
            # latency burst (existing users: pure re-folds)
            j = 0
            while not stop_posting.is_set():
                uid = f"u{j % 50}"
                storage.get_events().insert_batch(
                    rate_events(uid, n=2, base=j % 40), app.id)
                j += 1
                time.sleep(0.005)

        def client(cx):
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port)
                conn.connect()
                conn.sock.setsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                my = []
                barrier.wait()
                for q in range(queries_per_client):
                    body = json.dumps(
                        {"user": f"u{(cx * 131 + q * 17) % 1000}",
                         "num": 10})
                    t0 = time.perf_counter()
                    conn.request(
                        "POST", "/queries.json", body=body,
                        headers={"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    payload = resp.read()
                    my.append(time.perf_counter() - t0)
                    assert resp.status == 200, payload[:200]
                conn.close()
                with lat_lock:
                    lat.extend(my)
            except Exception as e:
                errors.append(e)

        fresh_s: list = []
        state = None
        post_thread = None
        try:
            post_thread = threading.Thread(target=poster, daemon=True)
            post_thread.start()
            threads = [threading.Thread(target=client, args=(cx,))
                       for cx in range(n_conns)]
            for t in threads:
                t.start()
            barrier.wait()
            for t in threads:
                t.join()
            stop_posting.set()
            if post_thread is not None:
                post_thread.join(timeout=5)
            if errors:
                raise errors[0]
            if foldin_on:
                # wire-level freshness: unseen user -> events -> first
                # personalized (non-empty) answer
                conn = http.client.HTTPConnection("127.0.0.1", port)
                for j in range(n_fresh_users):
                    uid = f"bench_fresh_{j}"
                    t0 = time.perf_counter()
                    storage.get_events().insert_batch(
                        rate_events(uid), app.id)
                    deadline = t0 + 10.0
                    served = False
                    while time.perf_counter() < deadline:
                        conn.request(
                            "POST", "/queries.json",
                            body=json.dumps({"user": uid, "num": 5}),
                            headers={"Content-Type": "application/json"})
                        resp = conn.getresponse()
                        body = json.loads(resp.read())
                        if resp.status == 200 and body.get("itemScores"):
                            served = True
                            break
                        time.sleep(0.01)
                    if not served:
                        raise RuntimeError(
                            f"fold-in freshness probe timed out for {uid}")
                    fresh_s.append(time.perf_counter() - t0)
                conn.close()
                state = api.handle("GET", "/")[1].get("foldin")
        finally:
            server.shutdown()
            api.close()
        lat_ms = np.asarray(lat) * 1e3
        return {"p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                }, fresh_s, state

    try:
        off, _f, _s = leg(False)
        on, fresh_s, state = leg(True)
    finally:
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    fresh = np.asarray(fresh_s)
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover - non-linux
        cores = os.cpu_count() or 1
    overhead_ok = (on["p99_ms"] <= off["p99_ms"] * 1.05
                   or on["p99_ms"] - off["p99_ms"] <= 0.2)
    p99_fresh = float(np.percentile(fresh, 99))
    return {
        "foldin_gate_capable": cores >= 4,
        "foldin_off": off,
        "foldin_on": on,
        "foldin_on_p99_ms": on["p99_ms"],
        "foldin_overhead_p99_pct": round(
            (on["p99_ms"] / max(off["p99_ms"], 1e-9) - 1.0) * 100, 2),
        "foldin_overhead_ok": bool(overhead_ok),
        "foldin_freshness_p50_s": round(float(np.percentile(fresh, 50)), 4),
        "foldin_freshness_p99_s": round(p99_fresh, 4),
        "foldin_freshness_ok": bool(p99_fresh <= 2.0),
        "foldin_fresh_users": int(fresh.size),
        "foldin_cursor_lag_events": int((state or {}).get("cursorLag") or 0),
        "foldin_drift": (state or {}).get("drift"),
        "foldin_state": state,
    }


def measure_serve_sharded(storage, engine, n_conns: int = 8,
                          queries_per_client: int = 100):
    """Sharded-serving leg (parallel/serve_dist.py): the same batched
    HTTP path with shard-serving off (replicated) vs forced on, plus a
    sequential probe set whose response BYTES must match between the
    two servers (the bit-parity contract, verified at the wire).

    Gates under BENCH_STRICT_EXTRAS=1: sharded-on p99 within 10% of
    replicated (absolute floor 0.2 ms like the telemetry/waterfall
    legs), and probe parity. Also records the HBM-ceiling demonstration
    (a synthetic factor matrix sized past one device's demonstration
    budget that only the sharded layout can host)."""
    import http.client
    import socket
    import threading

    from predictionio_tpu.data.api.http import make_server
    from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig

    probes = [json.dumps({"user": f"u{(7 * i) % 1000}", "num": 10})
              for i in range(16)]

    def leg(shard_mode: str):
        api = QueryAPI(storage=storage, engine=engine,
                       config=ServerConfig(batching="on",
                                           shard_serving=shard_mode))
        server = make_server(api, "127.0.0.1", 0)
        port = server.server_address[1]
        threading.Thread(target=server.serve_forever, daemon=True).start()
        lat_lock = threading.Lock()
        lat: list = []
        errors: list = []
        barrier = threading.Barrier(n_conns + 1)

        def client(cx):
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port)
                conn.connect()
                conn.sock.setsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                my = []
                barrier.wait()
                for q in range(queries_per_client):
                    body = json.dumps(
                        {"user": f"u{(cx * 131 + q * 17) % 1000}",
                         "num": 10})
                    t0 = time.perf_counter()
                    conn.request(
                        "POST", "/queries.json", body=body,
                        headers={"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    payload = resp.read()
                    my.append(time.perf_counter() - t0)
                    assert resp.status == 200, payload[:200]
                conn.close()
                with lat_lock:
                    lat.extend(my)
            except Exception as e:
                errors.append(e)

        try:
            # sequential probe set first: the parity evidence
            conn = http.client.HTTPConnection("127.0.0.1", port)
            conn.connect()
            bodies = []
            for p in probes:
                conn.request("POST", "/queries.json", body=p,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                payload = resp.read()
                assert resp.status == 200, payload[:200]
                bodies.append(payload)
            conn.close()
            threads = [threading.Thread(target=client, args=(cx,))
                       for cx in range(n_conns)]
            for t in threads:
                t.start()
            barrier.wait()
            for t in threads:
                t.join()
            if errors:
                raise errors[0]
            status = api.handle("GET", "/")[1]
            shards = (status.get("sharding") or {}).get("shards", 0)
        finally:
            server.shutdown()
            api.close()
        lat_ms = np.asarray(lat) * 1e3
        return {"p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                }, bodies, shards

    # pin BOTH legs to device-resident serving: the parity contract is
    # sharded-vs-replicated DEVICE kernels (host BLAS legitimately
    # differs in float accumulation order), and the overhead gate must
    # compare like with like — on the CPU backend the deploy probe
    # would otherwise flip the replicated leg onto the host path
    prior_probe = os.environ.get("PIO_SERVE_DEVICE_MS")
    os.environ["PIO_SERVE_DEVICE_MS"] = "1e9"
    try:
        off, bodies_off, _ = leg("off")
        on, bodies_on, shards = leg("on")
    finally:
        if prior_probe is None:
            os.environ.pop("PIO_SERVE_DEVICE_MS", None)
        else:
            os.environ["PIO_SERVE_DEVICE_MS"] = prior_probe
    parity_ok = bodies_off == bodies_on
    overhead_ok = (on["p99_ms"] <= off["p99_ms"] * 1.10
                   or on["p99_ms"] - off["p99_ms"] <= 0.2)
    return {
        "serve_sharded_off": off,
        "serve_sharded_on": on,
        "serve_sharded_p99_ms": on["p99_ms"],
        "serve_sharded_overhead_pct": round(
            (on["p99_ms"] / max(off["p99_ms"], 1e-9) - 1.0) * 100, 2),
        "serve_sharded_overhead_ok": bool(overhead_ok),
        "serve_sharded_shards": int(shards),
        "serve_sharded_parity_ok": bool(parity_ok),
        "serve_sharded_hbm_ceiling": _shard_hbm_ceiling_demo(),
    }


def _shard_hbm_ceiling_demo():
    """The leg that makes the sharding story literal: a synthetic factor
    matrix sized past ONE device's budget that only the sharded layout
    can host (replicated placement needs total bytes on every chip;
    sharded needs total/n_dev). The budget is the demonstration budget
    (``BENCH_SHARD_BUDGET_MB``, default 64 MiB) — actually exceeding the
    real HBM limit would OOM the bench process itself; the real
    per-device limit is recorded alongside when the platform reports
    one (KNOWN_ISSUES #8: CPU reports none)."""
    import jax

    from predictionio_tpu.parallel import serve_dist

    devs = jax.devices()
    n_dev = len(devs)
    budget = int(float(os.environ.get("BENCH_SHARD_BUDGET_MB", "64"))
                 * 2**20)
    real_limit = None
    try:
        ms = devs[0].memory_stats()
        if ms:
            real_limit = int(ms.get("bytes_limit", 0)) or None
    except Exception:
        pass
    out = {"budget_bytes": budget, "device_bytes_limit": real_limit,
           "n_devices": n_dev}
    if n_dev < 2:
        # one device cannot split anything: record the honest skip (the
        # multi-chip round demonstrates it; tier-1's 8 virtual devices
        # exercise it in every CPU smoke run)
        out["skipped"] = "single-device mesh - nothing to split"
        return out
    rank = 64
    # item matrix alone ~1.2x the budget; user matrix small
    n_items = int(budget * 1.2) // (rank * 4)
    n_users = 1024
    rng = np.random.default_rng(0)
    U = rng.standard_normal((n_users, rank), dtype=np.float32)
    V = rng.standard_normal((n_items, rank), dtype=np.float32)
    factor_bytes = (n_users + n_items) * rank * 4
    t0 = time.perf_counter()
    sharded = serve_dist.shard_factors(U, V)
    per_shard = sharded.per_shard_bytes()
    vals, idx = jax.device_get(
        sharded.topk(np.arange(8, dtype=np.int32), 10))
    served_ok = (bool(np.isfinite(vals).all())
                 and bool((idx >= 0).all())
                 and bool((idx < n_items).all()))
    out.update({
        "rank": rank, "n_items": n_items, "n_users": n_users,
        "factor_bytes": factor_bytes,
        "per_shard_bytes": per_shard,
        "replicated_fits_budget": bool(factor_bytes <= budget),
        "sharded_fits_budget": bool(per_shard <= budget),
        "sharded_served_ok": served_ok,
        "shard_and_serve_s": round(time.perf_counter() - t0, 3),
    })
    return out


def measure_serve_quant(storage, engine, n_conns: int = 8,
                        queries_per_client: int = 100):
    """Quantized-serving leg (ops/quant.py): the same batched HTTP path
    with serve-quant off (fp32) vs forced on (int8 per-row-scale
    factors + the fused kernel wherever PIO_SERVE_FUSED resolves it),
    plus a sequential probe set whose RANKINGS are compared between the
    two servers — bit-parity is off the table for int8, so the wire
    evidence is recall@k and exact-match@1 (the KNOWN_ISSUES #12
    ranking-parity contract).

    Gates under BENCH_STRICT_EXTRAS=1: quantized p99 <= the fp32 p99
    (absolute floor 0.2 ms like the telemetry/waterfall legs — int8
    halves the bandwidth bill, it must never cost latency),
    factor-matrix HBM ratio <= 0.30 (the int8 matrices vs fp32; the
    fp32 per-row scale vectors are reported next to it as
    `with_scales_ratio` — at rank 64 they are ~2% noise, at the bench's
    rank 10 they are visible, which is why the gate names the
    matrices), and recall@k >= 0.99. Also records the quantized
    HBM-ceiling demonstration (~4x the fp32 sharded catalog)."""
    import http.client
    import socket
    import threading

    from predictionio_tpu.data.api.http import make_server
    from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig

    k_probe = 10
    probes = [json.dumps({"user": f"u{(7 * i) % 1000}", "num": k_probe})
              for i in range(32)]

    def leg(quant_mode: str):
        api = QueryAPI(storage=storage, engine=engine,
                       config=ServerConfig(batching="on",
                                           serve_quant=quant_mode))
        server = make_server(api, "127.0.0.1", 0)
        port = server.server_address[1]
        threading.Thread(target=server.serve_forever, daemon=True).start()
        lat_lock = threading.Lock()
        lat: list = []
        errors: list = []
        barrier = threading.Barrier(n_conns + 1)

        def client(cx):
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port)
                conn.connect()
                conn.sock.setsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                my = []
                barrier.wait()
                for q in range(queries_per_client):
                    body = json.dumps(
                        {"user": f"u{(cx * 131 + q * 17) % 1000}",
                         "num": 10})
                    t0 = time.perf_counter()
                    conn.request(
                        "POST", "/queries.json", body=body,
                        headers={"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    payload = resp.read()
                    my.append(time.perf_counter() - t0)
                    assert resp.status == 200, payload[:200]
                conn.close()
                with lat_lock:
                    lat.extend(my)
            except Exception as e:
                errors.append(e)

        try:
            # sequential probe set first: the ranking-parity evidence
            conn = http.client.HTTPConnection("127.0.0.1", port)
            conn.connect()
            rankings = []
            for p in probes:
                conn.request("POST", "/queries.json", body=p,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                payload = resp.read()
                assert resp.status == 200, payload[:200]
                scores = json.loads(payload).get("itemScores") or []
                rankings.append([s["item"] for s in scores])
            conn.close()
            threads = [threading.Thread(target=client, args=(cx,))
                       for cx in range(n_conns)]
            for t in threads:
                t.start()
            barrier.wait()
            for t in threads:
                t.join()
            if errors:
                raise errors[0]
            status = api.handle("GET", "/")[1]
            quant_info = status.get("quant") or {}
            model = api.models[0]
        finally:
            server.shutdown()
            api.close()
        lat_ms = np.asarray(lat) * 1e3
        return {"p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                }, rankings, quant_info, model

    # pin BOTH legs to device-resident serving like the sharded leg:
    # the overhead gate must compare like with like
    prior_probe = os.environ.get("PIO_SERVE_DEVICE_MS")
    os.environ["PIO_SERVE_DEVICE_MS"] = "1e9"
    try:
        off, rank_off, _info_off, model_off = leg("off")
        on, rank_on, quant_info, _model_on = leg("on")
    finally:
        if prior_probe is None:
            os.environ.pop("PIO_SERVE_DEVICE_MS", None)
        else:
            os.environ["PIO_SERVE_DEVICE_MS"] = prior_probe

    # ranking parity AT THE WIRE: recall@k + exact-match@1 over the
    # probe set (empty answers — unknown users — agree trivially and
    # are excluded from the mean so they can't inflate recall)
    recalls, exact1 = [], []
    for a, b in zip(rank_off, rank_on):
        if not a and not b:
            continue
        k = max(len(a), 1)
        recalls.append(len(set(a) & set(b)) / k)
        exact1.append(1.0 if (a and b and a[0] == b[0]) else 0.0)
    recall = float(np.mean(recalls)) if recalls else None
    em1 = float(np.mean(exact1)) if exact1 else None

    # factor-matrix HBM bytes: the int8 matrices vs their fp32
    # equivalents, scales reported alongside (model_io accounting)
    n_u, rank = (int(d) for d in np.shape(model_off.user_factors))
    n_i = int(np.shape(model_off.item_factors)[0])
    fp32_bytes = (n_u + n_i) * rank * 4
    int8_matrix_bytes = (n_u + n_i) * rank
    scale_bytes = (n_u + n_i) * 4
    hbm_ratio = int8_matrix_bytes / fp32_bytes
    with_scales_ratio = (int8_matrix_bytes + scale_bytes) / fp32_bytes

    quant_active = bool(quant_info.get("enabled"))
    p99_ok = (on["p99_ms"] <= off["p99_ms"]
              or on["p99_ms"] - off["p99_ms"] <= 0.2)
    recall_ok = recall is not None and recall >= 0.99
    return {
        "serve_quant_off": off,
        "serve_quant_on": on,
        "serve_quant_p99_ms": on["p99_ms"],
        "serve_quant_p99_ok": bool(p99_ok),
        "serve_quant_active": quant_active,
        "serve_quant_info": quant_info,
        "serve_quant_hbm_ratio": round(hbm_ratio, 4),
        "serve_quant_hbm_ratio_with_scales": round(with_scales_ratio, 4),
        "serve_quant_hbm_ok": bool(hbm_ratio <= 0.30),
        "serve_quant_fp32_bytes": fp32_bytes,
        "serve_quant_int8_bytes": int8_matrix_bytes + scale_bytes,
        "serve_quant_recall": (round(recall, 4)
                               if recall is not None else None),
        "serve_quant_exact1": (round(em1, 4) if em1 is not None else None),
        "serve_quant_recall_ok": bool(recall_ok),
        "serve_quant_hbm_ceiling": _quant_hbm_ceiling_demo(),
    }


def _quant_hbm_ceiling_demo():
    """The quantized half of the HBM-ceiling story: a catalog sized so
    even the SHARDED fp32 layout busts the per-device demonstration
    budget (``BENCH_SHARD_BUDGET_MB``, same budget as
    ``_shard_hbm_ceiling_demo``) — roughly 4x the catalog the fp32 mesh
    ceiling allows — while the int8 shards fit with room to spare, and
    the quantized sharded top-k actually answers. Honestly skipped on
    1-device hosts (nothing to shard)."""
    import jax

    from predictionio_tpu.ops import quant as quant_mod
    from predictionio_tpu.parallel import serve_dist

    devs = jax.devices()
    n_dev = len(devs)
    budget = int(float(os.environ.get("BENCH_SHARD_BUDGET_MB", "64"))
                 * 2**20)
    out = {"budget_bytes": budget, "n_devices": n_dev}
    if n_dev < 2:
        out["skipped"] = "single-device mesh - nothing to split"
        return out
    rank = 64
    # catalog at ~3.5x the fp32 sharded ceiling (the ideal int8 gain is
    # 4x; the fp32 per-row scale vectors trim it to (4r)/(r+4) = 3.76x
    # at rank 64): fp32 per-shard lands at ~3.5x the budget — far past
    # the fp32 ceiling — while the int8 shards fit at ~0.93x of it
    n_items = int(budget * 3.5) * n_dev // (rank * 4)
    n_users = 1024
    rng = np.random.default_rng(0)
    U = rng.standard_normal((n_users, rank), dtype=np.float32)
    V = rng.standard_normal((n_items, rank), dtype=np.float32)
    fp32_per_shard = -(-n_items // n_dev) * rank * 4
    t0 = time.perf_counter()
    qf = quant_mod.QuantizedFactors.from_factors(U, V)
    sharded = serve_dist.shard_factors(U, V, quant=qf)
    per_shard = sharded.per_shard_bytes()
    vals, idx = jax.device_get(
        sharded.topk(np.arange(8, dtype=np.int32), 10))
    served_ok = (bool(np.isfinite(vals).all())
                 and bool((idx >= 0).all())
                 and bool((idx < n_items).all()))
    fp32_ceiling_items = budget * n_dev // (rank * 4)
    out.update({
        "rank": rank, "n_items": n_items, "n_users": n_users,
        "fp32_per_shard_bytes": fp32_per_shard,
        "int8_per_shard_bytes": per_shard,
        "fp32_sharded_fits_budget": bool(fp32_per_shard <= budget),
        "int8_sharded_fits_budget": bool(per_shard <= budget),
        "catalog_vs_fp32_ceiling": round(
            n_items / max(fp32_ceiling_items, 1), 2),
        "quant_sharded_served_ok": served_ok,
        "shard_and_serve_s": round(time.perf_counter() - t0, 3),
    })
    return out


_ROUTER_REPLICA_SCRIPT = """\
import sys
port, url = int(sys.argv[1]), sys.argv[2]
partition = sys.argv[3] if len(sys.argv) > 3 else ""
from predictionio_tpu.data.storage import Storage
from predictionio_tpu.workflow.create_server import (
    QueryAPI, ServerConfig, serve,
)
storage = Storage(env={
    "PIO_STORAGE_SOURCES_R_TYPE": "remote",
    "PIO_STORAGE_SOURCES_R_URL": url,
    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "R",
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "R",
    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "R",
})
api = QueryAPI(storage=storage,
               config=ServerConfig(batching="on", aot="off",
                                   partition=partition))
serve(api, host="127.0.0.1", port=port)
"""


def measure_router(n_conns: int = 8, queries_per_client: int = 60):
    """Fleet front-door leg (workflow/router.py): real replica
    PROCESSES (each with its own GIL — in-process "replicas" can't
    scale) deployed from a dedicated small model over a storage server,
    measured three ways with the same keep-alive client pump:

    - ``direct``: the pump against one replica, no router — the
      added-latency baseline;
    - ``router x1``: the same pump through the router over ONE replica —
      ``router_added_p99_ms`` is the p99 delta, gated <= 1 ms;
    - ``router x2`` (and ``x4`` on >= 4-core hosts): the scale-out
      claim — ``router_qps_scaling_2`` gated >= 1.6x on >= 4-core hosts
      (on a shared-core container every process fights for one core and
      the ratio measures the host; ``router_gate_capable`` records the
      skip).

    The leg runs on its OWN storage/instance so the fleet's small
    importable-factory model never becomes the bench storage's latest
    COMPLETED instance (later legs resolve that)."""
    import http.client
    import socket
    import subprocess
    import threading

    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.data.storage import App, Storage
    from predictionio_tpu.data.storage.remote import serve_storage
    from predictionio_tpu.models.recommendation import (
        ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
    )
    from predictionio_tpu.workflow import run_train
    from predictionio_tpu.workflow.context import WorkflowContext
    from predictionio_tpu.workflow.router import RouterAPI, RouterConfig

    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover - non-linux
        cores = os.cpu_count() or 1
    capable = cores >= 4
    replica_counts = [1, 2] + ([4] if capable else [])
    workdir = tempfile.mkdtemp(prefix="pio_router_bench_")
    storage = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": os.path.join(workdir, "el"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    app_id = storage.get_meta_data_apps().insert(App(0, "RouterBench"))
    storage.get_events().init(app_id)
    rng = np.random.default_rng(5)
    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.event import Event
    import datetime as _dt
    events = []
    for u in range(64):
        for i in rng.choice(48, size=12, replace=False).tolist():
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap(
                    {"rating": float(1 + (u * 7 + i) % 5)}),
                event_time=_dt.datetime(
                    2021, 1, 1, tzinfo=_dt.timezone.utc)))
    storage.get_events().insert_batch(events, app_id)
    run_train(
        WorkflowContext(storage=storage), RecommendationEngine(),
        EngineParams(
            data_source_params=DataSourceParams(appName="RouterBench"),
            algorithm_params_list=(("als", ALSAlgorithmParams(
                rank=8, numIterations=3, lambda_=0.05, seed=11)),)),
        engine_factory=(
            "predictionio_tpu.models.recommendation:RecommendationEngine"),
        params_json={
            "datasource": {"params": {"appName": "RouterBench"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 8, "numIterations": 3, "lambda": 0.05,
                "seed": 11}}]})
    rpc_server = serve_storage(storage, host="127.0.0.1", port=0)
    url = f"http://127.0.0.1:{rpc_server.server_address[1]}"

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    script = os.path.join(workdir, "replica.py")
    with open(script, "w") as f:
        f.write(_ROUTER_REPLICA_SCRIPT)
    pythonpath = HERE + os.pathsep + os.environ.get("PYTHONPATH", "")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": pythonpath.rstrip(os.pathsep)}
    n_replicas = max(replica_counts)
    ports = [free_port() for _ in range(n_replicas)]
    procs = [subprocess.Popen(
        [sys.executable, script, str(p), url], env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        for p in ports]

    def wait_ready(port, timeout=240.0):
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=2.0)
                conn.request("GET", "/readyz")
                ok = conn.getresponse().status == 200
                conn.close()
                if ok:
                    return True
            except OSError:
                pass
            time.sleep(0.25)
        return False

    def pump(port):
        """n_conns keep-alive clients x queries_per_client requests
        against one port; returns (qps, p50_ms, p99_ms)."""
        lat_lock = threading.Lock()
        lat: list = []
        errors: list = []
        barrier = threading.Barrier(n_conns + 1)

        def client(cx):
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port)
                conn.connect()
                conn.sock.setsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                my = []
                barrier.wait()
                for q in range(queries_per_client):
                    body = json.dumps(
                        {"user": f"u{(cx * 131 + q * 17) % 64}",
                         "num": 10})
                    t0 = time.perf_counter()
                    conn.request(
                        "POST", "/queries.json", body=body,
                        headers={"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    payload = resp.read()
                    my.append(time.perf_counter() - t0)
                    assert resp.status == 200, payload[:200]
                conn.close()
                with lat_lock:
                    lat.extend(my)
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=client, args=(cx,))
                   for cx in range(n_conns)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors:
            raise errors[0]
        lat_ms = np.asarray(lat) * 1e3
        return (round(n_conns * queries_per_client / wall, 1),
                round(float(np.percentile(lat_ms, 50)), 3),
                round(float(np.percentile(lat_ms, 99)), 3))

    out: dict = {"router_gate_capable": capable,
                 "router_replica_counts": replica_counts}
    routers = []
    try:
        for p in ports:
            if not wait_ready(p):
                raise RuntimeError(f"replica on port {p} never ready")
        pump(ports[0])   # warm every path once (compile, caches)
        qps_d, p50_d, p99_d = pump(ports[0])
        out["router_direct"] = {"qps": qps_d, "p50_ms": p50_d,
                                "p99_ms": p99_d}
        qps_by_n = {}
        for n in replica_counts:
            router = RouterAPI(RouterConfig(
                backends=tuple(f"http://127.0.0.1:{p}"
                               for p in ports[:n]),
                health_ms=100.0))
            routers.append(router)
            from predictionio_tpu.data.api.http import serve_background
            rserver, rport = serve_background(router)
            try:
                pump(rport)   # warm the router's pools
                qps, p50, p99 = pump(rport)
                qps_by_n[n] = qps
                out[f"router_x{n}"] = {"qps": qps, "p50_ms": p50,
                                       "p99_ms": p99}
                if n == 1:
                    out["router_added_p50_ms"] = round(p50 - p50_d, 3)
                    out["router_added_p99_ms"] = round(p99 - p99_d, 3)
                st = router.handle("GET", "/")[1]
                if st["shedCount"] or st["failoverCount"]:
                    # a healthy-fleet pump must not shed or fail over —
                    # either means the leg measured recovery, not routing
                    raise RuntimeError(
                        f"router x{n} shed {st['shedCount']} / failed "
                        f"over {st['failoverCount']} during a healthy "
                        "pump")
            finally:
                rserver.shutdown()
                router.close()
        out["router_qps_scaling_2"] = round(
            qps_by_n[2] / max(qps_by_n[1], 1e-9), 3)
        if 4 in qps_by_n:
            out["router_qps_scaling_4"] = round(
                qps_by_n[4] / max(qps_by_n[1], 1e-9), 3)
        out["router_added_p99_ok"] = bool(
            out["router_added_p99_ms"] <= 1.0)
        out["router_scaling_ok"] = bool(
            out["router_qps_scaling_2"] >= 1.6)
    finally:
        for proc in procs:
            proc.kill()
        rpc_server.shutdown()
        rpc_server.server_close()
        try:
            storage.get_events().close()   # flush before the dir vanishes
        except Exception:
            pass
        shutil.rmtree(workdir, ignore_errors=True)
    return out


class _RouterFleet:
    """Shared fixture for the partition/cache router legs: the small
    importable-factory model trained on its OWN storage (never the bench
    storage's latest COMPLETED instance), served to replica subprocesses
    over the remote-storage RPC server, plus the keep-alive pump."""

    def __init__(self, prefix: str):
        import socket

        from predictionio_tpu.controller.engine import EngineParams
        from predictionio_tpu.data.datamap import DataMap
        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.storage import App, Storage
        from predictionio_tpu.data.storage.remote import serve_storage
        from predictionio_tpu.models.recommendation import (
            ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
        )
        from predictionio_tpu.workflow import run_train
        from predictionio_tpu.workflow.context import WorkflowContext
        import datetime as _dt

        self._socket = socket
        self.workdir = tempfile.mkdtemp(prefix=prefix)
        self.storage = Storage(env={
            "PIO_STORAGE_SOURCES_M_TYPE": "memory",
            "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
            "PIO_STORAGE_SOURCES_EL_PATH": os.path.join(self.workdir, "el"),
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
        })
        app_id = self.storage.get_meta_data_apps().insert(
            App(0, "RouterBench"))
        self.storage.get_events().init(app_id)
        rng = np.random.default_rng(5)
        events = []
        for u in range(64):
            for i in rng.choice(48, size=12, replace=False).tolist():
                events.append(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap(
                        {"rating": float(1 + (u * 7 + i) % 5)}),
                    event_time=_dt.datetime(
                        2021, 1, 1, tzinfo=_dt.timezone.utc)))
        self.storage.get_events().insert_batch(events, app_id)
        run_train(
            WorkflowContext(storage=self.storage), RecommendationEngine(),
            EngineParams(
                data_source_params=DataSourceParams(appName="RouterBench"),
                algorithm_params_list=(("als", ALSAlgorithmParams(
                    rank=8, numIterations=3, lambda_=0.05, seed=11)),)),
            engine_factory=("predictionio_tpu.models.recommendation:"
                            "RecommendationEngine"),
            params_json={
                "datasource": {"params": {"appName": "RouterBench"}},
                "algorithms": [{"name": "als", "params": {
                    "rank": 8, "numIterations": 3, "lambda": 0.05,
                    "seed": 11}}]})
        self.rpc_server = serve_storage(self.storage, host="127.0.0.1",
                                        port=0)
        self.url = f"http://127.0.0.1:{self.rpc_server.server_address[1]}"
        self.script = os.path.join(self.workdir, "replica.py")
        with open(self.script, "w") as f:
            f.write(_ROUTER_REPLICA_SCRIPT)
        pythonpath = HERE + os.pathsep + os.environ.get("PYTHONPATH", "")
        self.env = {**os.environ, "JAX_PLATFORMS": "cpu",
                    "PYTHONPATH": pythonpath.rstrip(os.pathsep)}
        self.procs: list = []

    def free_port(self) -> int:
        s = self._socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def spawn_replica(self, port: int, partition: str = ""):
        import subprocess
        args = [sys.executable, self.script, str(port), self.url]
        if partition:
            args.append(partition)
        proc = subprocess.Popen(args, env=self.env,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        self.procs.append(proc)
        return proc

    def wait_ready(self, port: int, timeout: float = 240.0) -> bool:
        import http.client
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=2.0)
                conn.request("GET", "/readyz")
                ok = conn.getresponse().status == 200
                conn.close()
                if ok:
                    return True
            except OSError:
                pass
            time.sleep(0.25)
        return False

    def readyz(self, port: int) -> dict:
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5.0)
        try:
            conn.request("GET", "/readyz")
            return json.loads(conn.getresponse().read())
        finally:
            conn.close()

    def query_bytes(self, port: int, body: bytes) -> tuple:
        """One POST /queries.json; returns (status, raw payload bytes)."""
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10.0)
        try:
            conn.request("POST", "/queries.json", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def pump(self, port: int, n_conns: int, queries_per_client: int,
             body_fn) -> tuple:
        """n_conns keep-alive clients x queries_per_client requests;
        ``body_fn(cx, q)`` makes each request body. Returns
        (qps, p50_ms, p99_ms)."""
        import http.client
        import threading
        socket = self._socket
        lat_lock = threading.Lock()
        lat: list = []
        errors: list = []
        barrier = threading.Barrier(n_conns + 1)

        def client(cx):
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port)
                conn.connect()
                conn.sock.setsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                my = []
                barrier.wait()
                for q in range(queries_per_client):
                    body = body_fn(cx, q)
                    t0 = time.perf_counter()
                    conn.request(
                        "POST", "/queries.json", body=body,
                        headers={"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    payload = resp.read()
                    my.append(time.perf_counter() - t0)
                    assert resp.status == 200, payload[:200]
                conn.close()
                with lat_lock:
                    lat.extend(my)
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=client, args=(cx,))
                   for cx in range(n_conns)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors:
            raise errors[0]
        lat_ms = np.asarray(lat) * 1e3
        return (round(n_conns * queries_per_client / wall, 1),
                round(float(np.percentile(lat_ms, 50)), 3),
                round(float(np.percentile(lat_ms, 99)), 3))

    def close(self) -> None:
        for proc in self.procs:
            proc.kill()
        self.rpc_server.shutdown()
        self.rpc_server.server_close()
        try:
            self.storage.get_events().close()
        except Exception:
            pass
        shutil.rmtree(self.workdir, ignore_errors=True)


def measure_router_partition(n_conns: int = 6,
                             queries_per_client: int = 40,
                             n_partitions: int = 2):
    """Partition-routed serving leg (workflow/router.py scatter/merge +
    `pio deploy --partition i/N`): one FULL replica is the baseline,
    ``n_partitions`` row-range replicas behind the router are the
    system under test. Reports:

    - bit-parity: every user's wire answer through the partition fleet
      must equal the full replica's raw bytes (deterministic — checked
      on every host);
    - ``router_partition_added_p99_ms``: scatter+merge p99 over the
      direct full-replica p99 (the price of 1/N-catalog replicas);
    - the HBM-budget demo: per-replica item-factor bytes drop to ~1/N,
      so a demo budget sized UNDER the full model but OVER one
      partition serves only via the fleet — the "catalog 10x the mesh"
      story with honest numbers from /readyz metadata."""
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover - non-linux
        cores = os.cpu_count() or 1
    capable = cores >= 4
    fleet = _RouterFleet("pio_router_part_")
    out: dict = {"router_partition_gate_capable": capable,
                 "router_partition_width": n_partitions}
    routers = []
    try:
        from predictionio_tpu.data.api.http import serve_background
        from predictionio_tpu.workflow.router import RouterAPI, RouterConfig
        full_port = fleet.free_port()
        part_ports = [fleet.free_port() for _ in range(n_partitions)]
        fleet.spawn_replica(full_port)
        for idx, p in enumerate(part_ports):
            fleet.spawn_replica(p, partition=f"{idx}/{n_partitions}")
        for p in [full_port] + part_ports:
            if not fleet.wait_ready(p):
                raise RuntimeError(f"replica on port {p} never ready")
        # HBM-budget demo from the advertised ranges: rank-8 fp32 rows
        ready = fleet.readyz(part_ports[0])
        part = ready.get("partition") or {}
        rank = 8
        full_bytes = int(part.get("nItems", 0)) * rank * 4
        part_bytes = int(part.get("rows", 0)) * rank * 4
        budget = int(full_bytes * 0.6)
        out["router_partition_item_bytes_full"] = full_bytes
        out["router_partition_item_bytes_each"] = part_bytes
        out["router_partition_demo_budget_bytes"] = budget
        out["router_partition_full_fits_budget"] = bool(
            full_bytes <= budget)
        out["router_partition_each_fits_budget"] = bool(
            part_bytes <= budget)
        out["router_partition_catalog_multiple"] = n_partitions
        router = RouterAPI(RouterConfig(
            backends=tuple(f"http://127.0.0.1:{p}" for p in part_ports),
            health_ms=100.0))
        routers.append(router)
        rserver, rport = serve_background(router)
        try:
            deadline = time.perf_counter() + 20.0
            while time.perf_counter() < deadline:
                if router.handle("GET", "/readyz")[0] == 200 and \
                        router._pmap is not None:
                    break
                time.sleep(0.1)
            if router._pmap is None:
                raise RuntimeError("partition map never became complete")
            # bit-parity over the wire: every trained user, ties and all
            mismatches = 0
            for u in range(64):
                body = json.dumps({"user": f"u{u}", "num": 10}).encode()
                s_full, b_full = fleet.query_bytes(full_port, body)
                s_part, b_part = fleet.query_bytes(rport, body)
                if not (s_full == s_part == 200 and b_full == b_part):
                    mismatches += 1
            out["router_partition_parity_mismatches"] = mismatches
            out["router_partition_parity_ok"] = mismatches == 0

            def body_fn(cx, q):
                return json.dumps(
                    {"user": f"u{(cx * 131 + q * 17) % 64}",
                     "num": 10}).encode()

            fleet.pump(full_port, n_conns, queries_per_client, body_fn)
            qps_d, p50_d, p99_d = fleet.pump(
                full_port, n_conns, queries_per_client, body_fn)
            out["router_partition_direct"] = {
                "qps": qps_d, "p50_ms": p50_d, "p99_ms": p99_d}
            fleet.pump(rport, n_conns, queries_per_client, body_fn)
            qps_s, p50_s, p99_s = fleet.pump(
                rport, n_conns, queries_per_client, body_fn)
            out["router_partition_scatter"] = {
                "qps": qps_s, "p50_ms": p50_s, "p99_ms": p99_s}
            out["router_partition_added_p50_ms"] = round(p50_s - p50_d, 3)
            out["router_partition_added_p99_ms"] = round(p99_s - p99_d, 3)
        finally:
            rserver.shutdown()
            router.close()
    finally:
        fleet.close()
    return out


def measure_router_cache(n_conns: int = 6, queries_per_client: int = 80,
                         exponent: float = 1.1):
    """Front-door response-cache leg (workflow/router.py
    _ResponseCache): the SAME zipfian key stream (data/synthetic.py
    ``query_keys`` — rank-0-hottest, the workload real front doors see)
    pumped through the router with the cache off, then on. Reports the
    measured hit ratio (> 0 gated everywhere: the stream repeats keys
    by construction) and cached-vs-uncached p99; the p99 gate
    (cached <= uncached) is enforced on >= 4-core hosts under
    BENCH_STRICT_EXTRAS=1 — on a shared core the router, both replicas
    and the clients fight for one CPU and the delta measures the host
    (``router_cache_gate_capable`` records the honest skip)."""
    from predictionio_tpu.data.synthetic import query_keys

    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover - non-linux
        cores = os.cpu_count() or 1
    capable = cores >= 4
    fleet = _RouterFleet("pio_router_cache_")
    out: dict = {"router_cache_gate_capable": capable,
                 "router_cache_zipf_exponent": exponent}
    keys = query_keys(n_conns * queries_per_client, seed=7,
                      exponent=exponent, pool=64)

    def body_fn(cx, q):
        return json.dumps(
            {"user": f"u{int(keys[cx * queries_per_client + q])}",
             "num": 10}).encode()

    try:
        from predictionio_tpu.data.api.http import serve_background
        from predictionio_tpu.workflow.router import RouterAPI, RouterConfig
        ports = [fleet.free_port() for _ in range(2)]
        for p in ports:
            fleet.spawn_replica(p)
        for p in ports:
            if not fleet.wait_ready(p):
                raise RuntimeError(f"replica on port {p} never ready")
        backends = tuple(f"http://127.0.0.1:{p}" for p in ports)
        for cache_on in (False, True):
            router = RouterAPI(RouterConfig(
                backends=backends, health_ms=100.0,
                cache="on" if cache_on else "off",
                cache_mb=16, cache_ttl_ms=60_000.0))
            rserver, rport = serve_background(router)
            try:
                # warm pass: compiles/caches on the replicas, and (on
                # the cached run) fills the LRU with the hot keys
                fleet.pump(rport, n_conns, queries_per_client, body_fn)
                qps, p50, p99 = fleet.pump(
                    rport, n_conns, queries_per_client, body_fn)
                label = "router_cache" if cache_on else "router_uncached"
                out[label] = {"qps": qps, "p50_ms": p50, "p99_ms": p99}
                if cache_on:
                    stats = (router.handle("GET", "/")[1]
                             .get("cache") or {})
                    out["router_cache_hit_ratio"] = round(
                        float(stats.get("hitRatio") or 0.0), 4)
                    out["router_cache_hits"] = stats.get("hits")
                    out["router_cache_misses"] = stats.get("misses")
                    out["router_cache_evictions"] = stats.get("evictions")
                    out["router_cache_p99_ms"] = p99
                else:
                    out["router_uncached_p99_ms"] = p99
            finally:
                rserver.shutdown()
                router.close()
        out["router_cache_hit_ratio_ok"] = bool(
            (out.get("router_cache_hit_ratio") or 0.0) > 0.0)
        out["router_cache_p99_ok"] = bool(
            out["router_cache_p99_ms"] <= out["router_uncached_p99_ms"])
    finally:
        fleet.close()
    return out


def measure_autopilot(n_conns: int = 4, queries_per_client: int = 200,
                      exponent: float = 1.1):
    """Autopilot leg (workflow/autopilot.py): two chapters against a
    real subprocess fleet.

    **Chaos recovery** — a replica process is SIGKILLed mid-way through
    a zipfian client burst (the same ``query_keys`` stream as the cache
    leg) with the autopilot live; the leg measures the seconds until
    the fleet is back at full rotation with the corpse retired and a
    pool-spawned replacement serving, and asserts the burst saw zero
    client failures (the router's failover + the autopilot's refill
    together). The recovery-time gate is enforced on >= 4-core hosts
    under BENCH_STRICT_EXTRAS=1 (``autopilot_gate_capable`` records the
    honest skip — a replica subprocess cold-starts jax on one shared
    core otherwise).

    **Burn ladder** — with shrunk SLO windows, a synthetic error burst
    pushes BOTH burn windows over the 14.4x page threshold through the
    REAL signal path (registry exposition -> gather -> tick): the
    ladder must widen the router's shed thresholds, and after a clean
    stretch restore the EXACT prior values (gated everywhere — it is
    in-process arithmetic, not a timing race)."""
    from predictionio_tpu.common import journal, slo, telemetry
    from predictionio_tpu.data.api.http import serve_background
    from predictionio_tpu.data.synthetic import query_keys
    from predictionio_tpu.workflow.autopilot import (
        Autopilot, AutopilotConfig, LocalRouterControl, ReplicaPool,
    )
    from predictionio_tpu.workflow.router import RouterAPI, RouterConfig

    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover - non-linux
        cores = os.cpu_count() or 1
    capable = cores >= 4
    fleet = _RouterFleet("pio_autopilot_")
    out: dict = {"autopilot_gate_capable": capable}
    keys = query_keys(n_conns * queries_per_client, seed=7,
                      exponent=exponent, pool=64)

    def body_fn(cx, q):
        return json.dumps(
            {"user": f"u{int(keys[cx * queries_per_client + q])}",
             "num": 10}).encode()

    class _FleetPool(ReplicaPool):
        """The ReplicaPool hook backed by the bench fleet's replica
        subprocesses (what `pio autopilot --replica-cmd` does with
        shell commands)."""

        def __init__(self):
            self.procs: dict = {}
            self.spawns = 0

        def spawn(self):
            self.spawns += 1
            port = fleet.free_port()
            proc = fleet.spawn_replica(port)
            if not fleet.wait_ready(port):
                proc.kill()
                return None
            url = f"http://127.0.0.1:{port}"
            self.procs[url] = proc
            return url

        def stop(self, url):
            proc = self.procs.pop(url, None)
            if proc is None:
                return False
            proc.kill()
            return True

        def close(self):
            for proc in self.procs.values():
                proc.kill()

    import threading as _threading
    try:
        ports = [fleet.free_port() for _ in range(2)]
        procs = [fleet.spawn_replica(p) for p in ports]
        for p in ports:
            if not fleet.wait_ready(p):
                raise RuntimeError(f"replica on port {p} never ready")
        router = RouterAPI(RouterConfig(
            backends=tuple(f"http://127.0.0.1:{p}" for p in ports),
            health_ms=100.0))
        rserver, rport = serve_background(router)
        pool = _FleetPool()
        ap = Autopilot(LocalRouterControl(router),
                       config=AutopilotConfig(
                           poll_ms=100.0, cooldown_s=1.0,
                           min_replicas=2, max_replicas=3),
                       pool=pool)
        loop = _threading.Thread(target=ap.run, daemon=True)
        loop.start()
        try:
            # ---- chaos recovery: kill one replica mid-burst ----------
            pump_errors: list = []

            def burst():
                try:
                    fleet.pump(rport, n_conns, queries_per_client,
                               body_fn)
                except Exception as e:
                    pump_errors.append(f"{type(e).__name__}: {e}")

            pump_thread = _threading.Thread(target=burst)
            pump_thread.start()
            time.sleep(0.4)
            procs[0].kill()                      # the chaos event
            t_kill = time.perf_counter()
            dead_url = f"http://127.0.0.1:{ports[0]}"
            recovery_s = None
            deadline = time.perf_counter() + 180.0
            while time.perf_counter() < deadline:
                st = router.handle("GET", "/")[1]
                urls = {b["url"] for b in st["backends"]}
                if (st["inRotation"] >= 2 and dead_url not in urls
                        and all(b["inRotation"]
                                for b in st["backends"])):
                    recovery_s = round(time.perf_counter() - t_kill, 2)
                    break
                time.sleep(0.2)
            pump_thread.join(timeout=120.0)
            out["autopilot_recovery_s"] = recovery_s
            out["autopilot_replicas_spawned"] = pool.spawns
            out["autopilot_zero_failures"] = not pump_errors
            if pump_errors:
                out["autopilot_burst_error"] = pump_errors[0]
            ev = journal.snapshot(category="autopilot")["events"]
            out["autopilot_journaled_events"] = len(ev)
        finally:
            ap.stop()
            loop.join(timeout=10.0)

        # ---- burn ladder: widen on a real page, restore exactly ------
        telemetry.set_enabled(True)
        slo.reset()
        slo.install(slo.SLOConfig(availability=0.999,
                                  fast_window_s=1.0, slow_window_s=2.0))
        try:
            c = telemetry.registry().counter(
                "pio_http_requests_total",
                "HTTP requests by service and status",
                labelnames=("service", "status"))
            base = router.set_shed_thresholds()
            ap2 = Autopilot(LocalRouterControl(router),
                            config=AutopilotConfig(poll_ms=100.0,
                                                   cooldown_s=0.5))
            c.labels(service="AutopilotBench", status="200").inc(1000)
            ap2.gather()                 # baseline scrape + SLO snapshot
            time.sleep(0.2)
            c.labels(service="AutopilotBench", status="500").inc(100)
            c.labels(service="AutopilotBench", status="200").inc(900)
            time.sleep(0.2)
            acted = ap2.tick(ap2.gather())
            widened = any(a["action"] == "shed_widen" for a in acted)
            mid = router.set_shed_thresholds()
            c.labels(service="AutopilotBench", status="200").inc(5000)
            restored = False
            deadline = time.perf_counter() + 20.0
            while time.perf_counter() < deadline:
                time.sleep(0.4)
                ap2.tick(ap2.gather())
                if (router.set_shed_thresholds() == base
                        and ap2.summary()["ladderDepth"] == 0):
                    restored = True
                    break
            out["autopilot_ladder_widened"] = bool(widened
                                                   and mid != base)
            out["autopilot_ladder_restored"] = bool(restored)
            out["autopilot_ladder_ok"] = bool(
                out["autopilot_ladder_widened"] and restored)
            out["autopilot_actions_total"] = (
                ap.summary()["actionsTotal"]
                + ap2.summary()["actionsTotal"])
        finally:
            telemetry.set_enabled(None)
            slo.reset()
        rserver.shutdown()
        router.close()
        pool.close()
    finally:
        fleet.close()
    return out


def measure_autotrain(n_conns: int = 3, volume_events: int = 8):
    """Continuous-training leg (workflow/autotrain.py): two chapters
    against an embedded deploy on the leg's OWN storage (its extra
    COMPLETED instances must never become the bench storage's latest
    and change what the serving legs deploy).

    **Accept cycle** — a live event burst crosses the volume trigger
    while client threads pump /queries.json over the wire and the
    fold-in worker runs; the loop launches a REAL retrain (run_train on
    a thread), validates the candidate against the live generation
    (score tolerance + ranking-parity probe on a deterministic probe
    set), and publishes through the in-place swap. Records
    ``autotrain_cycle_s`` (trigger decision -> new generation live);
    the burst must see zero dropped queries and the generation must
    bump exactly once. Cycle completion + zero-drops gate on >= 4-core
    hosts under BENCH_STRICT_EXTRAS=1 (``autotrain_gate_capable``
    records the honest skip — the retrain compiles jax on one shared
    core otherwise and the wall clock measures the host).

    **Reject cycle** — a seeded provably-worse candidate (user factors
    negated: every ranking inverts) goes through the SAME validate
    path: it must be REJECTED with evidence, its ledger row flipped so
    no resolve ever deploys it, and the prior generation kept serving
    with no publish. Gated on every host — the verdict is in-process
    arithmetic, not a timing race."""
    import datetime as _dt
    import http.client
    import socket
    import threading

    from predictionio_tpu.common import journal
    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.data.api.http import serve_background
    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage import (
        App, EngineInstance, Model, Storage,
    )
    from predictionio_tpu.models.recommendation import (
        ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
    )
    from predictionio_tpu.workflow import model_io, run_train
    from predictionio_tpu.workflow.autotrain import (
        Autotrain, AutotrainConfig, LocalDeployControl, ThreadTrainer,
        Trainer,
    )
    from predictionio_tpu.workflow.context import WorkflowContext
    from predictionio_tpu.workflow.create_server import (
        QueryAPI, ServerConfig,
    )

    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover - non-linux
        cores = os.cpu_count() or 1
    capable = cores >= 4
    out: dict = {"autotrain_gate_capable": capable}
    app_name = "AutotrainBench"
    storage = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    app_id = storage.get_meta_data_apps().insert(App(0, app_name))
    storage.get_events().init(app_id)
    rng = np.random.default_rng(41)

    def rate_events(month):
        events = []
        for u in range(64):
            for i in rng.choice(48, size=12, replace=False).tolist():
                events.append(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item",
                    target_entity_id=f"i{i}",
                    properties=DataMap(
                        {"rating": float(1 + (u * 7 + i) % 5)}),
                    event_time=_dt.datetime(
                        2021, month, 1, tzinfo=_dt.timezone.utc)))
        return events

    storage.get_events().insert_batch(rate_events(1), app_id)
    params_json = {
        "datasource": {"params": {"appName": app_name}},
        "algorithms": [{"name": "als", "params": {
            "rank": 8, "numIterations": 3, "lambda": 0.05,
            "seed": 44}}]}
    run_train(
        WorkflowContext(storage=storage), RecommendationEngine(),
        EngineParams(
            data_source_params=DataSourceParams(appName=app_name),
            algorithm_params_list=(("als", ALSAlgorithmParams(
                rank=8, numIterations=3, lambda_=0.05, seed=44)),)),
        engine_factory=("predictionio_tpu.models.recommendation"
                        ":RecommendationEngine"),
        params_json=params_json)

    cursor_dir = tempfile.mkdtemp(prefix="pio_autotrain_cursor_")
    prev_env = {k: os.environ.get(k) for k in
                ("PIO_FOLDIN", "PIO_FOLDIN_CURSOR_DIR")}
    os.environ["PIO_FOLDIN_CURSOR_DIR"] = cursor_dir
    os.environ.pop("PIO_FOLDIN", None)
    api = server = at = None
    try:
        api = QueryAPI(storage=storage, engine=RecommendationEngine(),
                       config=ServerConfig(batching="on", foldin="on",
                                           foldin_tick_ms=20.0,
                                           foldin_headroom=16))
        server, port = serve_background(api)
        gen_before = api.generation
        live_before = api.engine_instance.id

        def _retrain() -> str:
            return run_train(
                api.ctx, api.engine, api.engine_params,
                engine_factory=("predictionio_tpu.models."
                                "recommendation:RecommendationEngine"),
                params_json=params_json)

        cfg = AutotrainConfig(
            poll_ms=50.0, cooldown_s=60.0, max_staleness_s=86400.0,
            volume_events=volume_events, lag_events=100_000,
            tolerance=0.05, parity_min=0.2, probe=64,
            publish_timeout_s=60.0)
        at = Autotrain(LocalDeployControl(api), storage=storage,
                       engine_params=api.engine_params,
                       trainer=ThreadTrainer(_retrain), config=cfg)
        api.attach_autotrain(at)

        # ---- accept cycle: burst -> volume trigger -> publish --------
        burst_errors: list = []
        stop = threading.Event()

        def burst(cx):
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port)
                conn.connect()
                conn.sock.setsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                while not stop.is_set():
                    conn.request(
                        "POST", "/queries.json",
                        body=json.dumps({"user": f"u{cx}", "num": 10}),
                        headers={"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    payload = resp.read()
                    if resp.status != 200:   # a dropped query IS a failure
                        burst_errors.append(payload[:200])
                        return
                conn.close()
            except Exception as e:
                burst_errors.append(f"{type(e).__name__}: {e}")

        clients = [threading.Thread(target=burst, args=(cx,))
                   for cx in range(n_conns)]
        for t in clients:
            t.start()
        t_trigger = None
        cycle_s = None
        try:
            # the live burst that crosses the volume trigger
            storage.get_events().insert_batch(rate_events(2), app_id)
            decided = False
            deadline = time.perf_counter() + 180.0
            while time.perf_counter() < deadline:
                at.tick(at.gather())
                if not decided and at._phase != "idle":
                    decided = True
                    t_trigger = time.perf_counter()
                if decided and at._phase == "idle":
                    cycle_s = time.perf_counter() - t_trigger
                    break
                time.sleep(0.05)
        finally:
            stop.set()
            for t in clients:
                t.join(timeout=30.0)
        s = at.summary()
        published = bool(s.get("lastCycle")) and api.generation \
            == gen_before + 1 and api.engine_instance.id != live_before
        out["autotrain_cycle_s"] = (
            round((s.get("lastCycle") or {}).get("cycleS", cycle_s)
                  or 0.0, 2) if published else None)
        out["autotrain_published"] = published
        out["autotrain_zero_drops"] = not burst_errors
        if burst_errors:
            out["autotrain_burst_error"] = str(burst_errors[0])
        out["autotrain_generation"] = api.generation

        # ---- reject cycle: seeded provably-worse candidate ----------
        live = api.engine_instance.id
        instances = storage.get_meta_data_engine_instances()
        models = model_io.deserialize_models(
            storage.get_model_data_models().get(live).models)
        models[0].user_factors = -np.asarray(
            models[0].user_factors, np.float32)
        cand = instances.insert(EngineInstance(
            **{**instances.get(live).__dict__,
               "id": "", "status": "COMPLETED"}))
        storage.get_model_data_models().insert(Model(
            id=cand, models=model_io.serialize_models(models)))

        class _SeededTrainer(Trainer):
            """Hands the state machine the pre-seeded candidate —
            the validate/reject path under test is downstream."""

            def start(self):
                pass

            def running(self):
                return False

            def poll(self):
                return {"ok": True, "instanceId": cand}

            def close(self):
                pass

        from predictionio_tpu.workflow.autotrain import Signals
        at2 = Autotrain(LocalDeployControl(api), storage=storage,
                        engine_params=api.engine_params,
                        trainer=_SeededTrainer(), config=cfg)
        at2._live_id = live
        gen_mid = api.generation
        at2.tick(Signals(now=time.monotonic(), staleness_s=1e9,
                         live_instance_id=live))
        deadline = time.perf_counter() + 60.0
        while at2._phase != "idle" and time.perf_counter() < deadline:
            at2.tick(Signals(now=time.monotonic()))
            time.sleep(0.02)
        at2.close()
        rejected = int(at2.summary()["candidatesRejected"])
        row = instances.get(cand)
        out["autotrain_candidates_rejected"] = rejected
        out["autotrain_reject_ok"] = bool(
            rejected == 1 and row is not None
            and row.status == "REJECTED"
            and api.generation == gen_mid
            and api.engine_instance.id == live
            and instances.get_latest_completed(
                at2.engine_id, at2.engine_version,
                at2.engine_variant).id != cand)
        out["autotrain_journaled_events"] = len(
            journal.snapshot(category="autotrain")["events"])
    finally:
        if at is not None:
            at.close()
        if server is not None:
            server.shutdown()
        if api is not None:
            api.close()
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(cursor_dir, ignore_errors=True)
    return out


def measure_multitenant(n_conns: int = 6, queries_per_client: int = 50,
                        flood_threads: int = 4):
    """Multi-tenant serving leg (serving/registry.py + the --engines
    deploy path): ONE process hosting N engine instances, measured on
    its two headline claims:

    - **shared-AOT compile flatness** — a 4-tenant deploy compiles
      exactly as many XLA programs as a 1-tenant deploy (later tenants
      memoize); ``mt_compile_count_4t`` vs ``mt_compile_count_1t``,
      strict-gated equal everywhere (compiling is deterministic);
    - **noisy-neighbor isolation** — tenant B's p99 while tenant A is
      flooded into its own small queue, over B's solo p99:
      ``mt_isolation_p99_ratio``, strict-gated <= 3x on >= 4-core
      hosts (on a shared core the flooders fight B for CPU and the
      ratio measures the host; ``mt_gate_capable`` records the skip).

    The leg runs on its own storage so its small per-tenant models
    never become the bench storage's latest COMPLETED instance."""
    import http.client
    import socket
    import threading

    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.data.storage import AccessKey, App, Storage
    from predictionio_tpu.models.recommendation import (
        ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
    )
    from predictionio_tpu.serving import aot
    from predictionio_tpu.serving.registry import TenantSpec
    from predictionio_tpu.workflow import run_train
    from predictionio_tpu.workflow.context import WorkflowContext
    from predictionio_tpu.workflow.create_server import (
        QueryAPI, ServerConfig,
    )

    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover - non-linux
        cores = os.cpu_count() or 1
    capable = cores >= 4
    workdir = tempfile.mkdtemp(prefix="pio_mt_bench_")
    storage = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": os.path.join(workdir, "el"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.event import Event
    import datetime as _dt

    n_tenants = 4
    specs_src = []
    for t in range(1, n_tenants + 1):
        app_name = f"MTBench{t}"
        app_id = storage.get_meta_data_apps().insert(App(0, app_name))
        storage.get_events().init(app_id)
        storage.get_meta_data_access_keys().insert(
            AccessKey(f"mt-key-{t}", app_id, ()))
        rng = np.random.default_rng(20 + t)
        events = []
        for u in range(64):
            for i in rng.choice(48, size=12, replace=False).tolist():
                events.append(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap(
                        {"rating": float(1 + (u * 7 + i + t) % 5)}),
                    event_time=_dt.datetime(
                        2021, 1, 1, tzinfo=_dt.timezone.utc)))
        storage.get_events().insert_batch(events, app_id)
        iid = run_train(
            WorkflowContext(storage=storage), RecommendationEngine(),
            EngineParams(
                data_source_params=DataSourceParams(appName=app_name),
                algorithm_params_list=(("als", ALSAlgorithmParams(
                    rank=8, numIterations=3, lambda_=0.05,
                    seed=30 + t)),)),
            engine_factory=("predictionio_tpu.models.recommendation"
                            ":RecommendationEngine"),
            params_json={
                "datasource": {"params": {"appName": app_name}},
                "algorithms": [{"name": "als", "params": {
                    "rank": 8, "numIterations": 3, "lambda": 0.05,
                    "seed": 30 + t}}]})
        specs_src.append((f"t{t}", f"mt-key-{t}", iid))

    def specs(n, **overrides):
        return tuple(TenantSpec(
            name=name, access_key=key, engine_instance_id=iid,
            **overrides.get(name, {}))
            for name, key, iid in specs_src[:n])

    out: dict = {"mt_gate_capable": capable, "mt_tenants": n_tenants}
    api = server = None
    try:
        # --- shared-AOT compile flatness: 1 tenant vs 4 tenants ------
        def compile_counts(n):
            aot.reset_memo()
            a = QueryAPI(storage=storage, config=ServerConfig(
                batching="on", aot="on", tenants=specs(n)))
            try:
                states = [a.registry.get(name).aot_state
                          for name, _k, _i in specs_src[:n]]
                if not all(s and s.get("enabled") for s in states):
                    raise RuntimeError("AOT did not enable for every "
                                       "tenant servable")
                return [int(s["compiled"]) for s in states]
            finally:
                a.close()

        c1 = compile_counts(1)
        c4 = compile_counts(n_tenants)
        out["mt_compile_count_1t"] = sum(c1)
        out[f"mt_compile_count_{n_tenants}t"] = sum(c4)
        out["mt_compile_flat_ok"] = bool(
            sum(c1) > 0 and sum(c4) == sum(c1))

        # --- noisy-neighbor isolation: flood t1, measure t2 ----------
        # t1 gets a deliberately small queue so the flood saturates IT
        # (tenant-scoped 503s), not the process; AOT off — flatness is
        # already measured and the pump only needs steady answers
        aot.reset_memo()
        api = QueryAPI(storage=storage, config=ServerConfig(
            batching="on", aot="off",
            tenants=specs(2, t1={"batch_max_queue": 8})))
        from predictionio_tpu.data.api.http import serve_background
        server, port = serve_background(api)

        def pump(key):
            """n_conns keep-alive clients x queries_per_client keyed
            requests; returns (qps, p50_ms, p99_ms)."""
            lat_lock = threading.Lock()
            lat: list = []
            errors: list = []
            barrier = threading.Barrier(n_conns + 1)
            path = f"/queries.json?accessKey={key}"

            def client(cx):
                try:
                    conn = http.client.HTTPConnection("127.0.0.1", port)
                    conn.connect()
                    conn.sock.setsockopt(
                        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    my = []
                    barrier.wait()
                    for q in range(queries_per_client):
                        body = json.dumps(
                            {"user": f"u{(cx * 131 + q * 17) % 64}",
                             "num": 10})
                        t0 = time.perf_counter()
                        conn.request(
                            "POST", path, body=body,
                            headers={"Content-Type": "application/json"})
                        resp = conn.getresponse()
                        payload = resp.read()
                        my.append(time.perf_counter() - t0)
                        assert resp.status == 200, payload[:200]
                    conn.close()
                    with lat_lock:
                        lat.extend(my)
                except Exception as e:
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(cx,))
                       for cx in range(n_conns)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if errors:
                raise errors[0]
            lat_ms = np.asarray(lat) * 1e3
            return (round(n_conns * queries_per_client / wall, 1),
                    round(float(np.percentile(lat_ms, 50)), 3),
                    round(float(np.percentile(lat_ms, 99)), 3))

        pump("mt-key-2")   # warm every path once
        qps_s, p50_s, p99_s = pump("mt-key-2")
        out["mt_b_solo"] = {"qps": qps_s, "p50_ms": p50_s,
                            "p99_ms": p99_s}

        stop = threading.Event()
        shed = [0]
        ok_flood = [0]

        def flooder():
            conn = http.client.HTTPConnection("127.0.0.1", port)
            body = json.dumps({"user": "u1", "num": 10})
            while not stop.is_set():
                try:
                    conn.request(
                        "POST", "/queries.json?accessKey=mt-key-1",
                        body=body,
                        headers={"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    resp.read()
                    if resp.status == 503:
                        shed[0] += 1
                    elif resp.status == 200:
                        ok_flood[0] += 1
                except OSError:
                    conn.close()
                    conn = http.client.HTTPConnection("127.0.0.1", port)
            conn.close()

        floods = [threading.Thread(target=flooder)
                  for _ in range(flood_threads)]
        for t in floods:
            t.start()
        try:
            time.sleep(0.2)   # let the flood build tenant 1's queue
            qps_f, p50_f, p99_f = pump("mt-key-2")
        finally:
            stop.set()
            for t in floods:
                t.join()
        out["mt_b_under_flood"] = {"qps": qps_f, "p50_ms": p50_f,
                                   "p99_ms": p99_f}
        out["mt_flood_503s"] = shed[0]
        out["mt_flood_oks"] = ok_flood[0]
        out["mt_isolation_p99_ratio"] = round(
            p99_f / max(p99_s, 1e-9), 3)
        out["mt_isolation_ok"] = bool(
            out["mt_isolation_p99_ratio"] <= 3.0)
    finally:
        if server is not None:
            server.shutdown()
        if api is not None:
            api.close()
        try:
            storage.get_events().close()
        except Exception:
            pass
        shutil.rmtree(workdir, ignore_errors=True)
    return out


def measure_recompile_watch(storage, engine, warmup_queries: int = 24,
                            steady_queries: int = 48):
    """Recompile-watchdog leg (common/devicewatch.py): deploy the engine
    with batching on and telemetry forced on, run a warmup burst, arm
    the steady-state detector, then run the standard bucketed burst.
    With the padding buckets holding, the post-warmup serving path must
    compile NOTHING — `serve_post_warmup_recompiles` lands in the JSON
    and BENCH_STRICT_EXTRAS=1 hard-fails when it is nonzero (the silent
    p99 cliff the buckets exist to prevent)."""
    from predictionio_tpu.common import devicewatch, telemetry
    from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig

    devicewatch.install()
    devicewatch.reset_watchdog()
    telemetry.set_enabled(True)
    api = None
    try:
        api = QueryAPI(storage=storage, engine=engine,
                       config=ServerConfig(batching="on"))

        def burst(n):
            for q in range(n):
                st, _ = api.handle(
                    "POST", "/queries.json",
                    body=json.dumps({"user": f"u{q * 37 % 1000}",
                                     "num": 10}).encode())
                assert st == 200
        burst(warmup_queries)
        devicewatch.mark_serving_warmup_done()
        before = devicewatch.post_warmup_recompiles()
        burst(steady_queries)
        recompiles = devicewatch.post_warmup_recompiles() - before
        return {
            "serve_post_warmup_recompiles": int(recompiles),
            "xla_compiles_total": int(devicewatch.compiles_total()),
        }
    finally:
        telemetry.set_enabled(None)
        if api is not None:
            api.close()


def measure_time_to_ready(storage, engine):
    """Warmup-cliff leg (serving/aot.py), two deploys of the trained
    instance:

    1. ``PIO_AOT=0`` lazy control, run FIRST so nothing serving-shaped
       has compiled in this process: the first batched query pays the
       real first-dispatch compile — ``first_query_compile_s``, the
       pre-AOT cliff, kept so benchtrend compares eras like with like.
    2. AOT deploy: prebuild every enumerated program before ready, then
       record ``time_to_ready_s`` (construction -> servable; the
       < 10 s warm-replica gate reads this), the prebuild split, and
       the first-query latency AFTER ready — which must contain no
       compile at all.
    """
    from predictionio_tpu.serving import aot
    from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig

    out = {}
    body = json.dumps({"user": "u1", "num": 10}).encode()
    prior = os.environ.get("PIO_AOT")
    os.environ["PIO_AOT"] = "0"
    try:
        api = QueryAPI(storage=storage, engine=engine,
                       config=ServerConfig(batching="on"))
        t0 = time.perf_counter()
        st, payload = api.handle("POST", "/queries.json", body=body)
        out["first_query_compile_s"] = round(time.perf_counter() - t0, 3)
        assert st == 200, payload
        api.close()
    finally:
        if prior is None:
            os.environ.pop("PIO_AOT", None)
        else:
            os.environ["PIO_AOT"] = prior
    # a fresh replica does its own prebuild: drop the in-process memo
    # (the jit/persistent caches stay — that's exactly the warm state a
    # restarted replica inherits from the cache artifact)
    aot.reset_memo()
    api = QueryAPI(storage=storage, engine=engine,
                   config=ServerConfig(batching="on"))
    try:
        st, info = api.handle("GET", "/")
        assert st == 200
        a = info.get("aot") or {}
        t1 = time.perf_counter()
        st, payload = api.handle("POST", "/queries.json", body=body)
        first_ms = (time.perf_counter() - t1) * 1e3
        assert st == 200, payload
        out.update({
            "time_to_ready_s": round(api.time_to_ready_s, 3),
            "aot_prebuild_s": a.get("prebuildS"),
            "aot_programs": a.get("programs"),
            "aot_failed": a.get("failed"),
            "first_query_after_ready_ms": round(first_ms, 3),
        })
        # <instance>.jaxcache artifact round-trip verification (the
        # ROADMAP item-2 follow-up): export the train's artifact blob
        # into a FRESH directory and record what imported — on the
        # TPU platform this is the per-round receipt that the
        # deploy-side pre-seed genuinely lands entry-for-entry
        out["cache_artifact_roundtrip"] = _cache_artifact_roundtrip(
            storage, api.engine_instance.id)
    finally:
        api.close()
    return out


def _cache_artifact_roundtrip(storage, instance_id: str):
    """Import the instance's compile-cache artifact into a throwaway dir
    and report {present, bytes, imported, skipped, reason}."""
    import tempfile

    from predictionio_tpu.workflow import model_io

    art = storage.get_model_data_models().get(
        model_io.cache_artifact_id(instance_id))
    if art is None:
        return {"present": False}
    fresh = tempfile.mkdtemp(prefix="pio-cache-rt-")
    try:
        summary = model_io.import_compile_cache(art.models, fresh)
        return {"present": True, "bytes": len(art.models),
                "imported": summary.get("imported", 0),
                "skipped": summary.get("skipped", 0),
                "reason": summary.get("reason") or None,
                "ok": (not summary.get("reason")
                       and summary.get("imported", 0) > 0)}
    except Exception as e:
        return {"present": True, "bytes": len(art.models),
                "ok": False, "reason": f"{type(e).__name__}: {e}"}
    finally:
        shutil.rmtree(fresh, ignore_errors=True)


def measure_train_stream(storage, engine, nnz: int, n_iters: int = 2):
    """Out-of-core training leg (ROADMAP item 6): the SAME front-door
    `pio train` over the same event store, in-core (PIO_TRAIN_STREAM=off)
    vs streamed (=on), with the layout cache disabled so both legs pay
    the full read + layout + train pipeline. Records end-to-end
    pipeline ratings/s for each mode, the peak host RSS and — the
    number the O(chunk) claim rests on — the peak PIPELINE RSS (RSS
    minus live jax array bytes, which is what isolates host-side
    staging on CPU backends where device buffers share the RSS;
    KNOWN_ISSUES #14). Strict gates: bit-identical model checksums
    (streamed training is a memory optimization, not a different
    model), streamed ratings/s >= 85% of in-core, streamed pipeline
    peak <= 1.10x in-core."""
    from predictionio_tpu.common import devicewatch
    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.models.recommendation import (
        ALSAlgorithmParams, DataSourceParams,
    )
    from predictionio_tpu.workflow import run_train
    from predictionio_tpu.workflow.context import WorkflowContext

    saved = {k: os.environ.get(k)
             for k in ("PIO_TRAIN_STREAM", "PIO_ALS_LAYOUT_CACHE")}

    def leg(mode):
        os.environ["PIO_TRAIN_STREAM"] = mode
        os.environ["PIO_ALS_LAYOUT_CACHE"] = "0"
        ctx = WorkflowContext(storage=storage)
        with devicewatch.RssWatcher() as w:
            t0 = time.perf_counter()
            iid = run_train(
                ctx, engine,
                EngineParams(
                    data_source_params=DataSourceParams(appName="BenchApp"),
                    algorithm_params_list=(("als", ALSAlgorithmParams(
                        rank=10, numIterations=n_iters, lambda_=0.01,
                        seed=21)),)),
                engine_factory="bench-stream")
            ck = model_checksum(storage, iid)  # host barrier inside timer
            wall = time.perf_counter() - t0
        ph = dict(ctx.phase_seconds)
        # read_io/read_encode are SUB-phases of "read" — summing them in
        # again would double-count the scan
        core_s = (ph.get("read", 0.0) + ph.get("layout", 0.0)
                  + ph.get("train", 0.0))
        return {
            "wall_s": round(wall, 3),
            "core_s": round(core_s, 3),
            "ratings_per_s": round(nnz * n_iters / max(core_s, 1e-9)),
            "peak_rss_mb": round(w.peak_rss / 2**20, 1),
            "peak_pipeline_mb": round(w.peak_pipeline / 2**20, 1),
            "checksum": ck,
        }

    try:
        off = leg("off")
        on = leg("on")
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    ratio = on["ratings_per_s"] / max(off["ratings_per_s"], 1e-9)
    return {
        "train_stream_off": off,
        "train_stream_on": on,
        "train_stream_ratings_per_s": on["ratings_per_s"],
        "train_stream_peak_rss_mb": on["peak_rss_mb"],
        "train_stream_peak_pipeline_mb": on["peak_pipeline_mb"],
        "train_stream_rss_delta_mb": round(
            off["peak_pipeline_mb"] - on["peak_pipeline_mb"], 1),
        "train_stream_rate_ratio": round(ratio, 3),
        "train_stream_rate_ok": ratio >= 0.85,
        "train_stream_rss_ok": (
            on["peak_pipeline_mb"] <= off["peak_pipeline_mb"] * 1.10 + 64),
        "train_stream_bitparity_ok": (
            np.isfinite(off["checksum"]) and np.isfinite(on["checksum"])
            and off["checksum"] == on["checksum"]),
    }


def serve_and_measure(storage, engine, n_queries: int = 200):
    """Deploy via QueryAPI + HTTP and time front-door query round-trips."""
    import http.client
    import socket
    import threading

    from predictionio_tpu.data.api.http import make_server
    from predictionio_tpu.workflow.create_server import QueryAPI

    api = QueryAPI(storage=storage, engine=engine)
    server = make_server(api, "127.0.0.1", 0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port)
        conn.connect()
        conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        lat = []
        for q in range(n_queries):
            body = json.dumps({"user": f"u{q * 37 % 1000}", "num": 10})
            t0 = time.perf_counter()
            conn.request("POST", "/queries.json", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = resp.read()
            lat.append(time.perf_counter() - t0)
            assert resp.status == 200, payload[:200]
        lat_ms = np.asarray(lat) * 1e3
        return float(np.percentile(lat_ms, 50)), float(np.percentile(lat_ms, 99))
    finally:
        server.shutdown()


def measure_lint():
    """`pio lint` over this checkout (tools/analyze): the bench round
    carries the static-analysis verdict next to the perf numbers, so
    benchtrend can gate `lint_findings_total` at 0 absolutely and trend
    the suppressed (accepted-debt) count, which should only shrink.
    In-process and stdlib-only — costs ~1 s, never touches the device."""
    try:
        from predictionio_tpu.tools.analyze.runner import run_lint
        r = run_lint()
        return {
            "lint_findings_total": len(r.active),
            "lint_suppressed_total": len(r.suppressed),
            "lint_stale_baseline_total": len(r.stale),
            "lint_modules_analyzed": r.modules_analyzed,
            "lint_exit": r.exit_code,
            "lint_rules_fired": sorted({f.rule for f in r.active}) or None,
        }
    except Exception as e:     # the lint must never sink a bench run…
        # …except under strict extras, where lint_error fails the round
        return {"lint_error": f"{type(e).__name__}: {e}"}


def model_checksum(storage, instance_id: str) -> float:
    """Sum the persisted factor matrices — a host-side consumption barrier
    AND a sanity signal (NaN/garbage shows up immediately)."""
    from predictionio_tpu.workflow import model_io

    blob = storage.get_model_data_models().get(instance_id)
    if blob is None:
        return float("nan")
    model = model_io.deserialize_models(blob.models)
    total = 0.0
    for m in model if isinstance(model, (list, tuple)) else [model]:
        for attr in ("user_factors", "item_factors", "product_features",
                     "user_features"):
            arr = getattr(m, attr, None)
            if arr is not None:
                total += float(np.sum(np.asarray(arr, dtype=np.float64)))
    return total


def main() -> None:
    import jax

    # persistent compile cache: the program is identical across runs on the
    # same libtpu, so only the first bench on a machine pays compilation
    cache_dir = os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(HERE, ".jax_cache"))
    try:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    except Exception:
        pass

    def cache_stats():
        """Compile-cache state, so a warmup_compile_s swing is explainable
        from the artifact alone (round-4 Weak #4: 136 s -> 419 s with no
        recorded cause). entries==0 before a run means fully cold."""
        try:
            files = [os.path.join(cache_dir, f)
                     for f in os.listdir(cache_dir)]
            return {"entries": len(files),
                    "bytes": int(sum(os.path.getsize(f) for f in files))}
        except OSError:
            return {"entries": 0, "bytes": 0}

    cache_before = cache_stats()

    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.data.storage import App, Storage
    from predictionio_tpu.models.recommendation import (
        ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
    )
    from predictionio_tpu.workflow import run_train
    from predictionio_tpu.workflow.context import WorkflowContext

    n_users = int(os.environ.get("BENCH_USERS", 138_000))
    n_items = int(os.environ.get("BENCH_ITEMS", 27_000))
    nnz = int(os.environ.get("BENCH_NNZ", 20_000_000))
    iters = int(os.environ.get("BENCH_ITERS", 10))
    data_seed = int(os.environ.get(
        "BENCH_DATA_SEED", int.from_bytes(os.urandom(4), "little") % (2**31)))
    i1, i2 = max(1, iters), max(1, iters) * 3   # slope endpoints

    workdir = tempfile.mkdtemp(prefix="pio_bench_")
    try:
        storage = Storage(env={
            "PIO_STORAGE_SOURCES_M_TYPE": "memory",
            "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
            "PIO_STORAGE_SOURCES_EL_PATH": os.path.join(workdir, "el"),
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
        })
        app_id = storage.get_meta_data_apps().insert(App(0, "BenchApp"))
        u, i, r = synth_codes(n_users, n_items, nnz, data_seed)
        write_s = seed_event_store(storage, app_id, u, i, r, n_users)

        # serial-vs-parallel bulk read leg, before anything warms caches
        read_modes = measure_read_modes(storage, app_id)

        ingest = None
        if os.environ.get("BENCH_SKIP_HTTP") != "1":
            try:
                ingest = measure_http_ingest(storage, n_users, n_items)
            except Exception as e:
                ingest = {"ingest_error": f"{type(e).__name__}: {e}"}

        engine = RecommendationEngine()

        def params(n_iters, seed):
            return EngineParams(
                data_source_params=DataSourceParams(appName="BenchApp"),
                algorithm_params_list=(("als", ALSAlgorithmParams(
                    rank=10, numIterations=n_iters, lambda_=0.01,
                    seed=seed)),))

        def one_train(n_iters, seed):
            """Full front-door `pio train`; returns (wall_s, phases, cksum).
            phases["train"] includes the nested "layout" phase; the slope
            uses their difference (pure iteration loop + fixed dispatch)."""
            ctx = WorkflowContext(storage=storage)
            t0 = time.perf_counter()
            iid = run_train(ctx, engine, params(n_iters, seed),
                            engine_factory="bench",
                            params_json={
                                "datasource": {"params": {
                                    "appName": "BenchApp"}},
                                "algorithms": [{"name": "als", "params": {
                                    "rank": 10, "numIterations": n_iters,
                                    "lambda": 0.01, "seed": seed}}]})
            cksum = model_checksum(storage, iid)   # host barrier inside timer
            wall = time.perf_counter() - t0
            return wall, dict(ctx.phase_seconds), cksum

        # Warm-up: compiles the exact programs the timed runs reuse
        # (iteration count is traced => i1 and i2 share one program).
        # The run's aot_export phase (serving-program AOT build + cache
        # artifact, serving/aot.py) is subtracted so warmup_compile_s
        # keeps meaning TRAIN-side compile, comparable with pre-AOT
        # rounds; the serving-side split is recorded separately.
        t0 = time.perf_counter()
        _wall_w, ph_w, _ck_w = one_train(1, 3)
        warm_total_s = time.perf_counter() - t0
        train_aot_export_s = ph_w.get("aot_export", 0.0)
        warm_s = warm_total_s - train_aot_export_s

        # TRUE cold-ETL run: compiles warm, but the process-wide layout
        # cache is bypassed so this wall-clock is what a fresh `pio train`
        # (sans compile) costs end to end. The slope passes after it run
        # layout-cached, which layout_s_runs makes visible.
        prior_cache_env = os.environ.get("PIO_ALS_LAYOUT_CACHE")
        os.environ["PIO_ALS_LAYOUT_CACHE"] = "0"
        try:
            wall_cold, ph_cold, _ck_cold = one_train(i1, 7)
        finally:
            if prior_cache_env is None:
                os.environ.pop("PIO_ALS_LAYOUT_CACHE", None)
            else:
                os.environ["PIO_ALS_LAYOUT_CACHE"] = prior_cache_env
        # the cold run evicted the layout/hybrid caches; repopulate with an
        # untimed train so slope leg a1 doesn't pay one-time hybrid prep
        # inside its 'train' phase (which would bias per_iter_a low — the
        # prep lands outside the 'layout' phase iter_core subtracts)
        one_train(1, 8)

        def iter_core(ph):
            return ph.get("train", 0.0) - ph.get("layout", 0.0)

        # Slope pass A (seed 11) and B (seed 12): fresh factor seeds.
        wall_a1, ph_a1, ck_a1 = one_train(i1, 11)
        wall_a2, ph_a2, ck_a2 = one_train(i2, 11)
        per_iter_a = (iter_core(ph_a2) - iter_core(ph_a1)) / (i2 - i1)
        wall_b1, ph_b1, ck_b1 = one_train(i1, 12)
        wall_b2, ph_b2, ck_b2 = one_train(i2, 12)
        per_iter_b = (iter_core(ph_b2) - iter_core(ph_b1)) / (i2 - i1)
        # a slope can only be negative when something external (host
        # contention, a device-link stall) ate one leg — a nonsensical pass
        # must not launder the headline through min()
        valid = [p for p in (per_iter_a, per_iter_b) if p > 1e-6]
        slope_passes_valid = len(valid)
        if not valid:
            print("BENCH FAILED: both slope passes non-positive "
                  f"({per_iter_a*1e3:.1f} / {per_iter_b*1e3:.1f} ms/iter) "
                  "— rerun on an idle host", file=sys.stderr)
            sys.exit(1)
        per_iter = min(valid)
        # spread is the measurement-quality signal; with one pass discarded
        # there IS no agreement to report — null, not a fake-perfect 0.0
        spread = ((max(valid) - min(valid)) / per_iter
                  if len(valid) == 2 else None)
        steady_s = per_iter * iters
        layouts = [round(p.get("layout", 0.0), 3)
                   for p in (ph_a1, ph_a2, ph_b1, ph_b2)]

        # time-to-ready leg (serving/aot.py): MUST run before any other
        # serving leg so its lazy-compile control measures the true
        # first-dispatch cliff of this process
        ttr_leg = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                ttr_leg = measure_time_to_ready(storage, engine)
            except Exception as e:
                ttr_leg = {"time_to_ready_error":
                           f"{type(e).__name__}: {e}"}

        p50_ms, p99_ms = serve_and_measure(storage, engine)

        # concurrent-client throughput leg: the same deployed engine with
        # the query micro-batcher off vs on. Batched QPS beating unbatched
        # QPS on the same hardware is the acceptance signal for the
        # serving subsystem; both tables land in the JSON either way.
        throughput = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                thr_off = measure_concurrent_qps(storage, engine, "off")
                thr_on = measure_concurrent_qps(storage, engine, "on")
                best = lambda t: max(  # noqa: E731
                    v["qps"] for k, v in t.items() if isinstance(k, int))
                throughput = {
                    "serve_qps_unbatched": thr_off,
                    "serve_qps_batched": thr_on,
                    "serve_batched_qps_gain": round(
                        best(thr_on) / max(best(thr_off), 1e-9), 3),
                }
            except Exception as e:
                throughput = {"serve_throughput_error":
                              f"{type(e).__name__}: {e}"}

        # telemetry leg: metrics-on vs metrics-off p99 through the same
        # batched path + a real /metrics scrape into the JSON detail
        # (padding-waste ratio, flush-size histogram, retry counts)
        telem = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                telem = measure_telemetry(storage, engine)
            except Exception as e:
                telem = {"telemetry_error": f"{type(e).__name__}: {e}",
                         "telemetry_scrape_ok": False}

        # waterfall leg (common/waterfall.py): stage sampling off vs on
        # through the same batched path + a /debug/slow.json read; the
        # sampled path's p99 tax gates at <= 5% under strict extras
        wf = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                wf = measure_waterfall(storage, engine)
            except Exception as e:
                wf = {"waterfall_error": f"{type(e).__name__}: {e}"}

        # flight-recorder leg (common/journal.py): journal off vs on
        # through the same batched path + a /debug/events.json read;
        # requests never emit, so the on-p99 tax gates at <= 5% under
        # strict extras and the deploy's lifecycle event must be there
        jrnl = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                jrnl = measure_journal(storage, engine)
            except Exception as e:
                jrnl = {"journal_error": f"{type(e).__name__}: {e}"}

        # metrics-flight-recorder leg (common/history.py): history off
        # vs on through the same batched path + a MID-BURST
        # /debug/history.json read; sampling runs off-thread, so the
        # on-p99 tax gates at <= 5% under strict extras and the rings
        # must hold pio_serve_seconds deltas and stay bounded
        hist_leg = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                hist_leg = measure_history(storage, engine)
            except Exception as e:
                hist_leg = {"history_error": f"{type(e).__name__}: {e}"}

        # realtime fold-in leg (realtime/foldin.py): serve p99 with the
        # worker off vs on (live event stream in the on leg, <= 5%
        # strict gate) + wire-level freshness for unseen users (p99
        # <= 2 s strict — the "signed up 10 seconds ago" contract)
        foldin_leg = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                foldin_leg = measure_foldin(storage, engine)
            except Exception as e:
                foldin_leg = {"foldin_error": f"{type(e).__name__}: {e}"}

        # sharded-serving leg (parallel/serve_dist.py): replicated vs
        # row-sharded p99 through the same batched path, wire-level
        # probe parity, and the HBM-ceiling demonstration; the sharded
        # path's p99 tax gates at <= 10% under strict extras
        shard_leg = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                shard_leg = measure_serve_sharded(storage, engine)
            except Exception as e:
                shard_leg = {"serve_sharded_error":
                             f"{type(e).__name__}: {e}"}

        # quantized-serving leg (ops/quant.py): fp32 vs int8(+fused)
        # p99, factor-matrix HBM ratio, and wire-level ranking parity
        # (recall@k / exact-match@1); strict gates: quant p99 <= fp32,
        # hbm_ratio <= 0.30, recall >= 0.99
        quant_leg = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                quant_leg = measure_serve_quant(storage, engine)
            except Exception as e:
                quant_leg = {"serve_quant_error":
                             f"{type(e).__name__}: {e}"}

        # fleet front-door leg (workflow/router.py): real replica
        # processes behind the router — router-added p99 <= 1 ms and
        # near-linear 1->2(->4) replica QPS scaling, gates enforced on
        # >= 4-core hosts (router_gate_capable records the honest skip)
        router_leg = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                router_leg = measure_router()
            except Exception as e:
                router_leg = {"router_error": f"{type(e).__name__}: {e}"}

        # partition-routed serving leg (workflow/router.py scatter/
        # merge + `pio deploy --partition i/N`): wire bit-parity vs one
        # full replica (deterministic, gated everywhere), scatter-added
        # p99, and the 1/N per-replica HBM-budget demo
        partition_leg = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                partition_leg = measure_router_partition()
            except Exception as e:
                partition_leg = {"router_partition_error":
                                 f"{type(e).__name__}: {e}"}

        # front-door response-cache leg (workflow/router.py
        # _ResponseCache): zipfian keys through the router cache off vs
        # on — hit ratio > 0 gated everywhere, cached p99 <= uncached
        # on >= 4-core hosts (router_cache_gate_capable records skips)
        cache_leg = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                cache_leg = measure_router_cache()
            except Exception as e:
                cache_leg = {"router_cache_error":
                             f"{type(e).__name__}: {e}"}

        # autopilot leg (workflow/autopilot.py): a replica SIGKILL under
        # a zipfian burst with the control loop live — recovery seconds
        # back to full rotation (strict on >= 4-core hosts;
        # autopilot_gate_capable records the honest skip) plus the
        # burn-ladder widen + exact-restore cycle (strict everywhere)
        autopilot_leg = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                autopilot_leg = measure_autopilot()
            except Exception as e:
                autopilot_leg = {"autopilot_error":
                                 f"{type(e).__name__}: {e}"}

        # continuous-training leg (workflow/autotrain.py): a live event
        # burst crosses the volume trigger under a query burst — real
        # retrain, validated, published in-place with zero drops and a
        # generation bump (strict on >= 4-core hosts;
        # autotrain_gate_capable records the honest skip) plus the
        # seeded-worse candidate REJECTED with the prior generation
        # kept serving (strict everywhere — in-process arithmetic)
        autotrain_leg = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                autotrain_leg = measure_autotrain()
            except Exception as e:
                autotrain_leg = {"autotrain_error":
                                 f"{type(e).__name__}: {e}"}

        # multi-tenant leg (serving/registry.py): one process, N engine
        # instances — shared-AOT compile flatness (strict everywhere)
        # and noisy-neighbor p99 isolation (strict on >= 4-core hosts;
        # mt_gate_capable records the honest skip)
        mt_leg = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                mt_leg = measure_multitenant()
            except Exception as e:
                mt_leg = {"multitenant_error": f"{type(e).__name__}: {e}"}

        # recompile-watchdog leg (common/devicewatch.py): after a warmup
        # burst the standard bucketed serving path must compile NOTHING —
        # a nonzero count is the padding-bucket p99 cliff, strict-fatal
        recompile_watch = None
        if os.environ.get("BENCH_SKIP_THROUGHPUT") != "1":
            try:
                recompile_watch = measure_recompile_watch(storage, engine)
            except Exception as e:
                recompile_watch = {
                    "recompile_watch_error": f"{type(e).__name__}: {e}"}

        # out-of-core training leg (data/store.py stream mode): in-core
        # vs streamed `pio train` over the same store — pipeline
        # ratings/s, peak host RSS, and the bit-parity contract; runs
        # AFTER the serving legs so its extra COMPLETED instances never
        # change which model those legs deploy
        stream_leg = None
        if os.environ.get("BENCH_SKIP_EXTRAS") != "1":
            try:
                stream_leg = measure_train_stream(storage, engine, nnz)
            except Exception as e:
                stream_leg = {"train_stream_error":
                              f"{type(e).__name__}: {e}"}

        # parity leg AFTER the timed passes: it reuses the already-compiled
        # hybrid program and adds only the csrb one, so warmup_compile_s
        # above stays an honest per-process compile measurement
        parity = None
        if os.environ.get("BENCH_SKIP_PARITY") != "1":
            p = measure_kernel_parity(u, i, r, n_users, n_items)
            parity = {f"parity_{k}": (round(v, 6)
                                      if isinstance(v, float) else v)
                      for k, v in p.items() if k != "ok"}
            parity["parity_ok"] = bool(p["ok"])
        del u, i, r

        eval_grid = ecom = None
        if os.environ.get("BENCH_SKIP_EXTRAS") != "1":
            try:
                ev_events = int(os.environ.get("BENCH_EVAL_EVENTS", 100_000))
                t0 = time.perf_counter()
                ew, best, nvar, ord_ok, reuse_hits = measure_eval_grid(
                    storage, ev_events)
                eval_grid = {"eval_grid_s": round(ew, 3),
                             "eval_variants": nvar,
                             "eval_best_p_at_10": round(best, 4),
                             "eval_ordering_ok": bool(ord_ok),
                             "eval_grid_reuse_hits": int(reuse_hits)}
            except Exception as e:  # extras must never sink the headline
                eval_grid = {"eval_error": f"{type(e).__name__}: {e}"}
            try:
                e50, e99 = measure_ecom_serving(storage, n_users)
                ecom = {"ecom_unseen_p50_ms": round(e50, 3),
                        "ecom_unseen_p99_ms": round(e99, 3)}
            except Exception as e:
                ecom = {"ecom_error": f"{type(e).__name__}: {e}"}

        # robustness leg: storage RPCs under 1% injected faults, breaker
        # off vs on (common/resilience.py); cheap, so it always runs with
        # the other extras — the hard gates on it are strict-only
        robust = None
        if os.environ.get("BENCH_SKIP_EXTRAS") != "1":
            try:
                robust = measure_robustness(workdir)
            except Exception as e:
                robust = {"robust_error": f"{type(e).__name__}: {e}"}

        # static-analysis leg (`pio lint`, tools/analyze): always runs —
        # ~1 s, stdlib-only — so every bench artifact records the lint
        # verdict; strict extras turn any finding into a failed round
        lint_leg = measure_lint()

        published = {}
        try:
            with open(os.path.join(HERE, "BASELINE.json")) as f:
                published = json.load(f).get("published", {}) or {}
        except Exception:
            pass
        base = published.get("als_train_ml20m_s")
        vs = (base / steady_s) if base else None

        cache_after = cache_stats()
        result = {
            "metric": "als_ml20m_train_steady10_s",
            "value": round(steady_s, 3),
            "unit": "s",
            "vs_baseline": vs,
            "detail": {
                "nnz": nnz, "rank": 10, "iterations": iters,
                "data_seed": data_seed,
                "steady_per_iter_ms": round(per_iter * 1e3, 1),
                "steady_per_iter_ms_runs": [round(per_iter_a * 1e3, 1),
                                            round(per_iter_b * 1e3, 1)],
                "slope_passes_valid": slope_passes_valid,
                "steady_rel_spread": (round(spread, 4)
                                      if spread is not None else None),
                "throughput_ratings_per_s": round(nnz / per_iter),
                "cold_pio_train_total_s": round(wall_cold, 3),
                "warm_pio_train_total_s": round(wall_a1, 3),
                "phase_read_s": round(ph_cold.get("read", 0.0), 3),
                "phase_read_io_s": round(ph_cold.get("read_io", 0.0), 3),
                "phase_read_encode_s": round(
                    ph_cold.get("read_encode", 0.0), 3),
                "phase_layout_s": round(ph_cold.get("layout", 0.0), 3),
                "phase_train_s": round(ph_cold.get("train", 0.0), 3),
                "phase_persist_s": round(ph_cold.get("persist", 0.0), 3),
                **read_modes,
                "layout_s_runs": layouts,
                "event_store_write_s": round(write_s, 3),
                **(ingest if ingest
                   else {"http_ingest_events_per_s": None}),
                # in the early rounds compilation ran remotely and the
                # local persistent cache did not apply; paid per process
                # there, and NOT part of any steady-state claim
                "warmup_compile_s": round(warm_s, 3),
                # first-class warmup-compile record: the cache delta
                # distinguishes a cold-cache round (entries_before == 0,
                # legitimately slow — ~399 s in BENCH_r05) from a true
                # compile regression; benchtrend only compares rounds
                # whose caches were both warm
                "warmup_compile": {
                    "seconds": round(warm_s, 3),
                    # serving-side AOT split (serving/aot.py): the
                    # warmup train's aot_export phase is EXCLUDED from
                    # `seconds` so the record stays train-compile-only,
                    # comparable with pre-AOT rounds
                    "train_aot_export_s": round(train_aot_export_s, 3),
                    "cold_cache": cache_before["entries"] == 0,
                    "cache_entries_before": cache_before["entries"],
                    "cache_entries_delta": (cache_after["entries"]
                                            - cache_before["entries"]),
                    "cache_bytes_delta": (cache_after["bytes"]
                                          - cache_before["bytes"]),
                },
                "compile_cache": {"dir": cache_dir,
                                  "before": cache_before,
                                  "after": cache_after},
                "kernel_knobs": {
                    k: os.environ.get(k, d) for k, d in (
                        ("PIO_ALS_KERNEL", "hybrid"),
                        ("PIO_ALS_HOT_K", "4096"),
                        ("PIO_ALS_DENSE_MIN_COUNT", "64"),
                        ("PIO_ALS_XPAD", "1"),
                        ("PIO_ALS_SOLVER", "gj"),
                        ("PIO_NNZ_BUCKETING", "1"))},
                "checksums": [round(ck_a1, 2), round(ck_a2, 2),
                              round(ck_b1, 2), round(ck_b2, 2)],
                **(parity or {}),
                "serve_http_p50_ms": round(p50_ms, 3),
                "serve_http_p99_ms": round(p99_ms, 3),
                **(ttr_leg or {}),
                **(throughput or {}),
                **(telem or {}),
                **(wf or {}),
                **(jrnl or {}),
                **(hist_leg or {}),
                **(foldin_leg or {}),
                **(shard_leg or {}),
                **(quant_leg or {}),
                **(router_leg or {}),
                **(partition_leg or {}),
                **(cache_leg or {}),
                **(autopilot_leg or {}),
                **(autotrain_leg or {}),
                **(mt_leg or {}),
                **(recompile_watch or {}),
                **(stream_leg or {}),
                **(eval_grid or {}),
                **(ecom or {}),
                **(robust or {}),
                **(lint_leg or {}),
                "device": str(jax.devices()[0]).split(":")[0],
            },
        }

        # bench-trajectory gate (tools/benchtrend.py): compare this run
        # against the historical BENCH_r*.json series; the per-metric
        # deltas land in the artifact, the hard failures are strict-only
        import glob as _glob

        from predictionio_tpu.tools import benchtrend
        trend_failures = []
        history = sorted(_glob.glob(os.path.join(HERE, "BENCH_r*.json")))
        if history:
            try:
                trend_failures, trend = benchtrend.gate_current(
                    result, history,
                    threshold=float(os.environ.get(
                        "BENCH_TREND_THRESHOLD",
                        benchtrend.DEFAULT_THRESHOLD)))
                result["detail"]["trend"] = trend
            except Exception as e:   # the trend must never sink the run
                result["detail"]["trend"] = {
                    "trend_error": f"{type(e).__name__}: {e}"}

        print(json.dumps(result))

        # hard gates (round-4 Weak #2a: the bench PRINTED [NaN,NaN,NaN,NaN]
        # checksums and the round still shipped an 87.8 ms/iter headline
        # measured on that garbage model) — a non-finite model, an at-scale
        # kernel-parity failure, or an inverted eval ordering is a FAILED
        # bench run, visible to the driver as a nonzero exit code
        failures = []
        if not all(np.isfinite(c)
                   for c in (ck_a1, ck_a2, ck_b1, ck_b2)):
            failures.append("non-finite model checksum")
        if parity is not None and not parity["parity_ok"]:
            failures.append("hybrid-vs-csrb parity failure at scale")
        if eval_grid is not None and eval_grid.get(
                "eval_ordering_ok") is False:
            failures.append("eval grid ordering inverted")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and not (
                read_modes["read_checksums_match"]):
            failures.append(
                "parallel and serial bulk reads disagree on checksums "
                "with BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and robust:
            if robust.get("robust_error"):
                failures.append(
                    f"robustness leg crashed ({robust['robust_error']}) "
                    "with BENCH_STRICT_EXTRAS=1")
            else:
                for leg_name in ("robust_breaker_off", "robust_breaker_on"):
                    leg_r = robust[leg_name]
                    if leg_r["err"] > 0:
                        failures.append(
                            f"{leg_name}: {leg_r['err']} storage errors "
                            "surfaced despite retries with "
                            "BENCH_STRICT_EXTRAS=1")
                    if leg_r["faults_injected"] == 0:
                        failures.append(
                            f"{leg_name}: no faults fired — the leg "
                            "measured nothing")
                if robust["robust_breaker_on"]["breaker_opened"]:
                    failures.append(
                        "breaker opened at a 1% fault rate (threshold "
                        "misconfigured) with BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and telem:
            if not telem.get("telemetry_scrape_ok"):
                failures.append(
                    "GET /metrics scrape failed "
                    f"({telem.get('telemetry_error', 'missing series')}) "
                    "with BENCH_STRICT_EXTRAS=1")
            elif not telem.get("telemetry_overhead_ok"):
                failures.append(
                    "metrics-on p99 "
                    f"({telem['telemetry_on']['p99_ms']} ms) exceeds "
                    "metrics-off "
                    f"({telem['telemetry_off']['p99_ms']} ms) by >5% "
                    "with BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and ingest:
            if ingest.get("ingest_error"):
                failures.append(
                    f"ingest leg crashed ({ingest['ingest_error']}) "
                    "with BENCH_STRICT_EXTRAS=1")
            elif ingest.get("ingest_gate_capable"):
                # host has cores to spare for the pump threads, so the
                # async figure is server-limited: enforce the contract
                speedup = ingest.get("ingest_async_speedup_32")
                if speedup is None or speedup < 3.0:
                    failures.append(
                        "async transport + group commit at 32 connections "
                        f"is {speedup}x threaded (< 3x) with "
                        "BENCH_STRICT_EXTRAS=1")
                a_p99 = ingest.get("ingest_admission_p99_ms")
                t_p99 = ingest.get("ingest_threaded_admission_p99_ms_8")
                if a_p99 is not None and t_p99 is not None \
                        and a_p99 > t_p99:
                    failures.append(
                        f"async admission p99 at 32 conns ({a_p99} ms) "
                        f"worse than threaded at 8 conns ({t_p99} ms) "
                        "with BENCH_STRICT_EXTRAS=1")
            # small hosts record the measured ratio but skip the gate
            # (ingest_gate_capable False in the artifact says why)
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and wf:
            if wf.get("waterfall_error"):
                failures.append(
                    f"waterfall leg crashed ({wf['waterfall_error']}) "
                    "with BENCH_STRICT_EXTRAS=1")
            elif not wf.get("waterfall_overhead_ok"):
                failures.append(
                    "waterfall-on p99 "
                    f"({wf['waterfall_on']['p99_ms']} ms) exceeds "
                    "sampling-off "
                    f"({wf['waterfall_off']['p99_ms']} ms) by >5% "
                    "with BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and jrnl:
            if jrnl.get("journal_error"):
                failures.append(
                    f"journal leg crashed ({jrnl['journal_error']}) "
                    "with BENCH_STRICT_EXTRAS=1")
            elif not jrnl.get("journal_overhead_ok"):
                failures.append(
                    "journal-on p99 "
                    f"({jrnl['journal_on']['p99_ms']} ms) exceeds "
                    "journal-off "
                    f"({jrnl['journal_off']['p99_ms']} ms) by >5% "
                    "with BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and hist_leg:
            if hist_leg.get("history_error"):
                failures.append(
                    f"history leg crashed ({hist_leg['history_error']}) "
                    "with BENCH_STRICT_EXTRAS=1")
            elif not hist_leg.get("history_overhead_ok"):
                failures.append(
                    "history-on p99 "
                    f"({hist_leg['history_on']['p99_ms']} ms) exceeds "
                    "history-off "
                    f"({hist_leg['history_off']['p99_ms']} ms) by >5% "
                    "with BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and foldin_leg:
            if foldin_leg.get("foldin_error"):
                failures.append(
                    f"fold-in leg crashed ({foldin_leg['foldin_error']}) "
                    "with BENCH_STRICT_EXTRAS=1")
            else:
                if foldin_leg.get("foldin_gate_capable") \
                        and not foldin_leg.get("foldin_overhead_ok"):
                    # shared-core hosts record the ratio but skip the
                    # gate (foldin_gate_capable False says why)
                    failures.append(
                        "fold-in-on serve p99 "
                        f"({foldin_leg['foldin_on']['p99_ms']} ms) "
                        "exceeds worker-off "
                        f"({foldin_leg['foldin_off']['p99_ms']} ms) "
                        "by >5% with BENCH_STRICT_EXTRAS=1")
                if not foldin_leg.get("foldin_freshness_ok"):
                    failures.append(
                        "fold-in freshness p99 "
                        f"({foldin_leg['foldin_freshness_p99_s']} s) "
                        "over the 2 s contract with BENCH_STRICT_EXTRAS=1")
                drift = foldin_leg.get("foldin_drift")
                if drift and not drift.get("ok", True):
                    failures.append(
                        "fold-in drift probe FAILED (published rows "
                        "diverge from a fresh half-step) with "
                        "BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and shard_leg:
            if shard_leg.get("serve_sharded_error"):
                failures.append(
                    f"sharded-serving leg crashed "
                    f"({shard_leg['serve_sharded_error']}) with "
                    "BENCH_STRICT_EXTRAS=1")
            else:
                if not shard_leg.get("serve_sharded_parity_ok"):
                    failures.append(
                        "sharded and replicated servers returned "
                        "DIFFERENT bytes for the same probe queries "
                        "(bit-parity contract broken) with "
                        "BENCH_STRICT_EXTRAS=1")
                if not shard_leg.get("serve_sharded_overhead_ok"):
                    failures.append(
                        "sharded-on p99 "
                        f"({shard_leg['serve_sharded_on']['p99_ms']} ms) "
                        "exceeds replicated "
                        f"({shard_leg['serve_sharded_off']['p99_ms']} ms) "
                        "by >10% with BENCH_STRICT_EXTRAS=1")
                ceiling = shard_leg.get("serve_sharded_hbm_ceiling") or {}
                if (not ceiling.get("skipped")
                        and not ceiling.get("sharded_served_ok")):
                    failures.append(
                        "HBM-ceiling leg: the oversized factor matrix "
                        "did not serve in sharded mode with "
                        "BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and quant_leg:
            if quant_leg.get("serve_quant_error"):
                failures.append(
                    f"quantized-serving leg crashed "
                    f"({quant_leg['serve_quant_error']}) with "
                    "BENCH_STRICT_EXTRAS=1")
            elif not quant_leg.get("serve_quant_active"):
                failures.append(
                    "serve-quant=on deploy fell back to fp32 (the "
                    "quantized layout or its recall probe failed) with "
                    "BENCH_STRICT_EXTRAS=1")
            else:
                if not quant_leg.get("serve_quant_recall_ok"):
                    failures.append(
                        "quantized serving recall@k "
                        f"({quant_leg.get('serve_quant_recall')}) below "
                        "the 0.99 ranking-parity contract with "
                        "BENCH_STRICT_EXTRAS=1")
                if not quant_leg.get("serve_quant_p99_ok"):
                    failures.append(
                        "quantized p99 "
                        f"({quant_leg['serve_quant_on']['p99_ms']} ms) "
                        "exceeds the fp32 path "
                        f"({quant_leg['serve_quant_off']['p99_ms']} ms) "
                        "with BENCH_STRICT_EXTRAS=1")
                if not quant_leg.get("serve_quant_hbm_ok"):
                    failures.append(
                        "quantized factor matrices measure "
                        f"{quant_leg.get('serve_quant_hbm_ratio')}x the "
                        "fp32 HBM bytes (> 0.30) with "
                        "BENCH_STRICT_EXTRAS=1")
                ceiling = quant_leg.get("serve_quant_hbm_ceiling") or {}
                if (not ceiling.get("skipped")
                        and not ceiling.get("quant_sharded_served_ok")):
                    failures.append(
                        "quantized HBM-ceiling leg: the 3.5x catalog "
                        "did not serve int8-sharded with "
                        "BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and router_leg:
            if router_leg.get("router_error"):
                failures.append(
                    f"router leg crashed ({router_leg['router_error']}) "
                    "with BENCH_STRICT_EXTRAS=1")
            elif router_leg.get("router_gate_capable"):
                # shared-core hosts record the numbers but skip the
                # gates (router_gate_capable False says why)
                if not router_leg.get("router_added_p99_ok"):
                    failures.append(
                        "router added-latency p99 "
                        f"({router_leg.get('router_added_p99_ms')} ms) "
                        "over the 1 ms front-door budget with "
                        "BENCH_STRICT_EXTRAS=1")
                if not router_leg.get("router_scaling_ok"):
                    failures.append(
                        "router 1->2 replica QPS scaling "
                        f"({router_leg.get('router_qps_scaling_2')}x) "
                        "below 1.6x with BENCH_STRICT_EXTRAS=1")
        if (os.environ.get("BENCH_STRICT_EXTRAS") == "1"
                and partition_leg):
            if partition_leg.get("router_partition_error"):
                failures.append(
                    "router partition leg crashed "
                    f"({partition_leg['router_partition_error']}) with "
                    "BENCH_STRICT_EXTRAS=1")
            else:
                # wire bit-parity is deterministic (same merge as the
                # device all-gather path) — gated on EVERY host
                if not partition_leg.get("router_partition_parity_ok"):
                    failures.append(
                        "partition-routed wire answers diverged from "
                        "the full replica on "
                        f"{partition_leg.get('router_partition_parity_mismatches')}"
                        " queries with BENCH_STRICT_EXTRAS=1")
                if not partition_leg.get(
                        "router_partition_each_fits_budget"):
                    failures.append(
                        "partition replicas did not fit the demo HBM "
                        "budget that the full model exceeds with "
                        "BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and cache_leg:
            if cache_leg.get("router_cache_error"):
                failures.append(
                    "router cache leg crashed "
                    f"({cache_leg['router_cache_error']}) with "
                    "BENCH_STRICT_EXTRAS=1")
            else:
                # zipfian traffic must hit a warm cache on any host;
                # the latency win is only gated where cores are real
                if not cache_leg.get("router_cache_hit_ratio_ok"):
                    failures.append(
                        "router response cache hit ratio "
                        f"({cache_leg.get('router_cache_hit_ratio')}) "
                        "was 0 under zipfian keys with "
                        "BENCH_STRICT_EXTRAS=1")
                if (cache_leg.get("router_cache_gate_capable")
                        and not cache_leg.get("router_cache_p99_ok")):
                    failures.append(
                        "cached p99 "
                        f"({cache_leg.get('router_cache_p99_ms')} ms) "
                        "did not beat uncached p99 "
                        f"({cache_leg.get('router_uncached_p99_ms')} ms)"
                        " with BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and autopilot_leg:
            if autopilot_leg.get("autopilot_error"):
                failures.append(
                    "autopilot leg crashed "
                    f"({autopilot_leg['autopilot_error']}) with "
                    "BENCH_STRICT_EXTRAS=1")
            else:
                # the ladder is in-process arithmetic: widen + exact
                # restore must hold on any host
                if not autopilot_leg.get("autopilot_ladder_ok"):
                    failures.append(
                        "autopilot burn ladder did not widen and "
                        "exactly restore (widened="
                        f"{autopilot_leg.get('autopilot_ladder_widened')}"
                        ", restored="
                        f"{autopilot_leg.get('autopilot_ladder_restored')}"
                        ") with BENCH_STRICT_EXTRAS=1")
                # recovery timing + zero-failure burst only where a
                # replica subprocess can cold-start off the burst's CPUs
                if autopilot_leg.get("autopilot_gate_capable"):
                    rec = autopilot_leg.get("autopilot_recovery_s")
                    if rec is None or rec > 120.0:
                        failures.append(
                            "autopilot did not recover the fleet "
                            f"within 120 s (recovery_s={rec}) after a "
                            "replica kill with BENCH_STRICT_EXTRAS=1")
                    if not autopilot_leg.get("autopilot_zero_failures"):
                        failures.append(
                            "client burst saw failures during the "
                            "autopilot chaos leg ("
                            f"{autopilot_leg.get('autopilot_burst_error')}"
                            ") with BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and autotrain_leg:
            if autotrain_leg.get("autotrain_error"):
                failures.append(
                    "autotrain leg crashed "
                    f"({autotrain_leg['autotrain_error']}) with "
                    "BENCH_STRICT_EXTRAS=1")
            else:
                # the reject verdict is in-process arithmetic — gated
                # on every host: a seeded provably-worse candidate
                # must never reach the serving path
                if not autotrain_leg.get("autotrain_reject_ok"):
                    failures.append(
                        "autotrain validation did not reject the "
                        "seeded-worse candidate and keep the prior "
                        "generation serving (rejected="
                        f"{autotrain_leg.get('autotrain_candidates_rejected')}"
                        ") with BENCH_STRICT_EXTRAS=1")
                # the full live cycle needs cores for the retrain to
                # run off the burst's CPUs (autotrain_gate_capable
                # False says why the gate is skipped)
                if autotrain_leg.get("autotrain_gate_capable"):
                    if not autotrain_leg.get("autotrain_published"):
                        failures.append(
                            "autotrain did not publish a validated "
                            "candidate within the leg deadline "
                            "(cycle_s="
                            f"{autotrain_leg.get('autotrain_cycle_s')}"
                            ") with BENCH_STRICT_EXTRAS=1")
                    if not autotrain_leg.get("autotrain_zero_drops"):
                        failures.append(
                            "client burst saw dropped queries during "
                            "the autotrain publish cycle ("
                            f"{autotrain_leg.get('autotrain_burst_error')}"
                            ") with BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and mt_leg:
            if mt_leg.get("multitenant_error"):
                failures.append(
                    "multi-tenant leg crashed "
                    f"({mt_leg['multitenant_error']}) with "
                    "BENCH_STRICT_EXTRAS=1")
            else:
                # compile flatness is deterministic — gated on EVERY
                # host: a 4-tenant deploy compiling more programs than
                # a 1-tenant deploy means the shared-AOT memo broke
                if not mt_leg.get("mt_compile_flat_ok"):
                    failures.append(
                        "shared-AOT compile count grew with tenant "
                        f"count ({mt_leg.get('mt_compile_count_4t')} "
                        f"programs at 4 tenants vs "
                        f"{mt_leg.get('mt_compile_count_1t')} at 1) "
                        "with BENCH_STRICT_EXTRAS=1")
                # isolation needs real cores for the flooders
                # (mt_gate_capable False says why the gate is skipped)
                if mt_leg.get("mt_gate_capable") \
                        and not mt_leg.get("mt_isolation_ok"):
                    failures.append(
                        "noisy-neighbor isolation: tenant B p99 grew "
                        f"{mt_leg.get('mt_isolation_p99_ratio')}x "
                        "under tenant A's flood (> 3x) with "
                        "BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and stream_leg:
            if stream_leg.get("train_stream_error"):
                failures.append(
                    "train-stream leg crashed "
                    f"({stream_leg['train_stream_error']}) with "
                    "BENCH_STRICT_EXTRAS=1")
            else:
                if not stream_leg.get("train_stream_bitparity_ok"):
                    failures.append(
                        "streamed and in-core trains produced DIFFERENT "
                        "model checksums (bit-parity contract broken) "
                        "with BENCH_STRICT_EXTRAS=1")
                if not stream_leg.get("train_stream_rate_ok"):
                    failures.append(
                        "streamed training pipeline rate is "
                        f"{stream_leg.get('train_stream_rate_ratio')}x "
                        "in-core (< 0.85) with BENCH_STRICT_EXTRAS=1")
                if not stream_leg.get("train_stream_rss_ok"):
                    failures.append(
                        "streamed training peak pipeline RSS "
                        f"({stream_leg.get('train_stream_peak_pipeline_mb')}"
                        " MB) exceeds the in-core leg by >10% with "
                        "BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and \
                recompile_watch is not None:
            if recompile_watch.get("recompile_watch_error"):
                failures.append(
                    "recompile-watchdog leg crashed "
                    f"({recompile_watch['recompile_watch_error']}) with "
                    "BENCH_STRICT_EXTRAS=1")
            elif recompile_watch.get("serve_post_warmup_recompiles", 0):
                failures.append(
                    f"{recompile_watch['serve_post_warmup_recompiles']} "
                    "post-warmup XLA recompiles on the serving path "
                    "(padding buckets not holding) with "
                    "BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and \
                ttr_leg is not None:
            if ttr_leg.get("time_to_ready_error"):
                failures.append(
                    "time-to-ready leg crashed "
                    f"({ttr_leg['time_to_ready_error']}) with "
                    "BENCH_STRICT_EXTRAS=1")
            else:
                if ttr_leg.get("aot_failed"):
                    failures.append(
                        f"{ttr_leg['aot_failed']} AOT program build(s) "
                        "failed at deploy with BENCH_STRICT_EXTRAS=1")
                # the warm-replica availability contract (< 10 s): only
                # a warm-cache round is accountable — a cold cache
                # legitimately pays full compiles, like warmup_compile_s
                if (cache_before["entries"] > 0
                        and ttr_leg.get("time_to_ready_s", 0.0) >= 10.0):
                    failures.append(
                        f"warm-cache time_to_ready_s "
                        f"{ttr_leg['time_to_ready_s']:g} breaches the "
                        "10 s warm-replica gate with "
                        "BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and lint_leg:
            if lint_leg.get("lint_error"):
                failures.append(
                    f"pio lint crashed ({lint_leg['lint_error']}) with "
                    "BENCH_STRICT_EXTRAS=1")
            elif lint_leg.get("lint_exit", 0) != 0:
                failures.append(
                    f"pio lint: {lint_leg.get('lint_findings_total', '?')} "
                    "active finding(s) "
                    f"(rules: {lint_leg.get('lint_rules_fired')}) — fix "
                    "them or accept them into conf/lint_baseline.json "
                    "with a reason, with BENCH_STRICT_EXTRAS=1")
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and trend_failures:
            failures.append(
                "bench trajectory regression vs best prior round: "
                + "; ".join(trend_failures))
        if os.environ.get("BENCH_STRICT_EXTRAS") == "1" and (
                eval_grid or {}).get("eval_error"):
            # by default a crashed eval leg records eval_error and the run
            # still exits 0 (extras must not sink the headline); under
            # BENCH_STRICT_EXTRAS=1 the ordering gate is genuinely hard —
            # a crash can no longer downgrade it to a silent skip
            failures.append(
                f"eval grid crashed ({eval_grid['eval_error']}) with "
                "BENCH_STRICT_EXTRAS=1")
        if failures:
            print("BENCH FAILED: " + "; ".join(failures), file=sys.stderr)
            sys.exit(1)
    finally:
        try:
            storage.get_events().close()   # flush before the dir vanishes
        except Exception:
            pass
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    main()
