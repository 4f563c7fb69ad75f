#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # one chip: train -> deploy -> queries
    python3 chip_smoke.py --chips 4  # the four-device train and serve only

Drives the main path once through the entry points a user calls, at the
full width of the model the repo exists for: the recommendation
engine's explicit ALS from the checked-in engine.json (rank 10, 10
iterations) on an ML-20M-shaped log (`pio train --synthetic 20000000`:
~138k users x ~27k items, seeded, no download), then `pio deploy` and
`POST /queries.json`, checked against plain NumPy on the persisted
factors.

One process per chip. THIS process never imports jax or anything under
predictionio_tpu (it is subprocess + urllib only, and checks
sys.modules before it reports); its children hold the chip one after
another:

  train      this file with --role train: refuses a CPU backend, prints
             native.available(), then runs cli.main(["train", ...]) —
             what `python -m predictionio_tpu.tools.cli train` runs —
             and prints the phases' and the compiler's seconds
  reference  this file with --role reference under JAX_PLATFORMS=cpu,
             never the chip: finite factors, training RMSE against the
             global-mean predictor on the same seeded ratings, and the
             fp32 top-10 of the queried users, all plain NumPy
  deploy     python -m predictionio_tpu.tools.cli deploy --telemetry,
             queried over HTTP and terminated; once more with
             --serve-quant on when the default deploy refused int8

Any failed phase exits non-zero; nothing is caught into a field. The
last line of stdout is the one JSON object the driver reads and carries
nothing else; everything to read is on the lines above it. Without an
accelerator (or without the repo beside this file) it exits non-zero
and prints no result.

CHIP_SMOKE_REHEARSE=<n_events> rehearses the control flow on whatever
backend is there at that size; it never prints a result and exits 3.
"""

import argparse
import concurrent.futures
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
ENGINE_JSON = os.path.join(HERE, "predictionio_tpu", "models",
                           "recommendation", "engine.json")
WORK = os.path.join(HERE, "chiprun_out", "chip_smoke")
N_EVENTS = 20_000_000
SEED = 7                      # data/synthetic.py's default, named here
K = 10
N_KNOWN = 64                  # one full batcher bucket of distinct users
UNKNOWN_USER = "nobody-ever-rated"
READY_TIMEOUT_S = 600
REHEARSE = int(os.environ.get("CHIP_SMOKE_REHEARSE", "0") or 0)
# `pio train --devices 4` against plain `pio train`, one seed: RMSE
# between what the two models predict for the 20 M training ratings, in
# rating stars. Not the same kernel: `--synthetic` is a streamed read,
# and over a mesh a streamed read trains with the csrb kernel (the
# hybrid kernel's dense-hot prep needs a host copy of the ratings that a
# streamed read never makes; parallel/als_dist.py _train_sharded), while
# one device trains with the default hybrid kernel, whose dense block is
# bf16. Measured on four chips: 0.0114 on the training ratings, training
# RMSEs 3.6e-5 apart. (A one-device csrb train would separate mesh from
# kernel, but its layout programs take the TPU compiler 460 s per side
# while four chips are held.) A shard that lost its rows would show as
# more than a star. Random (user, item) pairs are reported, not held to
# this: a user with 5-20 ratings barely determines ten factors at
# lambda 0.01, and there the bf16 rounding alone moves a prediction by
# 0.09 stars (0.077 over all random pairs on the chip).
FACTOR_RMSE_TOL = 0.05
LAYOUT_INT8 = "replicated int8"


def say(tag, **fields):
    print(json.dumps({"phase": tag, **fields}), flush=True)


def fail(msg):
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def cache_dir():
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(HERE, ".jax_cache"))


def cache_stats():
    d = cache_dir()
    if not os.path.isdir(d):
        return {"entries": 0, "bytes": 0}
    files = [os.path.join(d, f) for f in os.listdir(d)]
    return {"entries": len(files),
            "bytes": sum(os.path.getsize(f) for f in files
                         if os.path.isfile(f))}


def child_env(store, **extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["PIO_FS_BASEDIR"] = store
    env.update(extra)
    return env


def run_child(tag, argv, env):
    """Run one child to its end; its stdout lines that are JSON objects
    come back as a list. A non-zero exit fails the smoke."""
    before = cache_stats()
    t0 = time.perf_counter()
    proc = subprocess.run(argv, env=env, cwd=WORK, stdout=subprocess.PIPE,
                          text=True)
    sys.stdout.write(proc.stdout)
    sys.stdout.flush()
    after = cache_stats()
    say(f"{tag}:child", rc=proc.returncode,
        seconds=round(time.perf_counter() - t0, 2),
        compile_cache={"dir": cache_dir(), "before": before, "after": after})
    if proc.returncode != 0:
        fail(f"{tag} child exited {proc.returncode}")
    out = []
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


def engine_dir(name):
    d = os.path.join(WORK, name)
    os.makedirs(d, exist_ok=True)
    shutil.copy(ENGINE_JSON, os.path.join(d, "engine.json"))
    return d


# ---------------------------------------------------------------------------
# children (the only code here that imports jax / predictionio_tpu)
# ---------------------------------------------------------------------------

def role_train(args):
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform == "cpu" and not REHEARSE:
        print("chip_smoke: JAX found no accelerator (platform cpu)",
              file=sys.stderr)
        return 2
    from predictionio_tpu import native
    from predictionio_tpu.common import devicewatch, telemetry
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.tools import cli

    say("train:start", platform=platform, kind=devs[0].device_kind,
        count=len(devs), native_available=bool(native.available()))
    argv = ["train", "--engine-dir", args.engine_dir, "--synthetic",
            str(args.events), "--synthetic-seed", str(SEED), "--telemetry"]
    if args.devices:
        argv += ["--devices", str(args.devices)]
    rc = cli.main(argv)
    if rc != 0:
        return rc
    rows = [i for i in get_storage().get_meta_data_engine_instances()
            .get_all() if i.status == "COMPLETED"]
    assert len(rows) == 1, [(i.id, i.status) for i in rows]
    phases = {k[len("phase_"):-len("_s")]: float(v)
              for k, v in rows[0].runtime_conf.items()
              if k.startswith("phase_")}
    compile_s = sum(
        float(line.rsplit(" ", 1)[1])
        for line in telemetry.registry().exposition().splitlines()
        if line.startswith("pio_xla_compile_seconds_sum"))
    say("train:done", instance=rows[0].id, phase_seconds=phases,
        compiles=devicewatch.compiles_total(),
        compile_seconds=round(compile_s, 3),
        # the train phase holds the trainer's compile and its one-time
        # layout prep besides the iterations; the compiler's seconds
        # above are summed over every phase
        devices_used=args.devices or 1)
    return 0


def _load_model(store):
    import numpy as np

    os.environ["PIO_FS_BASEDIR"] = store
    from predictionio_tpu.data import storage
    from predictionio_tpu.workflow import model_io

    storage.reset_storage()
    st = storage.get_storage()
    rows = [i for i in st.get_meta_data_engine_instances().get_all()
            if i.status == "COMPLETED"]
    assert len(rows) == 1, [(i.id, i.status) for i in rows]
    model = model_io.deserialize_models(
        st.get_model_data_models().get(rows[0].id).models)[0]
    return (model, np.asarray(model.user_factors, np.float32),
            np.asarray(model.item_factors, np.float32))


def _on_training_ratings(model, n_events, *factors):
    """On the seeded ratings, regenerated chunk by chunk, NumPy only:
    the training RMSE of each (U, V) given, the global-mean predictor's
    RMSE, and the RMSE between the first two models' predictions."""
    import numpy as np

    from predictionio_tpu.data import synthetic

    src = synthetic.chunk_source(n_events, seed=SEED)
    uix = np.full(src.cfg.n_users, -1, np.int64)
    iix = np.full(src.cfg.n_items, -1, np.int64)
    for name, ix in model.user_vocab.to_dict().items():
        uix[int(name[1:])] = ix
    for name, ix in model.item_vocab.to_dict().items():
        iix[int(name[1:])] = ix
    n = s1 = s2 = between = 0.0
    se = [0.0] * len(factors)
    for c in range(src.n_chunks):
        u, i, r = src.chunk_codes(c)
        assert (uix[u] >= 0).all() and (iix[i] >= 0).all()
        r = r.astype(np.float64)
        preds = [np.einsum("nr,nr->n", U[uix[u]], V[iix[i]],
                           dtype=np.float64) for U, V in factors]
        n += r.size
        s1 += r.sum()
        s2 += (r * r).sum()
        for j, pred in enumerate(preds):
            se[j] += ((pred - r) ** 2).sum()
        if len(preds) > 1:
            between += ((preds[0] - preds[1]) ** 2).sum()
    mean = s1 / n
    return ([float(np.sqrt(x / n)) for x in se],
            float(np.sqrt(s2 / n - mean * mean)),
            float(np.sqrt(between / n)))


def role_reference(args):
    import numpy as np

    from predictionio_tpu.ops import quant
    from predictionio_tpu.parallel import serve_dist

    model, U, V = _load_model(args.store)
    factors = [(U, V)]
    if args.other_store:
        m2, U2, V2 = _load_model(args.other_store)
        assert U2.shape == U.shape and V2.shape == V.shape
        # compared by index: both reads must number the names alike
        assert m2.user_vocab.to_dict() == model.user_vocab.to_dict()
        assert m2.item_vocab.to_dict() == model.item_vocab.to_dict()
        factors.append((U2, V2))
    rmses, gm_rmse, between = _on_training_ratings(
        model, args.events, *factors)
    # the users to query: the heaviest raters first, then evenly spread
    # over the vocabulary down to the one-rating tail
    names = model.user_vocab.to_dict()
    by_ix = sorted(names, key=names.get)
    picks = sorted({*range(8), *np.linspace(
        0, len(by_ix) - 1, N_KNOWN).astype(int).tolist()})[:N_KNOWN]
    users = [by_ix[p] for p in picks]
    inv = model.item_vocab.inverse()

    def int8_rows(M):
        # symmetric per-row int8, written out here so that the int8
        # reference shares no code with ops/quant.py
        amax = np.abs(M).max(axis=1)
        scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        return (np.clip(np.rint(M / scale[:, None]), -127, 127)
                .astype(np.int32), scale)

    (Uq, su), (Vq, sv) = int8_rows(U), int8_rows(V)
    top, top_int8 = {}, {}
    for name in users:
        ix = names[name]
        for out, scores in (
                (top, V @ U[ix]),
                (top_int8, (Vq @ Uq[ix]).astype(np.float32)
                 * (su[ix] * sv))):
            order = np.argsort(-scores, kind="stable")[:K]
            out[name] = [[inv(int(i)), float(scores[i])] for i in order]
    ref = {"users": users, "top": top, "top_int8": top_int8, "k": K,
           "recall_floor": quant.recall_floor(),
           "score_rtol": serve_dist.SCORE_RTOL,
           "score_atol": serve_dist.SCORE_ATOL}
    if args.other_store:
        # four devices against one, same seed: compared on what the
        # factors predict, since (U, V) is only unique up to a rotation
        rng = np.random.default_rng(SEED)
        us = rng.integers(0, U.shape[0], 200_000)
        it = rng.integers(0, V.shape[0], 200_000)
        pa = np.einsum("nr,nr->n", U[us], V[it], dtype=np.float64)
        pb = np.einsum("nr,nr->n", U2[us], V2[it], dtype=np.float64)
        ref["factor_check"] = {
            "finite_other": bool(np.isfinite(U2).all()
                                 and np.isfinite(V2).all()),
            "train_rmse_other": rmses[1],
            "prediction_rmse_between": between,
            "on_random_pairs": float(np.sqrt(((pa - pb) ** 2).mean()))}
    with open(args.out, "w") as f:
        json.dump(ref, f)
    say("reference", n_users=int(U.shape[0]), n_items=int(V.shape[0]),
        rank=int(U.shape[1]),
        finite=bool(np.isfinite(U).all() and np.isfinite(V).all()),
        train_rmse=rmses[0], global_mean_rmse=gm_rmse, users=len(users),
        factor_check=ref.get("factor_check"))
    return 0


# ---------------------------------------------------------------------------
# the deploy child, driven over HTTP
# ---------------------------------------------------------------------------

def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(port, path, body=None, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read().decode()


class Deploy:
    """`pio deploy` as a child process, terminated on exit."""

    def __init__(self, tag, store, edir, *flags, **env):
        self.tag, self.port = tag, free_port()
        self.log = os.path.join(WORK, f"{tag}.log")
        self.before = cache_stats()
        self.t0 = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "predictionio_tpu.tools.cli", "deploy",
             "--engine-dir", edir, "--telemetry", "--ip", "127.0.0.1",
             "--port", str(self.port), *flags],
            env=child_env(store, **env), cwd=WORK,
            stdout=open(self.log, "w"), stderr=subprocess.STDOUT)

    def __enter__(self):
        deadline = self.t0 + READY_TIMEOUT_S
        while True:
            if self.proc.poll() is not None:
                self.tail()
                fail(f"{self.tag}: deploy exited {self.proc.returncode} "
                     "before /readyz")
            try:
                ready = json.loads(http(self.port, "/readyz", timeout=5))
                if ready.get("status") == "ready":
                    break
            except (urllib.error.URLError, OSError):
                pass
            if time.perf_counter() > deadline:
                self.stop()
                self.tail()
                fail(f"{self.tag}: not ready in {READY_TIMEOUT_S}s")
            time.sleep(0.5)
        say(f"{self.tag}:ready",
            seconds=round(time.perf_counter() - self.t0, 2))
        return self

    def tail(self):
        with open(self.log) as f:
            sys.stderr.write("".join(f.readlines()[-40:]))

    def stop(self):
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(30)

    def __exit__(self, *exc):
        self.stop()
        say(f"{self.tag}:child", rc=self.proc.returncode,
            seconds=round(time.perf_counter() - self.t0, 2),
            compile_cache={"dir": cache_dir(), "before": self.before,
                           "after": cache_stats()})

    def get(self, path):
        return json.loads(http(self.port, path))

    def query(self, user):
        body = json.loads(http(self.port, "/queries.json",
                               {"user": user, "num": K}))
        return [(s["item"], s["score"]) for s in body["itemScores"]]


def inspect_deploy(dep, want_shards, fp32_bytes):
    """The device and the serving layout, from the server's own status
    pages; fails unless the factors are on the accelerator and every
    AOT program built."""
    dev = dep.get("/debug/device.json")
    if not dev.get("telemetry"):
        fail("telemetry is off, says /debug/device.json")
    devices = dev["devices"]
    platform, kind = devices[0]["platform"], devices[0]["kind"]
    if platform == "cpu" and not REHEARSE:
        fail(f"the deploy serves from platform {platform!r}, not a chip")
    info = dep.get("/")
    aot = info.get("aot") or {}
    if not aot.get("programs") or aot.get("failed"):
        fail(f"AOT prebuild: {aot}")
    metrics = http(dep.port, "/metrics")
    if 'pio_aot_programs_total{status="failed"}' in metrics:
        fail("pio_aot_programs_total counts a failed program")
    if f"pio_compile_cache_entries {cache_stats()['entries']}" not in metrics:
        fail("the deploy's compile cache is not " + cache_dir())
    quant, shard = info.get("quant") or {}, info.get("sharding") or {}
    if shard.get("enabled"):
        layout = (f"row-sharded x{shard['shards']} "
                  f"{shard.get('dtype', 'float32')}")
    elif quant.get("enabled"):
        layout = LAYOUT_INT8
    else:
        layout = "replicated fp32 device arrays"
    if bool(shard.get("enabled")) != bool(want_shards):
        fail(f"expected sharded={want_shards}, the deploy chose {layout}")
    # device-resident, never host numpy: a host-serving model has no
    # device program to prebuild and no factor bytes among live arrays
    live = dev["liveArrays"]["bytes"]
    floor = quant["int8Bytes"] if quant.get("enabled") else fp32_bytes
    if not shard.get("enabled") and live < floor:
        fail(f"{live} live device bytes < the factors' {floor}")
    mem = [d["memoryStats"] for d in devices]
    say(f"{dep.tag}:layout", layout=layout, platform=platform, kind=kind,
        count=len(devices), aot=aot, quant=quant or None,
        sharding=shard or None, live_array_bytes=live,
        hbm_bytes_in_use=[m and m.get("bytes_in_use") for m in mem],
        memory_stats_keys=sorted(mem[0]) if mem[0] else None)
    if want_shards and platform != "cpu":   # the CPU reports no stats
        per_shard = shard["perShardFactorBytes"]
        for d, m in zip(devices, mem):
            if not m or m.get("bytes_in_use", 0) < per_shard:
                fail(f"device {d['id']} holds {m and m.get('bytes_in_use')}"
                     f" bytes, less than its shard's {per_shard}")
    return {"platform": platform, "kind": kind, "count": len(devices)}, layout


def same_answer(a, b, ref):
    """Identical ranking, ties included, and scores within the named
    float32 tolerance (programs of different batch size may order a
    dot product's additions differently)."""
    return ([i for i, _s in a] == [i for i, _s in b] and all(
        abs(x - y) <= ref["score_atol"] + ref["score_rtol"] * abs(y)
        for (_i, x), (_j, y) in zip(a, b)))


def serve_queries(dep, ref):
    """Singles, the unknown user, then a concurrent burst."""
    users = ref["users"]
    served = {u: dep.query(u) for u in users[:5]}
    if dep.query(UNKNOWN_USER) != []:
        fail("the unknown user did not come back empty")
    with concurrent.futures.ThreadPoolExecutor(N_KNOWN) as pool:
        burst = list(pool.map(dep.query, users * 2))
    for u, got in zip(users * 2, burst):
        if not same_answer(served.setdefault(u, got), got, ref):
            fail(f"user {u}: the burst answered {got}, earlier "
                 f"{served[u]}")
    b = dep.get("/")["batching"]
    say(f"{dep.tag}:queries", singles=6, burst=len(burst),
        batches=b["batches"], batch_size_hist=b["batchSizeHist"],
        bucket_hist=b["bucketHist"], rejected=b["rejected"])
    if max(map(int, b["batchSizeHist"])) < 2:
        fail("the burst never shared a flush: the batcher did not batch")
    return served


def check_recall(tag, served, ref, against="top"):
    hit = total = 0
    for u in ref["users"]:
        want = [i for i, _s in ref[against][u]]
        got = [i for i, _s in served[u]]
        if len(got) != len(want):
            fail(f"user {u}: {len(got)} items served, {len(want)} expected")
        hit += len(set(want) & set(got))
        total += len(want)
    recall = hit / total
    say(f"{tag}:recall", reference=f"NumPy {against}",
        users=len(ref["users"]), k=ref["k"], recall=recall,
        floor=ref["recall_floor"])
    if recall < ref["recall_floor"]:
        fail(f"{tag}: served top-{ref['k']} recall {recall} against the "
             f"NumPy reference ({against}) is under the floor "
             f"{ref['recall_floor']}")


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------

def train(tag, store, edir, devices=0, **env):
    out = run_child(tag, [
        sys.executable, os.path.abspath(__file__), "--role", "train",
        "--engine-dir", edir, "--events", str(REHEARSE or N_EVENTS),
        "--devices", str(devices)], child_env(store, **env))
    return out[0]


def reference(store, other_store=""):
    out_path = os.path.join(WORK, "reference.json")
    line = run_child("reference", [
        sys.executable, os.path.abspath(__file__), "--role", "reference",
        "--store", store, "--other-store", other_store, "--out", out_path,
        "--events", str(REHEARSE or N_EVENTS)],
        child_env(store, JAX_PLATFORMS="cpu"))[-1]
    if not line["finite"]:
        fail("the trained factors are not all finite")
    if not line["train_rmse"] < line["global_mean_rmse"]:
        fail(f"training RMSE {line['train_rmse']} does not beat the "
             f"global-mean predictor's {line['global_mean_rmse']}")
    with open(out_path) as f:
        return json.load(f), line


def _fp32_bytes(line):
    return (line["n_users"] + line["n_items"]) * line["rank"] * 4


def one_chip():
    store, edir = os.path.join(WORK, "store"), engine_dir("engine")
    started = train("train", store, edir)
    ref, line = reference(store)
    with Deploy("deploy", store, edir) as dep:
        device, layout = inspect_deploy(dep, False, _fp32_bytes(line))
        served = serve_queries(dep, ref)
    int8 = layout == LAYOUT_INT8
    check_recall("deploy", served, ref, "top_int8" if int8 else "top")
    if not int8:
        # the default deploy refused int8 (its recall probe on this
        # model's near-tied scores), so the other device-resident
        # layout of the default path has not touched the chip yet:
        # serve it once, held to NumPy int8 arithmetic
        with Deploy("deploy-int8", store, edir,
                    "--serve-quant", "on") as dep:
            _dev, layout = inspect_deploy(dep, False, _fp32_bytes(line))
            if layout != LAYOUT_INT8:
                fail(f"--serve-quant on served {layout}")
            served = serve_queries(dep, ref)
        check_recall("deploy-int8", served, ref, "top_int8")
    if device["platform"] != started["platform"]:
        fail(f"train ran on {started['platform']}, deploy on {device}")
    return device


def four_chips():
    """Only what exists across chips, and what it is compared with:
    `pio train --devices 4` against the one-device train from the same
    seed, and row-sharded serving against the one-device deploy of the
    same model."""
    store4, store1 = os.path.join(WORK, "store4"), os.path.join(WORK, "store1")
    edir = engine_dir("engine")
    started = train("train4", store4, edir, devices=4)
    if started["count"] != 4 and not REHEARSE:
        fail(f"--chips 4 found {started['count']} device(s)")
    train("train1", store1, edir)
    ref, line = reference(store4, other_store=store1)
    fc = line["factor_check"]
    if not fc["finite_other"]:
        fail("the one-device factors are not all finite")
    if not fc["train_rmse_other"] < line["global_mean_rmse"]:
        fail(f"the one-device training RMSE {fc['train_rmse_other']} does "
             f"not beat the global-mean predictor's")
    if not fc["prediction_rmse_between"] <= FACTOR_RMSE_TOL:
        fail(f"four-device and one-device factors predict "
             f"{fc['prediction_rmse_between']} apart (RMSE), over "
             f"{FACTOR_RMSE_TOL}")
    # on four real chips the default (auto) flips to row-sharded by
    # itself; virtual CPU devices have to be told
    flags = ("--shard-serving", "on") if REHEARSE else ()
    with Deploy("deploy-sharded", store4, edir, *flags) as dep:
        device, layout = inspect_deploy(dep, True, _fp32_bytes(line))
        sharded = serve_queries(dep, ref)
    with Deploy("deploy-one-device", store4, edir,
                "--shard-serving", "off") as dep:
        _dev, layout1 = inspect_deploy(dep, False, _fp32_bytes(line))
        single = serve_queries(dep, ref)
    for u in ref["users"]:
        if not same_answer(sharded[u], single[u], ref):
            fail(f"user {u}: sharded {sharded[u]} against one-device "
                 f"{single[u]}: the rankings differ or a score is "
                 f"outside rtol {ref['score_rtol']} atol "
                 f"{ref['score_atol']}")
    worst = max(abs(a - b) for u in ref["users"]
                for (_i, a), (_j, b) in zip(sharded[u], single[u]))
    say("sharded-vs-one-device", served_by=layout, compared_with=layout1,
        users=len(ref["users"]), rankings="identical",
        max_abs_score_diff=worst, rtol=ref["score_rtol"],
        atol=ref["score_atol"])
    return device


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--role", choices=("train", "reference"),
                    help=argparse.SUPPRESS)
    for name in ("--engine-dir", "--store", "--other-store", "--out"):
        ap.add_argument(name, default="", help=argparse.SUPPRESS)
    for name in ("--events", "--devices"):
        ap.add_argument(name, type=int, default=0, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.role:
        return {"train": role_train, "reference": role_reference}[
            args.role](args)

    if not os.path.isfile(ENGINE_JSON):
        fail(f"{ENGINE_JSON} is missing: the repo is not beside this file")
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    t0 = time.perf_counter()
    device = four_chips() if args.chips == 4 else one_chip()
    leaked = sorted(m for m in sys.modules
                    if m.split(".")[0] in ("jax", "jaxlib",
                                           "predictionio_tpu"))
    if leaked:
        fail(f"the parent imported {leaked[:5]}")
    say("done", seconds=round(time.perf_counter() - t0, 2), chips=args.chips)
    if REHEARSE:
        print("chip_smoke: rehearsal only, no result", file=sys.stderr)
        return 3
    if device["platform"] == "cpu" or device["count"] != args.chips:
        fail(f"ran on {device}, wanted {args.chips} accelerator chip(s)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
