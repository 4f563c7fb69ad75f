"""The child that holds the chip for a training cell: makes the data
sets from the seed, warms up, then drives `pio train` jobs back to back
for the window, each from prepared events to a persisted model read
back. Everything it observed goes to --out as one JSON object.
"""

import argparse
import glob
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import gen_ratings
import harness
import reduce


def cached_structure(shape):
    """The fixed structure, built once per checkout (9 s at ML-20M's
    shape) and kept in the work directory: it never depends on a seed."""
    import hashlib

    key = hashlib.sha256(json.dumps(
        [shape[k] for k in ("n_users", "n_items", "nnz", "user_degree",
                            "item_degree")] + [gen_ratings.STRUCT_SEED],
        sort_keys=True).encode()).hexdigest()[:16]
    os.makedirs(harness.WORK, exist_ok=True)
    path = os.path.join(harness.WORK, f"structure-{key}.npy")
    if os.path.exists(path):
        both = np.load(path)
        return both[0], both[1]
    su, si = gen_ratings.build_structure(shape)
    tmp = path + ".tmp.npy"
    np.save(tmp, np.stack([su, si]))
    os.replace(tmp, path)
    return su, si


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--fault", default="")
    args = ap.parse_args()
    rehearse = bool(os.environ.get("BENCH_REHEARSE"))
    t0 = float(os.environ["BENCH_T0"])
    spec = harness.load_cell(args.workload)
    device = harness.device_gate(spec["cell"]["chips"], rehearse)
    config, traffic = spec["config"], spec["traffic"]
    shape = config["data"]
    if rehearse:
        shape = gen_ratings.scaled_shape(shape, traffic["rehearse_cut"])
    work = harness.work_dir(args.workload)

    from predictionio_tpu import native
    from predictionio_tpu.common import devicewatch
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.tools import cli
    from predictionio_tpu.workflow import model_io
    import bench_engine
    import jax

    harness.log("train:start", **device,
                native_available=bool(native.available()))
    structure = cached_structure(shape)
    n_made = int(traffic["warmup_jobs"]) + int(traffic["jobs_made"])
    with ThreadPoolExecutor(int(traffic["generator_threads"])) as pool:
        datasets = list(pool.map(
            lambda j: gen_ratings.make_ratings(structure, shape, args.seed, j),
            range(n_made)))
    harness.log("train:data", datasets=n_made, nnz=int(datasets[0][0].size))
    fed = datasets
    if args.fault == "half_batch":
        # tests only: the program trains on every second rating
        fed = [(u[::2], i[::2], r[::2]) for u, i, r in datasets]
    elif args.fault == "unchanged_state":
        # tests only: the trainer hands back the factors it started from
        from predictionio_tpu.ops import als

        def unchanged(data, rank=10, seed=3, **_kw):
            return als._seed_factors(int(seed), data.n_users, data.n_items,
                                     rank)

        als.train_explicit = unchanged
    elif args.fault:
        harness.fail(f"a training cell has no fault {args.fault!r}")
    bench_engine.FEED = bench_engine.Feed(
        fed, shape["n_users"], shape["n_items"])
    argv = ["train", "--engine-dir",
            os.path.join(harness.ROOT, config["engine_dir"]), "--telemetry"]
    storage = get_storage()
    seen = set()

    def one_job():
        """prepared events -> persisted model, read back. -> record"""
        index = bench_engine.FEED.taken
        with jax.profiler.TraceAnnotation(f"bench:job{index}"):
            ts = time.time()
            rc = cli.main(argv)
            if rc != 0:
                harness.fail(f"pio train exited {rc}")
            rows = [i for i in
                    storage.get_meta_data_engine_instances().get_all()
                    if i.status == "COMPLETED" and i.id not in seen]
            if len(rows) != 1:
                harness.fail(f"expected one new COMPLETED instance, got "
                             f"{[(i.id, i.status) for i in rows]}")
            seen.add(rows[0].id)
            model = model_io.deserialize_models(
                storage.get_model_data_models().get(rows[0].id).models)[0]
            te = time.time()
        phases = {k[len("phase_"):-len("_s")]: float(v)
                  for k, v in rows[0].runtime_conf.items()
                  if k.startswith("phase_")}
        return {"index": index, "dataset": index % n_made, "start": ts,
                "end": te,
                "phases": phases, "instance": rows[0].id}, model

    for _ in range(int(traffic["warmup_jobs"])):
        rec, _model = one_job()
        harness.log("train:warmup", seconds=round(rec["end"] - rec["start"], 3),
                    phases=rec["phases"], compiles=devicewatch.compiles_total())
    compiles_before = devicewatch.compiles_total()

    trace_dir = os.path.join(work, "trace")
    trace_jobs = int(traffic["trace_jobs"]) if args.trace else 0
    tracing = False
    jobs, models = [], []
    t_start = time.time()
    setup_s = t_start - t0
    while True:
        if trace_jobs and len(jobs) == int(traffic["trace_after_jobs"]):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            window_span = jax.profiler.TraceAnnotation("bench:window")
            window_span.__enter__()
            tracing = True
        rec, model = one_job()
        rec["traced"] = tracing
        jobs.append(rec)
        models.append(model)
        if tracing and sum(j["traced"] for j in jobs) == trace_jobs:
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
        if rec["end"] - t_start >= args.seconds and not tracing:
            break
    t_end = jobs[-1]["end"]
    compiles_in_window = devicewatch.compiles_total() - compiles_before
    mem_peak = harness.memory_peak_bytes()

    # the job the reference follows: drawn from the seed
    pick = int(np.random.default_rng([args.seed, 0xC0]).integers(len(jobs)))
    m = models[pick]
    np.savez(os.path.join(work, "checked_job.npz"),
             U=np.asarray(m.user_factors, np.float32),
             V=np.asarray(m.item_factors, np.float32),
             user_order=np.asarray(
                 [m.user_vocab(f"u{k}") for k in range(shape["n_users"])],
                 np.int64),
             item_order=np.asarray(
                 [m.item_vocab(f"i{k}") for k in range(shape["n_items"])],
                 np.int64))
    out = {"device": device, "setup_s": setup_s,
           "window_s": t_end - t_start, "jobs": jobs,
           "checked_job": pick, "checked_dataset": jobs[pick]["dataset"],
           "compiles_in_window": compiles_in_window,
           "memory_peak_bytes": mem_peak, "trace": None}
    if args.trace:
        paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            harness.fail("the profiler left no .xplane.pb")
        trace = reduce.read_xplane(max(paths, key=os.path.getmtime))
        trace["spans"] = phase_spans(trace["spans"], jobs, n_made)
        summary = reduce.summarize_trace(trace, unnamed_gap="between_jobs")
        out["trace"] = summary
    with open(args.out, "w") as f:
        json.dump(out, f)
    harness.log("train:done", jobs=len(jobs), window_s=out["window_s"],
                compiles_in_window=compiles_in_window)
    return 0


def phase_spans(spans, jobs, n_made):
    """The traced jobs' spans with the program's phases laid inside them
    in the order the workflow runs them (read, prepare, then train, whose
    first part is layout, then persist, then the read-back): an idle gap
    is then named by the phase it lies in. Phase seconds are the
    program's (host clock); their starts are reckoned from the job's."""
    starts = {name: s for name, s, _d in spans}
    out = list(spans)
    for j in jobs:
        at = starts.get(f"job{j['index']}")
        if at is None:
            continue
        p = j["phases"]
        for name, dur in (
                ("read+prepare", p.get("read", 0) + p.get("prepare", 0)),
                ("layout", p.get("layout", 0)),
                ("train_after_layout", p.get("train", 0) - p.get("layout", 0)),
                ("persist", p.get("persist", 0)),
                ("read_back", (j["end"] - j["start"]) - sum(
                    p.get(k, 0) for k in ("read", "prepare", "train",
                                          "persist")))):
            out.append((name, at, max(dur, 0.0)))
            at += max(dur, 0.0)
    return sorted(out, key=lambda s: s[1])


if __name__ == "__main__":
    sys.exit(main())
