"""The child that holds the chip for a serving cell: makes the factors
from the seed, writes them as the completed engine instance a `pio
train` would have left (the program's own model_io and storage calls,
into the in-memory store of this process), then runs `pio deploy`'s
entry point, which serves until it is told to stop.

A side thread answers the parent's few questions over stdin, one JSON
object a line, each reply a file in --ctl-dir: the device's peak memory,
the compile counter, and starting and stopping the profiler, which only
the process that holds the chip can do.
"""

import argparse
import datetime
import json
import os
import sys
import threading
import time

import gen_factors
import harness


def control_loop(ctl_dir):
    import jax
    from predictionio_tpu.common import devicewatch

    def reply(msg, **fields):
        tmp = os.path.join(ctl_dir, f"reply_{msg['id']}.tmp")
        with open(tmp, "w") as f:
            json.dump(fields, f)
        os.replace(tmp, os.path.join(ctl_dir, f"reply_{msg['id']}.json"))

    span = None
    for line in sys.stdin:
        msg = json.loads(line)
        cmd = msg["cmd"]
        if cmd == "stats":
            reply(msg, memory_peak_bytes=harness.memory_peak_bytes(),
                  compiles=devicewatch.compiles_total(), t=time.time())
        elif cmd == "trace_start":
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(msg["dir"], profiler_options=opts)
            span = jax.profiler.TraceAnnotation("bench:window")
            span.__enter__()
            reply(msg, t=time.time())
        elif cmd == "trace_stop":
            span.__exit__(None, None, None)
            t = time.time()
            jax.profiler.stop_trace()
            reply(msg, t=t)
        else:
            reply(msg, error=f"unknown command {cmd!r}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--ctl-dir", required=True)
    ap.add_argument("--fault", default="")
    args = ap.parse_args()
    rehearse = bool(os.environ.get("BENCH_REHEARSE"))
    spec = harness.load_cell(args.workload)
    device = harness.device_gate(spec["cell"]["chips"], rehearse)
    config = spec["config"]
    model = config["model"]
    if rehearse:
        model = gen_factors.scaled_model(model, spec["traffic"]["rehearse_cut"])
    t0 = time.time()
    nu, ni, r = model["n_users"], model["n_items"], model["rank"]
    U = gen_factors.matrix(args.seed, "user", nu, r, model["decay"])
    V = gen_factors.matrix(args.seed, "item", ni, r, model["decay"])
    t_factors = time.time()

    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.storage import (EngineInstance, Model,
                                               get_storage)
    from predictionio_tpu.models.recommendation.als_algorithm import ALSModel
    from predictionio_tpu.tools import cli
    from predictionio_tpu.workflow import model_io

    engine_dir = os.path.join(harness.ROOT, config["engine_dir"])
    variant = harness.load_json(engine_dir, "engine.json")
    als = ALSModel(rank=r, user_factors=U, item_factors=V,
                   user_vocab=BiMap({f"u{k}": k for k in range(nu)}),
                   item_vocab=BiMap({f"i{k}": k for k in range(ni)}))
    blob = model_io.serialize_models([als], check_finite=True)
    del als, U, V
    storage = get_storage()
    now = datetime.datetime.now(datetime.timezone.utc)
    instance_id = storage.get_meta_data_engine_instances().insert(
        EngineInstance(
            id="", status="COMPLETED", start_time=now, end_time=now,
            engine_id=variant["id"], engine_version="NOT_USED",
            engine_variant=variant["id"],
            engine_factory=variant["engineFactory"],
            data_source_params=json.dumps(variant.get("datasource", {})),
            preparator_params=json.dumps(variant.get("preparator", {})),
            algorithms_params=json.dumps(variant.get("algorithms", [])),
            serving_params=json.dumps(variant.get("serving", {}))))
    storage.get_model_data_models().insert(Model(id=instance_id, models=blob))
    n_blob = len(blob)
    del blob
    if args.fault == "altered_answer":
        # tests only: every answer leaves with its best item replaced
        from predictionio_tpu.models.recommendation import als_algorithm
        from predictionio_tpu.models.recommendation.engine import (
            ItemScore, PredictedResult)

        honest = als_algorithm.ALSAlgorithm.predict_batch

        def altered(self, model, queries):
            out = []
            for res in honest(self, model, queries):
                items = list(res.itemScores)
                if items:
                    items[0] = ItemScore(item="i0", score=items[0].score)
                out.append(PredictedResult(tuple(items)))
            return out

        als_algorithm.ALSAlgorithm.predict_batch = altered
    elif args.fault:
        harness.fail(f"a serving cell has no fault {args.fault!r}")
    threading.Thread(target=control_loop, args=(args.ctl_dir,),
                     daemon=True).start()
    t_deploy = time.time()
    with open(os.path.join(args.ctl_dir, "deploy_start.json"), "w") as f:
        json.dump({"device": device, "t_child_start": t0,
                   "factors_s": t_factors - t0,
                   "instance_s": t_deploy - t_factors,
                   "t_deploy_start": t_deploy, "model_blob_bytes": n_blob}, f)
    return cli.main(["deploy", "--engine-dir", engine_dir, "--ip",
                     "127.0.0.1", "--port", str(args.port), "--telemetry",
                     *config["deploy_args"]])


if __name__ == "__main__":
    sys.exit(main())
