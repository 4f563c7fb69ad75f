"""The child that holds the chip for a serving cell: has the
configuration's adapter make the model from the seed (and, where the
deployment reads the event store while it serves, write its events),
writes it as the completed engine instance a `pio train` would have
left (the program's own model_io and storage calls, into the in-memory
store of this process), then runs `pio deploy`'s entry point, which
serves until it is told to stop.

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
    adapter = harness.adapter_of(config)
    model = config["model"]
    if rehearse:
        model = adapter.rehearsal_model(model,
                                        spec["traffic"]["rehearse_cut"])
    t0 = time.time()

    from predictionio_tpu.data.storage import (EngineInstance, Model,
                                               get_storage)
    from predictionio_tpu.tools import cli
    from predictionio_tpu.workflow import model_io

    engine_dir = os.path.join(harness.ROOT, config["engine_dir"])
    variant = harness.load_json(engine_dir, "engine.json")
    storage = get_storage()
    models = adapter.models(config, model, args.seed, storage, variant)
    t_models = time.time()
    blob = model_io.serialize_models(models, check_finite=True)
    del models
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
    if args.fault:
        # tests only: the timed path broken underneath
        if args.fault not in adapter.FAULTS:
            harness.fail(f"adapter {config['adapter']!r} has no fault "
                         f"{args.fault!r}")
        adapter.FAULTS[args.fault]()
    threading.Thread(target=control_loop, args=(args.ctl_dir,),
                     daemon=True).start()
    t_deploy = time.time()
    with open(os.path.join(args.ctl_dir, "deploy_start.json"), "w") as f:
        json.dump({"device": device, "t_child_start": t0,
                   "models_s": t_models - t0,
                   "instance_s": t_deploy - t_models,
                   "t_deploy_start": t_deploy, "model_blob_bytes": n_blob}, f)
    return cli.main(["deploy", "--engine-dir", engine_dir, "--ip",
                     "127.0.0.1", "--port", str(args.port), "--telemetry",
                     *config["deploy_args"]])


if __name__ == "__main__":
    sys.exit(main())
