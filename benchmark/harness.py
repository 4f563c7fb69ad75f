"""What every cell's parent and children share: where things live, the
children's environment, the device gate, the last line.

The parent (run.py) never imports jax or anything under
predictionio_tpu; its children hold the chip one after another.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".bench_work")          # listed in .gitignore
T_PROCESS_START = time.time()


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark_json():
    return load_json(ROOT, "BENCHMARK.json")


def load_cell(workload):
    """The cell named `workload`, resolved to its files by name alone:
    BENCHMARK.json -> configs/<file>, traffic/<traffic>.json, and the
    metrics/<name>.json of every per-layer metric that lists the cell."""
    bj = benchmark_json()
    cells = {w["name"]: w for w in bj["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json "
                         f"(have: {', '.join(sorted(cells))})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bj["configs"]}[cell["config"]]
    config = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    e2e = [m for m in bj["end_to_end"]
           if workload in m.get("workloads", [workload])]
    layer = []
    for m in bj["per_layer"]:
        if workload in m.get("workloads", [workload]):
            spec = load_json(HERE, "metrics", m["name"] + ".json")
            layer.append({**m, **spec})
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer,
            "run_seconds": bj["run_seconds"]}


def load_module(module, path):
    """The Python file at `path` as the module `module`, loaded once: a
    file the benchmark finds by a name in its data."""
    if module not in sys.modules:
        spec = importlib.util.spec_from_file_location(module, path)
        sys.modules[module] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[module])
    return sys.modules[module]


def load_adapter(name):
    """adapters/<name>.py, by the name a configuration's file gives
    under `adapter`: what knows one engine (adapters/rec_als.py says
    what an adapter holds). Its reference is under reference/."""
    path = os.path.join(HERE, "adapters", name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"run.py: no adapter {name!r} under "
                         "benchmark/adapters/")
    ref = os.path.join(HERE, "reference")
    if ref not in sys.path:
        sys.path.insert(0, ref)
    return load_module("adapter_" + name, path)


def adapter_of(config):
    if "adapter" not in config:
        raise SystemExit(f"run.py: configuration {config.get('name')!r} "
                         "names no `adapter` (there is no default)")
    return load_adapter(config["adapter"])


def peaks_for(device_kind):
    table = load_json(HERE, "peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"run.py: device kind {device_kind!r} is not in "
                         "benchmark/peaks.json; add it with its source")
    return table[device_kind]


def cache_dir():
    """JAX's persistent compile cache: where the machine says, else a
    fixed directory inside the checkout (the path is part of the key)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))


def work_dir(workload, fresh=False):
    d = os.path.join(WORK, workload)
    if fresh and os.path.isdir(d):
        shutil.rmtree(d)
    os.makedirs(d, exist_ok=True)
    return d


def child_env(rehearse=False, **extra):
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT, HERE, os.path.join(HERE, "engines")] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                        if p])
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir()
    # every store in memory: a run writes no model and no event to disk
    env["PIO_STORAGE_SOURCES_MEM_TYPE"] = "memory"
    for repo in ("METADATA", "EVENTDATA", "MODELDATA"):
        env[f"PIO_STORAGE_REPOSITORIES_{repo}_SOURCE"] = "MEM"
    env["PIO_FS_BASEDIR"] = os.path.join(WORK, "pio_fs")
    env["BENCH_T0"] = repr(T_PROCESS_START)
    env["TPU_LOG_DIR"] = "disabled"
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["BENCH_REHEARSE"] = "1"
    env.update({k: str(v) for k, v in extra.items()})
    return env


def log(tag, **fields):
    print(json.dumps({"at": round(time.time() - T_PROCESS_START, 3),
                      "phase": tag, **fields}), file=sys.stderr, flush=True)


def fail(msg, code=1):
    print(f"benchmark: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def require_program():
    if not os.path.isdir(os.path.join(ROOT, "predictionio_tpu")):
        fail("the program (predictionio_tpu/) is not beside benchmark/: "
             "nothing to measure", 4)


def device_gate(chips, rehearse):
    """In a child, before anything else touches the backend: refuse a
    run without the accelerator the cell asks for. -> device dict."""
    import jax

    devs = jax.devices()
    d = {"platform": devs[0].platform, "kind": devs[0].device_kind,
         "count": len(devs)}
    if rehearse:
        return d
    if d["platform"] == "cpu":
        fail("JAX found no accelerator (platform cpu)", 2)
    if len(devs) < chips:
        fail(f"the cell asks for {chips} chips, JAX sees {len(devs)}", 2)
    return d


def memory_peak_bytes():
    """Peak bytes in use on the fullest device of this process."""
    import jax

    peaks = []
    for d in jax.devices():
        ms = d.memory_stats() or {}
        peaks.append(int(ms.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_child(argv, env, log_path):
    """A child to its end, its stdout and stderr into `log_path`."""
    with open(log_path, "w") as out:
        return subprocess.run(argv, env=env, cwd=ROOT, stdout=out,
                              stderr=subprocess.STDOUT).returncode


def tail(path, n=60):
    try:
        with open(path) as f:
            sys.stderr.write("".join(f.readlines()[-n:]))
    except OSError:
        pass


def emit_result(correct, attempted, failed, metrics, device, compared,
                breakdown=None):
    """The compared numbers as the last lines of stderr, then the one
    result object as the last line of stdout (its `compared` key last)."""
    for name, (value, limit) in compared.items():
        print(f"compared {name} = {value!r} limit {limit!r}",
              file=sys.stderr)
    sys.stderr.flush()
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in compared.items()}
    print(json.dumps(out), flush=True)
