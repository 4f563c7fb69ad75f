"""A serving cell, from the parent's side: a child runs `pio deploy` on
the chip, this process offers the load over HTTP, and once the window
has closed and the child has gone the configuration's adapter checks a
sample of the replies against its plain reference. What a query and a
reply are is the adapter's (adapters/<name>.py). The parent stays off
jax.
"""

import glob
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import compare
import harness
import loadgen
import reduce

READY_TIMEOUT_S = 1100


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def get_json(port, path, timeout=30):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=timeout) as resp:
        return json.loads(resp.read().decode())


class Deploy:
    """child_serve.py as a child process with a line-a-question pipe."""

    def __init__(self, spec, args, work):
        self.work, self.port, self.asked = work, free_port(), 0
        self.log_path = os.path.join(work, "deploy.log")
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(harness.HERE, "child_serve.py"),
             "--workload", spec["cell"]["name"], "--seed", str(args.seed),
             "--port", str(self.port), "--ctl-dir", work]
            + (["--fault", args.fault] if args.fault else []),
            env=harness.child_env(rehearse=args.rehearse), cwd=harness.ROOT,
            stdin=subprocess.PIPE, stdout=self.log, stderr=subprocess.STDOUT,
            text=True)

    def wait_ready(self):
        deadline = time.time() + READY_TIMEOUT_S
        while True:
            rc = self.proc.poll()
            if rc is not None:
                harness.tail(self.log_path)
                harness.fail(f"the deploy child exited {rc} before /readyz",
                             rc if rc == 2 else 1)
            try:
                if get_json(self.port, "/readyz", 5).get("status") == "ready":
                    return time.time()
            except (urllib.error.URLError, OSError, ValueError):
                pass
            if time.time() > deadline:
                self.stop()
                harness.tail(self.log_path)
                harness.fail(f"not ready in {READY_TIMEOUT_S}s")
            time.sleep(0.25)

    def ask(self, cmd, timeout=120, **fields):
        self.asked += 1
        self.proc.stdin.write(json.dumps(
            {"id": self.asked, "cmd": cmd, **fields}) + "\n")
        self.proc.stdin.flush()
        path = os.path.join(self.work, f"reply_{self.asked}.json")
        deadline = time.time() + timeout
        while not os.path.exists(path):
            if time.time() > deadline or self.proc.poll() is not None:
                harness.tail(self.log_path)
                harness.fail(f"the deploy child did not answer {cmd!r}")
            time.sleep(0.01)
        return harness.load_json(path)

    def batching(self):
        """The batcher's counters, and under `page` the whole `GET /`
        object they came with, for the metrics' `counter` terms."""
        page = get_json(self.port, "/")
        b = page["batching"]
        return {"page": page,
                "batches": b["batches"], "queries": b["queries"],
                "rejected": b["rejected"],
                "queue_wait_s": b["avgQueueWaitMs"] * b["queries"] / 1e3,
                "flush_s": b["avgFlushMs"] * b["batches"] / 1e3,
                "sizes": b["batchSizeHist"], "buckets": b["bucketHist"]}

    def stop(self):
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(60)
        self.log.close()


def diff(a, b):
    return {k: b[k] - a[k] for k in ("batches", "queries", "rejected",
                                     "queue_wait_s", "flush_s")}


def read_trace(work, trace_dir):
    """reduce.summarize_trace's summary of the newest capture under
    `trace_dir`, read by a child: the parent stays off jax."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        harness.fail("the profiler left no .xplane.pb")
    summary_path = os.path.join(work, "trace_summary.json")
    rc = harness.run_child(
        [sys.executable, os.path.join(harness.HERE, "reduce_child.py"),
         max(paths, key=os.path.getmtime), summary_path],
        harness.child_env(rehearse=True), os.path.join(work, "reduce.log"))
    if rc != 0:
        harness.tail(os.path.join(work, "reduce.log"))
        harness.fail("the trace could not be reduced")
    return harness.load_json(summary_path)["summary"]


def offered(spec, adapter, model, seed, seconds):
    """What the cell sends, all of it from the seed: -> (due, asked,
    warm): an open loop's arrival offsets (None for a closed loop), the
    window's queries in the order they leave, and the warm-up's, which
    the window leaves alone."""
    traffic = spec["traffic"]
    if traffic["kind"] == "closed_loop":
        # more than any window can ask; the loop stops at its end
        due, n_window = None, int(traffic["max_queries"])
    else:
        due = loadgen.arrival_times(seed, traffic, seconds)
        n_window = len(due)
    queries = adapter.queries(spec, model, seed,
                              n_window + int(traffic["warmup_queries"]))
    return due, queries[:n_window], queries[n_window:]


def run(spec, args):
    workload = spec["cell"]["name"]
    config, traffic = spec["config"], spec["traffic"]
    work = harness.work_dir(workload, fresh=True)
    adapter = harness.adapter_of(config)
    model = config["model"]
    if args.rehearse:
        model = adapter.rehearsal_model(model, traffic["rehearse_cut"])
    wire = adapter.wire(spec)
    dep = Deploy(spec, args, work)
    try:
        t_ready = dep.wait_ready()
        started = harness.load_json(work, "deploy_start.json")
        device = started["device"]
        ready_s = t_ready - started["t_deploy_start"]
        harness.log("serve:ready", ready_s=ready_s,
                    models_s=started["models_s"],
                    instance_s=started["instance_s"], **device)
        conns = int(traffic["connections"])
        closed = traffic["kind"] == "closed_loop"
        due, asked, warm_up = offered(spec, adapter, model, args.seed,
                                      args.seconds)
        # warm-up: the window's own pattern, on queries the window leaves
        warm, _, _ = loadgen.closed_loop(
            dep.port, warm_up, wire, conns, traffic["warmup_seconds"])
        if not warm or any(r[-1] is None for r in warm):
            harness.fail("a warm-up query failed")
        s0, b0 = dep.ask("stats"), dep.batching()
        setup_s = time.time() - harness.T_PROCESS_START
        timers, marks = [], {}
        if args.trace:
            trace_dir = os.path.join(work, "trace")

            def start():
                marks["b_start"] = dep.batching()
                marks["t_start"] = dep.ask("trace_start", dir=trace_dir)["t"]

            def stop():
                marks["t_stop"] = dep.ask("trace_stop")["t"]
                marks["b_stop"] = dep.batching()

            timers = [threading.Timer(traffic["trace_after_s"], start),
                      threading.Timer(traffic["trace_after_s"]
                                      + traffic["trace_seconds"], stop)]
            for t in timers:
                t.start()
        if closed:
            records, t_start, t_end = loadgen.closed_loop(
                dep.port, asked, wire, conns, args.seconds)
        else:
            records, t_start = loadgen.open_loop(
                dep.port, asked, wire, due, conns)
            t_end = max(r[3] for r in records)
        for t in timers:
            t.join()
        s1, b1 = dep.ask("stats"), dep.batching()
    finally:
        dep.stop()
    window = diff(b0, b1)

    def full(r):
        return wire.whole(asked[r[0]], r[-1])

    failed = sum(1 for r in records if not full(r))
    good = len(records) - failed
    harness.log("serve:window", requests=len(records), failed=failed,
                window_s=t_end - t_start, batching=window,
                sizes=b1["sizes"], buckets=b1["buckets"],
                compiles=s1["compiles"] - s0["compiles"])
    checked = adapter.check(spec, model, args.seed, asked, records,
                            control=args.control)
    harness.log("serve:checked", **checked)
    numbers = dict(checked["program"])
    numbers["compiles_in_window"] = float(s1["compiles"] - s0["compiles"])
    ok, compared = compare.judge(numbers, config["limits"])

    facts = {"config": config, "chips": spec["cell"]["chips"],
             "window_s": t_end - t_start, "requests": len(records),
             "deploy_ready_s": ready_s,
             "compiles_in_window": s1["compiles"] - s0["compiles"],
             "flushes": window["batches"] or None,
             "queries": window["queries"] or None,
             "rejected": window["rejected"],
             "queue_wait_ms_total": window["queue_wait_s"] * 1e3,
             "flush_ms_total": window["flush_s"] * 1e3,
             "counters": {"window": [b0["page"], b1["page"]]},
             "trace": None}
    if not closed:
        late = loadgen.lateness_ms(records)
        facts["late_ms_p95"] = reduce.percentile_all(late, 0, 95)
        facts["offered_qps"] = len(records) / max(due[-1], 1e-9)
    if args.trace:
        summary = read_trace(work, trace_dir)
        traced = diff(marks["b_start"], marks["b_stop"])
        facts["counters"]["traced"] = [marks["b_start"]["page"],
                                       marks["b_stop"]["page"]]
        facts.update({"trace": summary,
                      "traced_flushes": traced["batches"] or None,
                      "traced_queries": traced["queries"] or None,
                      "mean_flush_rows": (traced["queries"] / traced["batches"]
                                          if traced["batches"] else None),
                      "trace.window_s": summary and summary["window_s"]})
    if args.rehearse:
        # which per-layer readers found something to read; on the CPU
        # their values mean nothing and are not kept, and there is no
        # chip whose peaks a share could be of
        facts["peaks"] = None
        return {"ok": ok, "numbers": numbers, "compared": compared,
                "requests": len(records),
                "failed": failed, "facts_keys": sorted(facts),
                "layer_metrics": sorted(reduce.layer_metrics(
                    spec["per_layer"], facts)),
                "reference": checked}
    device["memory_peak_bytes"] = s1["memory_peak_bytes"]
    facts["peaks"] = harness.peaks_for(device["kind"])
    if args.trace:
        if summary is None or summary["busy_s"] <= 0:
            harness.fail("the traced window holds no device operation")
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        metrics = reduce.layer_metrics(spec["per_layer"], facts)
        harness.log("serve:trace", programs=summary["programs"],
                    bounds=facts.get("bounds"), traced=traced,
                    longest_gaps=summary["longest_gaps"])
        breakdown = reduce.breakdown(summary)
    else:
        values = {"setup_s": setup_s}
        if closed:
            values["query_rate"] = good / (t_end - t_start)
        else:
            lat = [(r[3] - r[1]) * 1e3 for r in records if full(r)]
            worst = traffic["timeout_s"] * 1e3
            p95 = reduce.percentile_all(lat, failed, 95)
            values["query_p95_ms"] = min(p95, worst)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
        breakdown = None
    return {"correct": ok, "attempted": len(records), "failed": failed,
            "metrics": metrics, "device": device, "compared": compared,
            "breakdown": breakdown, "reference": checked}
