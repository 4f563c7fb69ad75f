"""A serving cell, from the parent's side: a child runs `pio deploy` on
the chip, this process offers the load over HTTP, and once the window
has closed and the child has gone it checks a sample of the replies
against the plain reference. The parent stays off jax.
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

import numpy as np

import compare
import gen_factors
import harness
import loadgen
import reduce

sys.path.insert(0, os.path.join(harness.HERE, "reference"))
import topk_reference  # noqa: E402

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
        b = get_json(self.port, "/")["batching"]
        return {"batches": b["batches"], "queries": b["queries"],
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


def check_replies(spec, model, seed, records, control=False):
    """A sample of the window's requests, drawn from the seed, against
    the reference: every one has to have come, with k distinct items
    whose reference scores are the best to within the limits."""
    traffic, config = spec["traffic"], spec["config"]
    k = config["query"]["num"]
    rng = np.random.default_rng([int(seed), 0xC4])
    n = min(int(traffic["checked_replies"]), len(records))
    picks = rng.choice(len(records), n, replace=False)
    nu, ni, r, decay = (model["n_users"], model["n_items"], model["rank"],
                        model["decay"])
    V = gen_factors.matrix(seed, "item", ni, r, decay)
    user_ixs = np.asarray([records[p][0] for p in picks], np.int64)
    rows = gen_factors.rows(seed, "user", user_ixs, nu, r, decay)
    replies = []
    for p in picks:
        items = records[p][-1]
        if items is not None:
            try:
                items = [(int(name[1:]), s) for name, s in items]
            except ValueError:
                items = None
        replies.append(items)
    precisions = {"program": "float32"}
    if control:
        precisions["control"] = config["serving"]["control_precision"]
    step = 32
    prepared = {prec: topk_reference.prepare(V, prec)
                for prec in set(precisions.values())}
    state = {name: {"rank_gap": 0.0, "score_gap": 0.0, "bad_replies": 0.0}
             for name in precisions}
    for s in range(0, n, step):
        ref = topk_reference.scores(rows[s:s + step], prepared["float32"])
        for name, prec in precisions.items():
            if name == "program":
                got = [(int(user_ixs[s + j]), replies[s + j])
                       for j in range(ref.shape[0])]
            else:
                # the control in the program's place: what the lower
                # precision would have served for the same queries
                low = topk_reference.scores(rows[s:s + step], prepared[prec], prec)
                got = []
                for j in range(ref.shape[0]):
                    top = topk_reference.topk(low[j], k)
                    got.append((int(user_ixs[s + j]),
                                [(int(i), float(low[j][i])) for i in top]))
            lookup = {int(user_ixs[s + j]): ref[j]
                      for j in range(ref.shape[0])}
            # one user may be asked twice in a block; same row either way
            nums = compare.topk_numbers(got, lookup.__getitem__, k)
            for key in state[name]:
                state[name][key] = (state[name][key] + nums[key]
                                    if key == "bad_replies"
                                    else max(state[name][key], nums[key]))
    return {**state, "checked": int(n)}


def run(spec, args):
    workload = spec["cell"]["name"]
    config, traffic = spec["config"], spec["traffic"]
    work = harness.work_dir(workload, fresh=True)
    model = config["model"]
    if args.rehearse:
        model = gen_factors.scaled_model(model, traffic["rehearse_cut"])
    k = config["query"]["num"]
    dep = Deploy(spec, args, work)
    try:
        t_ready = dep.wait_ready()
        started = harness.load_json(work, "deploy_start.json")
        device = started["device"]
        ready_s = t_ready - started["t_deploy_start"]
        harness.log("serve:ready", ready_s=ready_s,
                    factors_s=started["factors_s"],
                    instance_s=started["instance_s"], **device)
        conns = int(traffic["connections"])
        closed = traffic["kind"] == "closed_loop"
        if closed:
            # more than any window can ask; the loop stops at its end
            n_window = int(traffic["max_queries"])
            due = None
        else:
            due = loadgen.arrival_times(args.seed, traffic, args.seconds)
            n_window = len(due)
        n_warm = int(traffic["warmup_queries"])
        users = loadgen.query_users(args.seed, n_window + n_warm,
                                    model["n_users"],
                                    config["query"]["zipf_a"])
        # warm-up: the window's own pattern, on users the window leaves
        warm, _, _ = loadgen.closed_loop(
            dep.port, users[n_window:], k, conns, traffic["warmup_seconds"])
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
                dep.port, users[:n_window], k, conns, args.seconds)
        else:
            records, t_start = loadgen.open_loop(
                dep.port, users[:n_window], k, due, conns)
            t_end = max(r[3] for r in records)
        for t in timers:
            t.join()
        s1, b1 = dep.ask("stats"), dep.batching()
    finally:
        dep.stop()
    window = diff(b0, b1)
    def full(r):
        return r[-1] is not None and len(r[-1]) == k

    failed = sum(1 for r in records if not full(r))
    good = len(records) - failed
    harness.log("serve:window", requests=len(records), failed=failed,
                window_s=t_end - t_start, batching=window,
                sizes=b1["sizes"], buckets=b1["buckets"],
                compiles=s1["compiles"] - s0["compiles"])
    checked = check_replies(spec, model, args.seed, records,
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
             "trace": None}
    if not closed:
        late = loadgen.lateness_ms(records)
        facts["late_ms_p95"] = reduce.percentile_all(late, 0, 95)
        facts["offered_qps"] = len(records) / max(due[-1], 1e-9)
    if args.rehearse:
        return {"ok": ok, "numbers": numbers, "requests": len(records),
                "failed": failed, "facts_keys": sorted(facts),
                "reference": checked}
    device["memory_peak_bytes"] = s1["memory_peak_bytes"]
    facts["peaks"] = harness.peaks_for(device["kind"])
    if args.trace:
        paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            harness.fail("the profiler left no .xplane.pb")
        # read by a child: the parent stays off jax
        summary_path = os.path.join(work, "trace_summary.json")
        rc = harness.run_child(
            [sys.executable, os.path.join(harness.HERE, "reduce_child.py"),
             max(paths, key=os.path.getmtime), summary_path],
            harness.child_env(rehearse=True), os.path.join(work, "reduce.log"))
        if rc != 0:
            harness.tail(os.path.join(work, "reduce.log"))
            harness.fail("the trace could not be reduced")
        summary = harness.load_json(summary_path)["summary"]
        if summary is None or summary["busy_s"] <= 0:
            harness.fail("the traced window holds no device operation")
        traced = diff(marks["b_start"], marks["b_stop"])
        facts.update({"trace": summary,
                      "traced_flushes": traced["batches"] or None,
                      "traced_queries": traced["queries"] or None,
                      "mean_flush_rows": (traced["queries"] / traced["batches"]
                                          if traced["batches"] else None),
                      "trace.window_s": summary["window_s"]})
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
