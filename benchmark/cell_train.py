"""A training cell, from the parent's side: one child drives the jobs,
a second follows the checked job with the reference. The parent stays
off jax.
"""

import os
import sys

import compare
import harness
import reduce


def run(spec, args):
    workload = spec["cell"]["name"]
    work = harness.work_dir(workload, fresh=True)
    env = harness.child_env(rehearse=args.rehearse)
    out_path = os.path.join(work, "train.json")
    argv = [sys.executable, os.path.join(harness.HERE, "child_train.py"),
            "--workload", workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", str(args.trace),
            "--out", out_path]
    if args.fault:
        argv += ["--fault", args.fault]
    log_path = os.path.join(work, "train.log")
    rc = harness.run_child(argv, env, log_path)
    if rc != 0:
        harness.tail(log_path)
        harness.fail(f"the training child exited {rc}", rc if rc == 2 else 1)
    obs = harness.load_json(out_path)
    harness.log("train:observed", jobs=len(obs["jobs"]),
                window_s=obs["window_s"], setup_s=obs["setup_s"],
                compiles_in_window=obs["compiles_in_window"],
                job_seconds=[round(j["end"] - j["start"], 3)
                             for j in obs["jobs"]])
    # the reference follows, once the chip is free and the peak is read
    ref_path = os.path.join(work, "reference.json")
    argv = [sys.executable, os.path.join(harness.HERE, "child_reference.py"),
            "--workload", workload, "--seed", str(args.seed),
            "--dataset", str(obs["checked_dataset"]), "--out", ref_path]
    if args.control:
        argv.append("--control")
    log_path = os.path.join(work, "reference.log")
    rc = harness.run_child(argv, env, log_path)
    if rc != 0:
        harness.tail(log_path)
        harness.fail(f"the reference child exited {rc}")
    ref = harness.load_json(ref_path)
    harness.log("train:reference", **ref)
    numbers = dict(ref["program"])
    numbers["compiles_in_window"] = float(obs["compiles_in_window"])
    ok, compared = compare.judge(numbers, spec["config"]["limits"])

    config = spec["config"]
    jobs = obs["jobs"]
    traced = [j for j in jobs if j.get("traced")]
    facts = {"config": config, "chips": spec["cell"]["chips"],
             "jobs": len(jobs), "window_s": obs["window_s"],
             "traced_jobs": len(traced) or None,
             "iterations_per_call": config["engine_params"]["numIterations"],
             "compiles_in_window": obs["compiles_in_window"],
             "trace": obs["trace"]}
    for group, rows in (("phase", jobs), ("traced.phase", traced)):
        names = {p for j in rows for p in j["phases"]}
        for p in names:
            facts[f"{group}.{p}_s"] = [j["phases"].get(p, 0.0) for j in rows]
    facts["traced.job_s"] = [j["end"] - j["start"] for j in traced] or None
    device = dict(obs["device"])
    device["memory_peak_bytes"] = obs["memory_peak_bytes"]
    if args.rehearse:
        return {"ok": ok, "numbers": numbers, "facts_keys": sorted(facts),
                "reference": ref}
    facts["peaks"] = harness.peaks_for(device["kind"])
    if args.trace:
        if obs["trace"] is None or obs["trace"]["busy_s"] <= 0:
            harness.fail("the traced window holds no device operation")
        device["busy_s"] = obs["trace"]["busy_s"]
        device["window_s"] = obs["trace"]["window_s"]
        metrics = reduce.layer_metrics(spec["per_layer"], facts)
        harness.log("train:trace", programs=obs["trace"]["programs"],
                    bounds=facts.get("bounds"),
                    longest_gaps=obs["trace"]["longest_gaps"])
    else:
        values = {"train_job_s": obs["window_s"] / len(jobs),
                  "setup_s": obs["setup_s"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    return {"correct": ok, "attempted": len(jobs), "failed": 0,
            "metrics": metrics, "device": device, "compared": compared,
            "breakdown": reduce.breakdown(obs["trace"]) if args.trace
            else None, "reference": ref}
