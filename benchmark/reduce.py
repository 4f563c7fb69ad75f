"""From what a run observed to the numbers it reports: the trace
reduction, the fixed set of reducers the metric files name, and the
statistics of the end-to-end metrics. Pure Python + NumPy; the one
function that opens an .xplane.pb imports jax.profiler inside itself.

A reducer that finds nothing to read returns None and the metric is
left out of the line; a share of a roofline or of a peak is never 0.
"""

import bisect
import inspect
import math
import os
import re

import numpy as np

# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"


def read_xplane(path):
    """-> {plane: {line: [(name, start_s, dur_s), ...]}} of the device
    planes, plus the benchmark's own spans (TraceAnnotations named
    bench:*) from the host plane under the key "spans"."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes, spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
            planes[plane.name] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9))
    return {"planes": planes, "spans": sorted(spans, key=lambda s: s[1])}


def union(intervals):
    """Merged [(start, end)] of possibly nested or overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def module_name(event_name):
    """'jit__train_hybrid_jit(1234)' -> 'jit__train_hybrid_jit'."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name):
    """An op's HLO text -> 'fusion.25 s32[13369344]': its name and the
    shape it writes, which is what tells two fusions apart."""
    name, _, rest = event_name.partition(" = ")
    shape = re.match(r"[^\s{]+", rest)
    short = name.lstrip("%")
    return f"{short} {shape.group(0)}"[:96] if shape else short[:96]


def summarize_trace(trace, unnamed_gap="outside_any_span"):
    """The numbers every trace metric reads, over the window: the span
    named "window", else the extent of the device events.

    busy_s: union of the op intervals inside the window, averaged over
    the device planes; programs: {module: [seconds, calls]} from the
    modules line (whole executions that START inside the window, their
    full length); ops: {op: seconds} self-contained leaf ops only (an op
    that contains other ops, a while or a call, is left out of the
    ranking so nothing is counted twice); gaps: idle stretches of the
    first device, longest first, each with the benchmark span it lies in;
    spans: {name: [seconds, count]} of the bench:<name> annotations that
    START inside the window, their full length, as the programs'.

    A capture with spans and no device plane (a rehearsal on the CPU)
    gives the spans and a busy_s of 0, which no cell accepts from a chip.
    """
    planes = trace["planes"]
    spans = trace["spans"]
    if not planes and not spans:
        return None
    win = [s for s in spans if s[0] == "window"]
    if win:
        lo, hi = win[0][1], win[0][1] + win[0][2]
    else:
        ev = [(s, s + d) for ls in planes.values()
              for l in ls.values() for _n, s, d in l]
        if not ev:
            return None
        lo, hi = min(s for s, _ in ev), max(e for _, e in ev)
    busy, first_busy = [], None
    programs, ops = {}, {}
    for pname in sorted(planes):
        lines = planes[pname]
        src = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        merged = clip(union([(s, s + d) for _n, s, d in src]), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
        for name, s, d in lines.get(MODULES_LINE, []):
            if lo <= s < hi:
                rec = programs.setdefault(module_name(name), [0.0, 0])
                rec[0] += d
                rec[1] += 1
        mods = sorted((s, s + d, module_name(n))
                      for n, s, d in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in mods]
        evs = sorted(((s, s + d, n) for n, s, d in lines.get(OPS_LINE, [])
                      if lo <= s < hi), key=lambda t: (t[0], -t[1]))
        for k, (s, e, n) in enumerate(evs):
            nested = k + 1 < len(evs) and evs[k + 1][0] < e \
                and evs[k + 1][1] <= e and (evs[k + 1][0], evs[k + 1][1]) != (s, e)
            if nested:
                continue
            m = bisect.bisect_right(starts, s) - 1
            owner = mods[m][2] if m >= 0 and s < mods[m][1] else "?"
            key = f"{owner}/{op_name(n)}"
            ops[key] = ops.get(key, 0.0) + (e - s)
    n_dev = max(len(planes), 1)
    for rec in programs.values():
        rec[0] /= n_dev
    span_totals = {}
    for n, s, d in spans:
        if n != "window" and lo <= s < hi:
            rec = span_totals.setdefault(n, [0.0, 0])
            rec[0] += d
            rec[1] += 1
    gaps = []
    edges = [lo] + [t for se in (first_busy or []) for t in se] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = 0.5 * (a + b)
            inside = [n for n, s, d in spans
                      if n != "window" and s <= mid < s + d]
            gaps.append((inside[-1] if inside else unnamed_gap, b - a))
    by_name = {}
    for n, g in gaps:
        by_name[n] = by_name.get(n, 0.0) + g
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / n_dev,
        "devices": len(planes),
        "programs": programs,
        "spans": span_totals,
        "ops": {n: t / n_dev for n, t in ops.items()},
        "gap_seconds_by_span": by_name,
        "longest_gaps": sorted(gaps, key=lambda g: -g[1])[:10],
    }


def breakdown(summary):
    """The `breakdown` key of a traced run's line."""
    if summary is None:
        return None
    ops = sorted(summary["ops"].items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(summary["gap_seconds_by_span"].items(),
                  key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in gaps]}


# ---------------------------------------------------------------------------
# end-to-end statistics
# ---------------------------------------------------------------------------

def percentile_all(latencies_ms, failed, q):
    """The q-th percentile over ALL requests of the window: one that
    failed or was refused counts as the worst, ranked above every reply.
    Nearest-rank on the sorted values; None when there is no request."""
    n = len(latencies_ms) + int(failed)
    if n == 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * n))
    if rank > len(latencies_ms):
        return float("inf")
    return float(np.sort(np.asarray(latencies_ms, np.float64))[rank - 1])


# ---------------------------------------------------------------------------
# reducers (named by benchmark/metrics/*.json)
# ---------------------------------------------------------------------------

def _counter(pages, path):
    """By how much the number at a dotted path into the program's status
    page (`GET /`) rose between two readings of the page."""
    if not pages:
        return None
    ends = []
    for node in pages:
        for key in path.split("."):
            node = node.get(key) if isinstance(node, dict) else None
        if isinstance(node, bool) or not isinstance(node, (int, float)):
            return None
        ends.append(node)
    return float(ends[1] - ends[0])


#: a term's key -> (the table of the trace's summary it searches by
#: pattern, 0 for seconds or 1 for calls)
_TRACED = {"program_s": ("programs", 0), "program_n": ("programs", 1),
           "span_s": ("spans", 0), "span_n": ("spans", 1)}


def _term(term, facts):
    """One term of a sum: a named fact (number or list of numbers); a
    counter of the status page, differenced over the window (`counter`)
    or between the trace's marks (`traced_counter`); or seconds/calls of
    the traced programs (`program_s`/`program_n`) or of the program's
    bench:<span> annotations (`span_s`/`span_n`) whose name matches a
    pattern."""
    if "times_fact" in term:
        rest = {k: v for k, v in term.items() if k != "times_fact"}
        a, b = _term(rest, facts), facts.get(term["times_fact"])
        return None if a is None or b is None else a * float(b)
    if "fact" in term:
        v = facts.get(term["fact"])
        if v is None:
            return None
        return float(np.sum(v))
    for key, marks in (("counter", "window"), ("traced_counter", "traced")):
        if key in term:
            return _counter(facts.get("counters", {}).get(marks), term[key])
    key = next((k for k in _TRACED if k in term), None)
    if key is None:
        raise SystemExit(f"run.py: a metric's term {term!r} is none of "
                         f"fact, counter, traced_counter, {', '.join(_TRACED)}")
    trace = facts.get("trace")
    if trace is None:
        return None
    table, field = _TRACED[key]
    pat = re.compile(term[key])
    hits = [rec for name, rec in trace[table].items() if pat.search(name)]
    if not hits:
        return None
    return float(sum(rec[field] for rec in hits))


def reduce_sum(args, facts):
    """scale * sum(sign * term) / sum(over): covers sum, mean, per-job
    and per-flush. Any missing term leaves the metric out."""
    total = 0.0
    for term in args["terms"]:
        v = _term(term, facts)
        if v is None:
            return None
        total += term.get("sign", 1) * v
    if "over" in args:
        den = _term(args["over"], facts)
        if not den:
            return None
        total /= den
    return args.get("scale", 1.0) * total


def reduce_busy_union(args, facts):
    """Idle share of the traced window, in percent."""
    trace = facts.get("trace")
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def cost_function(name):
    """A metric's `cost` by name: a function defined in kernel_costs.py
    (not a module it imports), else the function `name` of
    costs/<name>.py, the file a PR that brings a kernel brings with it.
    Same signature, (config, facts)."""
    import harness
    import kernel_costs

    fn = getattr(kernel_costs, name, None)
    if inspect.isfunction(fn) and fn.__module__ == kernel_costs.__name__:
        return fn
    path = os.path.join(harness.HERE, "costs", name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"run.py: no cost function {name!r} in "
                         "benchmark/kernel_costs.py or benchmark/costs/")
    return getattr(harness.load_module("cost_" + name, path), name)


def reduce_roofline_share(args, facts):
    """Least time the chip could take for the calls of a kernel (the
    larger of operations/peak and bytes/bandwidth, from kernel_costs)
    over the device time the trace gives those calls, in percent. The
    bound that decides is kept in facts['bounds'] for the log."""
    secs = _term({"program_s": args["program"]}, facts)
    calls = _term({"program_n": args["program"]}, facts)
    if not secs or not calls:
        return None
    peaks = facts["peaks"]
    if peaks is None:               # a rehearsal: no chip, no share
        return None
    cost = cost_function(args["cost"])(facts["config"], facts)
    if cost is None:
        return None
    t_ops = cost["ops_per_call"] / peaks[args.get("peak", "flops_fp32")]
    t_bytes = cost["bytes_per_call"] / peaks["hbm_bytes_per_s"]
    facts.setdefault("bounds", {})[args["program"]] = (
        "bytes" if t_bytes >= t_ops else "ops")
    return 100.0 * max(t_ops, t_bytes) * calls / secs


def reduce_mfu(args, facts):
    """Operations the algorithm needs over (seconds * chips * peak)."""
    secs = _term(args["seconds"], facts)
    if not secs:
        return None
    if facts["peaks"] is None:      # a rehearsal: no chip, no share
        return None
    ops = cost_function(args["cost"])(facts["config"], facts)
    if ops is None:
        return None
    peak = facts["peaks"][args.get("peak", "flops_bf16")]
    return 100.0 * ops["ops_total"] / (secs * facts["chips"] * peak)


REDUCERS = {"sum": reduce_sum, "busy_union": reduce_busy_union,
            "roofline_share": reduce_roofline_share, "mfu": reduce_mfu}


def layer_metrics(specs, facts):
    """{name: {"value", "unit"}} for every metric whose reader found
    something to read."""
    out = {}
    for spec in specs:
        value = REDUCERS[spec["reducer"]](spec.get("args", {}), facts)
        if value is None or not math.isfinite(value):
            continue
        out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out
