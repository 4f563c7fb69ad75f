"""The five metrics that read the host's counters
(`serve.host_cpu_us.rate`, `transport.cpu_us.rate`, `batcher.wake_ms.rate`,
`batcher.lane_*`) on two recorded `GET /` pages of a batching deploy
(host_counter_pages.json: 400 queries over 32 connections), and on a
parent's pages, which lack every counter they read. `host.runQueueSeconds`
is on those pages but no metric reads it: the chip's host gives no
schedstat, so a metric of it would read nothing in any cell."""

import json
import math
import os

import pytest

import harness
import reduce

HERE = os.path.dirname(os.path.abspath(__file__))
FIVE = ("serve.host_cpu_us.rate", "transport.cpu_us.rate",
        "batcher.wake_ms.rate", "batcher.lane_cpu_ms.rate",
        "batcher.lane_offcpu_ms.rate")
CLOSED_LOOP = [w["name"] for w in harness.benchmark_json()["workloads"]
               if w["traffic"] == "closed128"]


def _pages():
    with open(os.path.join(HERE, "host_counter_pages.json")) as f:
        return json.load(f)["pages"]


def _facts(pages):
    return {"counters": {"window": pages}}


@pytest.mark.parametrize("cell", CLOSED_LOOP)
def test_every_closed_loop_cell_reads_the_five(cell):
    specs = [m for m in harness.load_cell(cell)["per_layer"]
             if m["name"] in FIVE]
    assert sorted(m["name"] for m in specs) == sorted(FIVE)
    got = reduce.layer_metrics(specs, _facts(_pages()))
    assert sorted(got) == sorted(FIVE)
    for name, m in got.items():
        assert math.isfinite(m["value"]) and m["value"] > 0, (name, m)


def test_the_five_by_hand():
    before, after = _pages()
    got = reduce.layer_metrics(
        harness.load_cell(CLOSED_LOOP[0])["per_layer"], _facts(_pages()))

    def d(block, *keys):
        a, b = before[block], after[block]
        for k in keys:
            a, b = a[k], b[k]
        return b - a

    queries = d("batching", "queries")
    flushes = d("batching", "batches")
    assert queries == 400 and flushes > 0
    want = {
        "serve.host_cpu_us.rate": d("host", "cpuSeconds") / queries * 1e6,
        "transport.cpu_us.rate": d("transport", "cpuSeconds")
            / d("transport", "requests") * 1e6,
        "batcher.wake_ms.rate": d("batching", "wakeSeconds") / queries * 1e3,
        "batcher.lane_cpu_ms.rate":
            d("batching", "lanes", "workCpuSeconds") / flushes * 1e3,
        "batcher.lane_offcpu_ms.rate":
            (d("batching", "lanes", "workWallSeconds")
             - d("batching", "lanes", "workCpuSeconds")) / flushes * 1e3,
    }
    for name, value in want.items():
        assert got[name]["value"] == pytest.approx(value), name
    units = {m["name"]: m["unit"] for m in harness.benchmark_json()["per_layer"]}
    assert [units[n] for n in FIVE] == ["us/query", "us/request",
                                       "ms/query", "ms/flush", "ms/flush"]


def test_a_parents_pages_leave_the_five_out():
    """The parent has none of the counters: each metric is left out of
    its line, and nothing raises."""
    pages = []
    for p in _pages():
        b = {k: v for k, v in p["batching"].items()
             if k not in ("lanes", "wakeSeconds")}
        t = {k: v for k, v in p["transport"].items() if k != "cpuSeconds"}
        pages.append({"batching": b, "transport": t})
    specs = harness.load_cell(CLOSED_LOOP[0])["per_layer"]
    got = reduce.layer_metrics(specs, _facts(pages))
    assert not set(FIVE) & set(got)
