"""What benchmark/ reads of the program, one case per thing read.

The benchmark (BENCHMARK.json, benchmark/) names parts of the program:
`pio deploy` flags in a configuration's `deploy_args`, an engine factory
in each engine.json, keys of the `GET /` batching block, device programs
by their module name in benchmark/metrics/*.json, `/readyz`, the compile
counter. A rename on the program's side shows first as a null under
`per_layer` in the ledger; here it fails a test. Nothing under
benchmark/ is edited or copied: the names come from its files, and what
it reads with is called in place (modules loaded by path).
"""

import contextlib
import datetime as dt
import glob
import importlib.util
import json
import os
import re
import sys
import threading

import numpy as np
import pytest

import jax

from predictionio_tpu.common import devicewatch, telemetry
from predictionio_tpu.data.storage import reset_storage, use_memory_storage

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
#: benchmark/child_train.py's `pio train` call for the parked cell
PARKED_TRAIN_ARGV = ("--telemetry",)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCHMARK = _json(ROOT, "BENCHMARK.json")
_CONFIG_FILE = {c["name"]: c["file"] for c in BENCHMARK["configs"]}


def _cell_configs():
    """(cell id, pio command, its configuration's file as read): the
    serving cells of BENCHMARK.json, then the training cell parked
    beside them."""
    out = []
    for w in BENCHMARK["workloads"]:
        out.append((w["name"], "deploy",
                    _json(ROOT, _CONFIG_FILE[w["config"]])))
    for path in sorted(glob.glob(os.path.join(BENCH, "parked", "*.json"))):
        parked = _json(path)["BENCHMARK.json"]
        files = {c["name"]: c["file"] for c in parked["configs"]}
        for w in parked["workloads"]:
            out.append(("parked:" + w["name"], "train",
                        _json(ROOT, files[w["config"]])))
    return out


def _cells():
    """(pio command, its flags, engine dir) per cell."""
    return [pytest.param(
        command,
        tuple(cfg["deploy_args"]) if command == "deploy"
        else PARKED_TRAIN_ARGV,
        cfg["engine_dir"], id=cell)
        for cell, command, cfg in _cell_configs()]


def _first_engine_dir(command):
    """The engine directory of the first cell `pio <command>` runs."""
    for _cell, cmd, cfg in _cell_configs():
        if cmd == command:
            return os.path.join(ROOT, cfg["engine_dir"])
    pytest.skip(f"the benchmark has no `pio {command}` cell")


def _batching_keys():
    """The `GET /` batching keys cell_serve.py's reader indexes."""
    with open(os.path.join(BENCH, "cell_serve.py")) as f:
        src = f.read()
    body = src[src.index("    def batching(self):"):
               src.index("    def stop(self):")]
    return sorted(set(re.findall(r'\bb\["(\w+)"\]', body)))


def _program_names():
    """Every device program a metric file names (`program_s`,
    `program_n`, a roofline's `program`), alternatives split."""
    names = set()

    def walk(node):
        if isinstance(node, dict):
            for k, v in node.items():
                if k in ("program_s", "program_n", "program"):
                    names.update(v.split("|"))
                else:
                    walk(v)
        elif isinstance(node, list):
            for v in node:
                walk(v)

    for path in (glob.glob(os.path.join(BENCH, "metrics", "*.json"))
                 + glob.glob(os.path.join(BENCH, "parked", "*.json"))):
        walk(_json(path))
    return sorted(names)


def _phase_facts():
    """The training phases the parked cell's metrics read as facts
    (`phase.persist_s`, `traced.phase.train_s`)."""
    phases = set()
    for path in glob.glob(os.path.join(BENCH, "metrics", "*.json")):
        with open(path) as f:
            phases.update(re.findall(r'"(?:traced\.)?phase\.(\w+)_s"',
                                     f.read()))
    return sorted(phases)


@contextlib.contextmanager
def _benchmark_on_path():
    """benchmark/ importable as its own children see it (harness.
    child_env's PYTHONPATH), and gone again afterwards with every module
    it brought, so tests/ keeps its sys.path."""
    before_path, before_mods = list(sys.path), set(sys.modules)
    sys.path[:0] = [BENCH, os.path.join(BENCH, "engines")]
    try:
        yield
    finally:
        sys.path[:] = before_path
        for name in set(sys.modules) - before_mods:
            mod_file = getattr(sys.modules[name], "__file__", "") or ""
            if mod_file.startswith(BENCH):
                del sys.modules[name]


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"_bench_{name}", os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# (a) a cell's flags parse, and its engine.json builds an engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("command,flags,engine_dir", _cells())
def test_cell_flags_parse_and_engine_builds(command, flags, engine_dir):
    from predictionio_tpu.controller import Engine
    from predictionio_tpu.controller.engine import engine_params_from_json
    from predictionio_tpu.tools import cli
    from predictionio_tpu.workflow.workflow_utils import (
        get_engine, read_engine_variant,
    )

    edir = os.path.join(ROOT, engine_dir)
    argv = [command, "--engine-dir", edir, *flags]
    if command == "deploy":     # child_serve.py's own part of the call
        argv += ["--ip", "127.0.0.1", "--port", "0", "--telemetry"]
    args = cli.build_parser().parse_args(argv)
    assert args.command == command and args.telemetry
    for flag, value in zip(flags[::2], flags[1::2]):
        assert getattr(args, flag.lstrip("-").replace("-", "_")) == value
    variant = read_engine_variant(edir, args.variant)
    with _benchmark_on_path():
        engine = get_engine(variant["engineFactory"])
        assert isinstance(engine, Engine)
        params = engine_params_from_json(engine, variant)
    (name, algo), = params.algorithm_params_list
    declared = variant["algorithms"][0]
    assert name == declared["name"] and algo.rank == declared[
        "params"]["rank"]


# ---------------------------------------------------------------------------
# (b), (d), (e) a tiny deploy, read with the benchmark's own readers
# ---------------------------------------------------------------------------

K, N_USERS = 10, 40


@pytest.fixture(scope="module")
def served():
    """child_serve.py's set-up at a tiny size (a COMPLETED instance of
    seeded factors in a memory store), `pio deploy`'s QueryAPI with
    batching and telemetry on behind a real socket, and what
    cell_serve.py reads of it: the batching block before the load, after
    one round of loadgen's closed loop and after a second, `/readyz`,
    and the compile counter round a query that has to compile."""
    from predictionio_tpu.data.api.http import make_server
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.storage import EngineInstance, Model
    from predictionio_tpu.models.recommendation import RecommendationEngine
    from predictionio_tpu.models.recommendation.als_algorithm import ALSModel
    from predictionio_tpu.workflow import model_io
    from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig

    variant = _json(_first_engine_dir("deploy"), "engine.json")
    mp = pytest.MonkeyPatch()
    # the CPU harness would else keep host arrays for a model this small
    mp.setenv("PIO_SERVE_DEVICE_MS", "1e9")
    telemetry.set_enabled(True)
    devicewatch.install()
    storage = use_memory_storage()
    rng = np.random.default_rng(5)
    U = rng.standard_normal((N_USERS, 8), dtype=np.float32)
    V = rng.standard_normal((300, 8), dtype=np.float32)
    now = dt.datetime.now(dt.timezone.utc)
    instance_id = storage.get_meta_data_engine_instances().insert(
        EngineInstance(
            id="", status="COMPLETED", start_time=now, end_time=now,
            engine_id=variant["id"], engine_version="NOT_USED",
            engine_variant=variant["id"],
            engine_factory=variant["engineFactory"],
            data_source_params=json.dumps(variant["datasource"]),
            preparator_params="{}",
            algorithms_params=json.dumps(variant["algorithms"]),
            serving_params="{}"))
    model = ALSModel(
        rank=8, user_factors=U, item_factors=V,
        user_vocab=BiMap({f"u{k}": k for k in range(N_USERS)}),
        item_vocab=BiMap({f"i{k}": k for k in range(300)}))
    storage.get_model_data_models().insert(Model(
        id=instance_id,
        models=model_io.serialize_models([model], check_finite=True)))
    api = QueryAPI(storage=storage, engine=RecommendationEngine(),
                   config=ServerConfig(batching="on", serve_quant="off"))
    server = make_server(api, "127.0.0.1", 0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        with _benchmark_on_path():
            cell_serve = _load("cell_serve")
            loadgen = sys.modules["loadgen"]
            dep = cell_serve.Deploy.__new__(cell_serve.Deploy)
            dep.port = port
            out = {"ready": cell_serve.get_json(port, "/readyz", 5),
                   "raw": cell_serve.get_json(port, "/")["batching"],
                   "b": [dep.batching()], "records": []}
            users = np.arange(N_USERS)
            for _round in range(2):
                records, _t0, _t1 = loadgen.closed_loop(
                    port, users, K, 4, 60.0)
                out["records"].append(records)
                out["b"].append(dep.batching())
            out["diffs"] = [cell_serve.diff(a, b)
                            for a, b in zip(out["b"], out["b"][1:])]
            # child_serve.py's `stats` reply round a query whose k no
            # program was prebuilt for
            c0 = devicewatch.compiles_total()
            status, data = loadgen.Client("127.0.0.1", port, 60.0).post(
                loadgen._body(0, 7))
            out["lazy"] = loadgen.parse_reply(status, data)
            out["compiles"] = (c0, devicewatch.compiles_total())
        yield out
    finally:
        server.shutdown()
        api.close()
        reset_storage()
        telemetry.set_enabled(None)
        mp.undo()


@pytest.mark.parametrize("key", _batching_keys())
def test_status_page_reports_the_batching_key(served, key):
    """A key cell_serve.py's `batching()` indexes is on `GET /`, a
    number (or a histogram of counts), and never runs backwards; the
    reader itself ran in the fixture, so a missing key is its KeyError
    there."""
    assert key in served["raw"], sorted(served["raw"])
    asked = 2 * N_USERS
    b0, b1, b2 = served["b"]
    if key.endswith("Hist"):
        field = {"batchSizeHist": "sizes", "bucketHist": "buckets"}[key]
        for b in (b1, b2):
            assert b[field] and all(
                isinstance(v, int) for v in b[field].values())
            assert sum(b[field].values()) == b["batches"]
    elif key.startswith("avg"):
        field = {"avgQueueWaitMs": "queue_wait_s",
                 "avgFlushMs": "flush_s"}[key]
        assert isinstance(served["raw"][key], (int, float))
        assert b0[field] <= b1[field] <= b2[field]
        assert all(d[field] >= 0 for d in served["diffs"])
        if key == "avgFlushMs":     # a flush takes time on any clock
            assert all(d[field] > 0 for d in served["diffs"])
    else:
        assert isinstance(served["raw"][key], int)
        assert b0[key] <= b1[key] <= b2[key]
        d1, d2 = served["diffs"]
        if key == "queries":
            assert d1[key] + d2[key] == asked
        elif key == "batches":      # two rounds, two flushes or more
            assert 1 <= d1[key] <= N_USERS and 1 <= d2[key] <= N_USERS
        else:
            assert key == "rejected" and d1[key] == d2[key] == 0


def test_readyz_says_ready(served):
    assert served["ready"].get("status") == "ready"


def test_compile_counter_counts_a_compile_on_the_serving_path(served):
    """`compile.in_window.*` is a difference of `compiles_total()`: an
    int, and a query that has to compile (a k nothing was prebuilt for)
    raises it."""
    before, after = served["compiles"]
    assert isinstance(before, int) and isinstance(after, int)
    assert served["lazy"] is not None and len(served["lazy"]) == 7
    assert after > before


def test_loadgen_reads_full_replies(served):
    """loadgen.py posts {"user": "u<ix>", "num": k} and parses
    `itemScores` of (item, score): every reply comes back full, with
    item names the reply check can turn back into indices."""
    for records in served["records"]:
        assert len(records) == N_USERS
        for _user, _t0, _t1, items in records:
            assert items is not None and len(items) == K
            assert all(name[0] == "i" and int(name[1:]) < 300
                       and isinstance(score, float)
                       for name, score in items)


# ---------------------------------------------------------------------------
# (c) a program a metric names is a jitted entry point's module
# ---------------------------------------------------------------------------

def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _lowered_topk_for_users(_mp):
    from predictionio_tpu.ops import topk
    return topk.topk_for_users.lower(
        _s((N_USERS, 8), np.float32), _s((300, 8), np.float32),
        _s((4,), np.int32), k=K)


def _lowered_topk_for_users_sharded(_mp):
    from predictionio_tpu.parallel import serve_dist
    rng = np.random.default_rng(5)
    sharded = serve_dist.shard_factors(
        rng.standard_normal((N_USERS, 8), dtype=np.float32),
        rng.standard_normal((300, 8), dtype=np.float32), n_shards=4)
    return serve_dist.sharded_program_specs(sharded, (4,), (K,))[0].lower()


def _lowered_masked_topk_rows(_mp):
    from predictionio_tpu.ops import topk
    return topk.masked_topk_rows.lower(
        _s((N_USERS, 8), np.float32), _s((300, 8), np.float32),
        _s((1, 300), np.uint32), _s((300,), np.bool_),
        _s((4,), np.int32), _s((4, 1), np.uint32),
        _s((4, topk.EXCLUDE_WIDTHS[0]), np.int32), k=K)


def _lowered_itemset_topk_rows(_mp):
    from predictionio_tpu.ops import topk
    return topk.itemset_topk_rows.lower(
        _s((300, 8), np.float32), _s((1, 300), np.uint32),
        _s((300,), np.bool_), _s((4, topk.QUERY_WIDTH), np.int32),
        _s((4, 1), np.uint32),
        _s((4, topk.EXCLUDE_WIDTHS[0]), np.int32), k=K)


def _lowered_trainer(entry, kernel):
    """The trainer as `pio train` reaches it: als.train_explicit on a
    tiny layout, with the jitted entry point lowered on the arguments
    the driver hands it."""
    def lowered(mp):
        from predictionio_tpu.ops import als
        real, seen = getattr(als, entry), []

        def spy(*args, **kwargs):
            seen.append(real.lower(*args, **kwargs))
            return real(*args, **kwargs)

        mp.setattr(als, entry, spy)
        mp.setenv("PIO_ALS_HOT_K", "8")     # a hot set 40 items can split
        rng = np.random.default_rng(3)
        u = rng.integers(0, 30, 600).astype(np.int32)
        i = rng.integers(0, 40, 600).astype(np.int32)
        r = rng.uniform(1, 5, 600).astype(np.float32)
        data = als.prepare_ratings(u, i, r, n_users=30, n_items=40)
        als.train_explicit(data, rank=4, iterations=1, kernel=kernel)
        assert seen, f"kernel={kernel!r} never reached als.{entry}"
        return seen[0]
    return lowered


#: program name in a metric file -> (the module jax names that jitted
#: entry point's program, its Lowered)
_ENTRY_POINTS = {
    "topk_for_users": ("jit_topk_for_users", _lowered_topk_for_users),
    "topk_for_users_sharded": ("jit_topk_for_users_sharded",
                               _lowered_topk_for_users_sharded),
    "masked_topk_rows": ("jit_masked_topk_rows", _lowered_masked_topk_rows),
    "itemset_topk_rows": ("jit_itemset_topk_rows",
                          _lowered_itemset_topk_rows),
    "train_hybrid": ("jit__train_hybrid_jit",
                     _lowered_trainer("_train_hybrid_jit", "hybrid")),
    "train_csrb": ("jit__train_csrb_jit",
                   _lowered_trainer("_train_csrb_jit", "csrb")),
    "train_explicit": ("jit__train_explicit_jit",
                       _lowered_trainer("_train_explicit_jit", "scan")),
}


@pytest.mark.parametrize("name", _program_names())
def test_metric_program_name_is_a_jitted_entry_points_module(
        name, monkeypatch):
    """The trace reader finds a program by searching the module names of
    the `XLA Modules` line for the metric's pattern (reduce.py `_term`);
    jax names a module `jit_<function>`. The entry point lowers to
    exactly the module placed here, and the metric's pattern finds it."""
    assert name in _ENTRY_POINTS, (
        f"benchmark/ names a program {name!r} this test cannot place: "
        "add the entry point that lowers to it")
    expected, lowered = _ENTRY_POINTS[name]
    module = re.search(r"module @(\w+)", lowered(monkeypatch).as_text()
                       ).group(1)
    assert module == expected and re.search(name, module), module


def _cell_program_patterns():
    """{cell: the program patterns its trace metrics search with}."""
    out = {}
    for m in BENCHMARK["per_layer"]:
        spec = json.dumps(_json(BENCH, "metrics", m["name"] + ".json"))
        pats = set(re.findall(
            r'"(?:program_s|program_n|program)": "([^"]+)"', spec))
        for cell in m.get("workloads", ()):
            out.setdefault(cell, set()).update(pats)
    return out


#: the cells whose engine applies business rules on the device: cell ->
#: (its program, configuration, engine factory, `GET /` block, layout,
#: the rule spans of a flush)
RULE_CELLS = {
    "serve.ecomm-amazon-r128.closed128": dict(
        program="masked_topk_rows", config="ecomm-als-amazon-r128",
        block="ecomm", layout="replicated+rules",
        spans={"rules", "rules.seen", "rules.constraint"}),
    "serve.simprod-amazon14-r128.closed128": dict(
        program="itemset_topk_rows", config="simprod-als-amazon14-r128",
        block="simprod", layout="items+rules",
        spans={"rules", "rules.items"}),
}
ECOMM_CELL = "serve.ecomm-amazon-r128.closed128"


@pytest.mark.parametrize("cell", sorted(RULE_CELLS))
def test_the_rule_cells_patterns_and_the_other_cells_keep_apart(cell):
    """PERF.md 7.7: a pattern is searched over every module name of a
    capture. A rule cell's pattern finds its own program's module and
    no other cell's; theirs do not find it."""
    program = RULE_CELLS[cell]["program"]
    pats = _cell_program_patterns()
    mine = pats.pop(cell)
    assert mine == {program}
    others = set().union(*pats.values())
    assert others and not mine & others
    modules = {name: module for name, (module, _l) in _ENTRY_POINTS.items()}
    for pat in mine:
        assert [n for n, mod in modules.items() if re.search(pat, mod)] \
            == [program]
    for pat in others:
        assert not re.search(pat, modules[program]), pat


def test_the_ecomm_cells_patterns_and_the_other_cells_keep_apart():
    """The name this case had when the e-commerce cell was the only
    one with rules; the parametrized case above holds both."""
    assert _cell_program_patterns()[ECOMM_CELL] == {"masked_topk_rows"}


# ---------------------------------------------------------------------------
# the cells with rules: every counter path and span name their metric
# files name is one a live deploy has (ISSUE 34's two cases, PERF.md 7.8)
# ---------------------------------------------------------------------------

def _metric_terms(cell, kind):
    """The `counter` / `traced_counter` paths (kind "counter") or the
    `span_s` / `span_n` patterns (kind "span") of the cell's metric
    files."""
    keys = {"counter": ("counter", "traced_counter"),
            "span": ("span_s", "span_n")}[kind]
    found = set()

    def walk(node):
        if isinstance(node, dict):
            for k, v in node.items():
                if k in keys:
                    found.add(v)
                else:
                    walk(v)
        elif isinstance(node, list):
            for v in node:
                walk(v)

    for m in BENCHMARK["per_layer"]:
        if cell in m.get("workloads", ()):
            walk(_json(BENCH, "metrics", m["name"] + ".json"))
    return sorted(found)


def _ecomm_metric_terms(kind):
    return _metric_terms(ECOMM_CELL, kind)


def _rule_cell_terms(kind):
    return [(cell, term) for cell in sorted(RULE_CELLS)
            if cell != ECOMM_CELL for term in _metric_terms(cell, kind)]


@contextlib.contextmanager
def _rule_cell_served(cell):
    """The cell's adapter at rehearsal size (its own `models`: the
    engine's model and whatever it reads while it serves), deployed by
    QueryAPI with batching and telemetry on; a few queries of its own
    generator answered on the batcher's worker with the spans recorded;
    -> the `GET /` page before and after, the span names, the replies."""
    from predictionio_tpu.common import profiling
    from predictionio_tpu.data.storage import EngineInstance, Model
    from predictionio_tpu.workflow import model_io
    from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig

    cfg = _json(ROOT, _CONFIG_FILE[RULE_CELLS[cell]["config"]])
    variant = _json(ROOT, cfg["engine_dir"], "engine.json")
    module, _, factory = variant["engineFactory"].partition(":")
    engine = getattr(importlib.import_module(module), factory)()
    mp = pytest.MonkeyPatch()
    mp.setenv("PIO_SERVE_DEVICE_MS", "1e9")
    telemetry.set_enabled(True)
    devicewatch.install()
    storage = use_memory_storage()
    spans = []
    real_annotate = profiling.annotate

    def annotate(name):
        spans.append(name)
        return real_annotate(name)

    mp.setattr(profiling, "annotate", annotate)
    try:
        with _benchmark_on_path():
            harness = _load("harness")
            sys.modules["harness"] = harness
            adapter = harness.adapter_of(cfg)
            spec = {"config": cfg, "traffic": _json(
                BENCH, "traffic", "closed128.json")}
            model = adapter.rehearsal_model(cfg["model"], 4000)
            # `models` is the deploy child's half of an adapter and may
            # hold the process it runs in to the configuration's layout
            # (adapters/simprod_als.py wraps `prepare_serving`): undone
            # with the rest
            for algo in set(engine.algorithm_class_map.values()):
                mp.setattr(algo, "prepare_serving", algo.prepare_serving)
            models = adapter.models(cfg, model, 5, storage, variant)
            asked = adapter.queries(spec, model, 5, 48)
            wire = adapter.wire(spec)
            now = dt.datetime.now(dt.timezone.utc)
            instance_id = storage.get_meta_data_engine_instances().insert(
                EngineInstance(
                    id="", status="COMPLETED", start_time=now, end_time=now,
                    engine_id=variant["id"], engine_version="NOT_USED",
                    engine_variant=variant["id"],
                    engine_factory=variant["engineFactory"],
                    data_source_params=json.dumps(variant["datasource"]),
                    preparator_params="{}",
                    algorithms_params=json.dumps(variant["algorithms"]),
                    serving_params="{}"))
            storage.get_model_data_models().insert(Model(
                id=instance_id, models=model_io.serialize_models(
                    models, check_finite=True)))
            api = QueryAPI(storage=storage, engine=engine,
                           config=ServerConfig(batching="on"))
            try:
                _st, before = api.handle("GET", "/")
                replies = []
                for query in asked:
                    status, body = api.handle(
                        "POST", "/queries.json", body=wire.body(query).encode())
                    replies.append(wire.parse(status, json.dumps(body)))
                _st, after = api.handle("GET", "/")
            finally:
                api.close()
            whole = [wire.whole(q, r) for q, r in zip(asked, replies)]
        yield {"before": before, "after": after, "spans": set(spans),
               "replies": replies, "whole": whole}
    finally:
        sys.modules.pop("harness", None)
        reset_storage()
        telemetry.set_enabled(None)
        mp.undo()


@pytest.fixture(scope="module")
def ecomm_served():
    with _rule_cell_served(ECOMM_CELL) as served:
        yield served


@pytest.fixture(scope="module")
def rule_cells_served():
    """cell -> what `_rule_cell_served` saw, for the rule cells that
    came after the e-commerce one."""
    out = {}
    for cell in sorted(RULE_CELLS):
        if cell != ECOMM_CELL:
            with _rule_cell_served(cell) as served:
                out[cell] = served
    return out


def _counter_ends(served, path):
    """benchmark/reduce.py `_counter` walks the dotted path into `GET /`
    and wants a number that does not run backwards."""
    ends = []
    for page in (served["before"], served["after"]):
        node = page
        for key in path.split("."):
            assert isinstance(node, dict) and key in node, (path, key)
            node = node[key]
        assert isinstance(node, (int, float)) and not isinstance(node, bool)
        ends.append(node)
    assert ends[0] <= ends[1]
    return ends


def test_ecomm_cell_deploys_on_the_device_layout_and_answers(ecomm_served):
    b = ecomm_served["after"]["batching"]
    assert b["layout"] == "replicated+rules" and b["excludeWidths"]
    assert all(ecomm_served["whole"]) and any(ecomm_served["replies"])


@pytest.mark.parametrize("path", _ecomm_metric_terms("counter"))
def test_ecomm_counter_path_is_on_the_status_page(ecomm_served, path):
    ends = _counter_ends(ecomm_served, path)
    if path == "ecomm.queries":
        assert ends[1] - ends[0] == len(ecomm_served["replies"])
    if path == "ecomm.hostFallbacks":
        assert ends[1] == ends[0]


@pytest.mark.parametrize("pattern", _ecomm_metric_terms("span"))
def test_ecomm_span_is_one_annotate_emits(ecomm_served, pattern):
    """A `span_s` term is searched over the names common/profiling.py
    annotate gave the worker's spans."""
    assert [n for n in ecomm_served["spans"] if re.search(pattern, n)], (
        pattern, sorted(ecomm_served["spans"]))


def test_ecomm_flush_emits_the_rule_spans_and_the_shared_stages(
        ecomm_served):
    assert {"rules", "rules.seen", "rules.constraint", "pad", "execute",
            "enqueue", "device_get", "unpack"} <= ecomm_served["spans"]


@pytest.mark.parametrize("cell", [c for c in sorted(RULE_CELLS)
                                  if c != ECOMM_CELL])
def test_rule_cell_deploys_on_the_device_layout_and_answers(
        rule_cells_served, cell):
    served, about = rule_cells_served[cell], RULE_CELLS[cell]
    b = served["after"]["batching"]
    assert b["layout"] == about["layout"] and b["excludeWidths"]
    # the layout the cell's adapter holds its deploy to
    cfg = _json(ROOT, _CONFIG_FILE[about["config"]])
    assert cfg["serving"]["deploy_layout"] == b["layout"]
    assert b["perShardBytes"] > 0 and b["topkSelection"]
    assert all(served["whole"]) and any(served["replies"])
    assert about["spans"] | {"pad", "execute", "enqueue", "device_get",
                             "unpack"} <= served["spans"]


@pytest.mark.parametrize("cell,path", _rule_cell_terms("counter"))
def test_rule_cell_counter_path_is_on_the_status_page(
        rule_cells_served, cell, path):
    ends = _counter_ends(rule_cells_served[cell], path)
    block = RULE_CELLS[cell]["block"]
    if path == block + ".queries":
        assert ends[1] - ends[0] == len(rule_cells_served[cell]["replies"])
    if path == block + ".hostFallbacks":
        assert ends[1] == ends[0]


@pytest.mark.parametrize("cell,pattern", _rule_cell_terms("span"))
def test_rule_cell_span_is_one_annotate_emits(rule_cells_served, cell,
                                              pattern):
    assert [n for n in rule_cells_served[cell]["spans"]
            if re.search(pattern, n)], (
        pattern, sorted(rule_cells_served[cell]["spans"]))


# ---------------------------------------------------------------------------
# the parked training cell's phases
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trained_instance():
    """child_train.py's job at a tiny size: `pio train --telemetry` on
    the parked cell's engine, fed through bench_engine.FEED; -> the one
    COMPLETED instance it leaves."""
    from predictionio_tpu.tools import cli

    engine_dir = _first_engine_dir("train")
    mp = pytest.MonkeyPatch()
    mp.setenv("PIO_TELEMETRY", "1")     # --telemetry sets it: undone below
    mp.setenv("PIO_TRAIN_STREAM", "off")
    storage = use_memory_storage()
    try:
        with _benchmark_on_path():
            import bench_engine
            rng = np.random.default_rng(3)
            sets = [(rng.integers(0, 30, 600).astype(np.int32),
                     rng.integers(0, 40, 600).astype(np.int32),
                     rng.uniform(1, 5, 600).astype(np.float32))
                    for _ in range(2)]
            bench_engine.FEED = bench_engine.Feed(sets, 30, 40)
            rc = cli.main(["train", "--engine-dir", engine_dir,
                           *PARKED_TRAIN_ARGV])
            assert rc == 0 and bench_engine.FEED.taken == 1
        rows = [i for i in
                storage.get_meta_data_engine_instances().get_all()
                if i.status == "COMPLETED"]
        assert len(rows) == 1
        yield rows[0]
    finally:
        reset_storage()
        telemetry.set_enabled(None)
        mp.undo()


@pytest.mark.parametrize("phase", _phase_facts())
def test_train_instance_carries_the_phase_the_metric_reads(
        trained_instance, phase):
    """child_train.py turns the instance's `phase_<name>_s` fields into
    the facts `phase.<name>_s` / `traced.phase.<name>_s`."""
    value = trained_instance.runtime_conf.get(f"phase_{phase}_s")
    assert value is not None, sorted(trained_instance.runtime_conf)
    assert float(value) >= 0
