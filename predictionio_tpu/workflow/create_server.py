"""The engine (deploy) server.

Reference: core/.../workflow/CreateServer.scala:105-697. The daemon loads
the latest COMPLETED EngineInstance's engine + models, pushes model arrays
into device memory (prepare_deploy), and answers:

  GET  /             -> status (engine instance info + serving stats)
  POST /queries.json -> supplement -> predict per algorithm -> serve
  POST /reload       -> hot-swap to the latest COMPLETED instance
  POST /stop         -> shut the server down
  GET  /plugins.json -> plugin inventory
  GET  /plugins/<type>/<name>/... -> plugin REST handoff

The query hot path never touches the host-side event store for ALS-style
models: factors stay device-resident between requests (BASELINE.json
north star).
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import logging
import os
import random
import string
import threading
import time
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

from predictionio_tpu.common import (
    devicewatch, history, journal, profiling, resilience, slo, telemetry,
    tracing, waterfall,
)
from predictionio_tpu.controller.engine import Engine, EngineParams
from predictionio_tpu.controller.persistent_model import PersistentModelManifest
from predictionio_tpu.data.api import http as http_transport
from predictionio_tpu.data.event import (
    format_event_time, tree_has_non_finite, utcnow,
)
from predictionio_tpu.data.storage import Storage, get_storage
from predictionio_tpu.serving import registry as registry_mod
from predictionio_tpu.serving.registry import (
    DEFAULT_TENANT, AdmissionError, ModelRegistry, ServableModel, TenantSpec,
)
from predictionio_tpu.workflow import json_extractor, model_io
from predictionio_tpu.workflow.context import WorkflowContext
from predictionio_tpu.workflow.server_plugins import EngineServerPluginContext
from predictionio_tpu.workflow.workflow_utils import get_engine, load_object

logger = logging.getLogger("predictionio_tpu.server")

#: (status, payload) or (status, payload, extra_headers) — the transport
#: (data/api/http.py) forwards the optional third element as response
#: headers (Retry-After on 503 saturation).
Response = Tuple[int, Any]

#: how each 200-or-500 reply was checked for NaN / infinity: in the walk
#: that built it, or again after feedback or an output blocker touched it
_M_REPLY_CHECKS = telemetry.registry().counter(
    "pio_reply_checks_total",
    "Replies checked for non-finite numbers, by whether the pass that "
    "built the JSON value did it (folded) or a second walk (walked)",
    labelnames=("kind",))
_M_REPLY_FOLDED = _M_REPLY_CHECKS.labels(kind="folded")
_M_REPLY_WALKED = _M_REPLY_CHECKS.labels(kind="walked")


def _codec_status() -> Dict[str, Any]:
    """`GET /`'s codec block, beside `batching`: the process-wide
    counters /metrics has, which stand still once every query and
    result class has been seen."""
    return {**json_extractor.stats(), "replyChecks": {
        "folded": int(_M_REPLY_FOLDED.value),
        "walked": int(_M_REPLY_WALKED.value)}}


def _host_status() -> Dict[str, Any]:
    """`GET /`'s process-wide host seconds (common/profiling.py): the
    counted spans of the batcher's lanes and the training phases
    (`hostSpans`), and the process's CPU, threads and run-queue wait
    (`host`)."""
    return {"hostSpans": profiling.span_totals(),
            "host": profiling.host_status()}


#: distinguishes concurrently-live QueryAPI instances in the process
#: metrics registry (tests, blue/green deploys in one process)
_query_api_seq = itertools.count()


@dataclasses.dataclass
class ServerConfig:
    """CreateServer args (CreateServer.scala:77-103) + micro-batching
    knobs (serving/batcher.py; no reference analogue — the reference
    answers strictly one query per request)."""
    engine_instance_id: Optional[str] = None
    engine_id: str = "default"
    engine_version: str = "NOT_USED"
    engine_variant: str = "default"
    engine_dir: Optional[str] = None
    ip: str = "localhost"
    port: int = 8000
    feedback: bool = False
    event_server_ip: str = "localhost"
    event_server_port: int = 7070
    access_key: Optional[str] = None
    verbose: bool = False
    #: "auto" batches when any algorithm has a real predict_batch
    #: (serving.protocol.batch_capable); "on" forces the batcher even for
    #: fallback-only engines (still amortizes queueing); "off" keeps the
    #: original one-query-per-request path, byte for byte.
    batching: str = "auto"
    batch_max_size: int = 64
    batch_max_delay_ms: float = 2.0
    #: admission control: queue depth beyond which /queries.json answers
    #: 503 + Retry-After instead of letting latency grow without bound.
    batch_max_queue: int = 256
    #: graceful-drain budget (SIGTERM / drain()): how long to wait for
    #: the batcher lanes to finish every admitted in-flight batch
    #: before the server exits anyway.
    drain_grace_s: float = 30.0
    #: AOT prebuild (serving/aot.py): "auto"/"on" eagerly compile every
    #: enumerated (bucket, template, k) serving program before /readyz
    #: flips ready and mark the recompile watchdog's warmup done; "off"
    #: keeps lazy first-dispatch compilation. PIO_AOT=0/1 overrides.
    aot: str = "auto"
    #: prebuild thread-pool width (0 = PIO_AOT_THREADS or default 4)
    aot_threads: int = 0
    #: SLO targets (common/slo.py): availability = fraction of non-5xx
    #: responses, latency = fraction of serves at/under the threshold.
    #: None defers to PIO_SLO_AVAILABILITY / PIO_SLO_LATENCY_MS /
    #: PIO_SLO_LATENCY_TARGET (defaults 0.999 / 25 ms / 0.99); the
    #: engine exports budget + burn-rate gauges at scrape time and
    #: feeds the `pio doctor` SLO line.
    slo_availability: Optional[float] = None
    slo_latency_ms: Optional[float] = None
    slo_latency_target: Optional[float] = None
    #: sharded serving (parallel/serve_dist.py): "on" row-shards the
    #: deployed factor matrices over every visible device and serves
    #: top-k from per-device local shards (bit-identical results;
    #: per-device HBM drops to total/n_dev); "auto" does so only on a
    #: real multi-device accelerator mesh and falls back to replicated
    #: on /reload hot-swap; "off" keeps the replicated path.
    #: PIO_SERVE_SHARD overrides.
    shard_serving: str = "auto"
    #: quantized serving (ops/quant.py): "on" serves top-k from int8
    #: factor matrices with per-row fp32 scales (~4x less HBM footprint
    #: and bandwidth; ranking-parity contract, KNOWN_ISSUES #12);
    #: "auto" quantizes only on a real accelerator backend AND when the
    #: deploy-time recall probe clears the floor; "off" keeps today's
    #: bit-compatible fp32 path. Composes with shard_serving (int8
    #: shards). PIO_SERVE_QUANT overrides.
    serve_quant: str = "auto"
    #: realtime fold-in (realtime/foldin.py): "on" runs the streaming
    #: speed-layer worker in-process — tail the event store, re-solve
    #: dirty users against the fixed item matrix with the ALS
    #: half-step, publish rows atomically into the live serving model
    #: (new users append into pre-padded headroom; exhaustion falls
    #: back to the /reload hot-swap). "off" (default) keeps every
    #: endpoint byte-identical. PIO_FOLDIN overrides.
    foldin: str = "off"
    #: fold-in tick cadence in ms (how often the tail is read and
    #: dirty users are re-solved; 0 = PIO_FOLDIN_TICK_MS or 250)
    foldin_tick_ms: float = 0.0
    #: user-row capacity headroom pre-padded at load for fold-in
    #: appends (0 = PIO_FOLDIN_HEADROOM or 1024)
    foldin_headroom: int = 0
    #: item-row capacity headroom pre-padded at load for fold-in of
    #: unseen ITEMS (0 = PIO_FOLDIN_ITEM_HEADROOM or 1024)
    foldin_item_headroom: int = 0
    #: partition-routed deploy (parallel/serve_dist.py helpers +
    #: workflow/router.py scatter/merge): "i/N" scopes this replica to
    #: the contiguous item-row range partition_rows(n_items, i, N) —
    #: item factors AND item vocab are sliced before prepare_serving,
    #: so sharding/quant/AOT/fold-in all see only the owned rows and
    #: per-replica HBM drops to ~1/N. /readyz and GET / advertise the
    #: owned range; /queries.json responses carry the candidates'
    #: global indices so the router's merge_candidates twin reassembles
    #: a bit-identical full-model answer. "" (default) keeps every
    #: endpoint wire-byte identical. PIO_DEPLOY_PARTITION overrides.
    partition: str = ""
    #: multi-tenant deploy (serving/registry.py): the parsed
    #: ``pio deploy --engines conf.json`` tenant specs. Empty () is the
    #: legacy single-engine server — every endpoint stays wire-byte
    #: identical (asserted by test). Non-empty hosts one ModelRegistry
    #: of N generation-versioned servables with per-tenant batcher
    #: queues, HBM budgets, and per-access-key admission; unset
    #: per-tenant knobs inherit the deploy-wide values above.
    tenants: Tuple[TenantSpec, ...] = ()


def resolve_engine_instance(storage: Storage, config: ServerConfig):
    """Latest COMPLETED instance unless one is pinned
    (commands/Engine.scala:224-239)."""
    instances = storage.get_meta_data_engine_instances()
    if config.engine_instance_id:
        instance = instances.get(config.engine_instance_id)
        if instance is None:
            raise ValueError(
                f"EngineInstance {config.engine_instance_id} not found")
        if instance.status != "COMPLETED":
            raise ValueError(
                f"EngineInstance {instance.id} is {instance.status}, not "
                "COMPLETED; cannot deploy")
        return instance
    instance = instances.get_latest_completed(
        config.engine_id, config.engine_version, config.engine_variant)
    if instance is None:
        raise ValueError(
            "No valid engine instance found for engine "
            f"{config.engine_id} {config.engine_version} "
            f"{config.engine_variant}. Try running `pio train` first.")
    return instance


def _train_cursor(instance) -> Optional[Any]:
    """The event-store cursor `run_train` snapshotted at the head of
    the training read (runtime_conf["train_cursor"], JSON-encoded).
    None for pre-cursor ledger rows — the fold-in rebase then restarts
    from the live tail head instead."""
    raw = (getattr(instance, "runtime_conf", None) or {}).get("train_cursor")
    if not raw:
        return None
    try:
        return json.loads(raw) if isinstance(raw, str) else raw
    except ValueError:
        return None


def engine_params_from_instance(engine: Engine, instance) -> EngineParams:
    """Rebuild EngineParams from the ledger row's JSON snapshots
    (Engine.engineInstanceToEngineParams, Engine.scala:422-492)."""
    def subtree(raw):
        obj = json.loads(raw or "{}")
        # rows hold either the {"params": {...}} subtree (as snapshotted
        # from engine.json by run_train) or bare params
        return obj if (not obj or "params" in obj) else {"params": obj}

    variant = {
        "datasource": subtree(instance.data_source_params),
        "preparator": subtree(instance.preparator_params),
        "serving": subtree(instance.serving_params),
    }
    algos = json.loads(instance.algorithms_params or "[]")
    if algos:
        variant["algorithms"] = algos
    return engine.engine_params_from_json(variant)


def _datasource_appname(engine_params) -> Optional[str]:
    """Best-effort appName from the variant's datasource params — the
    same field fold-in and eval use to find the engine's app."""
    dsp = getattr(engine_params, "data_source_params", None)
    app_name = getattr(dsp, "appName", None)
    return str(app_name) if app_name else None


def prepare_deploy(ctx, engine: Engine, engine_params: EngineParams,
                   instance_id: str, models: List[Any],
                   algorithms: Optional[List[Any]] = None) -> List[Any]:
    """Make persisted models servable (Engine.prepareDeploy,
    Engine.scala:199-269): manifest -> user loader; None -> retrain;
    otherwise hand the host-side blob to the algorithm. Device placement
    is each algorithm's prepare_serving decision (the recommendation
    template probes the deployed chip and moves factors into HBM only
    when the fused device dispatch actually wins) — a blanket
    device_put here made every host-numpy serving path pull the full
    factor matrix back over the link per query."""
    if algorithms is None:
        _, _, algorithms, _ = engine._instantiate(engine_params)
    out = []
    retrained: Optional[List[Any]] = None
    for i, (algo, model) in enumerate(zip(algorithms, models)):
        if isinstance(model, PersistentModelManifest):
            loader = load_object(f"{model.module_name}:{model.class_name}")
            out.append(loader.load(
                instance_id, getattr(algo, "_pio_params", None), ctx))
        elif model is None:
            # un-persistable model: retrain on deploy (Engine.scala:211-229)
            if retrained is None:
                logger.info("Some models cannot be loaded; retraining.")
                retrained = engine.train(ctx, engine_params)
            out.append(retrained[i])
        else:
            out.append(model)
    return out


def _partition_models(models: List[Any], index: int,
                      count: int) -> Tuple[List[Any], Dict[str, Any]]:
    """Slice every partitionable model down to the item rows partition
    ``index`` of ``count`` owns (parallel/serve_dist.py:partition_rows).

    A model is partitionable when it exposes ``item_factors`` + an
    ``item_vocab`` BiMap (the ALSModel shape). The slice is
    order-preserving — global item index ``g`` in [lo, hi) becomes local
    index ``g - lo`` — so the replica's local two-key top-k tie order
    equals the full model's order over those rows, which is what makes
    the router's merge_candidates reassembly bit-identical. The vocab is
    rebuilt over the owned rows only, so predict paths, k-clamping
    (min(num, len(item_vocab))) and name lookups all work unchanged."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.parallel.serve_dist import partition_rows
    state: Optional[Dict[str, Any]] = None
    out: List[Any] = []
    for m in models:
        fac = getattr(m, "item_factors", None)
        vocab = getattr(m, "item_vocab", None)
        if fac is None or vocab is None:
            out.append(m)
            continue
        n_items = len(vocab)
        lo, hi = partition_rows(n_items, index, count)
        inv = vocab.inverse()
        sliced_vocab = BiMap({inv(g): g - lo for g in range(lo, hi)})
        out.append(dataclasses.replace(
            m, item_factors=fac[lo:hi], item_vocab=sliced_vocab))
        state = {"index": index, "count": count, "lo": lo, "hi": hi,
                 "rows": hi - lo, "nItems": n_items}
    if state is None:
        raise ValueError(
            f"--partition {index}/{count} requested but no deployed model "
            "exposes item_factors + item_vocab to slice")
    return out, state


def _topk_selection(models: List[Any]) -> Optional[Dict[str, str]]:
    """Which selection the deployed top-k programs were built with, per
    declared k (serving/aot.py serving_ks): "sort", or "chunked L=512
    C=4767" — ops/topk.py selection_name, the kernel's own test of the
    static shape, over the score row those programs select from: the
    item rows of the first model of the ALSModel shape, padding and
    fold-in headroom included (the int8 layout's padded columns where
    that serves; ONE shard's rows, rowsPerShard.items, where the layout
    is row-sharded: parallel/serve_dist.py selects on each shard's own
    scores). None where no program of ops/topk.py, ops/quant.py or
    parallel/serve_dist.py serves: no such model, or host factors."""
    import numpy as np

    from predictionio_tpu.ops import topk
    from predictionio_tpu.serving import aot
    for m in models:
        rows = getattr(m, "topk_rows", None)
        if rows is not None:
            # a model that says itself which row its programs select
            # from (models/ecommerce: ops/topk.py masked_topk_rows;
            # models/similarproduct: itemset_topk_rows)
            n = rows()
            if n is None:
                continue
            return {str(k): topk.selection_name(n, k)
                    for k in aot.serving_ks(n)}
        fac = getattr(m, "item_factors", None)
        if fac is None:
            continue
        sharding = getattr(m, "sharding", None)
        quant = getattr(m, "quant", None)
        if sharding is not None:
            # the declared ks are clamped to the whole model, as
            # sharded_program_specs is handed them
            return {str(k): topk.selection_name(sharding.rows_dev_i, k)
                    for k in aot.serving_ks(sharding.n_items)}
        if quant is not None:
            n = int(np.shape(quant.vt_q)[1])
        elif isinstance(fac, np.ndarray):
            continue
        else:
            n = int(np.shape(fac)[0])
        return {str(k): topk.selection_name(n, k)
                for k in aot.serving_ks(n)}
    return None


def _serving_layout(models: List[Any]) -> Dict[str, Any]:
    """Where the factors that answer the flushes live, for the first
    model of the ALSModel shape: "row-sharded" over ``shards`` devices
    (parallel/serve_dist.py ShardedFactors),
    "replicated" device arrays on one, or "host"; null where no model
    has that shape. ``perShardBytes`` is what one device holds of them:
    a shard's factor bytes, or the registry's estimate of the model. A
    model of another shape names its own layout (models/ecommerce:
    "replicated+rules" with its declared ``excludeWidths``;
    models/similarproduct: "items+rules" with ``queryWidth`` beside
    them; or "host")."""
    import numpy as np

    for m in models:
        own = getattr(m, "serving_layout", None)
        if own is not None:
            return own()
        fac = getattr(m, "item_factors", None)
        if fac is None:
            continue
        sharding = getattr(m, "sharding", None)
        if sharding is not None:
            # ShardedFactors.summary()'s shards and perShardFactorBytes
            return {"layout": "row-sharded", "shards": sharding.n_shards,
                    "perShardBytes": sharding.per_shard_bytes()}
        quant = getattr(m, "quant", None)
        if quant is not None:
            # the int8 blocks are the only device copy (ops/quant.py)
            return {"layout": "replicated", "shards": 1,
                    "perShardBytes": quant.int8_bytes()}
        if isinstance(fac, np.ndarray):
            return {"layout": "host", "shards": 0, "perShardBytes": 0}
        return {"layout": "replicated", "shards": 1,
                "perShardBytes": registry_mod.model_hbm_bytes([m])}
    return {"layout": None, "shards": 0, "perShardBytes": 0}


class QueryAPI:
    """Pure route handler for the engine server (ServerActor routes,
    CreateServer.scala:384-693)."""

    def __init__(self, config: Optional[ServerConfig] = None,
                 storage: Optional[Storage] = None,
                 ctx: Optional[WorkflowContext] = None,
                 plugin_context: Optional[EngineServerPluginContext] = None,
                 engine: Optional[Engine] = None):
        self.config = config or ServerConfig()
        self.storage = storage or get_storage()
        self.ctx = ctx or WorkflowContext(storage=self.storage)
        self.plugin_context = plugin_context or EngineServerPluginContext()
        self._engine_override = engine
        self._lock = threading.Lock()
        self._stop_requested = threading.Event()
        self._draining = threading.Event()
        self._batcher = None
        #: the model registry replaces the single model field: every
        #: deploy — legacy included — publishes its servable(s) here.
        #: A legacy deploy installs one servable under DEFAULT_TENANT
        #: and keeps mirroring the flat attributes below for
        #: compatibility; a --engines deploy hosts N of them with
        #: per-tenant queues/budgets/admission.
        self.registry = ModelRegistry()
        #: per-access-key admission (multi-tenant only; None = legacy
        #: open door, wire parity)
        self._admission: Optional[registry_mod.AdmissionController] = None
        self._m_tenant_requests = None
        # serving stats (CreateServer.scala:399-401)
        self.request_count = 0
        self.avg_serving_sec = 0.0
        self.last_serving_sec = 0.0
        self.start_time = utcnow()
        #: model generation: bumped on every successful _load (initial
        #: deploy = 1, each /reload hot-swap +1). The journal's
        #: lifecycle events carry it, so "which model answered this?"
        #: joins against "when did that generation land?" — the
        #: zero-downtime hot-swap ROADMAP item reports into exactly
        #: this field.
        self.generation = 0
        # degraded accounting is registry-backed (single source of truth
        # for GET / and GET /metrics), per-instance labeled so a fresh
        # server starts at zero. TWO metrics because the batched serving
        # path's degraded flag is BATCH-granular (KNOWN_ISSUES #6): a
        # failed side-channel lookup taints every response of its flush,
        # so the per-query count is an UPPER BOUND on affected queries —
        # pio_degraded_batches_total counts actual tainted flushes.
        inst = {"server": f"query#{next(_query_api_seq)}"}
        # device observability: compile watchdog + HBM/live-array gauges
        # on this daemon's /metrics and /debug/device.json (idempotent)
        devicewatch.install()
        # the host's counted spans on /metrics, derived at scrape time
        # (idempotent: the registry dedupes the callable)
        telemetry.registry().register_collector(profiling.collect_spans)
        # SLO engine: this server's configured targets win over any
        # default install from a sibling daemon in the process
        slo.install(slo.SLOConfig.from_env(
            availability=self.config.slo_availability,
            latency_ms=self.config.slo_latency_ms,
            latency_target=self.config.slo_latency_target))
        # metrics flight recorder: bounded time-series rings behind
        # /debug/history.json (one sampler thread per process)
        history.install()
        #: wall-clock from construction to servable (model loaded, AOT
        #: prebuild done) — the metric the <10 s warm-replica gate reads
        self.time_to_ready_s: Optional[float] = None
        self._aot_state: Optional[Dict[str, Any]] = None
        self._shard_state: Optional[Dict[str, Any]] = None
        self._quant_state: Optional[Dict[str, Any]] = None
        #: partition-routed deploy: the owned item-row range advertised
        #: on /readyz and GET /; None = full-model replica (wire parity)
        self._partition_state: Optional[Dict[str, Any]] = None
        self._partition_spec = (self.config.partition
                                or os.environ.get("PIO_DEPLOY_PARTITION", ""))
        if self._partition_spec and self.config.tenants:
            raise ValueError(
                "--partition is a single-engine deploy scope; it does not "
                "compose with --engines multi-tenancy")
        #: realtime fold-in worker (realtime/foldin.py) — one per
        #: server, re-bound to each model generation by _load
        self._foldin_worker = None
        reg = telemetry.registry()
        self._m_time_to_ready = reg.gauge(
            "pio_time_to_ready_seconds",
            "Deploy wall-clock until servable: model load + device "
            "placement + AOT program prebuild (serving/aot.py)",
            labelnames=("server",)).labels(**inst)
        self._m_degraded_queries = reg.counter(
            "pio_degraded_queries_upper_bound",
            "Responses flagged degraded; batch-granular taint makes this "
            "an UPPER BOUND on truly affected queries (KNOWN_ISSUES #6)",
            labelnames=("server",)).labels(**inst)
        self._m_degraded_batches = reg.counter(
            "pio_degraded_batches_total",
            "Batched flushes tainted by a failed side-channel lookup "
            "(each taints up to batch_max_size responses)",
            labelnames=("server",)).labels(**inst)
        self._load()

    @property
    def degraded_count(self) -> int:
        """Legacy per-query degraded counter (the `GET /` degradedCount
        field), now read from the registry. Batch-granular: an upper
        bound on affected queries when batching is on."""
        return int(self._m_degraded_queries.value)

    # ------------------------------------------------------------- loading
    def _load(self) -> None:
        """Load (or hot-swap) every configured servable: the legacy
        single-engine path when no tenants are configured, else one
        registry install per tenant spec. POST /reload funnels here
        for both shapes — a multi-tenant reload hot-swaps every
        tenant, each against its own latest COMPLETED instance."""
        if self.config.tenants:
            self._load_tenants()
        else:
            self._load_single()

    def _load_single(self) -> None:
        t_load = time.perf_counter()
        instance = resolve_engine_instance(self.storage, self.config)
        engine = self._engine_override or get_engine(
            instance.engine_factory, base_dir=self.config.engine_dir)
        engine_params = engine_params_from_instance(engine, instance)
        blob = self.storage.get_model_data_models().get(instance.id)
        if blob is None:
            raise ValueError(f"No model data for EngineInstance {instance.id}")
        models = model_io.deserialize_models(blob.models)
        _, _, algorithms, serving = engine._instantiate(engine_params)
        for a in algorithms:
            a.bind_serving(self.ctx)
        models = prepare_deploy(
            self.ctx, engine, engine_params, instance.id, models,
            algorithms=algorithms)
        # partition scope: slice the owned item rows FIRST, so fold-in
        # padding, sharded/quant layouts, AOT program shapes and the
        # batcher all see only this replica's 1/N of the catalog
        partition_state = None
        if self._partition_spec:
            from predictionio_tpu.parallel import serve_dist as dist_mod
            p_index, p_count = dist_mod.parse_partition(self._partition_spec)
            models, partition_state = _partition_models(
                models, p_index, p_count)
        # realtime fold-in (realtime/foldin.py): capacity headroom must
        # be padded BEFORE prepare_serving so every layout (replicated,
        # sharded, int8) and every AOT program shape already includes
        # the rows new users will fold into — a later resize would be
        # the recompile cliff. A reload re-pads with the worker's hint
        # so the headroom-exhausted fallback always lands with room.
        from predictionio_tpu.realtime import foldin as foldin_mod
        foldin_on = foldin_mod.enabled(self.config.foldin)
        foldin_prep = None
        if foldin_on:
            headroom = (self.config.foldin_headroom
                        or foldin_mod.default_headroom())
            item_headroom = (self.config.foldin_item_headroom
                             or foldin_mod.default_item_headroom())
            if self._foldin_worker is not None:
                headroom = max(headroom,
                               self._foldin_worker.headroom_hint())
                item_headroom = max(
                    item_headroom,
                    self._foldin_worker.item_headroom_hint())
            foldin_prep = foldin_mod.pad_capacity(
                models, headroom, algorithms,
                item_headroom=item_headroom)
        # shard-serving + serve-quant scopes (parallel/serve_dist.py,
        # ops/quant.py): each algorithm's prepare_serving resolves the
        # deploy's modes inside them. A reload is flagged so sharding's
        # "auto" falls back to the replicated layout during hot-swap
        # (the swap window holds BOTH models; "on" stays sharded — the
        # operator's explicit call); quantization re-runs on every
        # load, reload included — re-quantizing IS the hot-swap
        # contract for the int8 path.
        from predictionio_tpu.ops import quant as serve_quant
        from predictionio_tpu.parallel import serve_dist
        is_reload = getattr(self, "engine_instance", None) is not None
        with serve_dist.deploy_scope(self.config.shard_serving,
                                     reload=is_reload), \
                serve_quant.deploy_scope(self.config.serve_quant,
                                         reload=is_reload):
            models = [a.prepare_serving(m)
                      for a, m in zip(algorithms, models)]
            quant_requested = serve_quant.serving_enabled()
        shard_state = next(
            (m.sharding.summary() for m in models
             if getattr(m, "sharding", None) is not None), None)
        serve_dist.record_state(shard_state)
        quant_state = serve_quant.summarize_deploy(
            models, requested=quant_requested)
        serve_quant.record_state(quant_state)
        foldin_specs = (foldin_mod.program_specs(models, foldin_prep)
                        if foldin_on else [])
        aot_state, serve_buckets = self._prebuild_aot(
            instance, algorithms, models, extra_specs=foldin_specs)
        batcher = self._make_batcher(algorithms, models, serving,
                                     buckets=serve_buckets)
        servable = ServableModel(
            name=DEFAULT_TENANT,
            spec=TenantSpec(name=DEFAULT_TENANT,
                            access_key=self.config.access_key),
            instance=instance, engine=engine, engine_params=engine_params,
            algorithms=list(algorithms), models=list(models),
            serving=serving, batcher=batcher, aot_state=aot_state,
            shard_state=shard_state, quant_state=quant_state,
            model_bytes=registry_mod.model_hbm_bytes(models))
        # the registry is the source of truth for every deploy shape;
        # budget enforcement (env opt-in for legacy) runs here, BEFORE
        # the attribute swap — a refused load keeps the previous
        # generation serving
        self.registry.install(servable)
        with self._lock:
            self.engine_instance = instance
            self.engine = engine
            self.engine_params = engine_params
            self.algorithms = algorithms
            self.models = models
            self.serving = serving
            self._aot_state = aot_state
            self._shard_state = shard_state
            self._quant_state = quant_state
            self._partition_state = partition_state
            old_batcher, self._batcher = self._batcher, batcher
        if old_batcher is not None:   # reload: drain in-flight, then retire
            old_batcher.close()
        self.time_to_ready_s = time.perf_counter() - t_load
        self._m_time_to_ready.set(self.time_to_ready_s)
        self.generation += 1
        logger.info("Engine instance %s deployed (%d algorithm(s), "
                    "batching %s, aot %s) in %.2fs", instance.id,
                    len(algorithms),
                    "on" if batcher is not None else "off",
                    "on" if aot_state is not None else "off",
                    self.time_to_ready_s)
        journal.emit(
            "lifecycle",
            (f"model generation {self.generation} live "
             f"({'reload hot-swap' if is_reload else 'initial deploy'}: "
             f"instance {instance.id})"),
            level=journal.INFO,
            generation=self.generation, instanceId=instance.id,
            reload=bool(is_reload),
            timeToReadyS=round(self.time_to_ready_s, 3))
        if foldin_on and foldin_prep is not None:
            self._install_foldin(engine_params, models, foldin_prep)
        elif foldin_on:
            journal.emit(
                "foldin", "fold-in requested but no model is fold-in-"
                "shaped (user/item factor matrices + vocabs); worker "
                "not started", level=journal.WARN)

    # -------------------------------------------------- multi-tenant loading
    def _tenant_config(self, spec: TenantSpec) -> ServerConfig:
        """The effective ServerConfig for one tenant's load: the spec's
        engine pin + its overrides over the deploy-wide defaults.
        Fold-in is forced off under multi-tenancy (the worker is a
        single-model speed layer; README documents the limitation)."""
        return dataclasses.replace(
            self.config,
            engine_instance_id=spec.engine_instance_id,
            engine_id=spec.engine_id,
            engine_version=spec.engine_version,
            engine_variant=spec.engine_variant,
            engine_dir=spec.engine_dir or self.config.engine_dir,
            access_key=spec.access_key,
            batching=spec.batching or self.config.batching,
            batch_max_size=(spec.batch_max_size
                            or self.config.batch_max_size),
            batch_max_delay_ms=(spec.batch_max_delay_ms
                                if spec.batch_max_delay_ms is not None
                                else self.config.batch_max_delay_ms),
            batch_max_queue=(spec.batch_max_queue
                             or self.config.batch_max_queue),
            foldin="off",
            tenants=())

    def _build_servable(self, spec: TenantSpec, *,
                        is_reload: bool) -> ServableModel:
        """One tenant's load pipeline: resolve → engine → models →
        prepare_deploy → prepare_serving → shared AOT prebuild → its
        OWN batcher. The AOT bucket set comes from the deploy-wide
        batch_max_size, so every tenant pads onto the same
        (bucket × template × k) program set and the process-wide memo
        keeps compile count flat as tenants multiply — tenants share
        compiled code, never queue capacity."""
        cfg = self._tenant_config(spec)
        instance = resolve_engine_instance(self.storage, cfg)
        engine = self._engine_override or get_engine(
            instance.engine_factory, base_dir=cfg.engine_dir)
        engine_params = engine_params_from_instance(engine, instance)
        blob = self.storage.get_model_data_models().get(instance.id)
        if blob is None:
            raise ValueError(
                f"No model data for EngineInstance {instance.id}")
        models = model_io.deserialize_models(blob.models)
        _, _, algorithms, serving = engine._instantiate(engine_params)
        for a in algorithms:
            a.bind_serving(self.ctx)
        models = prepare_deploy(
            self.ctx, engine, engine_params, instance.id, models,
            algorithms=algorithms)
        from predictionio_tpu.ops import quant as serve_quant
        from predictionio_tpu.parallel import serve_dist
        with serve_dist.deploy_scope(cfg.shard_serving,
                                     reload=is_reload), \
                serve_quant.deploy_scope(cfg.serve_quant,
                                         reload=is_reload):
            models = [a.prepare_serving(m)
                      for a, m in zip(algorithms, models)]
            quant_requested = serve_quant.serving_enabled()
        shard_state = next(
            (m.sharding.summary() for m in models
             if getattr(m, "sharding", None) is not None), None)
        serve_dist.record_state(shard_state)
        quant_state = serve_quant.summarize_deploy(
            models, requested=quant_requested)
        serve_quant.record_state(quant_state)
        aot_state, serve_buckets = self._prebuild_aot(
            instance, algorithms, models)
        batcher = self._make_batcher(algorithms, models, serving,
                                     buckets=serve_buckets, cfg=cfg,
                                     name=f"tenant-{spec.name}")
        return ServableModel(
            name=spec.name, spec=spec, instance=instance, engine=engine,
            engine_params=engine_params, algorithms=list(algorithms),
            models=list(models), serving=serving, batcher=batcher,
            aot_state=aot_state, shard_state=shard_state,
            quant_state=quant_state,
            model_bytes=registry_mod.model_hbm_bytes(models))

    def _load_tenants(self) -> None:
        t_load = time.perf_counter()
        is_reload = self.generation > 0
        for spec in self.config.tenants:
            servable = self._build_servable(spec, is_reload=is_reload)
            # install enforces the HBM budgets: past the hard cap the
            # load is refused (ValueError) and — on reload — the
            # tenant's previous generation keeps serving
            prior = self.registry.install(servable)
            if prior is not None and prior.batcher is not None:
                prior.batcher.close()
            journal.emit(
                "tenant",
                (f"tenant '{spec.name}' generation "
                 f"{servable.generation} live (instance "
                 f"{servable.instance.id}, "
                 f"{servable.model_bytes / (1024 * 1024):.1f} MiB)"),
                level=journal.INFO, tenant=spec.name,
                generation=servable.generation,
                instanceId=servable.instance.id,
                modelBytes=servable.model_bytes)
        self._admission = self._build_admission()
        # flat mirrors point at the first tenant so shared internals
        # (storage probe, plugin REST, tests poking api.algorithms)
        # keep working; the multi-tenant wire never reads them
        first = self.registry.get(self.config.tenants[0].name)
        with self._lock:
            self.engine_instance = first.instance
            self.engine = first.engine
            self.engine_params = first.engine_params
            self.algorithms = first.algorithms
            self.models = first.models
            self.serving = first.serving
        if self._m_tenant_requests is None:
            # registered lazily so a legacy deploy's /metrics carries
            # no tenant family at all (wire parity)
            self._m_tenant_requests = telemetry.registry().counter(
                "pio_tenant_requests_total",
                "Multi-tenant /queries.json requests by tenant and "
                "outcome (ok / saturated / rate_limited / denied / "
                "error)",
                labelnames=("tenant", "outcome"))
        telemetry.registry().register_collector(self.registry.collect)
        self.time_to_ready_s = time.perf_counter() - t_load
        self._m_time_to_ready.set(self.time_to_ready_s)
        self.generation += 1
        names = self.registry.names()
        logger.info("multi-tenant deploy: %d tenant(s) %s live in %.2fs",
                    len(names), names, self.time_to_ready_s)
        journal.emit(
            "lifecycle",
            (f"generation {self.generation} live (multi-tenant "
             f"{'reload hot-swap' if is_reload else 'initial deploy'}: "
             f"{len(names)} tenant(s))"),
            level=journal.INFO, generation=self.generation,
            tenants=names, reload=bool(is_reload),
            timeToReadyS=round(self.time_to_ready_s, 3))

    def _build_admission(self) -> registry_mod.AdmissionController:
        """The key→app→tenant resolution map: each tenant's configured
        access key names an app (AccessKeys DAO) and every key of that
        app routes to that tenant; a spec without a key falls back to
        its datasource appName (Apps DAO). Two tenants may not resolve
        to the same app — per-key routing would be ambiguous."""
        keys_dao = self.storage.get_meta_data_access_keys()
        apps_dao = self.storage.get_meta_data_apps()
        tenant_by_appid: Dict[int, str] = {}
        tenant_limits: Dict[str, Tuple[Optional[float],
                                       Optional[float]]] = {}
        for spec in self.config.tenants:
            tenant_limits[spec.name] = (spec.rate, spec.burst)
            appid = None
            if spec.access_key:
                row = keys_dao.get(spec.access_key)
                if row is not None:
                    appid = row.appid
            if appid is None:
                servable = self.registry.get(spec.name)
                app_name = _datasource_appname(
                    servable.engine_params if servable else None)
                if app_name:
                    app = apps_dao.get_by_name(app_name)
                    if app is not None:
                        appid = app.id
            if appid is None:
                journal.emit(
                    "tenant",
                    (f"tenant '{spec.name}' has no resolvable access "
                     "key or datasource appName; no key routes to it "
                     "until one is configured"),
                    level=journal.WARN, tenant=spec.name)
                continue
            if appid in tenant_by_appid:
                raise ValueError(
                    f"tenants '{tenant_by_appid[appid]}' and "
                    f"'{spec.name}' both resolve to app id {appid}; "
                    "per-key routing needs one app per tenant")
            tenant_by_appid[appid] = spec.name
        return registry_mod.AdmissionController(
            self.storage, tenant_by_appid, tenant_limits=tenant_limits)

    def _install_foldin(self, engine_params, models, prep) -> None:
        """Create (first load) or re-bind (reload) the fold-in worker
        against the freshly swapped model generation. Degrades soft:
        an engine without an appName, a backend without an incremental
        tail, or a missing app journals a WARN and serves without the
        speed layer — never a dead deploy."""
        from predictionio_tpu.realtime import foldin as foldin_mod
        worker = self._foldin_worker
        if worker is None:
            cfg = foldin_mod.config_for(
                engine_params, tick_ms=self.config.foldin_tick_ms,
                headroom=self.config.foldin_headroom or None,
                item_headroom=self.config.foldin_item_headroom or None)
            if cfg is None:
                journal.emit(
                    "foldin", "fold-in requested but the engine has no "
                    "datasource appName to tail; worker not started",
                    level=journal.WARN)
                return
            if prep.get("lambda_") is not None:
                cfg.lambda_ = prep["lambda_"]
            try:
                worker = foldin_mod.FoldinWorker(self.storage, cfg)
            except ValueError as e:
                journal.emit(
                    "foldin", f"fold-in worker failed to start: {e}",
                    level=journal.WARN, error=str(e))
                return
            if not worker.supported:
                journal.emit(
                    "foldin", "fold-in requested but this event-store "
                    "backend exposes no incremental tail (see the "
                    "README fold-in matrix); worker not started",
                    level=journal.WARN)
                return
            self._foldin_worker = worker
        # a reload that landed a NEW training generation (autotrain
        # publish, or a manual retrain + /reload) invalidates the
        # speed layer's folded state: those rows were solved against
        # the OLD batch base. Rebase — drop folded/pending state and
        # restart the tail from the new instance's training cursor
        # (head fallback) — BEFORE binding the fresh model.
        inst = self.engine_instance
        prev = getattr(self, "_foldin_instance_id", None)
        if (prev is not None and inst is not None
                and inst.id != prev):
            worker.rebase(cursor=_train_cursor(inst))
        self._foldin_instance_id = inst.id if inst is not None else None
        worker.bind(models[prep["index"]], generation=self.generation,
                    prep=prep, reload_cb=self._reload)
        worker.start()

    def _prebuild_aot(self, instance, algorithms, models,
                      extra_specs=None):
        """Kill the warmup cliff before /readyz flips ready
        (serving/aot.py): pre-seed the persistent compile cache from
        the instance's exported artifact, prune the padding-bucket set
        against observed flush sizes, eagerly build every enumerated
        serving program on a small thread pool, and mark the recompile
        watchdog's warmup done — from here on, a serving-path compile
        is an alarm, not a cliff. Returns (aot summary for `GET /`,
        bucket set for the batcher); (None, None) with AOT off — wire
        behavior then stays byte-identical to the pre-AOT server."""
        from predictionio_tpu.serving import aot

        mode = (self.config.aot or "auto").lower()
        if mode not in ("auto", "on", "off"):
            raise ValueError(
                f"ServerConfig.aot must be auto/on/off, got {mode!r}")
        if not aot.enabled(mode):
            devicewatch.note_aot(None)
            return None, None
        cache_dir = aot.ensure_persistent_cache()
        cache_import = None
        artifact = self.storage.get_model_data_models().get(
            model_io.cache_artifact_id(instance.id))
        if artifact is not None:
            cache_import = model_io.import_compile_cache(
                artifact.models, cache_dir)
            if cache_import.get("reason"):
                logger.warning("compile-cache artifact for %s not "
                               "imported: %s", instance.id,
                               cache_import["reason"])
        # this set is handed to the batcher, whose flush-scoped
        # installation makes every predict_batch pad onto exactly the
        # programs built below
        buckets = aot.pruned_serve_buckets(self.config.batch_max_size)
        specs = []
        for a, m in zip(algorithms, models):
            specs.extend(aot.algorithm_programs(a, m, buckets))
        # fold-in programs (realtime/foldin.py): the per-bucket solve +
        # publication scatters ride the same prebuild, so the first
        # tick after /readyz compiles nothing
        specs.extend(extra_specs or [])
        report = aot.prebuild(specs,
                              threads=self.config.aot_threads or None)
        devicewatch.mark_serving_warmup_done()
        state: Dict[str, Any] = {"enabled": True,
                                 "buckets": list(buckets),
                                 **report.summary()}
        if cache_import is not None:
            state["cacheImport"] = cache_import
        devicewatch.note_aot(state)
        return state, buckets

    def _make_batcher(self, algorithms, models, serving, buckets=None,
                      cfg: Optional[ServerConfig] = None,
                      name: Optional[str] = None):
        """Build the request micro-batcher for this deployment, or None.

        `batching: auto` (the default) engages only when some algorithm
        has a REAL batched predict — a fallback-only engine gains nothing
        from coalescing device work, so it keeps the inline path. The
        flush closes over THIS load's (algorithms, models, serving): a
        /reload swaps in a new batcher while in-flight batches finish
        against the engine they were admitted under. A tenant load
        passes its effective ``cfg`` (per-tenant queue capacity — one
        tenant's saturation 503s never consume another's slots) and a
        ``name`` that keys its own metric series."""
        from predictionio_tpu.serving import MicroBatcher, batch_capable
        from predictionio_tpu.serving import protocol
        cfg = cfg or self.config

        mode = (cfg.batching or "auto").lower()
        if mode not in ("auto", "on", "off"):
            raise ValueError(
                f"ServerConfig.batching must be auto/on/off, got {mode!r}")
        if mode == "off":
            return None
        if mode == "auto" and not any(batch_capable(a) for a in algorithms):
            return None

        def flush(queries):
            # degraded tracking rides the flushing lane's thread for the
            # whole batch (thread-local: the other lane's flush, which may
            # run at the same time, keeps its own flag): a failed
            # side-channel lookup during any query of the
            # flush taints every result of that flush (conservative — the
            # lookups run inside predict_batch where per-query attribution
            # is not visible from here; KNOWN_ISSUES documents this)
            resilience.reset_degraded()
            with waterfall.stage("supplement"):
                supplemented = [serving.supplement(q) for q in queries]
            # the batched device dispatch (ends in a real host transfer —
            # jax.device_get of the top-k — per KNOWN_ISSUES #3, so the
            # span duration is honest on every backend). Waterfall:
            # `dispatch` is the whole predict_batch; the algorithm
            # refines it with nested pad/execute stages.
            with tracing.span("dispatch", service="query-server"):
                with waterfall.stage("dispatch"):
                    per_algo = [protocol.predict_batch(a, m, supplemented)
                                for a, m in zip(algorithms, models)]
            with waterfall.stage("merge"):
                served = [serving.serve(q, [col[j] for col in per_algo])
                          for j, q in enumerate(queries)]
            degraded = bool(resilience.pop_degraded())
            if degraded:
                # ONE tainted flush, up to len(queries) flagged responses
                self._m_degraded_batches.inc()
            return [(p, degraded) for p in served]

        kwargs: Dict[str, Any] = {}
        if name is not None:
            kwargs["name"] = name
        return MicroBatcher(
            flush,
            max_batch_size=cfg.batch_max_size,
            max_delay_ms=cfg.batch_max_delay_ms,
            max_queue=cfg.batch_max_queue,
            buckets=buckets, **kwargs)

    @property
    def stop_requested(self) -> bool:
        return self._stop_requested.is_set()

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    @draining.setter
    def draining(self, value: bool) -> None:
        """Generic lifecycle hook (http.serve_forever flips this on
        SIGTERM for daemons without a richer drain path); setting it
        True runs the full drain."""
        if value:
            self.drain()

    def drain(self, grace_s: Optional[float] = None) -> None:
        """Graceful shutdown: stop admitting queries (/readyz -> 503,
        /queries.json -> 503 + Retry-After), let the batcher lanes
        finish EVERY already-admitted batch, then request stop. Safe to
        call more than once; every admitted in-flight request gets its
        real answer — zero are dropped."""
        if self._draining.is_set():
            return
        self._draining.set()
        logger.info("drain: stopped admitting; flushing batcher")
        journal.emit("lifecycle", "drain begin: stopped admitting "
                     "queries; flushing admitted batches",
                     level=journal.INFO, generation=self.generation)
        t0 = time.perf_counter()
        worker = self._foldin_worker
        if worker is not None:
            # the speed layer stops BEFORE the batcher drains: no new
            # publications race the final flushes (in-flight queries
            # still answer from the last published generation)
            worker.stop()
        with self._lock:
            batcher = self._batcher
        timeout = (grace_s if grace_s is not None
                   else self.config.drain_grace_s)
        for b in self._all_batchers(extra=batcher):
            b.close(timeout=timeout)
        self._stop_requested.set()
        logger.info("drain: complete")
        journal.emit("lifecycle", "drain complete: every admitted "
                     "in-flight request answered",
                     level=journal.INFO, generation=self.generation,
                     drainS=round(time.perf_counter() - t0, 3))

    def close(self) -> None:
        """Drain and retire the request batcher (server shutdown). Queries
        arriving afterwards fall back to the inline single-query path."""
        worker = self._foldin_worker
        if worker is not None:
            worker.stop()
        with self._lock:
            batcher, self._batcher = self._batcher, None
        for b in self._all_batchers(extra=batcher):
            b.close()

    def _all_batchers(self, extra=None):
        """Every live batcher, deduped: the registry's per-tenant ones
        plus the legacy flat mirror (the same object as the default
        servable's in a legacy deploy)."""
        seen: Dict[int, Any] = {}
        for s in self.registry.servables():
            if s.batcher is not None:
                seen[id(s.batcher)] = s.batcher
        if extra is not None:
            seen[id(extra)] = extra
        return list(seen.values())

    # ------------------------------------------------------------ dispatch
    def handle(self, method: str, path: str,
               query: Optional[Dict[str, str]] = None,
               body: bytes = b"",
               headers: Optional[Dict[str, str]] = None) -> Response:
        method = method.upper()
        path = (path or "/").rstrip("/") or "/"
        try:
            if path == "/" and method == "GET":
                return 200, self._status()
            if path == "/healthz" and method == "GET":
                # liveness: the process is up and dispatching
                return 200, {"status": "ok"}
            if path == "/readyz" and method == "GET":
                return self._readyz()
            t = telemetry.handle_route(
                method, path, query,
                accept=(headers or {}).get("accept")
                or (headers or {}).get("Accept"))
            if t is not None:    # /metrics, /traces.json, /debug/device.json
                return t
            if path == "/queries.json" and method == "POST":
                return self._queries(body, query)
            if path == "/reload" and method == "POST":
                threading.Thread(target=self._reload, daemon=True).start()
                return 200, {"message": "Reloading..."}
            if path == "/stop" and method == "POST":
                self._stop_requested.set()
                return 200, {"message": "Shutting down."}
            if path == "/plugins.json" and method == "GET":
                return 200, self.plugin_context.describe()
            if path.startswith("/plugins/") and method == "GET":
                return self._plugins_rest(path)
            return 404, {"message": "Not Found"}
        except Exception as e:
            logger.exception("engine server request failed: %s %s",
                             method, path)
            return 500, {"message": str(e)}

    @property
    def _multitenant(self) -> bool:
        return bool(self.config.tenants)

    def _status(self) -> Dict[str, Any]:
        if self._multitenant:
            return self._status_mt()
        i = self.engine_instance
        out = {
            "status": "alive",
            "engineInstance": {
                "id": i.id,
                "engineFactory": i.engine_factory,
                "startTime": format_event_time(i.start_time),
                "batch": i.batch,
            },
            "algorithms": [type(a).__name__ for a in self.algorithms],
            "requestCount": self.request_count,
            "avgServingSec": self.avg_serving_sec,
            "lastServingSec": self.last_serving_sec,
            "degradedCount": self.degraded_count,
            "draining": self._draining.is_set(),
            "serverStartTime": format_event_time(self.start_time),
            # model generation (bumped per _load): the router's reload
            # barrier and `pio doctor` key fleet coordination off it
            "generation": self.generation,
        }
        batcher = self._batcher
        out["batching"] = ({"enabled": True, **batcher.stats()}
                           if batcher is not None else {"enabled": False})
        out["codec"] = _codec_status()
        out["transport"] = http_transport.transport_status()
        out.update(_host_status())
        for m in self.models:
            # an engine's own block, only where that engine is deployed
            # (models/ecommerce: "ecomm", its rule reads and fallbacks;
            # models/similarproduct: "simprod")
            block = getattr(m, "status_block", None)
            if block is not None:
                name, value = block()
                out[name] = value
        if batcher is not None:
            # read-only, a fact of the compiled programs: whether the
            # flushes above sort whole score rows or k chunks of them
            # (null: no program of ops/topk.py serves this deploy)
            out["batching"]["topkSelection"] = _topk_selection(self.models)
            # likewise a fact of the deploy: the layout that answers them
            out["batching"].update(_serving_layout(self.models))
        if self._aot_state is not None:
            # only with AOT active: a PIO_AOT=0 deploy keeps the exact
            # legacy key set (wire parity, asserted by test)
            out["aot"] = {**self._aot_state,
                          "timeToReadyS": (round(self.time_to_ready_s, 3)
                                           if self.time_to_ready_s
                                           is not None else None)}
        if getattr(self, "_shard_state", None) is not None:
            # only when sharded serving is live: replicated deploys keep
            # the exact legacy key set (wire parity)
            out["sharding"] = {"enabled": True, **self._shard_state}
        if getattr(self, "_quant_state", None) is not None:
            # only when quantized serving is live OR was requested and
            # fell back (the operator must be able to see the fallback);
            # fp32 deploys keep the exact legacy key set (wire parity)
            out["quant"] = self._quant_state
        if getattr(self, "_partition_state", None) is not None:
            # only for --partition deploys: full-model replicas keep the
            # exact legacy key set (wire parity, asserted by test)
            out["partition"] = {"enabled": True, **self._partition_state}
        worker = getattr(self, "_foldin_worker", None)
        if worker is not None:
            # only with the fold-in worker live: PIO_FOLDIN=0 deploys
            # keep the exact legacy key set (wire parity, asserted)
            out["foldin"] = worker.state()
        at = getattr(self, "_autotrain", None)
        if at is not None:
            # only with --autotrain embedded: plain deploys keep the
            # exact legacy key set (wire parity)
            out["autotrain"] = at.summary()
        return out

    def attach_autotrain(self, autotrain) -> None:
        """Embedded `pio deploy --autotrain`: surface the scheduler's
        summary() under GET / so `pio doctor` and operators see the
        trigger/decision state next to the serving stats."""
        self._autotrain = autotrain

    def _status_mt(self) -> Dict[str, Any]:
        """The multi-tenant `GET /` shape: per-tenant state blocks and
        the generations dict the router's tenant skew check and the
        doctor's per-tenant lines read. The process-wide `generation`
        int stays (bumped once per _load call) so the PR 15 reload
        barrier's integer compare keeps working unchanged."""
        servables = self.registry.servables()
        return {
            "status": "alive",
            "tenants": {s.name: s.state() for s in servables},
            "generations": {s.name: s.generation for s in servables},
            "generation": self.generation,
            "requestCount": self.request_count,
            "avgServingSec": self.avg_serving_sec,
            "lastServingSec": self.last_serving_sec,
            "degradedCount": self.degraded_count,
            "draining": self._draining.is_set(),
            "serverStartTime": format_event_time(self.start_time),
            "modelBytesTotal": self.registry.total_model_bytes(),
            "hbmHardCapMb": self.registry.hard_cap_mb,
            "oversubscribed": self.registry.oversubscribed(),
            "codec": _codec_status(),
            "transport": http_transport.transport_status(),
            **_host_status(),
        }

    def _readyz(self) -> Response:
        if self._multitenant:
            return self._readyz_mt()
        """Readiness: a model is deployed, the admission queue has room,
        and the engine's storage answers a trivial probe. 503 while
        draining so load balancers stop routing here before shutdown."""
        if self._draining.is_set():
            return 503, {"status": "draining",
                         "generation": self.generation}
        checks: Dict[str, Any] = {}
        ready = True
        with self._lock:
            instance = getattr(self, "engine_instance", None)
            batcher = self._batcher
        checks["modelLoaded"] = instance is not None
        ready &= checks["modelLoaded"]
        aot_state = self._aot_state
        if aot_state is not None:
            # informational: prebuild runs synchronously inside _load,
            # so by the time this route answers the programs are warm;
            # failed builds degrade to lazy compile, not unreadiness
            checks["aotPrograms"] = aot_state.get("programs", 0)
        if batcher is not None:
            depth = batcher.depth()
            checks["queueDepth"] = depth
            # saturated queue = not ready for MORE traffic (the depth at
            # which submit() starts answering 503 anyway)
            ready &= depth < self.config.batch_max_queue
        try:
            # one cheap metadata point-read; for a `remote` source this is
            # a real RPC, i.e. the probe genuinely exercises the link
            if instance is not None:
                self.storage.get_meta_data_engine_instances().get(instance.id)
            checks["storage"] = "ok"
        except Exception as e:
            checks["storage"] = f"{type(e).__name__}: {e}"
            ready = False
        if self._partition_state is not None:
            # the owned range rides the readiness probe so the router's
            # membership poll assembles the partition map in the same
            # read it learns generation (full replicas: key absent)
            checks["partition"] = dict(self._partition_state)
        status = 200 if ready else 503
        # generation rides the readiness probe so the router's membership
        # poll learns "which model is this replica on" in the same read
        return status, {"status": "ready" if ready else "unready",
                        "generation": self.generation, **checks}

    def _readyz_mt(self) -> Response:
        """Multi-tenant readiness: every configured tenant is loaded
        and has queue room, storage answers. Carries both the
        process-wide generation int (the router barrier's compare) and
        the per-tenant generations dict (the per-tenant skew WARN)."""
        gens = self.registry.generations()
        if self._draining.is_set():
            return 503, {"status": "draining",
                         "generation": self.generation,
                         "generations": gens}
        checks: Dict[str, Any] = {}
        ready = True
        servables = self.registry.servables()
        checks["modelLoaded"] = len(servables) == len(self.config.tenants)
        ready &= checks["modelLoaded"]
        depths: Dict[str, int] = {}
        for s in servables:
            if s.batcher is None:
                continue
            depth = s.batcher.depth()
            depths[s.name] = depth
            cap = (s.spec.batch_max_queue
                   or self.config.batch_max_queue)
            # one saturated tenant queue makes the REPLICA not ready
            # for more traffic of that tenant; per-tenant shedding is
            # the router's job — readiness only flips when every
            # tenant is saturated (otherwise a single noisy neighbor
            # would eject the replica for everyone)
            if depth >= cap:
                checks.setdefault("saturatedTenants", []).append(s.name)
        if depths:
            checks["queueDepths"] = depths
        sat = checks.get("saturatedTenants")
        if sat and len(sat) == len(depths):
            ready = False
        try:
            instance = getattr(self, "engine_instance", None)
            if instance is not None:
                self.storage.get_meta_data_engine_instances().get(
                    instance.id)
            checks["storage"] = "ok"
        except Exception as e:
            checks["storage"] = f"{type(e).__name__}: {e}"
            ready = False
        status = 200 if ready else 503
        return status, {"status": "ready" if ready else "unready",
                        "generation": self.generation,
                        "generations": gens, **checks}

    def _reload(self) -> None:
        try:
            self._load()
        except Exception as e:
            logger.exception("reload failed; keeping previous engine")
            journal.emit(
                "lifecycle",
                f"reload FAILED; generation {self.generation} keeps "
                "serving",
                level=journal.WARN, generation=self.generation,
                error=f"{type(e).__name__}: {e}")

    # ---------------------------------------------------------- query path
    def _tenant_outcome(self, tenant: str, outcome: str) -> None:
        if self._m_tenant_requests is not None and telemetry.on():
            self._m_tenant_requests.labels(
                tenant=tenant, outcome=outcome).inc()

    def _queries(self, body: bytes,
                 url_query: Optional[Dict[str, str]] = None) -> Response:
        from predictionio_tpu.serving import ServerSaturated
        t0 = time.perf_counter()
        query_time = utcnow()
        if self._draining.is_set():
            # graceful drain: already-admitted requests finish; new ones
            # are steered to another replica
            return 503, {"message": "server is draining"}, \
                {"Retry-After": "1"}
        tenant: Optional[str] = None
        if self._multitenant:
            # per-access-key admission (serving/registry.py): key →
            # app → tenant against the AccessKeys DAO, then the key's
            # token bucket. 401 unknown key, 429 + Retry-After past
            # the rate limit — resolved ONCE here; every label below
            # inherits the verdict.
            try:
                tenant = self._admission.admit(
                    (url_query or {}).get("accessKey"))
            except AdmissionError as e:
                self._tenant_outcome(
                    "-", "denied" if e.status == 401 else "rate_limited")
                if e.retry_after_s is not None:
                    return e.status, {"message": e.message}, \
                        {"Retry-After": str(e.retry_after_s)}
                return e.status, {"message": e.message}
            servable = self.registry.get(tenant)
            if servable is None:
                self._tenant_outcome(tenant, "error")
                return 503, {"message":
                             f"tenant '{tenant}' is not loaded"}, \
                    {"Retry-After": "1"}
            algorithms, models, serving, batcher = (
                servable.algorithms, servable.models, servable.serving,
                servable.batcher)
            instance = servable.instance
        else:
            with self._lock:
                algorithms, models, serving, batcher = (
                    self.algorithms, self.models, self.serving,
                    self._batcher)
                instance = self.engine_instance
        try:
            query = json_extractor.extract_query(
                getattr(algorithms[0], "query_class", None), body)
        except (ValueError, UnicodeDecodeError) as e:
            return 400, {"message": str(e)}
        # latency waterfall (common/waterfall.py, PIO_WATERFALL=1): this
        # request's stage breakdown — rec is None when sampling is off
        # and every waterfall call below is a cheap no-op
        rec = waterfall.begin("batched" if batcher is not None
                              else "inline")
        if rec is not None and tenant is not None:
            # Dapper pattern: the request's tenant rides the waterfall
            # record so slow-trace triage attributes per tenant
            rec.note("tenant", tenant)
        if batcher is not None:
            # micro-batched path: block until this query's coalesced batch
            # is served; concurrent requests share one device dispatch.
            # Under multi-tenancy this is the TENANT'S batcher: its
            # saturation 503s come out of its own queue only.
            try:
                with waterfall.activate((rec,)):
                    prediction, degraded = batcher.submit(query)
            except ServerSaturated as e:
                if tenant is not None:
                    self._tenant_outcome(tenant, "saturated")
                return 503, {"message": (
                    "serving queue is saturated (admission control); "
                    "retry later")}, {"Retry-After": str(e.retry_after_s)}
            except RuntimeError:
                # lost the race with drain()/close(): the batcher stopped
                # admitting between our snapshot and submit
                return 503, {"message": "server is draining"}, \
                    {"Retry-After": "1"}
        else:
            # batching off: the original single-query path, unchanged —
            # plus request-scoped degradation tracking (a failed storage
            # side-channel lookup serves from on-device factors and flags
            # the response instead of 500ing). The devicewatch region
            # makes an XLA compile inside this request attributable (and
            # post-warmup, alarmed) exactly like the batched flush.
            resilience.reset_degraded()
            with devicewatch.serving_region("serve_inline",
                                            signature="inline"):
                with waterfall.activate((rec,)):
                    with waterfall.stage("supplement"):
                        supplemented = serving.supplement(query)
                    with waterfall.stage("dispatch"):
                        predictions = [a.predict(m, supplemented)
                                       for a, m in zip(algorithms, models)]
                    with waterfall.stage("merge"):
                        prediction = serving.serve(query, predictions)
            degraded = bool(resilience.pop_degraded())
            devicewatch.note_serving_flush()
        with waterfall.activate((rec,)):
            with waterfall.stage("serialize"):
                # the pass that builds the reply also says whether it is
                # finite: no second walk of what nobody changes below
                result, non_finite = json_extractor.to_json_checked(
                    prediction)
        if degraded:
            # per-RESPONSE count: with batching on this over-counts (the
            # whole flush is tainted), hence "upper bound" in the metric
            # name and the KNOWN_ISSUES #6 caveat on degradedCount
            self._m_degraded_queries.inc()
            # a degraded answer is a trace worth keeping: pin it in the
            # tail ring so its id resolves after the main ring churns
            tracing.pin_current("degraded")
            if batcher is None:
                # inline path: a degraded query IS a degraded "batch" of 1
                self._m_degraded_batches.inc()
            if isinstance(result, dict):
                result = {**result, "degraded": True}

        if self.config.feedback:
            result = self._feedback(instance, query, prediction, result,
                                    query_time)

        blockers = self.plugin_context.output_blockers
        for blocker in blockers.values():
            result = blocker.process(
                instance, json_extractor.to_json_obj(query), result,
                self.plugin_context)

        if self.config.feedback or blockers:
            # feedback or a blocker may have changed the payload since
            # the fold: what is validated is what is sent, so walk it
            non_finite = tree_has_non_finite(result)
            _M_REPLY_WALKED.inc()
        else:
            _M_REPLY_FOLDED.inc()
        if non_finite:
            # the reference contract is real scores (quickstart_test.py:
            # 95-100); json.dumps would otherwise emit bare NaN tokens —
            # invalid JSON — straight to clients. Checked AFTER feedback/
            # blockers so the final payload is what's validated.
            logger.error("prediction for instance %s contains non-finite "
                         "scores; refusing to serve it", instance.id)
            if tenant is not None:
                self._tenant_outcome(tenant, "error")
            return 500, {"message":
                         "prediction contains non-finite scores (the "
                         "deployed model is numerically invalid); retrain "
                         "or /reload a healthy instance"}

        if (self._partition_state is not None and isinstance(result, dict)
                and isinstance(result.get("itemScores"), list)):
            # partition-routed deploy: annotate the local top-k with the
            # candidates' GLOBAL item indices (local row + lo) so the
            # router's merge_candidates twin can run the same two-key
            # (value, lowest-global-index) sort the device merge uses.
            # The router strips this block before answering the client —
            # only scatter sub-responses carry it.
            ps = self._partition_state
            vocab = next(m.item_vocab for m in models
                         if getattr(m, "item_vocab", None) is not None)
            result = {**result, "partition": {
                **ps,
                "itemIndices": [vocab(s["item"]) + ps["lo"]
                                for s in result["itemScores"]],
            }}

        dt = time.perf_counter() - t0
        waterfall.end(rec)   # close the breakdown; offer to /debug/slow.json
        if telemetry.on():
            # end-to-end serve latency (parse -> batched/inline predict ->
            # serialize); the predict path ends in a host transfer, so
            # this histogram is honest on every backend (issue #3)
            telemetry.registry().histogram(
                "pio_serve_seconds",
                "POST /queries.json end-to-end serve latency",
                labelnames=("mode", "tenant")).labels(
                    mode="batched" if batcher is not None else "inline",
                    tenant=tenant or DEFAULT_TENANT,
            ).observe(dt)
        with self._lock:  # ThreadingHTTPServer: concurrent queries
            self.last_serving_sec = dt
            self.avg_serving_sec = (
                (self.avg_serving_sec * self.request_count) + dt
            ) / (self.request_count + 1)
            self.request_count += 1
        if tenant is not None:
            self._tenant_outcome(tenant, "ok")
            # the router learns key→tenant from this header and labels
            # its own counters without a second resolution
            return 200, result, {"X-PIO-Tenant": tenant}
        return 200, result

    def _feedback(self, instance, query, prediction, result,
                  query_time) -> Dict[str, Any]:
        """Async prediction feedback to the event server
        (CreateServer.scala:514-576)."""
        pr_id = getattr(prediction, "prId", "") or "".join(
            random.SystemRandom().choice(string.ascii_letters + string.digits)
            for _ in range(64))
        data = {
            "event": "predict",
            "eventTime": format_event_time(query_time),
            "entityType": "pio_pr",
            "entityId": pr_id,
            "properties": {
                "engineInstanceId": instance.id,
                "query": json_extractor.to_json_obj(query),
                "prediction": result,
            },
        }
        if getattr(query, "prId", None):
            data["prId"] = query.prId
        url = (f"http://{self.config.event_server_ip}:"
               f"{self.config.event_server_port}/events.json"
               f"?accessKey={self.config.access_key or ''}")

        def post():
            try:
                req = urllib.request.Request(
                    url, data=json.dumps(data).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST")
                with urllib.request.urlopen(req, timeout=10) as r:
                    if r.status != 201:
                        logger.error("Feedback event failed. Status code: %s",
                                     r.status)
            except Exception as e:
                logger.error("Feedback event failed: %s", e)

        threading.Thread(target=post, daemon=True).start()
        # inject prId into the served result (CreateServer.scala:568-576)
        if hasattr(prediction, "prId"):
            result = dict(result)
            result["prId"] = pr_id
        return result

    def _plugins_rest(self, path: str) -> Response:
        from predictionio_tpu.common.plugin_registry import (
            dispatch_plugin_rest,
        )
        return dispatch_plugin_rest(
            self.plugin_context, path,
            lambda p, args: p.handle_rest(args))


def undeploy(ip: str, port: int) -> bool:
    """POST /stop to a running engine server (commands/Engine.scala:240+)."""
    try:
        req = urllib.request.Request(
            f"http://{ip}:{port}/stop", data=b"", method="POST")
        with urllib.request.urlopen(req, timeout=5) as r:
            return r.status == 200
    except Exception:
        return False


def serve(api: QueryAPI, host: str = "localhost", port: int = 8000,
          bind_retries: int = 3) -> None:
    """Run until /stop or SIGTERM (MasterActor bind + retry,
    CreateServer.scala:347-357). SIGTERM triggers the graceful drain:
    /readyz flips to 503, new queries get 503 + Retry-After, the batcher
    finishes every admitted in-flight batch, then the server exits —
    the rolling-restart contract (zero dropped in-flight requests).

    The HTTP layer is the shared transport (data/api/http.py): the
    query server rides whichever ``PIO_TRANSPORT`` selects — the same
    event loop that lifted ingest throughput serves /queries.json
    concurrency — and both transports expose the identical lifecycle
    used below."""
    server = None
    for attempt in range(bind_retries):
        try:
            server = http_transport.make_server(api, host, port)
            break
        except OSError:
            if attempt == bind_retries - 1:
                raise
            logger.warning("Bind failed; retrying in 1s...")
            time.sleep(1)
    http_transport.install_sigterm_handler(api.drain)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    logger.info("Engine server online at http://%s:%s", host, port)
    try:
        while not api.stop_requested:
            time.sleep(0.2)
    except KeyboardInterrupt:
        pass
    server.shutdown()
    server.server_close()
    api.close()
