"""The `pio` command-line console.

Reference: tools/.../console/Console.scala:83-586 (command surface) and
console/Pio.scala (implementations). Verbs:

  version status build train eval deploy undeploy
  eventserver dashboard adminserver run
  app {new,list,show,delete,data-delete,channel-new,channel-delete}
  accesskey {new,list,delete}
  template {get,list}
  import export

spark-submit process spawning (Runner.scala:185-307) collapses to direct
in-process calls: train/eval/deploy run in this interpreter against the
TPU runtime.

Run as: python -m predictionio_tpu.tools.cli <command> [...]
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
from typing import List, Optional

from predictionio_tpu import __version__
from predictionio_tpu.data.storage import get_storage
from predictionio_tpu.tools import apps as app_cmds
from predictionio_tpu.tools.apps import CommandError

logger = logging.getLogger("pio")


def _info(msg: str) -> None:
    print(f"[INFO] {msg}")


def _error(msg: str) -> None:
    print(f"[ERROR] {msg}", file=sys.stderr)


# ---------------------------------------------------------------------------
# engine workflow commands
# ---------------------------------------------------------------------------

def cmd_build(args) -> int:
    """Validate the engine variant + factory import (the sbt compile step
    collapses to an import check; commands/Engine.scala:65-161)."""
    from predictionio_tpu.workflow.workflow_utils import (
        get_engine, read_engine_variant,
    )
    engine_dir = os.path.abspath(args.engine_dir)
    variant = read_engine_variant(engine_dir, args.variant)
    engine = get_engine(variant["engineFactory"], base_dir=engine_dir)
    engine.engine_params_from_json(variant)
    _info(f"Engine {variant['engineFactory']} validated "
          f"(variant {variant['id']}).")
    _info("Build finished successfully. (Python engines need no compile.)")
    return 0


def _load_engine_and_params(args):
    from predictionio_tpu.workflow.workflow_utils import (
        get_engine, read_engine_variant,
    )
    engine_dir = os.path.abspath(args.engine_dir)
    variant = read_engine_variant(engine_dir, args.variant)
    engine = get_engine(variant["engineFactory"], base_dir=engine_dir)
    engine_params = engine.engine_params_from_json(variant)
    return engine_dir, variant, engine, engine_params


def _make_context(batch: str = "", devices: int = 0,
                  profile_dir: Optional[str] = None,
                  coordinator: str = "", num_processes: int = 0,
                  process_id: int = 0):
    from predictionio_tpu.workflow import WorkflowContext, WorkflowParams
    mesh = None
    if coordinator:
        # multi-host job (Runner.scala:185-307 role): every host runs the
        # same command with its own --process-id; after initialize,
        # jax.devices() is the GLOBAL device set, so the mesh below spans
        # all hosts and XLA routes collectives over ICI/DCN
        from predictionio_tpu.parallel.mesh import init_distributed
        init_distributed(coordinator, num_processes, process_id)
        if not devices:
            devices = -1  # default to the whole global mesh
    if devices and (devices > 1 or devices < 0):
        from predictionio_tpu.parallel.mesh import get_mesh
        mesh = get_mesh(None if devices < 0 else devices)
    return WorkflowContext(
        workflow_params=WorkflowParams(batch=batch, profile_dir=profile_dir),
        mesh=mesh)


def _apply_telemetry_env(args) -> None:
    """Map the observability flags onto their env knobs (the library
    layers read PIO_TELEMETRY / PIO_TRACE so in-process callers and
    daemons honor the same switches; common/telemetry.py)."""
    if getattr(args, "telemetry", False):
        os.environ["PIO_TELEMETRY"] = "1"
    if getattr(args, "trace", False):
        os.environ["PIO_TRACE"] = "1"


def _apply_read_env(args) -> None:
    """Map the train read-pipeline flags onto their env knobs (the storage
    layer reads PIO_READ_THREADS / PIO_READ_OVERLAP so library callers and
    the storage server honor the same switches)."""
    if getattr(args, "read_threads", 0):
        os.environ["PIO_READ_THREADS"] = str(args.read_threads)
    overlap = getattr(args, "read_overlap", "")
    if overlap:
        os.environ["PIO_READ_OVERLAP"] = "1" if overlap == "on" else "0"
        os.environ["PIO_READ_STAGE"] = "1" if overlap == "on" else "0"
    stream = getattr(args, "stream", "")
    if stream:
        # out-of-core training read (data/store.py train_stream_mode)
        os.environ["PIO_TRAIN_STREAM"] = stream
    if getattr(args, "synthetic", 0):
        # seeded zipfian generator instead of the event store
        # (data/synthetic.py env_config)
        os.environ["PIO_SYNTHETIC_EVENTS"] = str(args.synthetic)
        if getattr(args, "synthetic_seed", None) is not None:
            os.environ["PIO_SYNTHETIC_SEED"] = str(args.synthetic_seed)


def cmd_train(args) -> int:
    _apply_read_env(args)
    _apply_telemetry_env(args)
    if getattr(args, "compile_cache", ""):
        # the run's new compile-cache entries export with the model as
        # a deploy artifact, snapshotted from here (serving/aot.py)
        os.environ["PIO_COMPILE_CACHE_DIR"] = args.compile_cache
    if getattr(args, "no_auto_resume", False):
        # disable the crashed-run checkpoint scan (workflow/core_workflow)
        os.environ["PIO_AUTO_RESUME"] = "0"
    if getattr(args, "coordinator", ""):
        if args.num_processes < 1:
            _error("--coordinator requires --num-processes >= 1")
            return 1
        if not (0 <= args.process_id < args.num_processes):
            _error("--process-id must be in [0, --num-processes)")
            return 1
        # must run before ANYTHING touches the XLA backend (engine loading
        # below may already jit) — jax.distributed.initialize requirement
        from predictionio_tpu.parallel.mesh import init_distributed
        init_distributed(args.coordinator, args.num_processes,
                         args.process_id)
    from predictionio_tpu.workflow import run_train
    _engine_dir, variant, engine, engine_params = _load_engine_and_params(args)
    ctx = _make_context(batch=args.batch, devices=args.devices,
                        profile_dir=args.profile or None,
                        coordinator=args.coordinator,
                        num_processes=args.num_processes,
                        process_id=args.process_id)
    instance_id = run_train(
        ctx, engine, engine_params,
        engine_id=variant.get("id", "default"),
        engine_variant=variant.get("id", "default"),
        engine_factory=variant["engineFactory"],
        params_json=variant,
        resume_from=args.resume_from,
    )
    _info(f"Training completed. EngineInstance ID: {instance_id}")
    return 0


def cmd_eval(args) -> int:
    from predictionio_tpu.workflow import run_evaluation
    from predictionio_tpu.workflow.workflow_utils import (
        get_engine_params_generator, get_evaluation,
    )
    engine_dir = os.path.abspath(args.engine_dir)
    evaluation = get_evaluation(args.evaluation_class, base_dir=engine_dir)
    if args.engine_params_generator_class:
        generator = get_engine_params_generator(
            args.engine_params_generator_class, base_dir=engine_dir)
        params_list = generator.engine_params_list
    else:
        generator = evaluation  # Evaluation may carry its own list
        params_list = getattr(evaluation, "engine_params_list", None)
        if params_list is None:
            _error("No EngineParamsGenerator given and the Evaluation "
                   "defines no engine_params_list.")
            return 1
    ctx = _make_context(batch=args.batch)
    result = run_evaluation(
        ctx, evaluation, params_list,
        evaluation_class=args.evaluation_class,
        generator_class=args.engine_params_generator_class or "",
        output_path=args.output_best_engine_params or "best.json",
    )
    print(str(result))
    return 0


def cmd_deploy(args) -> int:
    from predictionio_tpu.workflow.create_server import (
        QueryAPI, ServerConfig, serve, undeploy,
    )
    from predictionio_tpu.workflow.workflow_utils import read_engine_variant
    _apply_telemetry_env(args)
    tenants = ()
    if getattr(args, "engines", None):
        # multi-tenant deploy (serving/registry.py): each tenant spec
        # pins its own engine instance, so the single engine.json
        # variant read is skipped — there is no "the" engine dir
        from predictionio_tpu.serving.registry import load_engines_conf
        tenants = load_engines_conf(args.engines)
        variant = {}
    else:
        variant = read_engine_variant(os.path.abspath(args.engine_dir),
                                      args.variant)
    config = ServerConfig(
        engine_instance_id=args.engine_instance_id,
        engine_dir=os.path.abspath(args.engine_dir),
        engine_id=variant.get("id", "default"),
        engine_variant=variant.get("id", "default"),
        tenants=tenants,
        ip=args.ip, port=args.port,
        feedback=args.feedback,
        event_server_ip=args.event_server_ip,
        event_server_port=args.event_server_port,
        access_key=args.accesskey,
        batching=args.batching,
        batch_max_size=args.batch_max_size,
        batch_max_delay_ms=args.batch_max_delay_ms,
        batch_max_queue=args.batch_max_queue,
        drain_grace_s=args.drain_grace_s,
        aot=args.aot,
        aot_threads=args.aot_threads,
        slo_availability=args.slo_availability,
        slo_latency_ms=args.slo_latency_ms,
        shard_serving=args.shard_serving,
        serve_quant=args.serve_quant,
        foldin=args.foldin,
        foldin_tick_ms=args.foldin_tick_ms,
        foldin_headroom=args.foldin_headroom,
        foldin_item_headroom=getattr(args, "foldin_item_headroom", 0),
        partition=getattr(args, "partition", "") or "",
    )
    if args.compile_cache:
        os.environ["PIO_COMPILE_CACHE_DIR"] = args.compile_cache
    if args.waterfall:
        # per-request latency waterfalls + /debug/slow.json
        # (common/waterfall.py)
        os.environ["PIO_WATERFALL"] = "1"
    if args.profile_dir:
        # where POST /debug/profile captures land (common/profiling.py)
        os.environ["PIO_PROFILE_DIR"] = args.profile_dir
    # undeploy a previous server on the same port (CreateServer.scala:260-294)
    if undeploy(args.ip, args.port):
        _info(f"Undeployed previous server at {args.ip}:{args.port}.")
    api = QueryAPI(config=config)
    at = None
    if getattr(args, "autotrain", False) and not tenants:
        # embedded autotrain: the continuous-training loop rides the
        # serving process — retrains run in-process on a thread (the
        # streamed run_train path), publish is the in-place hot-swap
        import threading

        from predictionio_tpu.workflow.autotrain import (
            Autotrain, AutotrainConfig, LocalDeployControl,
            ThreadTrainer,
        )
        from predictionio_tpu.workflow.core_workflow import run_train

        def _retrain() -> str:
            return run_train(
                api.ctx, api.engine, api.engine_params,
                engine_id=config.engine_id,
                engine_variant=config.engine_variant,
                engine_factory=variant.get("engineFactory", ""),
                params_json=variant)

        at = Autotrain(
            LocalDeployControl(api), storage=api.storage,
            engine_params=api.engine_params,
            trainer=ThreadTrainer(_retrain),
            config=AutotrainConfig(
                dry_run=getattr(args, "autotrain_dry_run", False)),
            engine_id=config.engine_id,
            engine_variant=config.engine_variant)
        api.attach_autotrain(at)
        threading.Thread(target=at.run, name="pio-autotrain",
                         daemon=True).start()
        _info("Autotrain is "
              + ("DRY-RUN (journals would-have decisions only)."
                 if at.config.dry_run else "live."))
    _info(f"Engine is deployed and running. Engine API is live at "
          f"http://{args.ip}:{args.port}.")
    try:
        serve(api, host=args.ip, port=args.port)
    finally:
        if at is not None:
            at.close()
    return 0


def cmd_foldin(args) -> int:
    """Standalone fold-in soak (realtime/foldin.py run_standalone):
    load the latest COMPLETED instance's model into this process, run
    the tail→solve→publish pipeline against the live event stream, and
    report freshness/lag/drift — validating fold-in on a host without
    touching a serving fleet. Publication stays local (own model copy,
    own `standalone` cursor namespace); `pio deploy --foldin` is the
    serving integration. Exit 0 clean / 1 unsupported backend."""
    from predictionio_tpu.realtime.foldin import run_standalone
    return run_standalone(
        engine_dir=args.engine_dir, variant=args.variant,
        engine_instance_id=args.engine_instance_id,
        tick_ms=args.tick_ms, max_ticks=args.max_ticks or None)


def cmd_profile(args) -> int:
    """Bounded on-demand device-profile capture from a LIVE daemon
    (tools/profile.py -> POST /debug/profile): no restart, hard max
    duration, single concurrent capture; the artifact lands on the
    server's filesystem in the same xprof layout as `pio train
    --profile DIR`. Exit 0 non-empty artifact / 1 failed / 2 dead."""
    from predictionio_tpu.tools.profile import run_profile
    url = args.url or f"http://{args.ip}:{args.port}"
    return run_profile(url, ms=args.ms, out_dir=args.out or None,
                       timeout=args.timeout)


def cmd_doctor(args) -> int:
    """One-screen operator verdict against a running daemon's
    observability surface (tools/doctor.py): health, readiness, queue
    depth, serve p99, circuit breakers, degraded batches, post-warmup
    XLA recompiles, HBM headroom, trace buffer — plus the router line
    (membership, per-backend breakers, added-latency p99, generation
    skew) when the target is a `pio router`. `--targets url,...` runs
    the same verdict over every fleet member (router + replicas +
    storage) and exits with the WORST code. Exit 0 green / 1 red /
    2 unreachable."""
    from predictionio_tpu.tools.doctor import run_doctor, run_doctor_fleet
    if getattr(args, "targets", ""):
        return run_doctor_fleet(_parse_targets(args.targets),
                                timeout=args.timeout)
    url = args.url or f"http://{args.ip}:{args.port}"
    return run_doctor(url, timeout=args.timeout)


def _parse_targets(raw: str, flag: str = "--targets") -> List[str]:
    targets = [t.strip() for t in (raw or "").split(",") if t.strip()]
    if not targets:
        raise CommandError(
            f"{flag} requires at least one daemon base URL "
            "(comma-separated, e.g. "
            "http://host:8000,http://host:7070)")
    return targets


def cmd_trace(args) -> int:
    """Fleet trace assembly (common/traceview.py): fan a trace id out
    to every target's /traces.json?trace_id=, join the spans across
    processes with client/server clock-skew correction, and render ONE
    waterfall tree. Exit 0 assembled / 1 not found / 2 all targets
    unreachable."""
    from predictionio_tpu.common.traceview import run_trace
    return run_trace(args.trace_id, _parse_targets(args.targets),
                     timeout=args.timeout)


def cmd_events(args) -> int:
    """Fleet journal merge-tail (common/traceview.py): read every
    target's /debug/events.json (incremental since_seq cursors) and
    print the merged timeline oldest-first; --follow keeps polling.
    Exit 0 / 2 when every target is unreachable."""
    from predictionio_tpu.common.traceview import run_events
    return run_events(
        _parse_targets(args.targets), since_seq=args.since_seq,
        category=args.category or None, level=args.level or None,
        follow=args.follow, interval_s=args.interval,
        timeout=args.timeout)


def cmd_monitor(args) -> int:
    """One-screen auto-refreshing fleet view (tools/monitor.py): per
    target QPS, p99 and error rate derived from each daemon's OWN
    metrics-history rings (/debug/history.json), SLO burn from live
    gauges, plus the doctor-tier state flags (breakers, partition
    gaps, autopilot, fold-in lag). --once prints one frame; --record
    FILE appends each frame as a JSON line (the durable path out of
    the bounded per-process rings); --replay FILE re-renders a
    recording offline. Exit 0 / 2 all targets unreachable."""
    from predictionio_tpu.tools.monitor import run_monitor
    if args.replay:
        return run_monitor([], replay=args.replay,
                           interval_s=args.interval)
    return run_monitor(
        _parse_targets(args.targets), once=args.once,
        interval_s=args.interval, record=args.record or None,
        timeout=args.timeout)


def cmd_incident(args) -> int:
    """One ordered incident timeline for a fleet (tools/incident.py):
    journal WARN/RED events, metric change-points (rolling median +
    MAD step detection over each target's history rings), slow-ring
    exemplars, and any referenced traces — fused, clock-skew corrected
    via trace pairing, oldest first. Exit 0 clean window / 1 incident
    evidence found / 2 all targets unreachable."""
    from predictionio_tpu.tools.incident import run_incident
    return run_incident(
        _parse_targets(args.targets), window=args.window,
        trace_id=args.trace or None, timeout=args.timeout)


def cmd_lint(args) -> int:
    """Repo-wide static analysis (tools/analyze): the KNOWN_ISSUES
    invariants as lint passes — timing honesty, implicit host syncs,
    gather clipping, jit purity, lock ordering, declaration
    cross-checks, AOT registration, debug-surface unity. Exit 0 clean /
    1 findings / 2 internal error. Stdlib-only: runs without touching
    jax or a device."""
    from predictionio_tpu.tools.analyze.runner import main as lint_main
    argv = []
    if args.json:
        argv.append("--json")
    if args.update_baseline:
        argv.append("--update-baseline")
    if args.list_passes:
        argv.append("--list")
    if args.root:
        argv += ["--root", args.root]
    if args.baseline:
        argv += ["--baseline", args.baseline]
    return lint_main(argv)


def cmd_undeploy(args) -> int:
    from predictionio_tpu.workflow.create_server import undeploy
    if undeploy(args.ip, args.port):
        _info(f"Undeployed server at {args.ip}:{args.port}.")
        return 0
    _error(f"Undeploy failed: nothing listening at {args.ip}:{args.port}.")
    return 1


def cmd_run(args) -> int:
    """Run an arbitrary main class (console run, Console.scala:367-389)."""
    from predictionio_tpu.workflow.workflow_utils import load_object
    target = load_object(args.main_class,
                         base_dir=os.path.abspath(args.engine_dir))
    rv = target(*args.args) if callable(target) else None
    return int(rv or 0)


# ---------------------------------------------------------------------------
# daemons
# ---------------------------------------------------------------------------

def cmd_router(args) -> int:
    """Fleet front door (workflow/router.py): fan /queries.json out to
    N query-server replicas with health-driven membership, per-request
    failover, load shedding, and the coordinated /reload hot-swap
    barrier."""
    from predictionio_tpu.workflow.router import (
        RouterAPI, RouterConfig, serve,
    )
    _apply_telemetry_env(args)
    config = RouterConfig(
        backends=tuple(_parse_targets(args.backends, flag="--backends")),
        ip=args.ip, port=args.port,
        health_ms=args.health_ms,
        deadline_ms=args.deadline_ms,
        max_inflight=args.max_inflight,
        cache=getattr(args, "cache", "") or "",
        cache_mb=getattr(args, "cache_mb", 0) or 0,
        cache_ttl_ms=getattr(args, "cache_ttl_ms", 0.0) or 0.0)
    api = RouterAPI(config)
    ap = None
    if getattr(args, "autopilot", False):
        # embedded autopilot: the control loop rides the router process
        # and steers it through direct method calls (no HTTP hop)
        import threading

        from predictionio_tpu.workflow.autopilot import (
            Autopilot, AutopilotConfig, LocalRouterControl,
            SubprocessReplicaPool,
        )
        pool = None
        if getattr(args, "replica_cmd", ""):
            pool = SubprocessReplicaPool(args.replica_cmd)
        ap = Autopilot(
            LocalRouterControl(api),
            config=AutopilotConfig(
                dry_run=getattr(args, "autopilot_dry_run", False)),
            pool=pool)
        api.attach_autopilot(ap)
        threading.Thread(target=ap.run, name="pio-autopilot",
                         daemon=True).start()
        _info("Autopilot is "
              + ("DRY-RUN (journals would-have decisions only)."
                 if ap.config.dry_run else "live."))
    at = None
    if getattr(args, "autotrain", False):
        # embedded autotrain at the fleet front door: retrains run as
        # `pio train` subprocesses, accepted candidates publish through
        # this router's own zero-drop /reload barrier
        import shlex as _shlex
        import threading

        from predictionio_tpu.data.storage import get_storage
        from predictionio_tpu.workflow.autotrain import (
            Autotrain, AutotrainConfig, SubprocessTrainer,
        )
        from predictionio_tpu.workflow.autotrain import (
            LocalRouterControl as AutotrainRouterControl,
        )
        from predictionio_tpu.workflow.workflow_utils import (
            get_engine, read_engine_variant,
        )
        engine_dir = os.path.abspath(args.engine_dir)
        var = read_engine_variant(engine_dir, args.variant)
        engine = get_engine(var["engineFactory"], base_dir=engine_dir)
        train_cmd = getattr(args, "train_cmd", "") or (
            f"{_shlex.quote(sys.executable)} -m "
            f"predictionio_tpu.tools.cli train --engine-dir "
            f"{_shlex.quote(engine_dir)} --variant "
            f"{_shlex.quote(args.variant)}")
        at = Autotrain(
            AutotrainRouterControl(api), storage=get_storage(),
            engine_params=engine.engine_params_from_json(var),
            trainer=SubprocessTrainer(train_cmd),
            config=AutotrainConfig(
                dry_run=getattr(args, "autotrain_dry_run", False)),
            engine_id=var.get("id", "default"),
            engine_variant=var.get("id", "default"))
        api.attach_autotrain(at)
        threading.Thread(target=at.run, name="pio-autotrain",
                         daemon=True).start()
        _info("Autotrain is "
              + ("DRY-RUN (journals would-have decisions only)."
                 if at.config.dry_run else "live."))
    _info(f"Router is live at http://{args.ip}:{args.port} over "
          f"{len(api.backends)} backend(s).")
    try:
        serve(api, host=args.ip, port=args.port)
    finally:
        if ap is not None:
            ap.close()
        if at is not None:
            at.close()
    return 0


def cmd_autopilot(args) -> int:
    """SLO-driven fleet control loop (workflow/autopilot.py) over a
    running router's admin routes."""
    from predictionio_tpu.workflow.autopilot import run_autopilot
    _apply_telemetry_env(args)
    run_autopilot(args.router, dry_run=args.dry_run,
                  replica_cmd=args.replica_cmd)
    return 0


def cmd_autotrain(args) -> int:
    """Continuous-training control loop (workflow/autotrain.py) over a
    running deploy server or router: watch drift / cursor lag / event
    volume / staleness, retrain, validate, publish."""
    from predictionio_tpu.workflow.autotrain import run_autotrain
    _apply_telemetry_env(args)
    run_autotrain(args.server, engine_dir=args.engine_dir,
                  variant=args.variant, dry_run=args.dry_run,
                  train_cmd=args.train_cmd)
    return 0


def cmd_eventserver(args) -> int:
    from predictionio_tpu.data.api import EventAPI, EventServerConfig
    from predictionio_tpu.data.api.http import serve_forever
    _apply_telemetry_env(args)
    api = EventAPI(config=EventServerConfig(
        ip=args.ip, port=args.port, stats=args.stats))
    _info(f"Event Server is started at {args.ip}:{args.port}.")
    serve_forever(api, host=args.ip, port=args.port)
    return 0


def cmd_dashboard(args) -> int:
    from predictionio_tpu.data.api.http import serve_forever
    from predictionio_tpu.tools.dashboard import DashboardAPI
    _info(f"Dashboard is started at {args.ip}:{args.port}.")
    serve_forever(DashboardAPI(server_key=args.key or None),
                  host=args.ip, port=args.port)
    return 0


def cmd_adminserver(args) -> int:
    from predictionio_tpu.data.api.http import serve_forever
    from predictionio_tpu.tools.admin import AdminAPI
    _info(f"Admin server is started at {args.ip}:{args.port}.")
    serve_forever(AdminAPI(server_key=args.key or None),
                  host=args.ip, port=args.port)
    return 0


def cmd_storageserver(args) -> int:
    """Expose this node's storage over HTTP so other machines can point a
    `remote`-type source at it (the networked-store role the reference
    fills with PostgreSQL/HBase; data/storage/remote.py). SIGTERM drains
    gracefully: /readyz flips to 503, the listener stops accepting, and
    the backing event store flushes its WAL buffers before exit."""
    from predictionio_tpu.data.api.http import serve_forever
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.data.storage.remote import StorageRPCAPI
    _apply_telemetry_env(args)
    key = args.key or os.environ.get("PIO_STORAGE_SERVER_KEY") or None
    storage = get_storage()

    def flush_events():
        try:
            events = storage.get_events()
            if hasattr(events, "close"):
                events.close()
            _info("Storage server drained (event buffers flushed).")
        except Exception as e:  # pragma: no cover - backend-specific
            _error(f"Drain-time flush failed: {e}")

    _info(f"Storage server is started at {args.ip}:{args.port}"
          f"{' (key auth on)' if key else ''}.")
    serve_forever(StorageRPCAPI(storage, key=key),
                  host=args.ip, port=args.port, on_drain=flush_events)
    return 0


# ---------------------------------------------------------------------------
# status / app / accesskey / template / import / export
# ---------------------------------------------------------------------------

def cmd_shell(args) -> int:
    """Interactive shell with Storage preloaded (bin/pio-shell role —
    the reference opens a spark-shell with pio assemblies on the
    classpath; here the session gets the configured Storage, the store
    facades, and jax)."""
    import jax

    from predictionio_tpu.data import store
    from predictionio_tpu.data.storage import Storage

    storage = Storage()
    ns = {"storage": storage, "store": store, "jax": jax,
          "Storage": Storage}
    banner = ("predictionio_tpu shell\n"
              "  storage  -> configured Storage (env-driven)\n"
              "  store    -> event store facades "
              "(find/find_columnar/aggregate_properties)\n"
              "  jax      -> jax (devices: %s)" % (jax.devices(),))
    try:
        from IPython import start_ipython
        start_ipython(argv=[], user_ns=ns, display_banner=True)
    except ImportError:
        import code
        code.interact(banner=banner, local=ns)
    return 0


def cmd_status(args) -> int:
    """Verify installation + storage (commands/Management.scala:181,
    Storage.verifyAllDataObjects)."""
    _info(f"PredictionIO-TPU {__version__}")
    import jax
    _info(f"JAX {jax.__version__}; devices: "
          f"{[str(d) for d in jax.devices()]}")
    storage = get_storage()
    _info("Verifying configured storage backend(s)...")
    try:
        storage.verify_all_data_objects()
    except Exception as e:
        _error(f"Unable to connect to all storage backends: {e}")
        return 1
    _info("(sleeping 5 seconds for all messages to show up...)")
    _info("Your system is all ready to go.")
    return 0


def cmd_app(args) -> int:
    storage = get_storage()
    if args.app_command == "new":
        d = app_cmds.create(args.name, app_id=args.id,
                            description=args.description,
                            access_key=args.access_key or "",
                            storage=storage)
        _info(f"Initialized Event Store for this app ID: {d.app.id}.")
        _info("Created a new app:")
        _info(f"      Name: {d.app.name}")
        _info(f"        ID: {d.app.id}")
        _info(f"Access Key: {d.keys[0].key}")
    elif args.app_command == "list":
        _info(f"{'Name':20} | {'ID':4} | Access Key | Allowed Event(s)")
        for d in app_cmds.list_apps(storage):
            for k in d.keys:
                allowed = ",".join(k.events) if k.events else "(all)"
                _info(f"{d.app.name:20} | {d.app.id:4} | {k.key} | {allowed}")
        _info(f"Finished listing {len(app_cmds.list_apps(storage))} app(s).")
    elif args.app_command == "show":
        d, channels = app_cmds.show(args.name, storage=storage)
        _info(f"    App Name: {d.app.name}")
        _info(f"      App ID: {d.app.id}")
        _info(f" Description: {d.app.description or ''}")
        for k in d.keys:
            allowed = ",".join(k.events) if k.events else "(all)"
            _info(f"  Access Key: {k.key} | {allowed}")
        for c in channels:
            _info(f"     Channel: {c.name} (ID {c.id})")
    elif args.app_command == "delete":
        if not args.force and not _confirm(
                f"Delete app {args.name} and ALL of its data?"):
            return 1
        app_cmds.delete(args.name, storage=storage)
        _info(f"App {args.name} deleted.")
    elif args.app_command == "data-delete":
        if not args.force and not _confirm(
                f"Delete data of app {args.name}?"):
            return 1
        app_cmds.data_delete(args.name, channel=args.channel,
                             delete_all=args.all, storage=storage)
        _info(f"Data of app {args.name} deleted.")
    elif args.app_command == "channel-new":
        c = app_cmds.channel_new(args.name, args.channel, storage=storage)
        _info(f"Channel {c.name} (ID {c.id}) created for app {args.name}.")
    elif args.app_command == "channel-delete":
        if not args.force and not _confirm(
                f"Delete channel {args.channel} of app {args.name}?"):
            return 1
        app_cmds.channel_delete(args.name, args.channel, storage=storage)
        _info(f"Channel {args.channel} deleted.")
    return 0


def cmd_accesskey(args) -> int:
    storage = get_storage()
    if args.accesskey_command == "new":
        k = app_cmds.accesskey_new(args.app_name, key=args.key or "",
                                   events=args.event or (), storage=storage)
        _info(f"Created new access key: {k.key}")
    elif args.accesskey_command == "list":
        for k in app_cmds.accesskey_list(args.app_name, storage=storage):
            allowed = ",".join(k.events) if k.events else "(all)"
            _info(f"{k.key} | app {k.appid} | {allowed}")
    elif args.accesskey_command == "delete":
        app_cmds.accesskey_delete(args.key, storage=storage)
        _info(f"Deleted access key {args.key}.")
    return 0


def cmd_template(args) -> int:
    """Template gallery moved to the web in the reference too
    (Console.scala:546-560)."""
    _info("Engine templates ship inside predictionio_tpu.models.*:")
    _info("  recommendation    - ALS matrix factorization (MovieLens-style)")
    _info("  classification    - Naive Bayes over $set user properties")
    _info("  similarproduct    - implicit ALS item-vector similarity")
    _info("  ecommerce         - implicit ALS + business-rule filters")
    _info("Instantiate one by pointing engine.json's engineFactory at its "
          "factory, e.g. predictionio_tpu.models.recommendation:"
          "RecommendationEngine.")
    _info("Demo engines (the reference's examples/experimental set) live "
          "in predictionio_tpu.examples.* — helloworld, regression, "
          "friend_recommendation, dimsum, recommendation_variants, "
          "recommended_user, apps, movielens, stock; see that package's "
          "docstring for the map.")
    return 0


def cmd_import(args) -> int:
    from predictionio_tpu.tools.transfer import file_to_events
    n = file_to_events(args.input, args.appid, channel=args.channel)
    _info(f"Imported {n} events.")
    return 0


def cmd_export(args) -> int:
    from predictionio_tpu.tools.transfer import events_to_file
    n = events_to_file(args.output, args.appid, channel=args.channel)
    _info(f"Exported {n} events.")
    return 0


def cmd_unregister(args) -> int:
    """Console.scala:170-175 parity: the verb parses but engine
    registration metadata no longer exists (the reference removed its
    sbt registry; its own dispatch falls through to help + exit 1)."""
    _error("Nothing to unregister: engines are not registered — `pio "
           "build` validates in place and `pio train --engine-dir` points "
           "at the engine directory directly.")
    return 1


def cmd_upgrade(args) -> int:
    """Console.scala:396-399 + :664-666 parity (verbatim behavior)."""
    _error("Upgrade is no longer supported")
    return 1


def _confirm(prompt: str) -> bool:
    answer = input(f"{prompt} (Y/n) ")
    return answer.strip().lower() in ("", "y", "yes")


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pio",
        description="PredictionIO-TPU command-line console")
    p.add_argument("--verbose", action="store_true")
    sub = p.add_subparsers(dest="command")

    sub.add_parser("version", help="show version")
    sub.add_parser("status", help="verify installation and storage")

    def engine_flags(sp):
        sp.add_argument("--engine-dir", default=".",
                        help="engine directory (default: cwd)")
        sp.add_argument("--variant", default="engine.json",
                        help="engine variant JSON (default: engine.json)")

    def telemetry_flags(sp):
        sp.add_argument("--telemetry", action="store_true",
                        help="record hot-path metrics (sets "
                             "PIO_TELEMETRY=1; GET /metrics serves "
                             "Prometheus text either way)")
        sp.add_argument("--trace", action="store_true",
                        help="originate request traces (sets PIO_TRACE=1; "
                             "propagated X-PIO-Trace headers are always "
                             "honored); GET /traces.json")

    sp = sub.add_parser("build", help="validate an engine")
    engine_flags(sp)

    sp = sub.add_parser("train", help="train an engine instance")
    engine_flags(sp)
    sp.add_argument("--batch", default="", help="batch label")
    sp.add_argument("--resume-from", default=None,
                    help="instance id of a crashed run whose iteration "
                         "snapshots should seed this training")
    sp.add_argument("--no-auto-resume", action="store_true",
                    help="do not auto-resume from a prior crashed run's "
                         "iteration checkpoints (sets PIO_AUTO_RESUME=0)")
    sp.add_argument("--devices", type=int, default=0,
                    help="train block-sharded over the first N devices "
                         "(default: single-device; -1 = all, incl. every "
                         "host of a multi-host job)")
    sp.add_argument("--coordinator", default="",
                    help="host:port of process 0 for a multi-host train; "
                         "run the same command on every host with its own "
                         "--process-id (jax.distributed)")
    sp.add_argument("--num-processes", type=int, default=0,
                    help="total hosts in the multi-host job")
    sp.add_argument("--process-id", type=int, default=0,
                    help="this host's rank in [0, --num-processes)")
    sp.add_argument("--profile", default="",
                    help="write a jax.profiler trace to this directory")
    sp.add_argument("--read-threads", type=int, default=0,
                    help="parallel chunk-decode workers for the bulk event "
                         "read (default: PIO_READ_THREADS or min(8, "
                         "cores); 1 = serial, the pre-parallel behavior)")
    sp.add_argument("--read-overlap", choices=("on", "off"), default="",
                    help="overlap chunk decode with vocab-encode and "
                         "host->HBM staging (default on; sets "
                         "PIO_READ_OVERLAP / PIO_READ_STAGE)")
    sp.add_argument("--stream", choices=("auto", "on", "off"), default="",
                    help="out-of-core training read: scan the event log "
                         "in bounded chunks and stage each chunk to the "
                         "device as it decodes, so peak HOST memory is "
                         "O(chunk) instead of O(dataset); off = the "
                         "bit-compatible in-core path (sets "
                         "PIO_TRAIN_STREAM; factors are bit-identical "
                         "either way)")
    sp.add_argument("--synthetic", type=int, default=0,
                    help="train on N deterministic synthetic zipfian "
                         "ratings instead of the event store (seeded "
                         "generator, no dataset download — the "
                         "billion-rating scale surface; sets "
                         "PIO_SYNTHETIC_EVENTS)")
    sp.add_argument("--synthetic-seed", type=int, default=None,
                    help="seed for --synthetic (default 7; sets "
                         "PIO_SYNTHETIC_SEED)")
    sp.add_argument("--compile-cache", default="",
                    help="export the run's new compile-cache entries "
                         "with the model as a deploy artifact, "
                         "snapshotted from this directory (sets "
                         "PIO_COMPILE_CACHE_DIR; must agree with "
                         "JAX_COMPILATION_CACHE_DIR where that is set "
                         "— the cache itself defaults to "
                         "<checkout>/.jax_cache)")
    telemetry_flags(sp)

    sp = sub.add_parser("eval", help="run an evaluation")
    sp.add_argument("evaluation_class")
    sp.add_argument("engine_params_generator_class", nargs="?", default="")
    sp.add_argument("--engine-dir", default=".")
    sp.add_argument("--batch", default="")
    sp.add_argument("--output-best-engine-params", default="",
                    help="where to write best.json")

    sp = sub.add_parser("deploy", help="deploy the latest engine instance")
    engine_flags(sp)
    sp.add_argument("--engine-instance-id", default=None)
    sp.add_argument("--engines", default=None, metavar="CONF_JSON",
                    help="multi-tenant deploy: JSON file of tenant "
                         "specs (serving/registry.py) — one process "
                         "hosts N engine instances with per-tenant "
                         "batcher queues, HBM budgets, and per-access-"
                         "key admission; omit for the legacy single-"
                         "engine server")
    sp.add_argument("--ip", default="localhost")
    sp.add_argument("--port", type=int, default=8000)
    sp.add_argument("--feedback", action="store_true")
    sp.add_argument("--event-server-ip", default="localhost")
    sp.add_argument("--event-server-port", type=int, default=7070)
    sp.add_argument("--accesskey", default=None)
    sp.add_argument("--batching", choices=("auto", "on", "off"),
                    default="auto",
                    help="micro-batch concurrent queries (auto: on for "
                         "batch-capable algorithms)")
    sp.add_argument("--batch-max-size", type=int, default=64)
    sp.add_argument("--batch-max-delay-ms", type=float, default=2.0)
    sp.add_argument("--batch-max-queue", type=int, default=256,
                    help="admission control: 503 beyond this queue depth")
    sp.add_argument("--drain-grace-s", type=float, default=30.0,
                    help="SIGTERM graceful drain: seconds to wait for "
                         "in-flight batches before exiting")
    sp.add_argument("--aot", choices=("auto", "on", "off"), default="auto",
                    help="ahead-of-time compile every (bucket, template, "
                         "k) serving program before taking traffic "
                         "(serving/aot.py; PIO_AOT=0/1 overrides)")
    sp.add_argument("--aot-threads", type=int, default=0,
                    help="AOT prebuild thread-pool width (0 = "
                         "PIO_AOT_THREADS or 4)")
    sp.add_argument("--compile-cache", default="",
                    help="persistent XLA compile-cache directory to "
                         "pre-seed from the model's exported cache "
                         "artifact (sets PIO_COMPILE_CACHE_DIR; must "
                         "agree with JAX_COMPILATION_CACHE_DIR where "
                         "that is set)")
    sp.add_argument("--waterfall", action="store_true",
                    help="sample per-request latency waterfalls "
                         "(pio_serve_stage_seconds + /debug/slow.json; "
                         "sets PIO_WATERFALL=1)")
    sp.add_argument("--profile-dir", default="",
                    help="directory for POST /debug/profile capture "
                         "artifacts (sets PIO_PROFILE_DIR)")
    sp.add_argument("--shard-serving", choices=("auto", "on", "off"),
                    default="auto",
                    help="row-shard the deployed factor matrices over "
                         "the device mesh and serve top-k from "
                         "per-device shards (parallel/serve_dist.py; "
                         "bit-identical results, per-device HBM drops "
                         "to total/n_dev; auto = multi-device "
                         "accelerator meshes only; PIO_SERVE_SHARD "
                         "overrides)")
    sp.add_argument("--serve-quant", choices=("auto", "on", "off"),
                    default="auto",
                    help="serve top-k from int8 factor matrices with "
                         "per-row fp32 scales (ops/quant.py; ~4x less "
                         "HBM footprint and bandwidth, ranking-parity "
                         "contract recall@k >= 0.99 — KNOWN_ISSUES #12; "
                         "auto = accelerator backends only, gated by "
                         "the deploy-time recall probe; composes with "
                         "--shard-serving; PIO_SERVE_QUANT overrides)")
    sp.add_argument("--foldin", choices=("on", "off"), default="off",
                    help="run the realtime fold-in worker in-process "
                         "(realtime/foldin.py): tail the event store, "
                         "re-solve dirty users against the fixed item "
                         "matrix with the ALS half-step, publish rows "
                         "atomically into the live model — new users "
                         "get personalized top-k in seconds without a "
                         "retrain (PIO_FOLDIN=0/1 overrides)")
    sp.add_argument("--foldin-tick-ms", type=float, default=0.0,
                    help="fold-in tick cadence in ms (0 = "
                         "PIO_FOLDIN_TICK_MS or 250)")
    sp.add_argument("--foldin-headroom", type=int, default=0,
                    help="user-row capacity pre-padded for fold-in "
                         "appends (0 = PIO_FOLDIN_HEADROOM or 1024)")
    sp.add_argument("--foldin-item-headroom", type=int, default=0,
                    help="item-row capacity pre-padded for fold-in of "
                         "unseen ITEMS (0 = PIO_FOLDIN_ITEM_HEADROOM "
                         "or 1024)")
    sp.add_argument("--autotrain", action="store_true",
                    help="embed the continuous-training control loop "
                         "in this server process: drift / lag / volume "
                         "/ staleness triggers, in-process streamed "
                         "retrain, validation gates, in-place publish "
                         "(workflow/autotrain.py)")
    sp.add_argument("--autotrain-dry-run", action="store_true",
                    help="embedded autotrain journals would-have "
                         "retrain decisions without training")
    sp.add_argument("--partition", default="",
                    help="partition-routed deploy scope i/N (e.g. 0/4): "
                         "serve only the owned contiguous item-row "
                         "range — the per-replica model shrinks to "
                         "~1/N and `pio router` scatters each query "
                         "over all N partitions and merges bit-"
                         "identically (PIO_DEPLOY_PARTITION overrides; "
                         "default: full model)")
    sp.add_argument("--slo-availability", type=float, default=None,
                    help="availability SLO target, e.g. 0.999 "
                         "(default PIO_SLO_AVAILABILITY or 0.999)")
    sp.add_argument("--slo-latency-ms", type=float, default=None,
                    help="latency SLO threshold in ms, e.g. 25 "
                         "(default PIO_SLO_LATENCY_MS or 25)")
    telemetry_flags(sp)

    sp = sub.add_parser("undeploy", help="stop a deployed engine server")
    sp.add_argument("--ip", default="localhost")
    sp.add_argument("--port", type=int, default=8000)

    sp = sub.add_parser(
        "foldin",
        help="standalone realtime fold-in soak: tail the event store "
             "and re-solve dirty users against the latest trained "
             "model in this process (dry-run twin of `pio deploy "
             "--foldin`; exit 0 clean / 1 unsupported backend)")
    engine_flags(sp)
    sp.add_argument("--engine-instance-id", default=None)
    sp.add_argument("--tick-ms", type=float, default=0.0,
                    help="tick cadence in ms (0 = PIO_FOLDIN_TICK_MS "
                         "or 250)")
    sp.add_argument("--max-ticks", type=int, default=0,
                    help="stop after N ticks (0 = run until Ctrl-C)")

    sp = sub.add_parser(
        "doctor",
        help="one-screen health verdict for a running daemon "
             "(scrapes /healthz, /metrics, /traces.json, "
             "/debug/device.json; exit 0 green / 1 red / 2 unreachable)")
    sp.add_argument("url", nargs="?", default="",
                    help="daemon base URL (default http://<ip>:<port>)")
    sp.add_argument("--ip", default="localhost")
    sp.add_argument("--port", type=int, default=8000)
    sp.add_argument("--targets", default="",
                    help="comma-separated fleet base URLs (router + "
                         "replicas + storage): run the verdict over "
                         "every member, exit with the worst code")
    sp.add_argument("--timeout", type=float, default=5.0,
                    help="per-scrape timeout in seconds")

    sp = sub.add_parser(
        "profile",
        help="capture a bounded device profile from a running daemon "
             "(POST /debug/profile; artifact in xprof layout on the "
             "server; exit 0 non-empty / 1 failed / 2 unreachable)")
    sp.add_argument("url", nargs="?", default="",
                    help="daemon base URL (default http://<ip>:<port>)")
    sp.add_argument("--ip", default="localhost")
    sp.add_argument("--port", type=int, default=8000)
    sp.add_argument("--ms", type=int, default=2000,
                    help="capture length in ms (server clamps to its "
                         "PIO_PROFILE_MAX_MS, default 10000)")
    sp.add_argument("-o", "--out", default="",
                    help="server-side subdirectory (under the server's "
                         "PIO_PROFILE_DIR) for the artifact; paths "
                         "escaping the base are refused (400)")
    sp.add_argument("--timeout", type=float, default=5.0,
                    help="per-request timeout in seconds")

    sp = sub.add_parser(
        "trace",
        help="assemble one trace id across a daemon fleet into a "
             "single waterfall tree (fans out to every target's "
             "/traces.json?trace_id=, joins spans with clock-skew "
             "correction; exit 0 assembled / 1 not found / 2 "
             "unreachable)")
    sp.add_argument("trace_id", help="the 16-hex trace id (from "
                    "/debug/slow.json, a /metrics exemplar, a journal "
                    "event, or an X-PIO-Trace header)")
    sp.add_argument("--targets", required=True,
                    help="comma-separated daemon base URLs (query, "
                         "storage, event servers)")
    sp.add_argument("--timeout", type=float, default=5.0,
                    help="per-target timeout in seconds")

    sp = sub.add_parser(
        "events",
        help="merge-tail the operational journals "
             "(/debug/events.json) of a daemon fleet by timestamp "
             "(exit 0 / 2 when every target is unreachable)")
    sp.add_argument("--targets", required=True,
                    help="comma-separated daemon base URLs")
    sp.add_argument("--since-seq", type=int, default=0,
                    help="only events with seq beyond this cursor "
                         "(per target; default 0 = everything buffered)")
    sp.add_argument("--level", default="",
                    help="minimum severity: info (default) / warn / red")
    sp.add_argument("--category", default="",
                    help="narrow to one journal category (see the "
                         "README flight-recorder table)")
    sp.add_argument("--follow", action="store_true",
                    help="keep polling for new events (Ctrl-C to stop)")
    sp.add_argument("--interval", type=float, default=2.0,
                    help="--follow poll interval in seconds")
    sp.add_argument("--timeout", type=float, default=5.0,
                    help="per-target timeout in seconds")

    sp = sub.add_parser(
        "monitor",
        help="one-screen auto-refreshing fleet view: QPS, p99, error "
             "rate and SLO burn per target from each daemon's metrics "
             "history rings (/debug/history.json; exit 0 / 2 when "
             "every target is unreachable)")
    sp.add_argument("--targets", default="",
                    help="comma-separated daemon base URLs (router + "
                         "replicas + storage)")
    sp.add_argument("--once", action="store_true",
                    help="print one frame and exit (scripting)")
    sp.add_argument("--interval", type=float, default=5.0,
                    help="refresh interval in seconds")
    sp.add_argument("--record", default="",
                    help="append every frame's raw fetches to FILE as "
                         "JSON lines — the durable path out of the "
                         "bounded per-process rings (KNOWN_ISSUES #20)")
    sp.add_argument("--replay", default="",
                    help="re-render a --record file frame by frame "
                         "without touching the network")
    sp.add_argument("--timeout", type=float, default=5.0,
                    help="per-target timeout in seconds")

    sp = sub.add_parser(
        "incident",
        help="assemble one ordered incident timeline from a fleet: "
             "journal events + metric change-points (history rings) + "
             "slow exemplars + referenced traces, clock-skew "
             "corrected (exit 0 clean / 1 evidence found / 2 "
             "unreachable)")
    sp.add_argument("--targets", required=True,
                    help="comma-separated daemon base URLs")
    sp.add_argument("--window", default="10m",
                    help="lookback window, e.g. 10m / 90s / 1h "
                         "(default 10m)")
    sp.add_argument("--trace", default="",
                    help="seed the assembly with this trace id "
                         "(otherwise traces referenced by journal "
                         "events / slow exemplars are fetched)")
    sp.add_argument("--timeout", type=float, default=5.0,
                    help="per-target timeout in seconds")

    sp = sub.add_parser(
        "lint",
        help="repo-wide static analysis of the KNOWN_ISSUES invariants "
             "(tools/analyze; exit 0 clean / 1 findings / 2 internal "
             "error)")
    sp.add_argument("--json", action="store_true",
                    help="machine-readable result on stdout")
    sp.add_argument("--update-baseline", action="store_true",
                    help="accept the current findings into the "
                         "suppression baseline (conf/lint_baseline.json)")
    sp.add_argument("--list", dest="list_passes", action="store_true",
                    help="list passes and rules, run nothing")
    sp.add_argument("--root", default="",
                    help="repo root (default: autodetected)")
    sp.add_argument("--baseline", default="",
                    help="baseline path (default conf/lint_baseline.json)")

    sp = sub.add_parser("run", help="run an arbitrary entry point")
    sp.add_argument("main_class")
    sp.add_argument("args", nargs="*")
    sp.add_argument("--engine-dir", default=".")

    sp = sub.add_parser(
        "router",
        help="start the replica-fleet front door: fan /queries.json "
             "out to N query-server replicas with failover, load "
             "shedding, and the coordinated /reload hot-swap barrier "
             "(workflow/router.py)")
    sp.add_argument("--backends", required=True,
                    help="comma-separated query-server base URLs, e.g. "
                         "http://host:8000,http://host:8001")
    sp.add_argument("--ip", default="0.0.0.0")
    sp.add_argument("--port", type=int, default=8100)
    sp.add_argument("--health-ms", type=float, default=0.0,
                    help="membership poll cadence in ms (0 = "
                         "PIO_ROUTER_HEALTH_MS or 500)")
    sp.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-query deadline budget in ms, propagated "
                         "as X-PIO-Deadline-Ms (0 = "
                         "PIO_ROUTER_DEADLINE_MS or 2000)")
    sp.add_argument("--max-inflight", type=int, default=0,
                    help="admission ceiling before 503 + Retry-After "
                         "(0 = PIO_ROUTER_MAX_INFLIGHT or 256)")
    sp.add_argument("--cache", choices=("on", "off"), default="",
                    help="front-door response cache: answer repeat "
                         "(tenant, query bytes, model generation) hits "
                         "from a bounded LRU without touching a replica "
                         "— a /reload invalidates by construction, per "
                         "tenant (default PIO_ROUTER_CACHE or off)")
    sp.add_argument("--cache-mb", type=int, default=0,
                    help="response-cache byte budget in MB (0 = "
                         "PIO_ROUTER_CACHE_MB or 16)")
    sp.add_argument("--cache-ttl-ms", type=float, default=0.0,
                    help="response-cache entry TTL in ms — bounds "
                         "fold-in staleness, KNOWN_ISSUES #17 (0 = "
                         "PIO_ROUTER_CACHE_TTL_MS or 5000)")
    sp.add_argument("--autopilot", action="store_true",
                    help="embed the SLO-driven control loop in this "
                         "router process (workflow/autopilot.py)")
    sp.add_argument("--autopilot-dry-run", action="store_true",
                    help="embedded autopilot journals would-have "
                         "decisions without acting")
    sp.add_argument("--replica-cmd", default="",
                    help="shell command template (with a {port} "
                         "placeholder) the autopilot spawns local "
                         "replica subprocesses from; empty disables "
                         "elastic replica control")
    sp.add_argument("--autotrain", action="store_true",
                    help="embed the continuous-training control loop "
                         "in this router process: retrains run as pio "
                         "train subprocesses, accepted candidates "
                         "publish through the zero-drop /reload "
                         "barrier (workflow/autotrain.py)")
    sp.add_argument("--autotrain-dry-run", action="store_true",
                    help="embedded autotrain journals would-have "
                         "retrain decisions without training")
    sp.add_argument("--engine-dir", default=".",
                    help="engine directory the embedded autotrain "
                         "reads params and launches retrains from")
    sp.add_argument("--variant", default="engine.json")
    sp.add_argument("--train-cmd", default="",
                    help="retrain command the embedded autotrain "
                         "launches per cycle (default: pio train over "
                         "--engine-dir/--variant)")
    telemetry_flags(sp)

    sp = sub.add_parser(
        "autopilot",
        help="SLO-driven self-healing control loop over a running "
             "router: elastic replicas, degradation ladder, latency "
             "quarantine, burn-episode profile capture "
             "(workflow/autopilot.py)")
    sp.add_argument("--router", required=True,
                    help="router base URL, e.g. http://host:8100")
    sp.add_argument("--dry-run", action="store_true",
                    help="journal would-have decisions without acting")
    sp.add_argument("--replica-cmd", default="",
                    help="shell command template (with a {port} "
                         "placeholder) to spawn local replica "
                         "subprocesses; empty disables elastic "
                         "replica control")
    telemetry_flags(sp)

    sp = sub.add_parser(
        "autotrain",
        help="continuous-training control loop over a running deploy "
             "server or router: drift / cursor-lag / volume / "
             "staleness triggers, streamed retrain subprocesses with "
             "crash-resume, score + ranking-parity validation gates, "
             "barrier publish (workflow/autotrain.py)")
    sp.add_argument("--server", required=True,
                    help="deploy-server or router base URL, e.g. "
                         "http://host:8000")
    sp.add_argument("--engine-dir", default=".",
                    help="engine directory to read params and launch "
                         "retrains from")
    sp.add_argument("--variant", default="engine.json")
    sp.add_argument("--dry-run", action="store_true",
                    help="journal would-have retrain decisions "
                         "without training")
    sp.add_argument("--train-cmd", default="",
                    help="retrain command launched per cycle "
                         "(default: pio train over "
                         "--engine-dir/--variant)")
    telemetry_flags(sp)

    sp = sub.add_parser("eventserver", help="start the event server")
    sp.add_argument("--ip", default="0.0.0.0")
    sp.add_argument("--port", type=int, default=7070)
    sp.add_argument("--stats", action="store_true")
    telemetry_flags(sp)

    sp = sub.add_parser("dashboard", help="start the evaluation dashboard")
    sp.add_argument("--ip", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=9000)
    sp.add_argument("--key", default="",
                    help="require this server key (or set PIO_SERVER_KEY)")

    sp = sub.add_parser("adminserver", help="start the admin API server")
    sp.add_argument("--ip", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=7071)
    sp.add_argument("--key", default="",
                    help="require this server key (or set PIO_SERVER_KEY)")

    sub.add_parser("shell", help="interactive shell with Storage "
                   "preloaded (pio-shell)")

    sp = sub.add_parser("storageserver",
                        help="serve this node's storage to remote clients")
    sp.add_argument("--ip", default="0.0.0.0")
    sp.add_argument("--port", type=int, default=7072)
    sp.add_argument("--key", default="",
                    help="shared secret clients must send "
                         "(X-PIO-Storage-Key)")
    telemetry_flags(sp)

    sp = sub.add_parser("app", help="manage apps")
    asub = sp.add_subparsers(dest="app_command", required=True)
    a = asub.add_parser("new")
    a.add_argument("name")
    a.add_argument("--id", type=int, default=None)
    a.add_argument("--description", default=None)
    a.add_argument("--access-key", default=None)
    asub.add_parser("list")
    a = asub.add_parser("show")
    a.add_argument("name")
    a = asub.add_parser("delete")
    a.add_argument("name")
    a.add_argument("-f", "--force", action="store_true")
    a = asub.add_parser("data-delete")
    a.add_argument("name")
    a.add_argument("--channel", default=None)
    a.add_argument("--all", action="store_true")
    a.add_argument("-f", "--force", action="store_true")
    a = asub.add_parser("channel-new")
    a.add_argument("name")
    a.add_argument("channel")
    a = asub.add_parser("channel-delete")
    a.add_argument("name")
    a.add_argument("channel")
    a.add_argument("-f", "--force", action="store_true")

    sp = sub.add_parser("accesskey", help="manage access keys")
    ksub = sp.add_subparsers(dest="accesskey_command", required=True)
    k = ksub.add_parser("new")
    k.add_argument("app_name")
    k.add_argument("--key", default=None)
    k.add_argument("--event", action="append", default=None,
                   help="restrict to this event name (repeatable)")
    k = ksub.add_parser("list")
    k.add_argument("app_name", nargs="?", default=None)
    k = ksub.add_parser("delete")
    k.add_argument("key")

    sp = sub.add_parser("template", help="engine template info")
    tsub = sp.add_subparsers(dest="template_command")
    tsub.add_parser("list")
    t = tsub.add_parser("get")
    t.add_argument("name", nargs="?")

    sp = sub.add_parser(
        "unregister",
        help="unregister an engine (no-op; Console.scala:170 parity)")
    sp.add_argument("--engine-dir", default=".")
    sub.add_parser("upgrade", help="no longer supported")

    sp = sub.add_parser("import", help="import events from a JSON-lines file")
    sp.add_argument("--appid", type=int, required=True)
    sp.add_argument("--channel", default=None)
    sp.add_argument("--input", required=True)

    sp = sub.add_parser("export", help="export events to a JSON-lines file")
    sp.add_argument("--appid", type=int, required=True)
    sp.add_argument("--channel", default=None)
    sp.add_argument("--output", required=True)

    return p


_DISPATCH = {
    "build": cmd_build,
    "train": cmd_train,
    "eval": cmd_eval,
    "deploy": cmd_deploy,
    "undeploy": cmd_undeploy,
    "foldin": cmd_foldin,
    "doctor": cmd_doctor,
    "monitor": cmd_monitor,
    "incident": cmd_incident,
    "trace": cmd_trace,
    "events": cmd_events,
    "lint": cmd_lint,
    "profile": cmd_profile,
    "run": cmd_run,
    "router": cmd_router,
    "autopilot": cmd_autopilot,
    "autotrain": cmd_autotrain,
    "eventserver": cmd_eventserver,
    "dashboard": cmd_dashboard,
    "adminserver": cmd_adminserver,
    "storageserver": cmd_storageserver,
    "shell": cmd_shell,
    "status": cmd_status,
    "app": cmd_app,
    "accesskey": cmd_accesskey,
    "template": cmd_template,
    "import": cmd_import,
    "export": cmd_export,
    "unregister": cmd_unregister,
    "upgrade": cmd_upgrade,
}


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    if args.verbose:
        logging.basicConfig(level=logging.DEBUG)
    else:
        logging.basicConfig(level=logging.INFO)
    if args.command is None or args.command == "version":
        print(__version__)
        return 0
    try:
        return _DISPATCH[args.command](args)
    except (CommandError, FileNotFoundError, ValueError) as e:
        # operational failures (no COMPLETED instance for deploy, bad params,
        # incompatible checkpoints, missing files) print the reference-style
        # one-liner and exit 1; the traceback stays reachable under -v so a
        # genuine library bug surfacing as ValueError is still debuggable
        logging.getLogger(__name__).debug("command failed", exc_info=True)
        _error(str(e))
        return 1


if __name__ == "__main__":
    sys.exit(main())
