"""Training/evaluation run bookkeeping around the engine.

Reference: core/.../workflow/CoreWorkflow.scala:45-160 and
EvaluationWorkflow.scala:32-45. A train run: insert EngineInstance(INIT),
engine.train, serialize models into the Models store keyed by the instance
id, mark COMPLETED. An eval run: insert EvaluationInstance, batch-eval every
EngineParams variant (prefix-memoized, FastEvalEngine parity), score with
the MetricEvaluator, store results, mark EVALCOMPLETED.
"""

from __future__ import annotations

import datetime as _dt
import logging
import os
import traceback
from typing import Optional, Sequence

from predictionio_tpu.controller.engine import Engine, EngineParams
from predictionio_tpu.controller.evaluation import (
    Evaluation, MetricEvaluatorResult,
)
from predictionio_tpu.data.storage import (
    EngineInstance, EvaluationInstance, Model,
)
from predictionio_tpu.workflow import model_io
from predictionio_tpu.workflow.context import WorkflowContext
from predictionio_tpu.workflow.fast_eval import FastEvalEngineWorkflow

logger = logging.getLogger("predictionio_tpu.workflow")


def _now():
    return _dt.datetime.now(_dt.timezone.utc)


def _find_auto_resume(instances, engine_id: str,
                      engine_variant: str) -> Optional[str]:
    """Newest crashed run of this engine/variant whose iteration
    snapshots survived — the auto-resume candidate for `pio train`.

    ERROR rows are runs whose failure was recorded; INIT rows are runs
    that died before any ledger update (SIGKILL, OOM, power loss). Both
    keep their FactorCheckpointer directory, which run_train clears only
    on success. Caveat: an INIT row could belong to a training still
    running in another process — don't run two trains of the same
    variant concurrently against one ledger (same contract as the
    eventlog's single-writer rule); PIO_AUTO_RESUME=0 or
    `pio train --no-auto-resume` opts out."""
    from predictionio_tpu.workflow.checkpoint import (
        latest_step_in, run_checkpoint_dir,
    )
    best = None
    for row in instances.get_all():
        if (row.engine_id != engine_id
                or row.engine_variant != engine_variant
                or row.status not in ("ERROR", "INIT")):
            continue
        if latest_step_in(run_checkpoint_dir(row.id)) is None:
            continue
        if best is None or row.start_time > best.start_time:
            best = row
    return best.id if best else None


def run_train(
    ctx: WorkflowContext,
    engine: Engine,
    engine_params: EngineParams,
    engine_id: str = "default",
    engine_version: str = "NOT_USED",
    engine_variant: str = "default",
    engine_factory: str = "",
    params_json: Optional[dict] = None,
    resume_from: Optional[str] = None,
) -> str:
    """Run one training; returns the COMPLETED EngineInstance id
    (CoreWorkflow.runTrain, CoreWorkflow.scala:45-101).

    resume_from: instance id of a prior FAILED run — its iteration
    snapshots (if the algorithm checkpoints) seed this run instead of
    starting from iteration 0.

    Multi-host: every process of a jax.distributed job calls run_train
    (the sharded trainer's collectives need all of them), but only
    process 0 writes the ledger row and model blob — the others train
    and return "" (the Spark-driver-vs-executor split, SURVEY.md §2.7).

    Device observability: the devicewatch compile watchdog is installed
    before training so `pio train --telemetry` attributes every XLA
    compile to its phase/trainer (common/devicewatch.py).
    Iteration checkpointing is disabled UNIFORMLY on multi-host jobs:
    per-segment snapshots would give each rank a different compiled-call
    schedule (and resume a different restore state) unless the snapshot
    dir were a shared filesystem, which this runtime does not assume."""
    import jax

    from predictionio_tpu.common import devicewatch
    from predictionio_tpu.serving import aot
    devicewatch.install()
    # the persistent compile cache is always on (JAX_COMPILATION_CACHE_DIR
    # or <checkout>/.jax_cache). Compile-cache-as-artifact (serving/
    # aot.py) stays tied to an explicit --compile-cache: snapshot that
    # directory now — every entry this run adds (trainer programs + the
    # model's AOT-built serving programs) exports with the model so
    # `pio deploy` pre-seeds a warm cache
    aot.ensure_persistent_cache()
    cache_dir = aot.artifact_cache_dir()
    cache_before = (model_io.cache_snapshot(cache_dir)
                    if cache_dir else None)
    if jax.process_count() > 1:
        if resume_from:
            raise ValueError(
                "resume_from is not supported on multi-host jobs: iteration "
                "snapshots are per-host, so ranks would restore divergent "
                "factors. Re-run the training from scratch.")
        ctx.checkpoint_dir = None   # same single-segment schedule, all ranks
        if jax.process_index() != 0:
            engine.train(ctx, engine_params)
            return ""
    storage = ctx.storage
    instances = storage.get_meta_data_engine_instances()
    if (resume_from is None and jax.process_count() == 1
            and os.environ.get("PIO_AUTO_RESUME", "1") != "0"):
        # crash recovery: a prior run of this engine/variant that died
        # (ERROR, or INIT after a hard kill) and left iteration snapshots
        # seeds this run instead of restarting from iteration 0
        auto = _find_auto_resume(instances, engine_id, engine_variant)
        if auto:
            logger.info(
                "Auto-resuming from crashed run %s's iteration snapshots "
                "(disable with --no-auto-resume / PIO_AUTO_RESUME=0)", auto)
            resume_from = auto
    # out-of-core training mode resolution (PIO_TRAIN_STREAM, data/
    # store.py): resolved ONCE here against the event source's
    # capabilities so the ledger row records which read path this run
    # took; `off` is the bit-compatible in-core path, and a template
    # that never opts in simply ignores the resolution
    from predictionio_tpu.data import store as _store
    try:
        _events_dao = storage.get_events()
    except Exception:   # metadata-only storage in tests
        _events_dao = None
    train_stream = _store.resolve_train_stream(_events_dao)
    logger.info("train read path: %s (PIO_TRAIN_STREAM=%s)",
                "streamed (O(chunk) host)" if train_stream else "in-core",
                _store.train_stream_mode())
    # training cursor: snapshot the event-store head BEFORE the train
    # read so the ledger row records the batch base this model absorbed.
    # Conservative by design — events landing mid-read are re-processed
    # by the fold-in speed layer (idempotent re-solves), never lost.
    # autotrain's volume trigger and the fold-in rebase both key off it.
    train_cursor = None
    if _events_dao is not None and hasattr(_events_dao, "head_cursor"):
        try:
            dsp = getattr(engine_params, "data_source_params", None)
            _app_name = getattr(dsp, "appName", None)
            if _app_name:
                _app = storage.get_meta_data_apps().get_by_name(
                    str(_app_name))
                if _app is not None:
                    train_cursor = _events_dao.head_cursor(_app.id, None)
        except Exception:   # cursor capture is strictly best-effort
            train_cursor = None
    import json as _json
    pj = params_json or {}
    instance = EngineInstance(
        id="", status="INIT", start_time=_now(), end_time=_now(),
        engine_id=engine_id, engine_version=engine_version,
        engine_variant=engine_variant, engine_factory=engine_factory,
        batch=ctx.workflow_params.batch, env=dict(ctx.runtime_env),
        data_source_params=_json.dumps(pj.get("datasource", {})),
        preparator_params=_json.dumps(pj.get("preparator", {})),
        algorithms_params=_json.dumps(pj.get("algorithms", [])),
        serving_params=_json.dumps(pj.get("serving", {})),
    )
    instance_id = instances.insert(instance)
    logger.info("EngineInstance %s created (INIT)", instance_id)
    # iteration-checkpoint location for algorithms that opt in (an
    # improvement over the reference; workflow/checkpoint.py). Resuming a
    # crashed run reuses ITS directory so saved snapshots are consulted.
    from predictionio_tpu.workflow.checkpoint import run_checkpoint_dir
    if jax.process_count() == 1:
        ctx.checkpoint_dir = run_checkpoint_dir(resume_from or instance_id)
    try:
        profile_dir = getattr(ctx.workflow_params, "profile_dir", None)
        if profile_dir:
            # JAX profiler trace — the Spark-UI replacement (SURVEY.md §5);
            # view with tensorboard or xprof. Routed through
            # common/profiling.py so the train artifact shares one
            # format (capture.json + xprof layout) and one
            # single-capture guard with the daemons' on-demand
            # POST /debug/profile captures.
            from predictionio_tpu.common import profiling

            with profiling.trace(profile_dir, label="train"):
                models = engine.train(ctx, engine_params)
        else:
            models = engine.train(ctx, engine_params)
        with ctx.phase("persist"):
            models = engine.make_serializable_models(
                ctx, instance_id, engine_params, models)
            blob = model_io.serialize_models(
                models,
                check_finite=os.environ.get("PIO_FINITE_CHECK", "1") != "0")
            storage.get_model_data_models().insert(
                Model(id=instance_id, models=blob))
        if cache_dir and os.environ.get("PIO_AOT", "") != "0":
            # AOT-build the model's serving programs from declared
            # shapes and export the run's compile-cache delta as the
            # instance's deploy artifact (serving/aot.py). Only with an
            # explicit --compile-cache — the built executables ARE
            # the artifact's payload. Best-effort by contract:
            # export_train_artifact never raises, so a broken cache dir
            # cannot fail a finished training.
            with ctx.phase("aot_export"):
                _, _, algorithms, _serving = engine._instantiate(
                    engine_params)
                aot_summary = aot.export_train_artifact(
                    storage, instance_id, algorithms, models,
                    cache_dir, cache_before)
            logger.info("AOT export: %s", aot_summary)
        phases = dict(ctx.phase_seconds)
        if profile_dir:
            # the telemetry phase table lands NEXT TO the XLA profile so
            # `pio train --profile DIR` yields both views of the same run:
            # xprof/tensorboard for device time, this JSON for the
            # host-side phase split (each phase ends in a real host
            # transfer — KNOWN_ISSUES #3 — so the two can be reconciled)
            import json as _pj
            try:
                os.makedirs(profile_dir, exist_ok=True)
                with open(os.path.join(profile_dir,
                                       "telemetry_phases.json"), "w") as f:
                    _pj.dump({"engineInstanceId": instance_id,
                              "phaseSeconds": {k: round(v, 6)
                                               for k, v in phases.items()}},
                             f, indent=2, sort_keys=True)
            except OSError:
                logger.warning("could not write telemetry phase table to "
                               "%s", profile_dir, exc_info=True)
        logger.info("Training completed; EngineInstance %s COMPLETED "
                    "(model blob %d bytes)", instance_id, len(blob))
        row = instances.get(instance_id)
        instances.update(EngineInstance(
            **{**row.__dict__, "status": "COMPLETED", "end_time": _now(),
               "runtime_conf": {**row.runtime_conf,
                                "train_stream":
                                    "on" if train_stream else "off",
                                **({"train_cursor":
                                    _json.dumps(train_cursor)}
                                   if train_cursor is not None else {}),
                                **{f"phase_{k}_s": f"{v:.3f}"
                                   for k, v in phases.items()}}}))
        if phases:
            width = max(len(k) for k in phases)
            table = "\n".join(f"  {k.ljust(width)}  {v:8.3f}s"
                              for k, v in phases.items())
            logger.info("Phase wall-clock:\n%s", table)
        # the model blob persists the final state; snapshots are scratch
        if ctx.checkpoint_dir:
            from predictionio_tpu.workflow.checkpoint import (
                FactorCheckpointer,
            )
            FactorCheckpointer(ctx.checkpoint_dir).clear()
        return instance_id
    except Exception:
        row = instances.get(instance_id)
        if row is not None:
            instances.update(EngineInstance(
                **{**row.__dict__, "status": "ERROR", "end_time": _now()}))
        logger.error("Training failed:\n%s", traceback.format_exc())
        raise


def run_evaluation(
    ctx: WorkflowContext,
    evaluation: Evaluation,
    engine_params_list: Sequence[EngineParams],
    evaluation_class: str = "",
    generator_class: str = "",
    output_path: Optional[str] = None,
) -> MetricEvaluatorResult:
    """Evaluate every variant, pick the best, persist the ledger row
    (CoreWorkflow.runEvaluation :103-160 + EvaluationWorkflow.scala:32-45)."""
    storage = ctx.storage
    instances = storage.get_meta_data_evaluation_instances()
    instance_id = instances.insert(EvaluationInstance(
        id="", status="INIT", start_time=_now(), end_time=_now(),
        evaluation_class=evaluation_class,
        engine_params_generator_class=generator_class,
        batch=ctx.workflow_params.batch, env=dict(ctx.runtime_env)))
    try:
        workflow = FastEvalEngineWorkflow(evaluation.engine, ctx)
        # hoist the data read + device-side layout out of the per-variant
        # loop: one read + one layout per (data-source, preparator) prefix
        # and fold; rank-compatible variants below reuse them
        workflow.prepare_shared_layouts(engine_params_list)
        engine_eval_data_sets = [
            (ep, workflow.eval(ep)) for ep in engine_params_list]
        evaluator = evaluation.evaluator
        if output_path:
            evaluator.output_path = output_path
        result = evaluator.evaluate_base(ctx, evaluation, engine_eval_data_sets)
        row = instances.get(instance_id)
        if getattr(result, "no_save", False):
            # FakeEvalResult.noSave parity: ledger row only, no results
            instances.update(EvaluationInstance(
                **{**row.__dict__, "status": "EVALCOMPLETED",
                   "end_time": _now()}))
        else:
            instances.update(EvaluationInstance(
                **{**row.__dict__, "status": "EVALCOMPLETED",
                   "end_time": _now(),
                   "evaluator_results": str(result),
                   "evaluator_results_html": result.to_html(),
                   "evaluator_results_json": result.to_json()}))
        logger.info("EvaluationInstance %s EVALCOMPLETED", instance_id)
        return result
    except Exception:
        row = instances.get(instance_id)
        if row is not None:
            instances.update(EvaluationInstance(
                **{**row.__dict__, "status": "ERROR", "end_time": _now()}))
        raise
