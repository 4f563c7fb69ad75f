"""WorkflowContext — the SparkContext analogue.

Reference: core/.../workflow/WorkflowContext.scala:28-50 (context factory)
and WorkflowParams (core/.../workflow/WorkflowParams.scala).

One context per run. It owns:
- the device mesh (None = single-device; tests/dry-runs pass a CPU mesh);
- the WorkflowParams (batch label, sanity-check / stop-after flags);
- the Storage handle engines read events through.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Optional

from predictionio_tpu.data.storage import Storage, get_storage


@dataclasses.dataclass
class WorkflowParams:
    """Mirror of WorkflowParams.scala (batch, verbose, skipSanityCheck,
    stopAfterRead, stopAfterPrepare) + profile_dir: when set, run_train
    wraps training in jax.profiler.trace (SURVEY.md §5 — the Spark-UI
    replacement)."""
    batch: str = ""
    verbose: int = 2
    skip_sanity_check: bool = False
    stop_after_read: bool = False
    stop_after_prepare: bool = False
    profile_dir: Optional[str] = None


class WorkflowContext:
    def __init__(
        self,
        workflow_params: Optional[WorkflowParams] = None,
        mesh=None,
        storage: Optional[Storage] = None,
        runtime_env: Optional[Dict[str, str]] = None,
        app_name: str = "",
    ):
        self.workflow_params = workflow_params or WorkflowParams()
        self.mesh = mesh
        self._storage = storage
        self.runtime_env = dict(runtime_env or {})
        # appName analogue: "PredictionIO <mode>: <batch>" (WorkflowContext.scala:36-38)
        self.app_name = app_name
        # per-phase wall-clock (SURVEY.md §5 tracing: the Spark-UI
        # replacement); run_train persists it in the EngineInstance row
        self.phase_seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        """Accumulate one named phase's wall-clock.

        Timing honesty (KNOWN_ISSUES #3): every phase body ends in a real
        host transfer (a one-element jax.device_get) before this clock
        stops — never block_until_ready alone, which returned early on
        the early rounds' backend. The same number is mirrored into the metrics
        registry (`pio_train_phase_seconds{phase=...}`) when telemetry
        is on, so `GET /metrics` and the EngineInstance phase table agree.

        XLA compiles inside a phase are attributed to it
        (`pio_xla_compiles_total{fn="train:<phase>",...}`, common/
        devicewatch.py) unless a narrower region — the ALS trainers —
        claims them first.
        """
        from predictionio_tpu.common import devicewatch
        t0 = time.perf_counter()
        try:
            with devicewatch.attribution(f"train:{name}", phase="train"):
                yield
        finally:
            self.note_phase(name, time.perf_counter() - t0)

    def note_phase(self, name: str, seconds: float) -> None:
        """Accumulate an externally-timed (sub-)phase — e.g. the bulk
        read's read_io/read_encode split, measured inside the store —
        into the phase table AND the metrics registry, identically to a
        `with ctx.phase(name)` region."""
        from predictionio_tpu.common import telemetry
        self.phase_seconds[name] = (
            self.phase_seconds.get(name, 0.0) + seconds)
        if telemetry.on():
            telemetry.registry().histogram(
                "pio_train_phase_seconds",
                "Train/eval phase wall-clock (read/layout/train/persist "
                "+ read_io/read_encode sub-phases; regions end in a host "
                "transfer per KNOWN_ISSUES #3)",
                labelnames=("phase",),
                buckets=(0.01, 0.05, 0.1, 0.5, 1.0, 2.5, 5.0, 10.0,
                         30.0, 60.0, 300.0)).labels(
                phase=name).observe(seconds)

    @property
    def storage(self) -> Storage:
        return self._storage if self._storage is not None else get_storage()

    @property
    def n_devices(self) -> int:
        if self.mesh is None:
            return 1
        return int(self.mesh.devices.size)
