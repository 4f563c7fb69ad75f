"""Multi-tenant model registry + admission control.

One ``pio deploy --engines conf.json`` process hosts N engine
instances — the production shape (ROADMAP item 1: heavy traffic is
never one model). This module is the lifecycle substrate:

- :class:`TenantSpec` / :func:`load_engines_conf` — the ``--engines``
  conf file: which engine instance each tenant serves, its access key,
  its HBM budget, and its private batcher-queue knobs.
- :class:`ServableModel` — one tenant's generation-versioned servable
  unit (engine + prepared models + serving + its OWN MicroBatcher).
  This replaces the single model field the query server used to hold.
- :class:`ModelRegistry` — the name → ServableModel map. Generations
  are per-tenant (a reload of tenant A never bumps B). HBM budgets are
  enforced at install: a tenant over its own soft budget is flagged
  (``pio doctor`` WARNs); a process past the hard cap
  (``PIO_TENANT_HBM_HARD_CAP_MB``) refuses the load outright.
- :class:`AdmissionController` — per-access-key admission resolved
  against the AccessKeys DAO (401 unknown key) with per-key token
  buckets (429 + Retry-After past the rate limit). Dapper's lesson:
  the key→tenant resolution happens ONCE here at the front of the
  request, and every downstream surface (serve histogram, SLO,
  waterfall, journal) inherits the ``tenant`` label.

Tenants share compiled code but not queue capacity: every tenant's
batcher pads onto the same process-wide (bucket × template × k) AOT
program set (serving/aot.py memoizes executables by shape), so compile
count stays flat as tenant count grows, while each tenant's 503s come
out of its OWN ``batch_max_queue``.

The budget is a load-time host-side estimate of model array bytes —
see KNOWN_ISSUES #16 for what it deliberately does not cover.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from predictionio_tpu.common import journal, telemetry

__all__ = [
    "TenantSpec", "ServableModel", "ModelRegistry",
    "AdmissionError", "AdmissionController",
    "load_engines_conf", "model_hbm_bytes",
]

#: the tenant name a no-``--engines`` (legacy single-engine) deploy
#: serves under — internal bookkeeping only; the legacy wire shape
#: never mentions it
DEFAULT_TENANT = "default"


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def _env_opt_float(name: str) -> Optional[float]:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        return None


# ---------------------------------------------------------------------------
# tenant specs (--engines conf.json)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant's slice of a multi-engine deploy: which trained
    instance it serves, the access key that routes to it, and its
    private capacity/budget knobs. Unset batching knobs inherit the
    deploy-wide ServerConfig values."""
    name: str
    access_key: Optional[str] = None
    engine_id: str = "default"
    engine_version: str = "NOT_USED"
    engine_variant: str = "default"
    engine_instance_id: Optional[str] = None
    engine_dir: Optional[str] = None
    #: per-tenant batcher knobs (None = inherit ServerConfig)
    batching: Optional[str] = None
    batch_max_size: Optional[int] = None
    batch_max_delay_ms: Optional[float] = None
    batch_max_queue: Optional[int] = None
    #: soft HBM budget in MiB (None = PIO_TENANT_HBM_BUDGET_MB or
    #: unbudgeted); exceeding it flags the tenant for the doctor WARN
    hbm_budget_mb: Optional[float] = None
    #: per-key token-bucket overrides (None = PIO_TENANT_RATE /
    #: PIO_TENANT_BURST; 0 rate = unlimited)
    rate: Optional[float] = None
    burst: Optional[float] = None


_CONF_KEYS = {
    "name": "name",
    "accessKey": "access_key",
    "engineId": "engine_id",
    "engineVersion": "engine_version",
    "engineVariant": "engine_variant",
    "engineInstanceId": "engine_instance_id",
    "engineDir": "engine_dir",
    "batching": "batching",
    "batchMaxSize": "batch_max_size",
    "batchMaxDelayMs": "batch_max_delay_ms",
    "batchMaxQueue": "batch_max_queue",
    "hbmBudgetMb": "hbm_budget_mb",
    "rate": "rate",
    "burst": "burst",
}


def parse_tenant_specs(obj: Any) -> Tuple[TenantSpec, ...]:
    """Parse the decoded ``--engines`` conf: either a bare list of
    tenant objects or ``{"tenants": [...]}``. Names must be unique and
    non-empty; access keys, when given, must be unique too (a key
    routes to exactly one tenant)."""
    if isinstance(obj, dict):
        obj = obj.get("tenants")
    if not isinstance(obj, list) or not obj:
        raise ValueError(
            "--engines conf must be a non-empty list of tenant objects "
            'or {"tenants": [...]}')
    specs: List[TenantSpec] = []
    for i, entry in enumerate(obj):
        if not isinstance(entry, dict):
            raise ValueError(f"--engines tenant #{i} is not an object")
        unknown = sorted(set(entry) - set(_CONF_KEYS))
        if unknown:
            raise ValueError(
                f"--engines tenant #{i}: unknown key(s) {unknown}; "
                f"expected a subset of {sorted(_CONF_KEYS)}")
        kwargs = {_CONF_KEYS[k]: v for k, v in entry.items()}
        name = str(kwargs.get("name") or "").strip()
        if not name:
            raise ValueError(f"--engines tenant #{i} has no name")
        kwargs["name"] = name
        specs.append(TenantSpec(**kwargs))
    names = [s.name for s in specs]
    if len(set(names)) != len(names):
        raise ValueError(f"--engines tenant names are not unique: {names}")
    keys = [s.access_key for s in specs if s.access_key]
    if len(set(keys)) != len(keys):
        raise ValueError("--engines access keys are not unique; a key "
                         "must route to exactly one tenant")
    return tuple(specs)


def load_engines_conf(path: str) -> Tuple[TenantSpec, ...]:
    """Read + parse a ``--engines`` conf file."""
    with open(path, "r", encoding="utf-8") as f:
        try:
            obj = json.load(f)
        except json.JSONDecodeError as e:
            raise ValueError(f"--engines conf {path} is not valid JSON: {e}")
    return parse_tenant_specs(obj)


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------

def model_hbm_bytes(models: Iterable[Any]) -> int:
    """Best-effort byte count of the array payload behind a tenant's
    prepared models: walk each model's attributes (one container level
    deep) and sum ``.nbytes`` of every distinct array found. This is
    the load-time estimate the budget is enforced against — it sees
    factor matrices and vocab arrays, not XLA scratch or fold-in
    growth (KNOWN_ISSUES #16). A budget is one device's HBM, so a
    device array counts for what its fullest device holds of it: all
    of a replicated array, one block of a row-sharded one
    (parallel/serve_dist.py). A model that keeps host copies beside
    its device arrays says what the device holds itself
    (``hbm_bytes()``: models/ecommerce ECommModel,
    models/similarproduct ALSModel)."""
    total = 0
    seen: set = set()

    def add(x: Any) -> None:
        nonlocal total
        n = getattr(x, "nbytes", None)
        if isinstance(n, (int, float)) and not isinstance(x, (str, bytes)):
            if id(x) not in seen:
                seen.add(id(x))
                sharding = getattr(x, "sharding", None)
                if sharding is not None:
                    # shape arithmetic only: the array is not touched
                    n = (math.prod(sharding.shard_shape(x.shape))
                         * x.dtype.itemsize)
                total += int(n)

    for model in models:
        if model is None:
            continue
        own = getattr(model, "hbm_bytes", None)
        if callable(own):
            total += int(own())
            continue
        add(model)
        attrs = getattr(model, "__dict__", None)
        values = list(attrs.values()) if isinstance(attrs, dict) else []
        if dataclasses.is_dataclass(model) and not isinstance(model, type):
            values.extend(getattr(model, f.name, None)
                          for f in dataclasses.fields(model))
        for v in values:
            add(v)
            if isinstance(v, dict):
                for vv in v.values():
                    add(vv)
            elif isinstance(v, (list, tuple)):
                for vv in v:
                    add(vv)
    return total


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServableModel:
    """One tenant's generation-versioned servable unit — everything
    the query path snapshots per request. ``generation`` is stamped by
    :meth:`ModelRegistry.install`."""
    name: str
    spec: TenantSpec
    instance: Any
    engine: Any
    engine_params: Any
    algorithms: List[Any]
    models: List[Any]
    serving: Any
    batcher: Any = None
    aot_state: Optional[Dict[str, Any]] = None
    shard_state: Optional[Dict[str, Any]] = None
    quant_state: Optional[Dict[str, Any]] = None
    model_bytes: int = 0
    generation: int = 0
    over_budget: bool = False

    @property
    def hbm_budget_mb(self) -> Optional[float]:
        if self.spec.hbm_budget_mb is not None:
            return float(self.spec.hbm_budget_mb)
        return _env_opt_float("PIO_TENANT_HBM_BUDGET_MB")

    def queue_depth(self) -> int:
        return self.batcher.depth() if self.batcher is not None else 0

    def state(self) -> Dict[str, Any]:
        """The per-tenant block `GET /` and `pio doctor` read."""
        budget = self.hbm_budget_mb
        out: Dict[str, Any] = {
            "generation": self.generation,
            "instanceId": self.instance.id,
            "algorithms": [type(a).__name__ for a in self.algorithms],
            "queueDepth": self.queue_depth(),
            "modelBytes": self.model_bytes,
            "batching": self.batcher is not None,
        }
        if budget is not None:
            out["budgetMb"] = budget
            out["overBudget"] = self.over_budget
        return out


class ModelRegistry:
    """Name → :class:`ServableModel`, with per-tenant generations and
    load-time HBM budget enforcement. ``install`` of an existing name
    is the hot-swap: the new servable takes generation+1 and the old
    batcher is the caller's to drain."""

    def __init__(self, hard_cap_mb: Optional[float] = None):
        self._lock = threading.Lock()
        self._servables: Dict[str, ServableModel] = {}
        self._hard_cap_mb = (hard_cap_mb if hard_cap_mb is not None
                             else _env_opt_float("PIO_TENANT_HBM_HARD_CAP_MB"))

    @property
    def hard_cap_mb(self) -> Optional[float]:
        return self._hard_cap_mb

    def install(self, servable: ServableModel) -> ServableModel:
        """Stamp the next generation and publish the servable. Raises
        ValueError (load refused, previous generation keeps serving)
        when the process total would cross the hard cap. Returns the
        PREVIOUS servable of that name (None on first install) so the
        caller can drain its batcher."""
        name = servable.name
        budget = servable.hbm_budget_mb
        servable.over_budget = bool(
            budget is not None
            and servable.model_bytes > budget * 1024 * 1024)
        with self._lock:
            prior = self._servables.get(name)
            others = sum(s.model_bytes for n, s in self._servables.items()
                         if n != name)
            total_mb = (others + servable.model_bytes) / (1024 * 1024)
            if self._hard_cap_mb is not None and total_mb > self._hard_cap_mb:
                raise ValueError(
                    f"tenant '{name}' load refused: process model bytes "
                    f"{total_mb:.1f} MiB would exceed the hard HBM cap "
                    f"{self._hard_cap_mb:g} MiB "
                    "(PIO_TENANT_HBM_HARD_CAP_MB)")
            servable.generation = (prior.generation + 1) if prior else 1
            self._servables[name] = servable
        if servable.over_budget:
            journal.emit(
                "tenant",
                (f"tenant '{name}' is over its HBM budget: "
                 f"{servable.model_bytes / (1024 * 1024):.1f} MiB loaded "
                 f"vs {budget:g} MiB budgeted (soft — serving continues; "
                 "pio doctor WARNs)"),
                level=journal.WARN, tenant=name,
                modelBytes=servable.model_bytes, budgetMb=budget)
        return prior

    def get(self, name: str) -> Optional[ServableModel]:
        with self._lock:
            return self._servables.get(name)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._servables)

    def servables(self) -> List[ServableModel]:
        with self._lock:
            return [self._servables[n] for n in sorted(self._servables)]

    def __len__(self) -> int:
        with self._lock:
            return len(self._servables)

    def generations(self) -> Dict[str, int]:
        with self._lock:
            return {n: s.generation
                    for n, s in sorted(self._servables.items())}

    def total_model_bytes(self) -> int:
        with self._lock:
            return sum(s.model_bytes for s in self._servables.values())

    def oversubscribed(self) -> List[str]:
        """Tenants over their soft budget (the doctor WARN list)."""
        with self._lock:
            return sorted(n for n, s in self._servables.items()
                          if s.over_budget)

    # ------------------------------------------------------------ collector
    def collect(self) -> Iterable[str]:
        """Scrape-time per-tenant gauges (registered on the metrics
        registry by the query server). Nothing until telemetry is on —
        wire parity with single-tenant deploys."""
        if not telemetry.on():
            return []
        servables = self.servables()
        if not servables:
            return []
        lines: List[str] = [
            "# TYPE pio_tenant_generation gauge",
            "# TYPE pio_tenant_queue_depth gauge",
            "# TYPE pio_tenant_model_bytes gauge",
        ]
        budget_lines: List[str] = []
        for s in servables:
            lines.append(
                f'pio_tenant_generation{{tenant="{s.name}"}} {s.generation}')
            lines.append(
                f'pio_tenant_queue_depth{{tenant="{s.name}"}} '
                f'{s.queue_depth()}')
            lines.append(
                f'pio_tenant_model_bytes{{tenant="{s.name}"}} '
                f'{s.model_bytes}')
            budget = s.hbm_budget_mb
            if budget is not None:
                budget_lines.append(
                    f'pio_tenant_hbm_budget_bytes{{tenant="{s.name}"}} '
                    f'{int(budget * 1024 * 1024)}')
        if budget_lines:
            lines.append("# TYPE pio_tenant_hbm_budget_bytes gauge")
            lines.extend(budget_lines)
        return lines


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

class AdmissionError(Exception):
    """Admission verdict: carries the HTTP status (401 unknown key,
    429 rate-limited) and an optional Retry-After value in seconds."""

    def __init__(self, status: int, message: str,
                 retry_after_s: Optional[int] = None):
        super().__init__(message)
        self.status = status
        self.message = message
        self.retry_after_s = retry_after_s


class _TokenBucket:
    """Classic token bucket; ``rate`` tokens/s, ``burst`` capacity.
    Not thread-safe on its own — the controller serializes access."""

    def __init__(self, rate: float, burst: float):
        self.rate = float(rate)
        self.burst = max(1.0, float(burst))
        self.tokens = self.burst
        self.last = time.monotonic()

    def take(self, now: Optional[float] = None) -> Optional[int]:
        """Take one token. Returns None on success, otherwise a
        Retry-After value in whole seconds (>= 1)."""
        now = time.monotonic() if now is None else now
        self.tokens = min(self.burst,
                          self.tokens + (now - self.last) * self.rate)
        self.last = now
        if self.tokens >= 1.0:
            self.tokens -= 1.0
            return None
        need = (1.0 - self.tokens) / self.rate if self.rate > 0 else 1.0
        return max(1, int(need + 0.999))


class AdmissionController:
    """Per-access-key admission for the multi-tenant query server.

    ``admit(key)`` resolves key → app (AccessKeys DAO) → tenant (the
    app-id map built at load from each tenant's configured access key)
    and charges the key's token bucket. Raises :class:`AdmissionError`
    401 for a missing/unknown/unmapped key, 429 + Retry-After when the
    bucket is dry. Successful resolutions are cached (keys are
    append-mostly); unknown keys are re-checked against the DAO every
    time so a key created after deploy starts working immediately."""

    def __init__(self, storage: Any, tenant_by_appid: Dict[int, str],
                 rate: Optional[float] = None,
                 burst: Optional[float] = None,
                 tenant_limits: Optional[
                     Dict[str, Tuple[Optional[float],
                                     Optional[float]]]] = None):
        self._storage = storage
        self._tenant_by_appid = dict(tenant_by_appid)
        self._rate = (rate if rate is not None
                      else _env_float("PIO_TENANT_RATE", 0.0))
        self._burst = (burst if burst is not None
                       else _env_float("PIO_TENANT_BURST", 0.0))
        self._tenant_limits = dict(tenant_limits or {})
        self._lock = threading.Lock()
        self._key_tenant: Dict[str, str] = {}
        self._buckets: Dict[str, _TokenBucket] = {}

    def _limits_for(self, tenant: str) -> Tuple[float, float]:
        rate, burst = self._tenant_limits.get(tenant, (None, None))
        rate = self._rate if rate is None else float(rate)
        burst = self._burst if burst is None else float(burst)
        if burst <= 0:
            # default burst: 2 s of rate (at least 1)
            burst = max(1.0, 2.0 * rate)
        return rate, burst

    def resolve(self, key: Optional[str]) -> str:
        """Key → tenant name, no rate accounting. 401s unmapped keys."""
        if not key:
            raise AdmissionError(401, "Missing accessKey.")
        with self._lock:
            cached = self._key_tenant.get(key)
        if cached is not None:
            return cached
        row = self._storage.get_meta_data_access_keys().get(key)
        tenant = (self._tenant_by_appid.get(row.appid)
                  if row is not None else None)
        if tenant is None:
            raise AdmissionError(401, "Invalid accessKey.")
        with self._lock:
            self._key_tenant[key] = tenant
        return tenant

    def admit(self, key: Optional[str]) -> str:
        """Resolve AND charge the key's token bucket. Returns the
        tenant name; raises :class:`AdmissionError` otherwise."""
        tenant = self.resolve(key)
        rate, burst = self._limits_for(tenant)
        if rate <= 0:      # unlimited (the default)
            return tenant
        with self._lock:
            bucket = self._buckets.get(key)
            if bucket is None:
                bucket = self._buckets[key] = _TokenBucket(rate, burst)
            retry = bucket.take()
        if retry is not None:
            raise AdmissionError(
                429,
                f"access key rate limit exceeded ({rate:g} req/s); "
                "retry later", retry_after_s=retry)
        return tenant
