"""Ahead-of-time compilation of the serving (and training) programs.

The warmup cliff: every jitted serving kernel compiles lazily on its
first dispatch, so a cold `pio deploy` spends its first minutes paying
(padding buckets x templates x k) XLA compiles on the latency path, a
cost that grows with that product. This module moves the whole product off the request
path:

- **Program registry.** Every ``@jax.jit`` entry point on the serving
  path is registered here (:func:`register_jit`); a tier-1 lint
  (tests/test_aot.py) walks the serving modules and fails when a new
  jitted kernel is not registered, so the cliff cannot silently come
  back. Registration records *how to enumerate* the programs a deploy
  will need from declared shapes — no example batch ever runs.

- **Shape oracle.** Concrete program shapes come from the model's
  declared dimensions (n_users, n_items, rank), the padding-bucket set
  (serving/protocol.py), the declared k set (``PIO_AOT_KS``), and — for
  the training programs — ``ops.als.bucket_units``, the same geometric
  rounding the layout code applies, so the enumerated shapes are
  exactly the shapes the lazy path would trace.

- **Bucket pruning.** The enumerated bucket set is pruned against the
  observed flush-size histogram the batcher already records
  (``pio_batcher_batch_size`` in the process metrics registry): buckets
  no real traffic maps to are dropped (the largest bucket is always
  kept as the overflow cap). A fresh process has no observations and
  prunes nothing. ``PIO_AOT_PRUNE=0`` disables pruning.

- **Eager prebuild.** :func:`prebuild` compiles every enumerated
  program via the AOT path (``jit(...).lower(shapes...).compile()``)
  on a small thread pool at deploy time, BEFORE ``/readyz`` flips
  ready, and then marks the devicewatch serving warmup done — warmup
  end becomes an explicit AOT-complete mark instead of a flush count.
  Compiled-executable handles are memoized process-wide so a /reload
  onto same-shape factors is instant.

- **The compile cache.** Always on, and placed from outside:
  ``JAX_COMPILATION_CACHE_DIR`` where it is set (jax's own handling;
  the code sets no directory), else ``<checkout>/.jax_cache``
  (:func:`persistent_cache_dir`), so train and deploy share one cache.

- **The compile cache as a deploy artifact.** With an explicit
  ``pio train --compile-cache DIR`` the run snapshots that directory
  around itself, AOT-builds the model's serving programs, and exports
  the new cache entries into the Models store next to the model blob
  (workflow/model_io.py). ``pio deploy`` pre-seeds its cache from that
  artifact, so a warm replica's prebuild is a string of cache hits —
  seconds, not minutes. Cache keys include the jaxlib version and
  platform; a mismatched artifact is skipped entry-free (lazy compile,
  never an error — KNOWN_ISSUES #9).

``PIO_AOT=0`` disables the whole subsystem: deploy is wire-byte-
identical to the pre-AOT server (asserted by test).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from predictionio_tpu.common import devicewatch, telemetry
from predictionio_tpu.serving import protocol

logger = logging.getLogger("predictionio_tpu.aot")

#: prebuild thread-pool width; compiles release the GIL inside XLA so a
#: few threads overlap well without starving the host
_DEFAULT_THREADS = 4


# ---------------------------------------------------------------------------
# program registry (the lint's source of truth)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Registered:
    """One jitted entry point known to the AOT subsystem."""
    fn: Any
    kind: str           # "serving" | "training" | "eval"
    note: str = ""      # why it is (or is not) eagerly enumerated


_REGISTRY: Dict[str, _Registered] = {}


def register_jit(name: str, fn: Any, kind: str = "serving",
                 note: str = "") -> Any:
    """Declare a jitted entry point to the AOT subsystem. Idempotent.

    Registration is a statement of coverage: either the entry point is
    enumerated by a spec builder below, or ``note`` says why eager
    enumeration does not apply (e.g. an eval-only kernel that never
    runs on the serving latency path)."""
    _REGISTRY[name] = _Registered(fn=fn, kind=kind, note=note)
    return fn


def registered_names() -> frozenset:
    return frozenset(_REGISTRY)


def registry_snapshot() -> Dict[str, Dict[str, str]]:
    return {name: {"kind": r.kind, "note": r.note}
            for name, r in sorted(_REGISTRY.items())}


# ---------------------------------------------------------------------------
# shape oracle: declared k set + observed-bucket pruning
# ---------------------------------------------------------------------------

def serving_ks(n_items: int) -> Tuple[int, ...]:
    """The declared top-k set to prebuild, clamped to the model.

    ``PIO_AOT_KS`` (comma-separated, default "10" — the template
    default `num`) declares which k values deployments serve; each is
    clamped to n_items exactly as the query path clamps
    ``min(num, n_items)``, so the enumerated programs are the programs
    real queries trace."""
    raw = os.environ.get("PIO_AOT_KS", "10")
    ks = []
    for tok in raw.split(","):
        tok = tok.strip()
        if not tok:
            continue
        try:
            k = int(tok)
        except ValueError:
            continue
        k = min(k, int(n_items))
        if k >= 1:
            ks.append(k)
    return tuple(sorted(set(ks)))


def observed_flush_sizes() -> Dict[int, int]:
    """The flush-size histogram the batcher has recorded in THIS process
    (``pio_batcher_batch_size{size=...}``), summed over instances."""
    reg = telemetry.registry()
    with reg._lock:
        fam = reg._families.get("pio_batcher_batch_size")
    if fam is None:
        return {}
    out: Dict[int, int] = {}
    for _name, labels, value, *_rest in fam.samples():
        size = dict(labels).get("size")
        try:
            n = int(size)
        except (TypeError, ValueError):
            continue
        if value > 0:
            out[n] = out.get(n, 0) + int(value)
    return out


def prune_buckets(buckets: Iterable[int],
                  observed: Optional[Dict[int, int]] = None
                  ) -> Tuple[int, ...]:
    """Drop padding buckets no observed flush maps to.

    Keeps every bucket some observed flush size rounds up to, plus the
    LARGEST bucket always (the overflow cap: without it, a burst beyond
    the biggest surviving bucket would compile at its exact size — the
    recompile cliff this module exists to kill). With no observations
    (fresh process) or ``PIO_AOT_PRUNE=0`` the set is unchanged."""
    buckets = protocol.pad_buckets(tuple(buckets))
    if os.environ.get("PIO_AOT_PRUNE", "1") == "0":
        return buckets
    if observed is None:
        observed = observed_flush_sizes()
    if not observed:
        return buckets
    keep = {buckets[-1]}
    for size in observed:
        keep.add(protocol.bucket_for(size, buckets))
    return tuple(b for b in buckets if b in keep)


def pruned_serve_buckets(max_batch_size: Optional[int] = None
                         ) -> Tuple[int, ...]:
    """The deploy's effective bucket set: configured buckets, capped at
    the batcher's max batch size (a bucket the batcher can never fill
    past is dead weight in the program product), then observation-
    pruned. At least one bucket always survives."""
    buckets = protocol.pad_buckets()
    if max_batch_size:
        capped = tuple(b for b in buckets if b <= int(max_batch_size))
        if capped:
            buckets = capped
    return prune_buckets(buckets)


# ---------------------------------------------------------------------------
# program specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One concrete (entry point, shapes, statics) device program.

    Two ways to make it warm, used from different places:

    - ``lower`` returns the jax Lowered built from declared
      ShapeDtypeStructs; :meth:`build` compiles it — the pure AOT path
      (``jit(...).lower().compile()``), data-free, used by the `pio
      train` cache-artifact export and the parity tests. It seeds the
      persistent compile cache but NOT the jit dispatch cache.

    - ``prime`` dispatches the real jitted entry point once with
      model-shaped arguments, populating the jit dispatch cache the
      serving path actually hits (and, with a persistent cache
      configured, the on-disk entries too — where the train artifact
      pre-seeded them, the backend compile inside this dispatch is a
      disk hit and the prime costs milliseconds). Deploy prebuild
      prefers it: after prime, the first query compiles NOTHING.
    """
    name: str
    key: Tuple
    lower: Optional[Callable[[], Any]] = None
    prime: Optional[Callable[[], None]] = None

    def build(self) -> Any:
        if self.lower is None:
            raise ValueError(f"{self.name}: no declared-shape lowering")
        return self.lower().compile()


#: process-wide memo of built programs: a /reload onto factors with the
#: same declared shapes skips every compile (and every test after the
#: first QueryAPI construction rides it too)
_memo_lock = threading.Lock()
_MEMO: Dict[Tuple, Any] = {}


def reset_memo() -> None:
    """Forget built programs (tests)."""
    with _memo_lock:
        _MEMO.clear()


def specs_topk_for_users(n_users: int, n_items: int, rank: int,
                         buckets: Iterable[int], ks: Iterable[int],
                         arrays: Optional[Tuple[Any, Any]] = None
                         ) -> List[ProgramSpec]:
    """The batched device serving programs: one per (bucket, k).
    ``arrays=(U, V)`` (the live device-resident factors) attaches prime
    closures so deploy prebuild can warm the jit dispatch cache."""
    from predictionio_tpu.ops import topk
    out = []
    for b in buckets:
        for k in ks:
            out.append(ProgramSpec(
                name="topk_for_users",
                key=("topk_for_users", n_users, n_items, rank,
                     int(b), int(k)),
                lower=_topk_users_lowerer(topk, n_users, n_items, rank,
                                          int(b), int(k)),
                prime=(_topk_users_primer(topk, arrays, int(b), int(k))
                       if arrays is not None else None)))
    return out


def specs_topk_for_user(n_users: int, n_items: int, rank: int,
                        ks: Iterable[int],
                        arrays: Optional[Tuple[Any, Any]] = None
                        ) -> List[ProgramSpec]:
    """The inline (batching-off) single-query programs: one per k."""
    from predictionio_tpu.ops import topk
    return [ProgramSpec(
        name="topk_for_user",
        key=("topk_for_user", n_users, n_items, rank, int(k)),
        lower=_topk_user_lowerer(topk, n_users, n_items, rank, int(k)),
        prime=(_topk_user_primer(topk, arrays, int(k))
               if arrays is not None else None))
        for k in ks]


def specs_masked_topk_rows(n_users: int, n_items: int, rank: int,
                           n_words: int, buckets: Iterable[int],
                           ks: Iterable[int], device: Any = None
                           ) -> List[ProgramSpec]:
    """The e-commerce engine's batched device programs: one per
    (bucket, exclusion width of ops/topk.py EXCLUDE_WIDTHS, k).
    ``device`` (models/ecommerce RuleDevice: the live resident arrays)
    attaches prime closures."""
    from predictionio_tpu.ops import topk
    out = []
    for b in buckets:
        for width in topk.EXCLUDE_WIDTHS:
            for k in ks:
                shape = (n_users, n_items, rank, n_words,
                         int(b), int(width), int(k))
                out.append(ProgramSpec(
                    name="masked_topk_rows",
                    key=("masked_topk_rows", *shape),
                    lower=_masked_rows_lowerer(topk, *shape),
                    prime=(_masked_rows_primer(topk, device, *shape[3:])
                           if device is not None else None)))
    return out


def _masked_rows_lowerer(topk, n_users, n_items, rank, n_words, bucket,
                         width, k):
    def lower():
        import jax
        import numpy as np
        return topk.masked_topk_rows.lower(
            jax.ShapeDtypeStruct((n_users, rank), np.float32),
            jax.ShapeDtypeStruct((n_items, rank), np.float32),
            jax.ShapeDtypeStruct((n_words, n_items), np.uint32),
            jax.ShapeDtypeStruct((n_items,), np.bool_),
            jax.ShapeDtypeStruct((bucket,), np.int32),
            jax.ShapeDtypeStruct((bucket, n_words), np.uint32),
            jax.ShapeDtypeStruct((bucket, width), np.int32), k=k)
    return lower


def _masked_rows_primer(topk, device, n_words, bucket, width, k):
    def prime():
        import jax
        import numpy as np
        # a flush with no rule in it: user 0 (in bounds), every bit
        # wanted, every exclusion padding
        jax.device_get(topk.masked_topk_rows(
            device.user_factors, device.item_factors, device.rule_words,
            device.eligible, np.zeros((bucket,), np.int32),
            *topk.blank_rule_arguments(
                bucket, n_words, width, int(device.item_factors.shape[0])),
            k=k))
    return prime


def specs_itemset_topk_rows(n_items: int, rank: int, n_words: int,
                             buckets: Iterable[int], ks: Iterable[int],
                             device: Any = None) -> List[ProgramSpec]:
    """The similar-product engine's batched device programs: one per
    (bucket, exclusion width of ops/topk.py EXCLUDE_WIDTHS, k), every
    one at the one declared query width (topk.QUERY_WIDTH).
    ``device`` (models/similarproduct ItemSetDevice: the live resident
    arrays) attaches prime closures."""
    from predictionio_tpu.ops import topk
    out = []
    for b in buckets:
        for width in topk.EXCLUDE_WIDTHS:
            for k in ks:
                shape = (n_items, rank, n_words, int(b), int(width), int(k))
                out.append(ProgramSpec(
                    name="itemset_topk_rows",
                    key=("itemset_topk_rows", topk.QUERY_WIDTH, *shape),
                    lower=_itemset_rows_lowerer(topk, *shape),
                    prime=(_itemset_rows_primer(topk, device, *shape[3:])
                           if device is not None else None)))
    return out


def _itemset_rows_lowerer(topk, n_items, rank, n_words, bucket, width, k):
    def lower():
        import jax
        import numpy as np
        return topk.itemset_topk_rows.lower(
            jax.ShapeDtypeStruct((n_items, rank), np.float32),
            jax.ShapeDtypeStruct((n_words, n_items), np.uint32),
            jax.ShapeDtypeStruct((n_items,), np.bool_),
            jax.ShapeDtypeStruct((bucket, topk.QUERY_WIDTH), np.int32),
            jax.ShapeDtypeStruct((bucket, n_words), np.uint32),
            jax.ShapeDtypeStruct((bucket, width), np.int32), k=k)
    return lower


def _itemset_rows_primer(topk, device, bucket, width, k):
    def prime():
        import jax
        import numpy as np
        # a flush with no query in it: every row's item list padding,
        # every bit wanted, every exclusion padding
        jax.device_get(device.topk(*device.rule_arguments(bucket, width))(
            np.full((bucket, topk.QUERY_WIDTH), device.n_items, np.int32),
            k))
    return prime


def _topk_users_lowerer(topk, n_users, n_items, rank, bucket, k):
    def lower():
        import jax
        import numpy as np
        return topk.topk_for_users.lower(
            jax.ShapeDtypeStruct((n_users, rank), np.float32),
            jax.ShapeDtypeStruct((n_items, rank), np.float32),
            jax.ShapeDtypeStruct((bucket,), np.int32), k=k)
    return lower


def _topk_user_lowerer(topk, n_users, n_items, rank, k):
    def lower():
        import jax
        import numpy as np
        return topk.topk_for_user.lower(
            jax.ShapeDtypeStruct((n_users, rank), np.float32),
            jax.ShapeDtypeStruct((n_items, rank), np.float32),
            jax.ShapeDtypeStruct((), np.int32), k=k)
    return lower


def _topk_users_primer(topk, arrays, bucket, k):
    def prime():
        import jax
        import numpy as np
        U, V = arrays
        # index 0 is always in-bounds (an OOB pad would gather NaN,
        # KNOWN_ISSUES #5); device_get ends the dispatch in a real host
        # transfer, the honest barrier per KNOWN_ISSUES #3
        ix = np.zeros((bucket,), dtype=np.int32)
        jax.device_get(topk.topk_for_users(U, V, ix, k=k))
    return prime


def _topk_user_primer(topk, arrays, k):
    def prime():
        import jax
        import numpy as np
        U, V = arrays
        jax.device_get(topk.topk_for_user(U, V, np.int32(0), k=k))
    return prime


def training_program_specs(n_users: int, n_items: int, rank: int,
                           nnz: int, chunk: int = 1 << 18,
                           reg_scaling: str = "count",
                           kernel: Optional[str] = None
                           ) -> List[ProgramSpec]:
    """The ALS training programs, from declared shapes.

    ``ops.als.bucket_units`` is the shape oracle: the COO pad the
    layout code would build for ``nnz`` ratings is computed without
    touching data, so the enumerated trainer program is byte-for-byte
    the program ``pio train`` traces. Only the "scan" kernel enumerates
    from shapes alone — the hybrid/csrb kernels derive statics from the
    data's skew (hot-id split, mini-block plan), so their programs ride
    the compile-cache artifact exported from the real training run
    instead (the registry notes say so)."""
    from predictionio_tpu.ops import als
    k = als._kernel_flag(kernel)
    if k != "scan":
        return []
    return [ProgramSpec(
        name="als_train_scan",
        key=("als_train_scan", n_users, n_items, rank,
             als.declared_nnz_pad(nnz, chunk), reg_scaling,
             als._tuning_key()),
        lower=lambda: als.lower_train_explicit(
            n_users, n_items, rank, nnz, chunk=chunk,
            reg_scaling=reg_scaling))]


def algorithm_programs(algo: Any, model: Any,
                       buckets: Iterable[int],
                       declared: bool = False) -> List[ProgramSpec]:
    """Ask one algorithm for its serving programs (the optional
    ``aot_serving_programs`` hook; controller/base.py). Algorithms
    without the hook — or whose prepare_serving chose the host path —
    contribute nothing and deploy stays instant for them."""
    hook = getattr(algo, "aot_serving_programs", None)
    if hook is None:
        return []
    try:
        return list(hook(model, tuple(buckets), declared=declared))
    except Exception:
        logger.exception("aot_serving_programs failed for %s; continuing "
                         "with lazy compilation", type(algo).__name__)
        return []


# ---------------------------------------------------------------------------
# eager prebuild
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AOTReport:
    """What the prebuild did; ``GET /`` and /debug/device.json serve
    the summary."""
    programs: List[Tuple[str, str, float]]   # (key, status, seconds)
    seconds: float

    def count(self, status: str) -> int:
        return sum(1 for _k, s, _t in self.programs if s == status)

    def summary(self) -> Dict[str, Any]:
        return {
            "programs": len(self.programs),
            "compiled": self.count("compiled") + self.count("primed"),
            "memoized": self.count("memoized"),
            "failed": self.count("failed"),
            "prebuildS": round(self.seconds, 3),
        }


def _threads(n_specs: int, threads: Optional[int]) -> int:
    if threads:
        return max(1, int(threads))
    raw = os.environ.get("PIO_AOT_THREADS", "")
    try:
        if raw:
            return max(1, int(raw))
    except ValueError:
        pass
    return max(1, min(_DEFAULT_THREADS, n_specs))


def prebuild(specs: Iterable[ProgramSpec],
             threads: Optional[int] = None) -> AOTReport:
    """Compile every spec on a small thread pool; never raises.

    A failed build logs and counts (``pio_aot_programs_total{status=
    "failed"}``) — the lazy jit path still serves that program, so a
    broken enumeration degrades to today's behavior instead of taking
    the replica down."""
    specs = list(specs)
    reg = telemetry.registry()
    m_programs = reg.counter(
        "pio_aot_programs_total",
        "AOT-enumerated device programs by prebuild outcome",
        labelnames=("status",))
    t0 = time.perf_counter()
    results: List[Tuple[str, str, float]] = []
    lock = threading.Lock()

    def build_one(spec: ProgramSpec) -> None:
        key_str = ":".join(str(p) for p in spec.key)
        with _memo_lock:
            hit = spec.key in _MEMO
        if hit:
            status, dt = "memoized", 0.0
        else:
            t = time.perf_counter()
            try:
                # attribute the compile to the AOT phase so the serving
                # watchdog never counts a prebuild as a request stall.
                # Prime (one real jit dispatch) is preferred: it warms
                # the dispatch cache the serving path hits, and with a
                # persistent cache configured the backend compile
                # inside it is a disk hit off the train artifact.
                # Lower-only specs (train export) AOT-compile instead.
                with devicewatch.attribution(spec.name, phase="aot"):
                    if spec.prime is not None:
                        spec.prime()
                        built: Any = True
                        status = "primed"
                    else:
                        built = spec.build()
                        status = "compiled"
                with _memo_lock:
                    _MEMO[spec.key] = built
                dt = time.perf_counter() - t
            except Exception as e:
                status, dt = "failed", time.perf_counter() - t
                logger.warning(
                    "AOT prebuild of %s failed (%s: %s); the program "
                    "will compile lazily on first dispatch",
                    key_str, type(e).__name__, e)
                from predictionio_tpu.common import journal
                journal.emit(
                    "aot",
                    f"AOT prebuild of {spec.name} failed; it will "
                    "compile lazily on the latency path",
                    level=journal.WARN, program=key_str,
                    error=f"{type(e).__name__}: {e}")
        m_programs.labels(status=status).inc()
        with lock:
            results.append((key_str, status, round(dt, 4)))

    if specs:
        with ThreadPoolExecutor(
                max_workers=_threads(len(specs), threads),
                thread_name_prefix="pio-aot") as pool:
            list(pool.map(build_one, specs))
    seconds = time.perf_counter() - t0
    reg.gauge(
        "pio_aot_prebuild_seconds",
        "Wall-clock of the most recent AOT prebuild").labels().set(seconds)
    return AOTReport(programs=sorted(results), seconds=seconds)


# ---------------------------------------------------------------------------
# enable gate + persistent-cache config
# ---------------------------------------------------------------------------

def enabled(mode: str = "auto") -> bool:
    """Is AOT prebuild on for this deploy? ``PIO_AOT`` overrides the
    ServerConfig mode (0 = off everywhere, the wire-parity escape
    hatch; 1 = on even for `aot="off"` configs). "auto" and "on" both
    build eagerly — enumeration is a no-op for host-serving models, so
    auto costs nothing where there is nothing to compile."""
    env = os.environ.get("PIO_AOT", "")
    if env == "0":
        return False
    if env == "1":
        return True
    mode = (mode or "auto").lower()
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"aot mode must be auto/on/off, got {mode!r}")
    return mode != "off"


#: <checkout>/.jax_cache — the fixed default (chip_smoke.py and
#: diagnostics/ml20m_repro.py use the same path): never a temp name, a
#: pid or a time, so every process of a checkout finds what the last
#: one compiled.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def artifact_cache_dir() -> str:
    """The directory `pio train` snapshots its compile-cache deploy
    artifact from: only an explicit ``--compile-cache`` /
    ``PIO_COMPILE_CACHE_DIR`` — the default cache never starts shipping
    hundreds of MB with every model."""
    return os.environ.get("PIO_COMPILE_CACHE_DIR", "")


def persistent_cache_dir() -> str:
    """Where this process keeps jax's persistent compile cache.

    ``JAX_COMPILATION_CACHE_DIR`` set: that directory, through jax's own
    handling of the variable; ``PIO_COMPILE_CACHE_DIR`` then only names
    where the deploy artifact is snapshotted from and must name the same
    directory (ValueError otherwise). Not set: ``PIO_COMPILE_CACHE_DIR``
    if given, else ``<checkout>/.jax_cache``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    explicit = artifact_cache_dir()
    if not placed:
        return explicit or _DEFAULT_CACHE_DIR
    if explicit and os.path.realpath(explicit) != os.path.realpath(placed):
        raise ValueError(
            f"--compile-cache / PIO_COMPILE_CACHE_DIR ({explicit}) "
            f"disagrees with JAX_COMPILATION_CACHE_DIR ({placed}): the "
            "compile cache is placed by JAX_COMPILATION_CACHE_DIR when "
            "it is set, and the deploy artifact is snapshotted from "
            "that same directory — name it, or drop one of the two")
    return placed


def ensure_persistent_cache() -> str:
    """Make sure jax's persistent compile cache is on at
    :func:`persistent_cache_dir` and return that directory. With
    ``JAX_COMPILATION_CACHE_DIR`` set jax has configured itself from it
    and this sets no directory. Threshold 0 by default so even fast-
    compiling serving programs persist (``PIO_COMPILE_CACHE_MIN_S``
    overrides)."""
    import jax
    d = persistent_cache_dir()
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ.get("PIO_COMPILE_CACHE_MIN_S", "0")))
    if (not os.environ.get("JAX_COMPILATION_CACHE_DIR")
            and jax.config.jax_compilation_cache_dir != d):
        jax.config.update("jax_compilation_cache_dir", d)
        # a compile that ran before this (a library user who trained in
        # this process) latched the cache as "not in use"; un-latch it
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    return d


# ---------------------------------------------------------------------------
# train-time export (run_train calls this after the model persists)
# ---------------------------------------------------------------------------

def export_train_artifact(storage: Any, instance_id: str,
                          algorithms: Iterable[Any],
                          models: Iterable[Any],
                          cache_dir: str,
                          before: Optional[frozenset]) -> Dict[str, Any]:
    """AOT-build the trained model's serving programs, then export every
    compile-cache entry the run produced (training programs included —
    the trainer compiled them minutes ago) into the Models store as
    ``<instance_id>.jaxcache``. Best-effort: any failure logs and
    returns a summary; it never fails the training run."""
    from predictionio_tpu.data.storage import Model as _Model
    from predictionio_tpu.workflow import model_io

    summary: Dict[str, Any] = {"programs": 0, "entries": 0}
    try:
        buckets = pruned_serve_buckets()
        specs: List[ProgramSpec] = []
        for algo, model in zip(algorithms, models):
            specs.extend(algorithm_programs(algo, model, buckets,
                                            declared=True))
        report = prebuild(specs)
        summary.update(report.summary())
        if cache_dir:
            blob = model_io.export_compile_cache(cache_dir, since=before)
            if blob is not None:
                storage.get_model_data_models().insert(_Model(
                    id=model_io.cache_artifact_id(instance_id),
                    models=blob))
                summary["entries"] = len(
                    model_io.cache_snapshot(cache_dir)
                    - (before or frozenset()))
                summary["artifactBytes"] = len(blob)
    except Exception:
        logger.exception("compile-cache export for %s failed; deploys "
                         "will compile lazily", instance_id)
    return summary


# ---------------------------------------------------------------------------
# entry-point registration (the lint checks serving-path jits against
# this table; keep it in one visible place)
# ---------------------------------------------------------------------------

def _register_builtin() -> None:
    from predictionio_tpu.ops import als, topk
    register_jit("topk_for_users", topk.topk_for_users, kind="serving",
                 note="enumerated per (bucket, k) by specs_topk_for_users")
    register_jit("topk_for_user", topk.topk_for_user, kind="serving",
                 note="enumerated per k by specs_topk_for_user "
                      "(inline / batching-off path)")
    register_jit("masked_topk_rows", topk.masked_topk_rows, kind="serving",
                 note="enumerated per (bucket, exclusion width, k) by "
                      "specs_masked_topk_rows (the e-commerce engine's "
                      "device layout)")
    register_jit("topk_scores", topk.topk_scores, kind="serving",
                 note="host-prep templates score via host_masked_topk; "
                      "device dispatch of this kernel is eval/batch-"
                      "predict only, off the serving latency path")
    register_jit("topk_scores_batch", topk.topk_scores_batch, kind="eval",
                 note="batch_predict/eval path, not request serving")
    register_jit("itemset_topk_rows", topk.itemset_topk_rows,
                 kind="serving",
                 note="enumerated per (bucket, exclusion width, k) by "
                      "specs_itemset_topk_rows (the similar-product "
                      "engine's device layout)")
    register_jit("als_train_scan", als._train_explicit_jit, kind="training",
                 note="enumerated from declared shapes by "
                      "training_program_specs (bucket_units shape oracle)")
    register_jit("als_train_hybrid", als._train_hybrid_jit, kind="training",
                 note="statics derive from data skew (hot-id split); "
                      "programs ship via the compile-cache artifact")
    register_jit("als_train_csrb", als._train_csrb_jit, kind="training",
                 note="statics derive from the mini-block plan; programs "
                      "ship via the compile-cache artifact")


_register_builtin()
