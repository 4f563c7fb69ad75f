"""Device-level observability: the XLA boundary, watched.

PR 4 gave every daemon host-side metrics and traces; this module watches
the layer that actually makes a TPU-native server fast — the compiled
device programs — and turns its two silent failure modes into counters:

- **Recompilation watchdog.** A jitted entry point that re-traces on
  the serving path (a padding-bucket regression, a stray dynamic shape)
  does not error: it just adds a multi-hundred-ms compile stall to some
  unlucky request's p99. The watchdog hooks JAX's own compile events
  (``jax.monitoring`` duration listeners — host-side timings, so the
  KNOWN_ISSUES #3/#7 host-transfer rule is satisfied by construction:
  compile time is measured by JAX on the host, never by us around
  device work) and attributes them to the entry point that triggered
  them via thread-local attribution regions:

      pio_xla_compiles_total{fn,phase}      every backend compile
      pio_xla_compile_seconds               compile-duration histogram
      pio_xla_post_warmup_recompiles_total{fn}
                                            the alarm: compiles on the
                                            SERVING path after warmup

  Serving code wraps its device dispatch in :func:`serving_region`
  (serving/batcher.py flush, the inline query path); training wraps in
  :func:`attribution` (ops/als.py trainers, WorkflowContext.phase). The
  steady-state detector records the abstract shape signature of every
  post-warmup serving compile (``debug_snapshot()["watchdog"]
  ["recentPostWarmup"]``) so the operator sees *which* shape broke the
  bucket contract, not just that one did. Warmup ends after
  ``PIO_SERVE_WARMUP_FLUSHES`` flushes (default 32) or an explicit
  :func:`mark_serving_warmup_done`.

  Where ``jax.monitoring`` is unavailable (older/stripped runtimes),
  :func:`serving_region`'s signature-novelty tracking is the wrapper
  fallback: a never-seen signature entering the serving path after
  warmup counts as a recompile even without compile events.

- **Device gauges** (scrape-time collector, held in the PR-4 registry):

      pio_hbm_bytes_in_use{device} / pio_hbm_bytes_limit{device} /
      pio_hbm_peak_bytes_in_use{device}
                                from device.memory_stats(); gracefully
                                absent when the platform returns None
                                (CPU does; see KNOWN_ISSUES #8)
      pio_live_arrays / pio_live_array_bytes
                                jax.live_arrays() census
      pio_compile_cache_entries / pio_compile_cache_bytes
                                the persistent compile cache dir

  plus a human-readable ``GET /debug/device.json`` on every daemon
  (served by telemetry.handle_route).

Everything gates on :func:`telemetry.on` (``PIO_TELEMETRY=1``): with
telemetry off the listener is a no-op, the collector emits nothing, and
``/debug/device.json`` answers ``{"telemetry": false}`` — wire behavior
stays byte-identical to the pre-devicewatch code (asserted by test).

jax is imported lazily: importing this module from a daemon that never
touches the device (event server) costs nothing.
"""

from __future__ import annotations

import contextlib
import datetime as _dt
import logging
import os
import sys
import threading
from collections import deque
from typing import Any, Dict, Iterator, List, Optional

from predictionio_tpu.common import telemetry

logger = logging.getLogger("predictionio_tpu.devicewatch")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: compile durations: 10 ms CPU re-traces through the minutes a cold
#: compile of the full hybrid trainer takes (PERF.md section 6, PR 25)
_COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 30.0, 60.0,
                    120.0, 300.0, 600.0)

_tls = threading.local()
_lock = threading.Lock()
_installed = False
_have_monitoring = False
_serving_sigs: set = set()
_serving_flushes = 0
_warmup_done = False
#: bounded flight recorder of post-warmup serving compiles (the
#: signatures the operator needs; /debug/device.json serves it)
_post_warmup_events: deque = deque(maxlen=32)


def _warmup_flush_count() -> int:
    raw = os.environ.get("PIO_SERVE_WARMUP_FLUSHES", "")
    try:
        return max(1, int(raw)) if raw else 32
    except ValueError:
        return 32


# ---------------------------------------------------------------------------
# attribution regions (thread-local; compiles fire synchronously on the
# thread that traced them, so the active region names the culprit)
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def attribution(fn: str, phase: str = "other") -> Iterator[None]:
    """Attribute any XLA compile inside the block to ``fn`` under
    ``phase`` (train/layout/request/...). Nesting: innermost wins —
    a trainer inside a ctx.phase("train") region reports its own name.
    Two thread-local writes; safe to wrap hot paths unconditionally."""
    prev = (getattr(_tls, "fn", None), getattr(_tls, "phase", None))
    _tls.fn, _tls.phase = fn, phase
    try:
        yield
    finally:
        _tls.fn, _tls.phase = prev


@contextlib.contextmanager
def serving_region(fn: str = "serve", signature: str = "") -> Iterator[None]:
    """Attribution for the SERVING path: compiles inside the block after
    warmup are the padding-bucket alarm (pio_xla_post_warmup_recompiles_
    total), recorded with ``signature`` — the caller's abstract shape
    description of this dispatch (e.g. ``flush:n=3,k=10``).

    Also the wrapper fallback where jax.monitoring is missing: a novel
    signature entering post-warmup counts as a recompile on its own."""
    prev = (getattr(_tls, "fn", None), getattr(_tls, "phase", None),
            getattr(_tls, "serving", False), getattr(_tls, "sig", ""))
    _tls.fn, _tls.phase, _tls.serving, _tls.sig = (
        fn, "serving", True, signature)
    if signature and telemetry.on():
        with _lock:
            novel = signature not in _serving_sigs
            if novel:
                _serving_sigs.add(signature)
            warm = _warmup_done
        if novel and warm and not _have_monitoring:
            # no compile events to listen to: signature novelty IS the
            # detector (conservative — counts a cache-warm novel shape
            # too, but a novel shape post-warmup is a bug either way)
            _note_post_warmup(fn, signature, None)
    try:
        yield
    finally:
        _tls.fn, _tls.phase, _tls.serving, _tls.sig = prev


def note_serving_flush() -> None:
    """One serving flush completed (the batcher calls this per batch);
    after PIO_SERVE_WARMUP_FLUSHES of them the watchdog arms itself."""
    global _serving_flushes, _warmup_done
    with _lock:
        _serving_flushes += 1
        if not _warmup_done and _serving_flushes >= _warmup_flush_count():
            _warmup_done = True


def mark_serving_warmup_done() -> None:
    """Arm the steady-state detector now. The AOT deploy path
    (serving/aot.py) calls this the moment its prebuild completes —
    warmup end is an explicit AOT-complete mark, not a flush count —
    and tests call it after a deliberate warmup burst."""
    global _warmup_done
    with _lock:
        _warmup_done = True


#: most recent AOT prebuild summary (serving/aot.py via note_aot);
#: /debug/device.json and `pio doctor` read it
_aot_state: Optional[Dict[str, Any]] = None


def note_aot(summary: Optional[Dict[str, Any]]) -> None:
    """Record (or with None, clear) the deploy's AOT prebuild summary
    for the debug surface."""
    global _aot_state
    with _lock:
        _aot_state = dict(summary) if summary is not None else None


#: most recent sharded-serving layout (parallel/serve_dist.py via
#: note_sharding); /debug/device.json and `pio doctor` read it
_sharding_state: Optional[Dict[str, Any]] = None


def note_sharding(summary: Optional[Dict[str, Any]]) -> None:
    """Record (or with None, clear) the deploy's sharded-serving layout
    (shard count, merge strategy, per-shard bytes) for the debug
    surface."""
    global _sharding_state
    with _lock:
        _sharding_state = dict(summary) if summary is not None else None


#: most recent quantized-serving state (ops/quant.py via note_quant);
#: /debug/device.json and `pio doctor`'s quant line read it
_quant_state: Optional[Dict[str, Any]] = None


def note_quant(summary: Optional[Dict[str, Any]]) -> None:
    """Record (or with None, clear) the deploy's quantized-serving
    state (mode, factor bytes fp32 -> int8, last recall-gate value,
    fell-back flag) for the debug surface."""
    global _quant_state
    with _lock:
        _quant_state = dict(summary) if summary is not None else None


#: most recent realtime fold-in state (realtime/foldin.py via
#: note_foldin); /debug/device.json and `pio doctor`'s foldin line
#: read it
_foldin_state: Optional[Dict[str, Any]] = None


def note_foldin(summary: Optional[Dict[str, Any]]) -> None:
    """Record (or with None, clear) the fold-in worker's state (cursor
    lag, last tick, freshness percentiles, drift verdict) for the
    debug surface."""
    global _foldin_state
    with _lock:
        _foldin_state = dict(summary) if summary is not None else None


def serving_warmup_done() -> bool:
    with _lock:
        return _warmup_done


def reset_watchdog() -> None:
    """Forget warmup state, seen signatures and recorded events (tests;
    registry counters are left alone — assert on deltas)."""
    global _serving_flushes, _warmup_done
    with _lock:
        _serving_flushes = 0
        _warmup_done = False
        _serving_sigs.clear()
        _post_warmup_events.clear()


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------

def _note_post_warmup(fn: str, signature: str,
                      duration_s: Optional[float]) -> None:
    telemetry.registry().counter(
        "pio_xla_post_warmup_recompiles_total",
        "XLA compiles on the serving path AFTER warmup — each one is a "
        "latent p99 cliff (padding-bucket regression or dynamic shape)",
        labelnames=("fn",)).labels(fn=fn).inc()
    event = {
        "fn": fn,
        "signature": signature or "?",
        "durationS": (round(duration_s, 4)
                      if duration_s is not None else None),
        "at": _dt.datetime.now(_dt.timezone.utc).isoformat(
            timespec="seconds"),
    }
    with _lock:
        _post_warmup_events.append(event)
    logger.warning(
        "post-warmup XLA recompile on the serving path: fn=%s "
        "signature=%s duration=%s — a padding bucket or static shape "
        "stopped holding", fn, signature or "?",
        f"{duration_s:.3f}s" if duration_s is not None else "n/a")
    from predictionio_tpu.common import journal
    journal.emit(
        "recompile",
        f"post-warmup XLA recompile on the serving path: {fn} "
        f"[{signature or '?'}]",
        level=journal.RED, fn=fn, signature=signature or "?",
        durationS=event["durationS"])


def _on_compile_duration(event: str, duration: float, **_kw: Any) -> None:
    """jax.monitoring duration listener: every backend compile in this
    process lands here, on the thread that traced it. Must never raise —
    a broken metric must not fail a compile."""
    if event != _COMPILE_EVENT or not telemetry.on():
        return
    try:
        fn = getattr(_tls, "fn", None) or "unattributed"
        phase = getattr(_tls, "phase", None) or "other"
        reg = telemetry.registry()
        reg.counter(
            "pio_xla_compiles_total",
            "XLA backend compiles by attributed entry point and phase "
            "(timings from JAX's own host-side compile events)",
            labelnames=("fn", "phase")).labels(fn=fn, phase=phase).inc()
        reg.histogram(
            "pio_xla_compile_seconds",
            "XLA backend compile duration (JAX host-side event)",
            buckets=_COMPILE_BUCKETS).labels().observe(float(duration))
        if getattr(_tls, "serving", False) and serving_warmup_done():
            _note_post_warmup(fn, getattr(_tls, "sig", "") or "?",
                              float(duration))
    except Exception:
        logger.exception("devicewatch compile listener failed")


def watch_jit(fn: Any, name: str, phase: str = "other") -> Any:
    """Wrap a jitted callable so its compiles are attributed to ``name``
    — the explicit-wrapper alternative to an inline attribution block
    for entry points called from many sites."""
    def wrapped(*args: Any, **kwargs: Any) -> Any:
        with attribution(name, phase=phase):
            return fn(*args, **kwargs)
    wrapped.__name__ = getattr(fn, "__name__", name)
    wrapped.__wrapped__ = fn
    return wrapped


# ---------------------------------------------------------------------------
# readback (doctor / tests)
# ---------------------------------------------------------------------------

def _family_sum(name: str) -> float:
    reg = telemetry.registry()
    with reg._lock:
        fam = reg._families.get(name)
    if fam is None:
        return 0.0
    return sum(s[2] for s in fam.samples() if s[0] == name)


def compiles_total() -> int:
    return int(_family_sum("pio_xla_compiles_total"))


def post_warmup_recompiles() -> int:
    return int(_family_sum("pio_xla_post_warmup_recompiles_total"))


# ---------------------------------------------------------------------------
# device gauges (scrape-time)
# ---------------------------------------------------------------------------

def _jax_module():
    """The jax module if this process already imported it, else None —
    a /metrics scrape must never be what initializes an XLA backend."""
    return sys.modules.get("jax")


def compile_cache_dir() -> str:
    jax = _jax_module()
    if jax is not None:
        try:
            d = jax.config.jax_compilation_cache_dir
            if d:
                return str(d)
        except Exception:
            pass
    return os.environ.get("JAX_COMPILATION_CACHE_DIR", "")


def compile_cache_stats() -> Dict[str, int]:
    """{entries, bytes} of the persistent compile cache directory."""
    d = compile_cache_dir()
    if not d:
        return {"entries": 0, "bytes": 0}
    try:
        files = [os.path.join(d, f) for f in os.listdir(d)]
        return {"entries": len(files),
                "bytes": int(sum(os.path.getsize(f) for f in files
                                 if os.path.isfile(f)))}
    except OSError:
        return {"entries": 0, "bytes": 0}


_HBM_KEYS = (  # memory_stats() key -> exported gauge
    ("bytes_in_use", "pio_hbm_bytes_in_use"),
    ("bytes_limit", "pio_hbm_bytes_limit"),
    ("peak_bytes_in_use", "pio_hbm_peak_bytes_in_use"),
)


def _device_stats() -> List[Dict[str, Any]]:
    """Per-device platform + memory_stats (None where unsupported —
    CPU always; KNOWN_ISSUES #8)."""
    jax = _jax_module()
    if jax is None:
        return []
    try:
        devices = jax.local_devices()
    except Exception:
        return []
    out = []
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        out.append({"id": int(getattr(d, "id", len(out))),
                    "platform": str(getattr(d, "platform", "?")),
                    "kind": str(getattr(d, "device_kind", "?")),
                    "memoryStats": ms})
    return out


def host_memory_stats() -> Dict[str, Optional[int]]:
    """Host process memory from ``/proc``: resident set (VmRSS), its
    high-water mark (VmHWM) and the machine total (MemTotal) — the
    observability the out-of-core training claim rests on (peak host
    RSS must stay O(chunk), not O(dataset)). Gracefully absent (None
    values) where ``/proc`` does not exist, per the KNOWN_ISSUES #8
    pattern for platform-dependent gauges. NOTE: on CPU jax backends,
    device arrays ARE host memory and therefore count in RSS — subtract
    the live-array census when judging the pipeline's own footprint
    (KNOWN_ISSUES #14)."""
    out: Dict[str, Optional[int]] = {
        "rssBytes": None, "peakRssBytes": None, "memTotalBytes": None}
    try:
        with open("/proc/self/status", encoding="ascii",
                  errors="replace") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    out["rssBytes"] = int(line.split()[1]) * 1024
                elif line.startswith("VmHWM:"):
                    out["peakRssBytes"] = int(line.split()[1]) * 1024
    except OSError:
        return out
    try:
        with open("/proc/meminfo", encoding="ascii",
                  errors="replace") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    out["memTotalBytes"] = int(line.split()[1]) * 1024
                    break
    except OSError:
        pass
    return out


def host_rss_bytes() -> Optional[int]:
    """Current resident-set size, or None where /proc is unavailable."""
    return host_memory_stats()["rssBytes"]


class RssWatcher:
    """Sampling thread for peak-memory claims (the streamed-train tests
    and the 1 B-rating soak): records the peak RSS and the peak of
    RSS minus live jax array bytes — the latter is what isolates the
    HOST pipeline's footprint on CPU backends, where device buffers
    live in the same RSS (KNOWN_ISSUES #14). Timing uses sleep
    intervals only; no timed region is claimed, so the KNOWN_ISSUES #3
    host-transfer rule does not apply here."""

    def __init__(self, interval_s: float = 0.05):
        self._interval = interval_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.peak_rss = 0
        self.peak_pipeline = 0   # max over samples of rss - live_bytes
        #: the FIRST sample's pipeline value — long-lived processes
        #: (the shared test runner) measure their own growth as
        #: peak_pipeline - baseline_pipeline instead of inheriting
        #: every earlier allocation in the absolute number
        self.baseline_pipeline: Optional[int] = None
        self.samples = 0

    def _run(self) -> None:
        while not self._stop.is_set():
            st = host_memory_stats()
            rss = st["rssBytes"]
            if rss is not None:
                self.samples += 1
                if rss > self.peak_rss:
                    self.peak_rss = rss
                live = _live_array_stats()["bytes"]
                pipeline = max(rss - live, 0)
                if self.baseline_pipeline is None:
                    self.baseline_pipeline = pipeline
                if pipeline > self.peak_pipeline:
                    self.peak_pipeline = pipeline
            self._stop.wait(self._interval)

    def __enter__(self) -> "RssWatcher":
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="pio-rss-watch")
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)


def _live_array_stats() -> Dict[str, int]:
    jax = _jax_module()
    if jax is None or not hasattr(jax, "live_arrays"):
        return {"count": 0, "bytes": 0}
    try:
        arrs = jax.live_arrays()
        return {"count": len(arrs),
                "bytes": int(sum(int(getattr(a, "nbytes", 0) or 0)
                                 for a in arrs))}
    except Exception:
        return {"count": 0, "bytes": 0}


class _DeviceCollector:
    """Scrape-time exposition lines for the device gauges. Registered as
    a bound method (the registry holds it weakly); the module-level
    singleton keeps it alive for the process."""

    def collect(self) -> List[str]:
        if not telemetry.on():
            return []   # wire parity: telemetry off => no new series
        lines: List[str] = []
        devices = _device_stats()
        hbm = [(d, d["memoryStats"]) for d in devices if d["memoryStats"]]
        if hbm:
            for key, gauge in _HBM_KEYS:
                if not any(key in ms for _d, ms in hbm):
                    continue
                lines.append(f"# TYPE {gauge} gauge")
                for d, ms in hbm:
                    if key in ms:
                        lines.append(
                            f'{gauge}{{device="{d["id"]}"}} {int(ms[key])}')
        live = _live_array_stats()
        lines.append("# TYPE pio_live_arrays gauge")
        lines.append(f"pio_live_arrays {live['count']}")
        lines.append("# TYPE pio_live_array_bytes gauge")
        lines.append(f"pio_live_array_bytes {live['bytes']}")
        host = host_memory_stats()
        if host["rssBytes"] is not None:
            lines.append("# TYPE pio_host_rss_bytes gauge")
            lines.append(f"pio_host_rss_bytes {host['rssBytes']}")
        if host["peakRssBytes"] is not None:
            lines.append("# TYPE pio_host_rss_peak_bytes gauge")
            lines.append(
                f"pio_host_rss_peak_bytes {host['peakRssBytes']}")
        cache = compile_cache_stats()
        lines.append("# TYPE pio_compile_cache_entries gauge")
        lines.append(f"pio_compile_cache_entries {cache['entries']}")
        lines.append("# TYPE pio_compile_cache_bytes gauge")
        lines.append(f"pio_compile_cache_bytes {cache['bytes']}")
        lines.extend(self._breaker_lines())
        return lines

    @staticmethod
    def _breaker_lines() -> List[str]:
        """pio_breaker_open{endpoint}: 1 while a shared circuit breaker
        is open — the live-state gauge `pio doctor` reads (the existing
        transitions counter can't distinguish open from recovered).
        Naturally absent by default: no PIO_BREAKER_ENABLED, no
        breakers, no lines."""
        from predictionio_tpu.common.resilience import CircuitBreaker
        with CircuitBreaker._registry_lock:
            breakers = list(CircuitBreaker._registry.values())
        if not breakers:
            return []
        lines = ["# TYPE pio_breaker_open gauge"]
        for br in breakers:
            is_open = 1 if br.state == CircuitBreaker.OPEN else 0
            ep = telemetry._escape_label(br.endpoint or "?")
            lines.append(f'pio_breaker_open{{endpoint="{ep}"}} {is_open}')
        return lines


_collector = _DeviceCollector()


# ---------------------------------------------------------------------------
# install + /debug/device.json
# ---------------------------------------------------------------------------

def install() -> bool:
    """Register the compile-event listener and the device-gauge
    collector (idempotent; every daemon calls this from its
    constructor). Returns whether jax.monitoring hooks are live."""
    global _installed, _have_monitoring
    with _lock:
        already = _installed
        _installed = True
    if not already:
        try:
            from jax import monitoring as _monitoring
            _monitoring.register_event_duration_secs_listener(
                _on_compile_duration)
            _have_monitoring = True
        except Exception:   # stripped runtime: signature fallback only
            _have_monitoring = False
            logger.info("jax.monitoring unavailable; recompile watchdog "
                        "falls back to signature novelty detection")
    # collector registration dedupes on the callable, so re-calling
    # install() after a registry reset (tests) re-attaches it
    telemetry.registry().register_collector(_collector.collect)
    return _have_monitoring


def debug_snapshot() -> Dict[str, Any]:
    """The ``GET /debug/device.json`` payload. With telemetry off the
    subsystem is dormant and the payload says only that (wire parity:
    the endpoint leaks nothing new until the operator opts in)."""
    if not telemetry.on():
        return {"telemetry": False}
    from predictionio_tpu.common.resilience import CircuitBreaker
    with _lock:
        watchdog = {
            "monitoringHooks": _have_monitoring,
            "servingWarmupDone": _warmup_done,
            "servingFlushes": _serving_flushes,
            "servingSignatures": sorted(_serving_sigs),
            "recentPostWarmup": list(_post_warmup_events),
        }
        aot_state = dict(_aot_state) if _aot_state is not None else None
        sharding_state = (dict(_sharding_state)
                          if _sharding_state is not None else None)
        quant_state = (dict(_quant_state)
                       if _quant_state is not None else None)
        foldin_state = (dict(_foldin_state)
                        if _foldin_state is not None else None)
    watchdog["compilesTotal"] = compiles_total()
    watchdog["postWarmupRecompiles"] = post_warmup_recompiles()
    with CircuitBreaker._registry_lock:
        breakers = [br.stats() for br in
                    CircuitBreaker._registry.values()]
    return {
        "telemetry": True,
        "watchdog": watchdog,
        "aot": aot_state,
        "sharding": sharding_state,
        "quant": quant_state,
        "foldin": foldin_state,
        "devices": _device_stats(),
        "liveArrays": _live_array_stats(),
        "hostMemory": host_memory_stats(),
        "compileCache": {"dir": compile_cache_dir(),
                         **compile_cache_stats()},
        "breakers": breakers,
    }
