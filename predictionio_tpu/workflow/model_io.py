"""Model (de)serialization for the Models store.

Reference role: the Kryo blob path (CoreWorkflow.scala:76-81 serialize;
CreateServer.scala:195-199 deserialize). Here the container is pickle with
every jax.Array converted to numpy on save and restored host-side on load;
`device_put_tree` can push a model's arrays into HBM for algorithms whose
prepare_serving probes the device path as faster (deploy itself hands
models to algorithms host-side; per-query host serving is the default).

Models are arbitrary user objects (dataclasses, dicts, tuples, BiMaps...),
not registered pytrees, so the walker is structural rather than
jax.tree_util-based.
"""

from __future__ import annotations

import dataclasses
import os
import pickle
from typing import Any, Callable, List

import jax
import numpy as np


def _map_arrays(obj: Any, leaf_p: Callable[[Any], bool],
                fn: Callable[[Any], Any]) -> Any:
    if leaf_p(obj):
        return fn(obj)
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        changes = {
            f.name: _map_arrays(getattr(obj, f.name), leaf_p, fn)
            for f in dataclasses.fields(obj)}
        try:
            return dataclasses.replace(obj, **changes)
        except (TypeError, ValueError):
            # non-init fields etc.: mutate a shallow copy
            import copy
            new = copy.copy(obj)
            for k, v in changes.items():
                object.__setattr__(new, k, v)
            return new
    if isinstance(obj, dict):
        return {k: _map_arrays(v, leaf_p, fn) for k, v in obj.items()}
    if isinstance(obj, tuple):
        return tuple(_map_arrays(x, leaf_p, fn) for x in obj)
    if isinstance(obj, list):
        return [_map_arrays(x, leaf_p, fn) for x in obj]
    return obj


def to_host(obj: Any) -> Any:
    """jax.Array leaves -> numpy (blocking transfer)."""
    return _map_arrays(obj, lambda x: isinstance(x, jax.Array),
                       lambda x: np.asarray(x))


class NonFiniteModelError(ValueError):
    """A trained model array contains NaN/Inf.

    Raised by serialize_models(check_finite=True) so run_train refuses to
    mark the EngineInstance COMPLETED (the reference's status ledger exists
    precisely so deploy never serves a bad instance — CoreWorkflow.scala:
    84-88, commands/Engine.scala:224-239; a poisoned blob would pass both
    and serve garbage scores)."""


def non_finite_report(obj: Any, limit: int = 8) -> List[str]:
    """Describe every float array in a host-side model tree that contains
    non-finite values. Empty list == clean. Walks the same structure
    serialization walks, so anything persisted is covered."""
    bad: List[str] = []

    def check(x):
        if len(bad) < limit:
            n_nan = int(np.isnan(x).sum())
            n_inf = int(np.isinf(x).sum())
            if n_nan or n_inf:
                bad.append(f"array shape={x.shape} dtype={x.dtype}: "
                           f"{n_nan} NaN, {n_inf} Inf")
        return x

    _map_arrays(
        obj,
        lambda x: isinstance(x, np.ndarray)
        and np.issubdtype(x.dtype, np.floating),
        check)
    return bad


def factor_bytes_by_dtype(obj: Any) -> dict:
    """Array bytes in a model tree, summed per dtype name — the storage
    / serving-footprint accounting the quantized-serving surfaces
    (ops/quant.py summary) report. Walks the
    same structure serialization walks, so quantized int8 blocks and
    their fp32 scale vectors (which ride the pickle container like any
    other dataclass leaves) are each counted under their own dtype."""
    out: dict = {}

    def count(x):
        key = str(x.dtype)
        out[key] = out.get(key, 0) + int(x.nbytes)
        return x

    _map_arrays(
        to_host(obj),
        lambda x: isinstance(x, np.ndarray) and x.dtype != object,
        count)
    return out


def serialize_models(models: List[Any], check_finite: bool = False) -> bytes:
    host = to_host(models)
    if check_finite:
        bad = non_finite_report(host)
        if bad:
            raise NonFiniteModelError(
                "trained model contains non-finite values — refusing to "
                "persist it as COMPLETED (deploy would serve garbage "
                "scores): " + "; ".join(bad) + ". If this model family "
                "legitimately stores ±Inf (e.g. log-space probabilities "
                "with zero smoothing), set PIO_FINITE_CHECK=0.")
    return pickle.dumps(host, protocol=pickle.HIGHEST_PROTOCOL)


def deserialize_models(blob: bytes) -> List[Any]:
    return pickle.loads(blob)


# ---------------------------------------------------------------------------
# compile-cache deploy artifact (serving/aot.py)
# ---------------------------------------------------------------------------
#
# The persistent compile cache (.jax_cache) holds the XLA executables a
# training run and its model's serving programs compiled; exporting the
# run's new entries next to the model blob lets `pio deploy` pre-seed a
# cold replica's cache and skip minutes of backend compiles. Cache keys
# bake in the jaxlib version and platform, so the artifact records that
# fingerprint and import SKIPS (never errors) on mismatch — a stale
# artifact degrades to lazy compilation (KNOWN_ISSUES #9).

#: a single cache entry larger than this is almost certainly not one of
#: ours (the full hybrid trainer is ~10s of MB); cap the artifact so a
#: shared cache dir can't balloon the Models store
_CACHE_ENTRY_MAX_BYTES = 256 * 1024 * 1024


def cache_artifact_id(instance_id: str) -> str:
    """Models-store key of an instance's compile-cache artifact (kept
    separate from the model blob so pre-artifact readers see exactly
    the rows they always did)."""
    return f"{instance_id}.jaxcache"


def cache_fingerprint() -> dict:
    """The environment attributes jax's cache keys depend on; an
    artifact only imports into a matching environment."""
    import jaxlib

    return {
        "jax": getattr(jax, "__version__", "?"),
        "jaxlib": getattr(jaxlib, "__version__", "?"),
        "backend": jax.default_backend(),
    }


def cache_snapshot(cache_dir: str) -> frozenset:
    """Filenames currently in the persistent cache directory (the
    before/after delta is what a training run exports)."""
    try:
        return frozenset(
            f for f in os.listdir(cache_dir)
            if os.path.isfile(os.path.join(cache_dir, f)))
    except OSError:
        return frozenset()


def export_compile_cache(cache_dir: str,
                         since: Any = None) -> "bytes | None":
    """Pack the cache entries added since ``since`` (a
    :func:`cache_snapshot`; None = everything) into an artifact blob.
    Returns None when there is nothing to export."""
    names = cache_snapshot(cache_dir)
    if since:
        names = names - frozenset(since)
    entries = {}
    for name in sorted(names):
        path = os.path.join(cache_dir, name)
        try:
            if os.path.getsize(path) > _CACHE_ENTRY_MAX_BYTES:
                continue
            with open(path, "rb") as f:
                entries[name] = f.read()
        except OSError:
            continue
    if not entries:
        return None
    return pickle.dumps(
        {"format": "pio-jaxcache-v1", "meta": cache_fingerprint(),
         "entries": entries},
        protocol=pickle.HIGHEST_PROTOCOL)


def import_compile_cache(blob: bytes, cache_dir: str) -> dict:
    """Pre-seed ``cache_dir`` from an exported artifact.

    Graceful by contract: a corrupt blob, a jaxlib/platform mismatch,
    or an unwritable directory returns a summary with ``skipped`` —
    deploy then compiles lazily exactly as before the artifact existed.
    Existing files are never overwritten (the local cache is at least
    as fresh)."""
    summary = {"imported": 0, "skipped": 0, "reason": ""}
    try:
        artifact = pickle.loads(blob)
        if (not isinstance(artifact, dict)
                or artifact.get("format") != "pio-jaxcache-v1"):
            summary["reason"] = "unrecognized artifact format"
            return summary
        meta = artifact.get("meta") or {}
        here = cache_fingerprint()
        if meta != here:
            summary["skipped"] = len(artifact.get("entries") or {})
            summary["reason"] = (
                f"environment mismatch (artifact {meta}, this process "
                f"{here}); compiling lazily")
            return summary
        os.makedirs(cache_dir, exist_ok=True)
        for name, data in (artifact.get("entries") or {}).items():
            # refuse path traversal from a hostile blob
            if os.path.basename(name) != name or name.startswith("."):
                summary["skipped"] += 1
                continue
            path = os.path.join(cache_dir, name)
            if os.path.exists(path):
                summary["skipped"] += 1
                continue
            tmp = path + ".pio_tmp"
            with open(tmp, "wb") as f:
                f.write(data)
            os.replace(tmp, path)
            summary["imported"] += 1
    except Exception as e:
        summary["reason"] = (f"{type(e).__name__}: {e}; compiling lazily")
    return summary


def device_put_tree(obj: Any, sharding=None) -> Any:
    """Push every numeric numpy leaf of a model tree into device memory
    (optionally with a NamedSharding for multi-chip serving)."""
    def put(x):
        return (jax.device_put(x, sharding) if sharding is not None
                else jax.device_put(x))
    return _map_arrays(
        obj,
        lambda x: isinstance(x, np.ndarray) and x.dtype != object,
        put)
