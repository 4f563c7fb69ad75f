"""Typed JSON codec for queries, predictions, and params.

Reference: core/.../workflow/JsonExtractor.scala:37-167. The reference kept
two JSON stacks (json4s for Scala, Gson for Java); here one structural
dataclass codec covers both roles: `extract` builds a dataclass from a JSON
object (unknown fields rejected, like json4s strict mode), `to_json_obj`
renders one back (None fields dropped, matching json4s Option behavior).

What reflection says of a dataclass (its resolved type hints, its fields,
which of them are required, its aliases) is read once, when the class is
first seen, and kept as its plan; a request only runs the plan. Counted
in the registry: ``pio_codec_plans_total`` and
``pio_codec_requests_total{path}``.
"""

from __future__ import annotations

import dataclasses
import json
import math
import threading
import types
import typing
import weakref
from typing import Any, List, Optional, Tuple, Type

from predictionio_tpu.common import telemetry

_M_PLANS = telemetry.registry().counter(
    "pio_codec_plans_total",
    "Dataclass codec plans made (one a class; still once every class "
    "has been seen)").child()
_M_REQUESTS = telemetry.registry().counter(
    "pio_codec_requests_total",
    "Request bodies extracted, by whether every dataclass in the query "
    "ran its plan or some class fell back to per-request reflection",
    labelnames=("path",))
_M_PLANNED = _M_REQUESTS.labels(path="planned")
_M_REFLECTED = _M_REQUESTS.labels(path="reflected")


def _fields_of(cls: Type) -> Tuple[Tuple[str, Any, bool], ...]:
    """(name, resolved hint, required) of each field: the reflection a
    plan keeps. Raises what `typing.get_type_hints` raises."""
    hints = typing.get_type_hints(cls)
    return tuple(
        (f.name, hints.get(f.name),
         f.default is dataclasses.MISSING
         and f.default_factory is dataclasses.MISSING)
        for f in dataclasses.fields(cls))


class _Plan:
    """One dataclass as the codec needs it. ``fields`` is None for a
    class whose hints could not be resolved when the plan was made:
    `extract` then asks for them again on every request. ``reflects``
    says that of the class or of any dataclass its fields name."""

    __slots__ = ("names", "fields", "known", "aliases", "reflects")

    def __init__(self, cls: Type):
        self.names: Tuple[str, ...] = tuple(
            f.name for f in dataclasses.fields(cls))
        self.known = frozenset(self.names)
        self.aliases = getattr(cls, "JSON_ALIASES", {})
        try:
            self.fields = _fields_of(cls)
        except Exception:  # an annotation that names nothing (yet)
            self.fields = None
        self.reflects = self.fields is None


#: class object -> its plan. Keyed on the object, not the name: a
#: /reload that brings a new class of the same name gets a new plan,
#: and the old one goes with its class.
_PLANS: "weakref.WeakKeyDictionary[Type, _Plan]" = weakref.WeakKeyDictionary()
_PLANS_LOCK = threading.RLock()


def _dataclasses_in(tp: Any, out: List[Type]) -> None:
    if isinstance(tp, type) and dataclasses.is_dataclass(tp):
        out.append(tp)
    for arg in typing.get_args(tp):
        _dataclasses_in(arg, out)


def plan_of(cls: Type) -> _Plan:
    """The plan of dataclass ``cls``, made on first sight together with
    the plans of the dataclasses its fields name, so that the first
    query of a warm-up makes every plan its class will need."""
    plan = _PLANS.get(cls)
    if plan is not None:
        return plan
    with _PLANS_LOCK:
        plan = _PLANS.get(cls)
        if plan is None:
            plan = _PLANS[cls] = _Plan(cls)
            _M_PLANS.inc()
            nested: List[Type] = []
            for _name, hint, _required in plan.fields or ():
                _dataclasses_in(hint, nested)
            # the entry above is what ends a class that names itself
            if any(plan_of(n).reflects for n in nested):
                plan.reflects = True
        return plan


def extract(cls: Optional[Type], obj: Any):
    """JSON value -> instance of cls (recursively over dataclass fields)."""
    if cls is None or cls is Any:
        return obj
    # a plain class has no origin; typing need not be asked
    origin = None if type(cls) is type else typing.get_origin(cls)
    is_union = origin is typing.Union or origin is types.UnionType
    if obj is None:
        if cls is type(None) or (
                is_union and type(None) in typing.get_args(cls)):
            return None
        raise ValueError(f"null is not allowed for {cls}")
    if is_union:  # Optional[T] and unions, both typing.Union and X | Y
        args = [a for a in typing.get_args(cls) if a is not type(None)]
        last_err = None
        for a in args:
            try:
                return extract(a, obj)
            except (TypeError, ValueError) as e:
                last_err = e
        raise ValueError(f"cannot extract {obj!r} as {cls}: {last_err}")
    if origin in (list, tuple, set, frozenset):
        if not isinstance(obj, (list, tuple)):
            raise ValueError(f"expected an array for {cls}, got {obj!r}")
        args = typing.get_args(cls)
        if origin is tuple and args and args[-1] is Ellipsis:
            elem = args[0]
            return tuple(extract(elem, x) for x in obj)
        if origin is tuple and args:
            return tuple(extract(a, x) for a, x in zip(args, obj))
        elem = args[0] if args else None
        seq = [extract(elem, x) for x in obj]
        return origin(seq) if origin is not list else seq
    if origin is dict:
        if not isinstance(obj, dict):
            raise ValueError(f"expected an object for {cls}, got {obj!r}")
        _, vt = (typing.get_args(cls) or (None, None))
        return {k: extract(vt, v) for k, v in obj.items()}
    if dataclasses.is_dataclass(cls):
        if not isinstance(obj, dict):
            raise ValueError(f"expected an object for {cls.__name__}, got {obj!r}")
        plan = plan_of(cls)
        # no plan to run: per-request reflection, raising what it raises
        fields = plan.fields if plan.fields is not None else _fields_of(cls)
        if plan.aliases:
            aliases = plan.aliases
            obj = {aliases.get(k, k): v for k, v in obj.items()}
        if not plan.known.issuperset(obj):
            raise ValueError(
                f"unknown field(s) {sorted(set(obj) - plan.known)} for "
                f"{cls.__name__} (accepts {sorted(plan.names)})")
        kwargs = {}
        for name, hint, required in fields:
            if name in obj:
                kwargs[name] = extract(hint, obj[name])
            elif required:
                raise ValueError(
                    f"field {name} is required for {cls.__name__}")
        return cls(**kwargs)
    # bool is an int subclass; reject bool-for-int/float confusions
    if cls in (int, float) and isinstance(obj, bool):
        raise ValueError(f"expected {cls.__name__}, got {obj!r}")
    if cls is float and isinstance(obj, int):
        return float(obj)
    if isinstance(cls, type) and not isinstance(obj, cls):
        raise ValueError(f"expected {cls.__name__}, got {obj!r}")
    return obj


def _to_json(obj: Any, bad: list) -> Any:
    t = type(obj)
    if t is str or t is int or t is bool or obj is None:
        return obj
    if t is float:
        if not math.isfinite(obj):
            bad.append(obj)
        return obj
    if dataclasses.is_dataclass(t):
        out = {}
        for name in plan_of(t).names:
            v = _to_json(getattr(obj, name), bad)
            if v is not None:
                out[name] = v
        return out
    if isinstance(obj, dict):
        return {k: _to_json(v, bad) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, set, frozenset)):
        return [_to_json(x, bad) for x in obj]
    if hasattr(obj, "item") and callable(getattr(obj, "item", None)) and \
            getattr(obj, "shape", None) == ():
        obj = obj.item()  # 0-d numpy/jax scalars
    if isinstance(obj, float) and not math.isfinite(obj):
        bad.append(obj)
    return obj


def to_json_obj(obj: Any) -> Any:
    """Dataclass tree -> plain JSON value (None fields dropped)."""
    return _to_json(obj, [])


def to_json_checked(obj: Any) -> Tuple[Any, bool]:
    """`to_json_obj` and, from the same walk, whether the value holds a
    NaN or an infinity: what `data.event.tree_has_non_finite` would say
    of it in a second one."""
    bad: list = []
    value = _to_json(obj, bad)
    return value, bool(bad)


def extract_query(cls: Optional[Type], body: bytes):
    """HTTP body -> query object (CreateServer.scala:479-485)."""
    obj = json.loads(body.decode("utf-8"))
    if cls is None:
        return obj
    if dataclasses.is_dataclass(cls) and plan_of(cls).reflects:
        _M_REFLECTED.inc()
    else:
        _M_PLANNED.inc()
    return extract(cls, obj)


def stats() -> dict:
    """The codec's counters, as `GET /` shows them."""
    return {"plans": int(_M_PLANS.value),
            "requests": {"planned": int(_M_PLANNED.value),
                         "reflected": int(_M_REFLECTED.value)}}


def render(obj: Any) -> str:
    return json.dumps(to_json_obj(obj))
