"""Typed JSON codec tests (ref: core/src/test/scala/.../JsonExtractorSuite)."""

import dataclasses
import json
import sys
import threading
import types
import typing
from typing import Any, Dict, FrozenSet, List, Optional, Set, Tuple

import numpy as np
import pytest

from predictionio_tpu.common import telemetry
from predictionio_tpu.data.event import tree_has_non_finite
from predictionio_tpu.models.classification import engine as classification
from predictionio_tpu.models.ecommerce import engine as ecommerce
from predictionio_tpu.models.recommendation import engine as recommendation
from predictionio_tpu.models.recommendation.als_algorithm import (
    ALSAlgorithmParams,
)
from predictionio_tpu.models.similarproduct import engine as similarproduct
from predictionio_tpu.workflow import json_extractor
from predictionio_tpu.workflow.json_extractor import (
    extract, extract_query, plan_of, to_json_checked, to_json_obj,
)


@dataclasses.dataclass(frozen=True)
class Inner:
    name: str
    weight: float = 1.0


@dataclasses.dataclass(frozen=True)
class Q:
    user: str
    num: int
    items: Optional[Tuple[str, ...]] = None
    inner: Optional[Inner] = None


def test_extract_nested_and_defaults():
    q = extract(Q, {"user": "u1", "num": 3,
                    "items": ["a", "b"],
                    "inner": {"name": "x"}})
    assert q == Q("u1", 3, ("a", "b"), Inner("x", 1.0))
    # int widening to float
    assert extract(Inner, {"name": "x", "weight": 2}).weight == 2.0


def test_extract_rejects_bad_input():
    with pytest.raises(ValueError, match="required"):
        extract(Q, {"user": "u1"})
    with pytest.raises(ValueError, match="unknown field"):
        extract(Q, {"user": "u1", "num": 1, "zzz": 2})
    with pytest.raises(ValueError, match="expected int"):
        extract(Q, {"user": "u1", "num": "3"})
    with pytest.raises(ValueError, match="expected int"):
        extract(Q, {"user": "u1", "num": True})
    # null for a required non-Optional field is rejected
    with pytest.raises(ValueError, match="null"):
        extract(Q, {"user": None, "num": 3})
    # null for Optional passes
    assert extract(Q, {"user": "u", "num": 1, "items": None}).items is None


def test_extract_pep604_union():
    @dataclasses.dataclass(frozen=True)
    class Modern:
        name: str
        inner: Inner | None = None
        count: int | str = 0

    m = extract(Modern, {"name": "a", "inner": {"name": "i"}})
    assert m.inner == Inner("i")  # validated, not a raw dict
    with pytest.raises(ValueError):
        extract(Modern, {"name": "a", "inner": {"nope": 1}})
    assert extract(Modern, {"name": "a", "count": "x"}).count == "x"
    with pytest.raises(ValueError, match="null"):
        extract(Modern, {"name": None})


def test_to_json_obj_drops_none_fields():
    assert to_json_obj(Q("u", 2)) == {"user": "u", "num": 2}
    assert to_json_obj(Q("u", 2, ("i",), Inner("x"))) == {
        "user": "u", "num": 2, "items": ["i"],
        "inner": {"name": "x", "weight": 1.0}}


def test_extract_query_bytes():
    assert extract_query(Q, b'{"user": "u", "num": 1}') == Q("u", 1)
    assert extract_query(None, b'{"free": 1}') == {"free": 1}


# ---------------------------------------------------------------------------
# the planned codec against per-request reflection
# ---------------------------------------------------------------------------
# `_ref_extract` / `_ref_to_json_obj` are the codec as it stood while it
# asked typing and dataclasses on every request: the reference the planned
# walk is held to, object for object and error message for error message.


def _ref_extract(cls, obj):
    if cls is None or cls is Any:
        return obj
    origin = typing.get_origin(cls)
    is_union = origin is typing.Union or origin is types.UnionType
    if obj is None:
        if cls is type(None) or (
                is_union and type(None) in typing.get_args(cls)):
            return None
        raise ValueError(f"null is not allowed for {cls}")
    if is_union:
        args = [a for a in typing.get_args(cls) if a is not type(None)]
        last_err = None
        for a in args:
            try:
                return _ref_extract(a, obj)
            except (TypeError, ValueError) as e:
                last_err = e
        raise ValueError(f"cannot extract {obj!r} as {cls}: {last_err}")
    if origin in (list, tuple, set, frozenset):
        if not isinstance(obj, (list, tuple)):
            raise ValueError(f"expected an array for {cls}, got {obj!r}")
        args = typing.get_args(cls)
        if origin is tuple and args and args[-1] is Ellipsis:
            elem = args[0]
            return tuple(_ref_extract(elem, x) for x in obj)
        if origin is tuple and args:
            return tuple(_ref_extract(a, x) for a, x in zip(args, obj))
        elem = args[0] if args else None
        seq = [_ref_extract(elem, x) for x in obj]
        return origin(seq) if origin is not list else seq
    if origin is dict:
        if not isinstance(obj, dict):
            raise ValueError(f"expected an object for {cls}, got {obj!r}")
        _, vt = (typing.get_args(cls) or (None, None))
        return {k: _ref_extract(vt, v) for k, v in obj.items()}
    if dataclasses.is_dataclass(cls):
        if not isinstance(obj, dict):
            raise ValueError(f"expected an object for {cls.__name__}, got {obj!r}")
        aliases = getattr(cls, "JSON_ALIASES", {})
        obj = {aliases.get(k, k): v for k, v in obj.items()}
        hints = typing.get_type_hints(cls)
        fields = {f.name: f for f in dataclasses.fields(cls)}
        unknown = set(obj) - set(fields)
        if unknown:
            raise ValueError(
                f"unknown field(s) {sorted(unknown)} for {cls.__name__} "
                f"(accepts {sorted(fields)})")
        kwargs = {}
        for name, f in fields.items():
            if name in obj:
                kwargs[name] = _ref_extract(hints.get(name), obj[name])
            elif (f.default is dataclasses.MISSING
                  and f.default_factory is dataclasses.MISSING):
                raise ValueError(
                    f"field {name} is required for {cls.__name__}")
        return cls(**kwargs)
    if cls in (int, float) and isinstance(obj, bool):
        raise ValueError(f"expected {cls.__name__}, got {obj!r}")
    if cls is float and isinstance(obj, int):
        return float(obj)
    if isinstance(cls, type) and not isinstance(obj, cls):
        raise ValueError(f"expected {cls.__name__}, got {obj!r}")
    return obj


def _ref_to_json_obj(obj):
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        out = {}
        for f in dataclasses.fields(obj):
            v = _ref_to_json_obj(getattr(obj, f.name))
            if v is not None:
                out[f.name] = v
        return out
    if isinstance(obj, dict):
        return {k: _ref_to_json_obj(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, set, frozenset)):
        return [_ref_to_json_obj(x) for x in obj]
    if hasattr(obj, "item") and callable(getattr(obj, "item", None)) and \
            getattr(obj, "shape", None) == ():
        return obj.item()
    return obj


@dataclasses.dataclass
class Held:
    v: float


@dataclasses.dataclass
class Holder:
    held: Tuple[Held, ...] = ()
    named: Optional[Dict[str, Held]] = None
    kin: Optional[List["Holder"]] = None   # names itself


@dataclasses.dataclass
class Odd:
    """The shapes no template has."""
    name: str
    ratio: float = 0.5
    flag: bool = False
    tags: List[str] = dataclasses.field(default_factory=list)
    pair: Tuple[int, str] = (0, "")
    weights: Dict[str, float] = dataclasses.field(default_factory=dict)
    seen: Set[int] = dataclasses.field(default_factory=set)
    frozen: FrozenSet[str] = frozenset()
    inner: Inner | None = None
    inners: Optional[List[Inner]] = None
    either: int | str = 0
    free: Any = None
    bare: list = dataclasses.field(default_factory=list)
    nothing: None = None


_rec_result = recommendation.PredictedResult(tuple(
    recommendation.ItemScore(item=f"i{j}", score=1.0 / (j + 3))
    for j in range(10)))

EXTRACT_CASES = [
    # every template's query class, good and bad
    ("rec.query", recommendation.Query, {"user": "u1", "num": 10}),
    ("rec.query.unknown", recommendation.Query,
     {"user": "u1", "num": 10, "zzz": 1, "aaa": 2}),
    ("rec.query.missing", recommendation.Query, {"user": "u1"}),
    ("rec.query.null", recommendation.Query, {"user": None, "num": 1}),
    ("rec.query.bool_for_int", recommendation.Query,
     {"user": "u1", "num": True}),
    ("rec.query.str_for_int", recommendation.Query,
     {"user": "u1", "num": "3"}),
    ("rec.query.float_for_int", recommendation.Query,
     {"user": "u1", "num": 3.0}),
    ("rec.query.int_for_str", recommendation.Query, {"user": 7, "num": 3}),
    ("rec.query.not_an_object", recommendation.Query, ["u1", 3]),
    ("rec.query.unknown_before_type", recommendation.Query,
     {"user": 7, "num": "x", "zzz": 1}),
    ("rec.result", recommendation.PredictedResult,
     {"itemScores": [{"item": "i1", "score": 2}, {"item": "i2", "score": 0.5}]}),
    ("rec.result.bad_item", recommendation.PredictedResult,
     {"itemScores": [{"item": "i1"}]}),
    ("rec.result.not_an_array", recommendation.PredictedResult,
     {"itemScores": {"item": "i1", "score": 1.0}}),
    ("rec.rating", recommendation.Rating,
     {"user": "u", "item": "i", "rating": 4}),
    ("sim.query", similarproduct.Query,
     {"items": ["i1", "i2"], "num": 4, "categories": ["c1"],
      "whiteList": None, "blackList": []}),
    ("sim.query.bad_elem", similarproduct.Query, {"items": ["i1", 2], "num": 4}),
    ("sim.query.null_items", similarproduct.Query, {"items": None, "num": 4}),
    ("sim.result", similarproduct.PredictedResult,
     {"itemScores": [{"item": "i9", "score": 0.25}]}),
    ("ecom.query", ecommerce.Query,
     {"user": "u1", "num": 4, "categories": ["c1", "c2"],
      "whiteList": ["i1"], "blackList": None}),
    ("ecom.query.bad_optional", ecommerce.Query,
     {"user": "u1", "num": 4, "categories": "c1"}),
    ("ecom.result", ecommerce.PredictedResult, {"itemScores": []}),
    ("ecom.item", ecommerce.Item, {"categories": ["a"]}),
    ("cls.query", classification.Query, {"features": [1, 2.5, 3]}),
    ("cls.query.bool", classification.Query, {"features": [1.0, True]}),
    ("cls.result", classification.PredictedResult, {"label": 1}),
    ("cls.result.null", classification.PredictedResult, {"label": None}),
    # JSON_ALIASES, defaults
    ("params.alias", ALSAlgorithmParams,
     {"rank": 8, "numIterations": 3, "lambda": 0.05, "seed": 3}),
    ("params.canonical", ALSAlgorithmParams, {"rank": 8, "lambda_": 0.25}),
    ("params.both", ALSAlgorithmParams, {"lambda_": 0.25, "lambda": 0.5}),
    ("params.defaults", ALSAlgorithmParams, {}),
    ("params.unknown", ALSAlgorithmParams, {"lambda": 0.05, "mu": 1}),
    # nested dataclasses, Optional, X | None
    ("q.nested", Q, {"user": "u1", "num": 3, "items": ["a", "b"],
                     "inner": {"name": "x"}}),
    ("q.nested.bad", Q, {"user": "u1", "num": 3, "inner": {"nope": 1}}),
    ("q.nested.null", Q, {"user": "u1", "num": 3, "inner": None,
                          "items": None}),
    ("inner.widen", Inner, {"name": "x", "weight": 2}),
    ("odd.all", Odd, {
        "name": "n", "ratio": 1, "flag": True, "tags": ["a", "b"],
        "pair": [3, "x"], "weights": {"a": 1, "b": 2.5}, "seen": [1, 2, 2],
        "frozen": ["x", "y"], "inner": {"name": "i", "weight": 3},
        "inners": [{"name": "j"}, {"name": "k", "weight": 0}],
        "either": "s", "free": {"any": [1, None]}, "bare": [1, "2"],
        "nothing": None}),
    ("odd.defaults", Odd, {"name": "n"}),
    ("odd.union_first", Odd, {"name": "n", "either": 5}),
    ("odd.union_none_fits", Odd, {"name": "n", "either": 1.5}),
    ("odd.union_bool", Odd, {"name": "n", "either": True}),
    ("odd.pair_wrong", Odd, {"name": "n", "pair": ["x", 3]}),
    ("odd.pair_short", Odd, {"name": "n", "pair": [3]}),
    ("odd.dict_not_object", Odd, {"name": "n", "weights": [1, 2]}),
    ("odd.dict_bad_value", Odd, {"name": "n", "weights": {"a": "x"}}),
    ("odd.set_not_array", Odd, {"name": "n", "seen": {"1": 2}}),
    ("odd.flag_int", Odd, {"name": "n", "flag": 1}),
    ("odd.ratio_bool", Odd, {"name": "n", "ratio": False}),
    ("odd.ratio_null", Odd, {"name": "n", "ratio": None}),
    ("odd.inner_null", Odd, {"name": "n", "inner": None}),
    ("odd.inners_bad", Odd, {"name": "n", "inners": [{"name": 1}]}),
    ("odd.bare_not_list", Odd, {"name": "n", "bare": "x"}),
    ("odd.nothing_set", Odd, {"name": "n", "nothing": 1}),
    # outside a dataclass
    ("optional.list", Optional[List[str]], ["a"]),
    ("optional.list.null", Optional[List[str]], None),
    ("list.null", List[str], None),
    ("tuple.var", Tuple[float, ...], [1, 2]),
    ("builtin.generic", list[int], [1, "2"]),
    ("dict.nested", Dict[str, List[Inner]], {"a": [{"name": "x"}]}),
    ("any", Any, {"x": [1]}),
    ("none.cls", None, [1]),
    ("nonetype", type(None), None),
    ("nonetype.value", type(None), 0),
]


def _outcome(fn, *args):
    try:
        return ("ok", fn(*args))
    except Exception as e:  # the message is part of the contract
        return (type(e).__name__, str(e))


def _same(a, b):
    """Equal, and of the same types all the way down; NaN is NaN."""
    if type(a) is not type(b):
        return False
    if isinstance(a, float):
        return a == b or (a != a and b != b)
    if dataclasses.is_dataclass(a) and not isinstance(a, type):
        return all(_same(getattr(a, f.name), getattr(b, f.name))
                   for f in dataclasses.fields(a))
    if isinstance(a, dict):
        return list(a) == list(b) and all(_same(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(_same(x, y) for x, y in zip(a, b))
    return a == b


@pytest.mark.parametrize("cls,obj", [c[1:] for c in EXTRACT_CASES],
                         ids=[c[0] for c in EXTRACT_CASES])
def test_planned_extract_is_what_reflection_gave(cls, obj):
    want = _outcome(_ref_extract, cls, obj)
    for _ in range(2):   # making the plan, then running it
        got = _outcome(extract, cls, obj)
        assert got[0] == want[0]
        assert _same(got[1], want[1]), (got, want)
    # and from the bytes of a request
    assert _same(_outcome(extract_query, cls, json.dumps(obj).encode())[1],
                 want[1])


TO_JSON_CASES = [
    ("rec.result", _rec_result),
    ("rec.result.empty", recommendation.PredictedResult(())),
    ("rec.query", recommendation.Query("u1", 10)),
    ("sim.query", similarproduct.Query(("i1", "i2"), 4, ("c",), None, ())),
    ("sim.result", similarproduct.PredictedResult(
        (similarproduct.ItemScore("i9", 0.25),))),
    ("ecom.query", ecommerce.Query("u1", 4, None, ("i1",), None)),
    ("ecom.result", ecommerce.PredictedResult(
        (ecommerce.ItemScore("i1", 1e-9), ecommerce.ItemScore("i2", -3.5)))),
    ("cls.query", classification.Query((1.0, 2.5))),
    ("cls.result", classification.PredictedResult(label=1.0)),
    ("params", ALSAlgorithmParams(rank=4, lambda_=0.05)),
    ("q.none_dropped", Q("u", 2)),
    ("q.nested", Q("u", 2, ("i",), Inner("x"))),
    ("odd", Odd("n", tags=["a"], weights={"a": 1.0, "b": None},
                seen={3}, frozen=frozenset({"f"}), inner=Inner("i"),
                inners=[Inner("j"), Inner("k", 0.0)], either="s",
                free={"k": (1, None, [2.5])})),
    ("np.scalars", {"f32": np.float32(0.1), "f64": np.float64(0.1),
                    "i32": np.int32(7), "b": np.bool_(True),
                    "zero_d": np.array(2.5), "s": np.str_("x")}),
    ("np.array_1d_passes_through", {"a": (1, 2)}),
    ("leaves", [None, True, 0, -1, 1.5, "s", b"bytes"]),
    ("nan", recommendation.PredictedResult(
        (recommendation.ItemScore("i1", float("nan")),))),
    ("inf", recommendation.PredictedResult(
        (recommendation.ItemScore("i1", 1.0),
         recommendation.ItemScore("i2", float("-inf"))))),
    ("nan.np32", {"scores": [np.float32("nan")]}),
    ("inf.np64", {"deep": {"er": (np.float64("inf"),)}}),
    ("inf.zero_d", [np.array(np.inf, np.float32)]),
    ("nan.in_set", {frozenset({float("nan")}): 1, "v": {1.0}}),
    ("a_class_is_a_leaf", [Inner]),
]


@pytest.mark.parametrize("value", [c[1] for c in TO_JSON_CASES],
                         ids=[c[0] for c in TO_JSON_CASES])
def test_planned_to_json_is_what_reflection_gave(value):
    want = _ref_to_json_obj(value)
    assert _same(to_json_obj(value), want)
    got, non_finite = to_json_checked(value)
    assert _same(got, want)
    # the folded verdict is the second walk's
    assert non_finite is tree_has_non_finite(want)
    try:
        wire = json.dumps(want)
    except TypeError:   # bytes, a class, a set for a key: no JSON either way
        return
    assert json.dumps(got) == wire   # key order included


def _counters():
    s = json_extractor.stats()
    return s["plans"], s["requests"]["planned"], s["requests"]["reflected"]


def test_same_name_twice_is_two_plans():
    def make(extra):
        @dataclasses.dataclass
        class Reloaded:
            name: str
            n: extra = 0
        return Reloaded

    first, second = make(int), make(float)
    assert first.__qualname__ == second.__qualname__
    plans0 = _counters()[0]
    assert extract(first, {"name": "a", "n": 1}).n == 1
    assert type(extract(second, {"name": "a", "n": 1}).n) is float
    assert plan_of(first) is not plan_of(second)
    assert plan_of(first) is plan_of(first)
    assert _counters()[0] == plans0 + 2
    with pytest.raises(ValueError, match="expected int"):
        extract(first, {"name": "a", "n": 1.5})


def test_nested_plans_are_made_with_the_first():
    @dataclasses.dataclass
    class Leaf:
        v: float

    @dataclasses.dataclass
    class Tree:
        leaves: Tuple[Leaf, ...] = ()
        named: Optional[Dict[str, Leaf]] = None
        kin: Optional[List["Tree"]] = None   # names itself

    # under `from __future__ import annotations` a hint that names a
    # local class cannot be resolved: Tree reflects, and falls back
    plans0 = _counters()[0]
    assert plan_of(Tree).reflects and plan_of(Tree).fields is None
    assert _counters()[0] == plans0 + 1
    assert to_json_obj(Tree((Leaf(1.0),))) == {"leaves": [{"v": 1.0}]}

    # module-level classes resolve: Holder names Held three ways and itself
    plans0 = _counters()[0]
    plan = plan_of(Holder)
    assert not plan.reflects and _counters()[0] == plans0 + 2
    got = extract(Holder, {"held": [{"v": 1}], "named": {"a": {"v": 2}},
                           "kin": [{"held": []}]})
    assert got == Holder((Held(1.0),), {"a": Held(2.0)}, [Holder()])
    assert to_json_obj(got) == {
        "held": [{"v": 1.0}], "named": {"a": {"v": 2.0}},
        "kin": [{"held": []}]}
    assert _counters()[0] == plans0 + 2     # Held's came with Holder's


def test_unresolvable_hints_are_reflected_and_counted():
    @dataclasses.dataclass
    class Later:
        name: str
        other: "NotYetDefined" = None   # noqa: F821

    body = b'{"name": "a"}'
    _, planned0, reflected0 = _counters()
    want = _outcome(_ref_extract, Later, {"name": "a"})
    assert want[0] == "NameError"
    for _ in range(3):
        assert _outcome(extract_query, Later, body) == want
    assert plan_of(Later).fields is None
    _, planned1, reflected1 = _counters()
    assert (planned1 - planned0, reflected1 - reflected0) == (0, 3)
    # rendering needs no hints: the field names are the plan's
    assert to_json_obj(Later("a")) == {"name": "a"}
    # a planned class beside it counts as planned
    extract_query(Q, b'{"user": "u", "num": 1}')
    assert _counters()[1] == planned1 + 1
    fam = telemetry.registry().exposition()
    assert 'pio_codec_requests_total{path="reflected"}' in fam
    assert "pio_codec_plans_total" in fam


def test_64_threads_get_one_plan():
    @dataclasses.dataclass(frozen=True)
    class Fresh:
        user: str
        num: int = 3

    plans0 = _counters()[0]
    barrier = threading.Barrier(64)
    plans, results, errors = [], [], []
    lock = threading.Lock()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def ask():
        try:
            barrier.wait(30)
            got = extract(Fresh, {"user": "u"})
            with lock:
                plans.append(plan_of(Fresh))
                results.append(got)
        except Exception as e:  # noqa: BLE001 - reported below
            with lock:
                errors.append(e)

    try:
        threads = [threading.Thread(target=ask) for _ in range(64)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    assert len(plans) == 64 and all(p is plans[0] for p in plans)
    assert results == [Fresh("u", 3)] * 64
    assert _counters()[0] == plans0 + 1
    assert [f[0] for f in plans[0].fields] == ["user", "num"]
