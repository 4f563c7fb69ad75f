"""The router's front-door response cache (workflow/router.py
`_ResponseCache`, `PIO_ROUTER_CACHE*`) + the zipfian bench sampler.

The contracts under test:

- the LRU unit: hit/miss accounting, TTL expiry and byte-budget
  evictions both counted, oversize bodies never stored;
- a hot key is answered WITHOUT touching a replica (the backend's
  request count stands still on a hit) and only 200s are stored;
- the key carries the PER-TENANT model generation (the PR 16
  `generations` dict, not the process scalar): one tenant's /reload
  invalidates exactly that tenant's entries — the other tenant keeps
  serving cached answers, and the invalidation is journaled;
- per-tenant generation SKEW across the fleet bypasses the cache
  entirely (neither lookup nor store) rather than serve either
  generation's answer for the other;
- cache off (the default) is advertisement-free: GET / has no
  `cache` key (wire parity is asserted in test_router_partition.py);
- `data/synthetic.query_keys` (the zipfian sampler, built on
  the same `_zipf_cdf` the synthetic ratings use): deterministic per
  seed, properly skewed, bounded to the pool.
"""

import http.client
import json
import time

import numpy as np

from predictionio_tpu.common import journal
from predictionio_tpu.data.api.http import serve_background
from predictionio_tpu.data.synthetic import query_keys
from predictionio_tpu.workflow.router import (
    RouterAPI, RouterConfig, _ResponseCache,
)


# ---------------------------------------------------------------------------
# the LRU unit (no fleet needed)
# ---------------------------------------------------------------------------

def test_response_cache_hit_miss_and_lru_eviction():
    cache = _ResponseCache(max_bytes=256, ttl_s=60.0)
    assert cache.get(("t", ("s", 1), b"q1")) is None          # miss
    assert cache.put(("t", ("s", 1), b"q1"), 200,
                     {"itemScores": []}, None) == 0
    hit = cache.get(("t", ("s", 1), b"q1"))
    assert hit is not None and hit[0] == 200                   # hit
    st = cache.stats()
    assert st["hits"] == 1 and st["misses"] == 1
    assert st["entries"] == 1 and 0 < st["bytes"] <= 256
    # a different generation is a different key — no false hit
    assert cache.get(("t", ("s", 2), b"q1")) is None
    # byte budget: inserting past it evicts the LEAST recently used
    evicted = 0
    for n in range(2, 30):
        evicted += cache.put(("t", ("s", 1), b"q%d" % n), 200,
                             {"itemScores": [], "n": n}, None)
    assert evicted > 0
    st = cache.stats()
    assert st["bytes"] <= 256 and st["evictions"] == evicted
    assert cache.get(("t", ("s", 1), b"q1")) is None           # aged out
    # oversize bodies are never stored (no eviction storm either)
    big = _ResponseCache(max_bytes=64, ttl_s=60.0)
    big.put(("t", ("s", 1), b"q"), 200, {"pad": "x" * 500}, None)
    assert big.stats()["entries"] == 0


def test_response_cache_ttl_expiry_counts_as_eviction():
    cache = _ResponseCache(max_bytes=1 << 20, ttl_s=0.05)
    cache.put(("t", ("s", 1), b"q"), 200, {"a": 1}, None)
    assert cache.get(("t", ("s", 1), b"q")) is not None
    time.sleep(0.08)
    assert cache.get(("t", ("s", 1), b"q")) is None
    st = cache.stats()
    assert st["entries"] == 0 and st["evictions"] == 1
    assert st["misses"] == 1 and st["hits"] == 1


def test_response_cache_invalidate_tenant_is_scoped():
    cache = _ResponseCache(max_bytes=1 << 20, ttl_s=60.0)
    cache.put(("shop", ("t", 1), b"a"), 200, {"s": 1}, None)
    cache.put(("shop", ("t", 1), b"b"), 200, {"s": 2}, None)
    cache.put(("news", ("t", 1), b"a"), 200, {"n": 1}, None)
    assert cache.invalidate_tenant("shop") == 2
    assert cache.get(("shop", ("t", 1), b"a")) is None
    assert cache.get(("news", ("t", 1), b"a")) is not None
    assert cache.stats()["evictions"] == 2


# ---------------------------------------------------------------------------
# through the router: hits skip the replica, generations scope the key
# ---------------------------------------------------------------------------

class _CountingStub:
    """A single-tenant replica double that counts /queries.json work
    and can answer non-200 on demand — the surface the cache fronts."""

    def __init__(self, generation=1):
        self.generation = generation
        self.query_count = 0

    def handle(self, method, path, query=None, body=b"", headers=None):
        path = (path or "/").rstrip("/") or "/"
        if method == "GET" and path in ("/", "/healthz", "/readyz"):
            return 200, {"status": "ready", "generation": self.generation}
        if method == "POST" and path == "/queries.json":
            self.query_count += 1
            req = json.loads(body or b"{}")
            if req.get("user") == "boom":
                return 503, {"message": "synthetic unavailability"}
            return 200, {"itemScores": [], "served": self.query_count}
        return 404, {"message": "Not Found"}


class _MTStub:
    """A multi-tenant replica double: /readyz carries the per-tenant
    ``generations`` dict, /queries.json resolves the access key and
    answers with X-PIO-Tenant — the surfaces the per-tenant cache
    keying reads."""

    KEYMAP = {"shop-key": "shop", "news-key": "news"}

    def __init__(self, generations):
        self.generations = dict(generations)
        self.query_count = 0

    def handle(self, method, path, query=None, body=b"", headers=None):
        path = (path or "/").rstrip("/") or "/"
        if method == "GET" and path in ("/", "/healthz", "/readyz"):
            return 200, {"status": "ready",
                         "generation": max(self.generations.values()),
                         "generations": dict(self.generations)}
        if method == "POST" and path == "/queries.json":
            self.query_count += 1
            tenant = self.KEYMAP.get((query or {}).get("accessKey"))
            if tenant is None:
                return 401, {"message": "Invalid accessKey."}
            return 200, {"tenant": tenant, "served": self.query_count}, \
                {"X-PIO-Tenant": tenant}
        return 404, {"message": "Not Found"}


def _cached_router(ports, **kw):
    kw.setdefault("health_ms", 60.0)
    kw.setdefault("cache", "on")
    kw.setdefault("cache_mb", 1)
    kw.setdefault("cache_ttl_ms", 60_000.0)
    router = RouterAPI(RouterConfig(
        backends=tuple(f"http://127.0.0.1:{p}" for p in ports), **kw))
    server, rport = serve_background(router)
    deadline = time.monotonic() + 10
    while (time.monotonic() < deadline
           and router.handle("GET", "/")[1]["inRotation"] != len(ports)):
        time.sleep(0.02)
    return router, server, rport


def _post(rport, body, key=None):
    conn = http.client.HTTPConnection("127.0.0.1", rport)
    try:
        path = "/queries.json" + (f"?accessKey={key}" if key else "")
        conn.request("POST", path, body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def test_cache_hit_skips_replica_and_skips_non_200():
    stub = _CountingStub()
    server, port = serve_background(stub)
    router, rserver, rport = _cached_router([port])
    try:
        body = json.dumps({"user": "u1", "num": 3}).encode()
        first = _post(rport, body)
        assert first[0] == 200
        served = stub.query_count
        # the hot key is answered at the front door: same bytes, the
        # replica's counter stands still
        for _ in range(3):
            assert _post(rport, body) == first
        assert stub.query_count == served
        # a different body is a different key
        assert _post(rport, json.dumps(
            {"user": "u2", "num": 3}).encode())[0] == 200
        assert stub.query_count == served + 1
        # non-200s pass through and are never stored
        boom = json.dumps({"user": "boom"}).encode()
        assert _post(rport, boom)[0] == 503
        assert _post(rport, boom)[0] == 503
        assert stub.query_count == served + 3
        st = router.handle("GET", "/")[1]["cache"]
        assert st["enabled"] and st["entries"] == 2
        assert st["hits"] == 3 and st["hitRatio"] > 0
    finally:
        rserver.shutdown()
        router.close()
        server.shutdown()


def test_tenant_reload_invalidates_only_that_tenant():
    """THE satellite contract: two tenants cached; bumping ONE
    tenant's generation (its /reload) drops exactly its entries —
    the other tenant's next query is still a front-door hit — and
    the invalidation rides the router journal."""
    journal.clear()
    stub = _MTStub({"shop": 1, "news": 1})
    server, port = serve_background(stub)
    router, rserver, rport = _cached_router([port])
    try:
        body = json.dumps({"user": "u1", "num": 3}).encode()
        # prime both tenants twice: learn the label, then store
        for key in ("shop-key", "news-key"):
            assert _post(rport, body, key)[0] == 200
            assert _post(rport, body, key)[0] == 200
        shop_answer = _post(rport, body, "shop-key")
        news_answer = _post(rport, body, "news-key")
        served = stub.query_count
        # both hot now: replica untouched
        assert _post(rport, body, "shop-key") == shop_answer
        assert _post(rport, body, "news-key") == news_answer
        assert stub.query_count == served

        # news reloads: generation 1 -> 2 on the backend
        stub.generations["news"] = 2
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if router.handle("GET", "/")[1]["cache"]["evictions"] >= 1:
                break
            time.sleep(0.03)
        # shop still answers from cache...
        assert _post(rport, body, "shop-key") == shop_answer
        assert stub.query_count == served
        # ...news goes back to the replica (fresh served counter)
        status, payload = _post(rport, body, "news-key")
        assert status == 200 and payload != news_answer[1]
        assert stub.query_count == served + 1
        ev = journal.snapshot(category="router")
        assert any("response cache invalidated for tenant 'news'"
                   in e["message"] for e in ev["events"]), \
            [e["message"] for e in ev["events"]]
        assert not any("tenant 'shop'" in e["message"]
                       for e in ev["events"])
    finally:
        rserver.shutdown()
        router.close()
        server.shutdown()


def test_generation_skew_bypasses_cache():
    """Two backends disagreeing on a tenant's generation (mid-barrier
    skew): that tenant's queries bypass the cache entirely — every
    request reaches a replica, nothing is stored — while an agreed
    tenant keeps caching."""
    stub0 = _MTStub({"shop": 1, "news": 7})
    stub1 = _MTStub({"shop": 2, "news": 7})   # shop: split vote
    server0, port0 = serve_background(stub0)
    server1, port1 = serve_background(stub1)
    router, rserver, rport = _cached_router([port0, port1])
    try:
        body = json.dumps({"user": "u1", "num": 3}).encode()
        for _ in range(4):
            assert _post(rport, body, "shop-key")[0] == 200
        shop_hits = stub0.query_count + stub1.query_count
        assert shop_hits == 4          # every one touched a replica
        # news agrees across the fleet: second query is a hit
        assert _post(rport, body, "news-key")[0] == 200
        assert _post(rport, body, "news-key")[0] == 200
        assert _post(rport, body, "news-key")[0] == 200
        assert stub0.query_count + stub1.query_count <= shop_hits + 2
        st = router.handle("GET", "/")[1]["cache"]
        # only news entries made it in
        assert st["entries"] == 1, st
    finally:
        rserver.shutdown()
        router.close()
        server0.shutdown()
        server1.shutdown()


def test_cache_off_is_advertisement_free():
    stub = _CountingStub()
    server, port = serve_background(stub)
    router = RouterAPI(RouterConfig(
        backends=(f"http://127.0.0.1:{port}",), health_ms=60.0))
    rserver, rport = serve_background(router)
    try:
        body = json.dumps({"user": "u1", "num": 3}).encode()
        assert _post(rport, body)[0] == 200
        assert _post(rport, body)[0] == 200
        assert stub.query_count == 2   # no front-door answering
        assert "cache" not in router.handle("GET", "/")[1]
    finally:
        rserver.shutdown()
        router.close()
        server.shutdown()


# ---------------------------------------------------------------------------
# the bench's zipfian key sampler
# ---------------------------------------------------------------------------

def test_query_keys_deterministic_and_skewed():
    a = query_keys(5000, seed=7, exponent=1.1, pool=64)
    b = query_keys(5000, seed=7, exponent=1.1, pool=64)
    assert np.array_equal(a, b)                      # seeded => replay
    assert a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 64
    assert not np.array_equal(a, query_keys(5000, seed=8,
                                            exponent=1.1, pool=64))
    # zipf skew: the hottest key draws far more than the uniform share
    counts = np.bincount(a, minlength=64)
    assert counts.max() > 4 * (5000 / 64)
    # a steeper exponent concentrates harder
    steep = np.bincount(query_keys(5000, seed=7, exponent=2.0, pool=64),
                        minlength=64)
    assert steep.max() > counts.max()
    assert query_keys(0, seed=1).size == 0
