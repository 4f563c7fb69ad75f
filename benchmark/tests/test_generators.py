"""The generators: same seed -> same bytes; the seed never changes a
count that a compiled shape depends on; the load generator's schedule
and lateness arithmetic."""

import json
import os

import numpy as np
import pytest

import gen_factors
import gen_ratings
import harness
import loadgen

rec_als = harness.load_adapter("rec_als")

HERE = os.path.dirname(os.path.abspath(__file__))
SHAPE = gen_ratings.scaled_shape(json.load(open(os.path.join(
    HERE, "..", "configs", "rec-als-ml20m-r10.json")))["data"], 30)


@pytest.fixture(scope="module")
def structure():
    return gen_ratings.build_structure(SHAPE)


def test_degrees_sum_exactly_and_keep_floor_and_cap():
    d = gen_ratings.zipf_quantile_degrees(1000, 50_003, 20, 400, 0.75)
    assert d.sum() == 50_003 and d.min() >= 20 and d.max() <= 400
    assert (np.diff(d[1:]) <= 1).all()          # descending but for +1s


def test_structure_is_simple_complete_and_fixed(structure):
    su, si = structure
    assert su.size == SHAPE["nnz"]
    assert np.unique(su.astype(np.int64) * SHAPE["n_items"] + si).size \
        == su.size, "a (user, item) pair twice"
    assert np.bincount(su, minlength=SHAPE["n_users"]).min() >= 1
    assert np.bincount(si, minlength=SHAPE["n_items"]).min() >= 1
    again = gen_ratings.build_structure(SHAPE)
    assert (again[0] == su).all() and (again[1] == si).all()


def test_same_seed_same_bytes_and_seeds_share_every_count(structure):
    a = gen_ratings.make_ratings(structure, SHAPE, 2**31 + 5, 1)
    b = gen_ratings.make_ratings(structure, SHAPE, 2**31 + 5, 1)
    assert all(x.tobytes() == y.tobytes() for x, y in zip(a, b))
    seen = set()
    for seed, job in ((1, 0), (2, 0), (2**31 + 7, 3)):
        u, i, r = gen_ratings.make_ratings(structure, SHAPE, seed, job)
        assert u.dtype == np.int32 and r.dtype == np.float32
        assert ((r * 2) == np.rint(r * 2)).all() and r.min() >= 0.5 \
            and r.max() <= 5.0
        du = np.sort(np.bincount(u, minlength=SHAPE["n_users"]))
        di = np.sort(np.bincount(i, minlength=SHAPE["n_items"]))
        seen.add((u.size, du.tobytes(), di.tobytes()))
        assert not (u == a[0]).all() or (seed, job) == (2**31 + 5, 1)
    assert len(seen) == 1, "a count changed with the seed"
    c = gen_ratings.make_ratings(structure, SHAPE, 1, 1)
    d = gen_ratings.make_ratings(structure, SHAPE, 1, 2)
    assert not (c[0] == d[0]).all(), "two jobs got the same ratings"


def test_factor_blocks_are_reproducible_alone():
    n, r = gen_factors.BLOCK + 1000, 8
    whole = gen_factors.matrix(11, "item", n, r, 0.5, threads=3)
    assert whole.tobytes() == gen_factors.matrix(11, "item", n, r, 0.5).tobytes()
    ixs = np.array([5, gen_factors.BLOCK + 7, 0, gen_factors.BLOCK - 1])
    assert (gen_factors.rows(11, "item", ixs, n, r, 0.5) == whole[ixs]).all()
    assert not (gen_factors.matrix(11, "user", n, r, 0.5) == whole).all()
    scales = gen_factors.column_scales(r, 0.5)
    assert np.isclose((scales ** 2).sum(), 1.0) and (np.diff(scales) < 0).all()


def test_query_users_are_seeded_skewed_and_in_range():
    a = rec_als.query_users(2**31 + 1, 20_000, 1000, 1.1)
    assert (a == rec_als.query_users(2**31 + 1, 20_000, 1000, 1.1)).all()
    assert a.min() >= 0 and a.max() < 1000
    counts = np.sort(np.bincount(a, minlength=1000))[::-1]
    assert counts[0] > 20 * np.median(counts)        # a head
    assert (rec_als.query_users(3, 2000, 1000, 1.1) != a[:2000]).any()


def test_poisson_schedule_and_lateness():
    tr = {"rate_qps": 200.0}
    due = loadgen.arrival_times(9, tr, 10.0)
    assert (np.diff(due) > 0).all() and due[-1] < 10.0
    assert abs(len(due) - 2000) < 5 * np.sqrt(2000)
    assert (due == loadgen.arrival_times(9, tr, 10.0)).all()
    recs = [(1, 10.0, 10.004, 10.1, []), (2, 11.0, 10.9, 11.2, [])]
    assert loadgen.lateness_ms(recs) == [pytest.approx(4.0), 0.0]


def test_the_wire_is_the_adapters_and_tier1s_old_names_still_reach_it():
    wire = rec_als.Wire(10)
    assert wire.body(np.int64(7)) == '{"user": "u7", "num": 10}'
    reply = wire.parse(200, b'{"itemScores": [{"item": "i3", "score": 1}]}')
    assert reply == [("i3", 1.0)] and not wire.whole(7, reply)
    assert wire.whole(7, reply * 10) and not wire.whole(7, None)
    assert wire.parse(503, b"") is None and wire.parse(200, b"{}") is None
    # tests/test_benchmark_contract.py calls these (loadgen.py says why)
    assert loadgen._body(7, 10) == wire.body(7)
    assert loadgen.parse_reply(200, b'{"itemScores": []}') == []
