"""Compile rehearsal for the chip, kept as tests.

The TPU's compiler is installed in the sandbox and compiles for a chip
that is described, not attached. These tests hand it the main path's
programs at the widths the repo exists for — the ML-20M recommendation
model: 138,493 users x 26,744 items, rank 10, k 10 — so what the chip's
compiler refuses fails here, at no chip time. Nothing runs: a pass says
"compiles", never "correct" or "fast".

Rules of this file (guide: on-chip-measurement, section 2): the
topology is described inside a module-scoped fixture, which skips where
it cannot be; shardings, meshes and shapes are built in fixtures or
tests, never at import; nothing here is autouse or lives in conftest;
the persistent compile cache is off around every test (an entry written
for a described chip cannot be read back without one). All cases stay
in this ONE file: the process that describes the topology holds the
TPU library until it exits.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

N_USERS, N_ITEMS, RANK, K, TILE = 138_493, 26_744, 10, 10, 512
NNZ = 20_000_000
BUCKETS = (1, 64)


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from predictionio_tpu.parallel import serve_dist
    return Mesh(np.asarray(topo.devices[:4]), (serve_dist.AXIS,))


@pytest.fixture()
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _s(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fp32_factors(sh):
    return (_s((N_USERS, RANK), jnp.float32, sh),
            _s((N_ITEMS, RANK), jnp.float32, sh))


def _int8_layout(sh):
    """QuantizedServing's device layout: item matrix transposed and
    padded to ``quant.ITEM_TILE``."""
    n_pad = -(-N_ITEMS // TILE) * TILE
    return (_s((N_USERS, RANK), jnp.int8, sh),
            _s((N_USERS,), jnp.float32, sh),
            _s((RANK, n_pad), jnp.int8, sh),
            _s((n_pad,), jnp.float32, sh))


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# one-chip serving: what `pio deploy` dispatches on a TPU backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bucket", BUCKETS)
def test_topk_for_users_quant_compiles(one_chip, no_compile_cache, bucket):
    from predictionio_tpu.ops import quant
    compiled = quant.topk_for_users_quant.lower(
        *_int8_layout(one_chip), _s((bucket,), jnp.int32, one_chip),
        k=K, n_items=N_ITEMS).compile()
    assert not _has_kernel(compiled)        # plain XLA, no Pallas


def test_topk_for_user_quant_compiles(one_chip, no_compile_cache):
    from predictionio_tpu.ops import quant
    quant.topk_for_user_quant.lower(
        *_int8_layout(one_chip), _s((), jnp.int32, one_chip),
        k=K, n_items=N_ITEMS).compile()


@pytest.mark.parametrize("bucket", BUCKETS)
def test_topk_for_users_compiles(one_chip, no_compile_cache, bucket):
    from predictionio_tpu.ops import topk
    topk.topk_for_users.lower(
        *_fp32_factors(one_chip), _s((bucket,), jnp.int32, one_chip),
        k=K).compile()


def test_topk_for_user_compiles(one_chip, no_compile_cache):
    from predictionio_tpu.ops import topk
    topk.topk_for_user.lower(
        *_fp32_factors(one_chip), _s((), jnp.int32, one_chip),
        k=K).compile()


@pytest.mark.parametrize("bucket", BUCKETS)
def test_topk_for_users_compiles_at_the_benchmark_cells_shape(
        one_chip, no_compile_cache, bucket):
    """The serving cells' program (BENCHMARK.json, rec-als-amazon-r128:
    6,643,669 x 128 users, 2,441,053 x 128 items) at its real size. What
    the chip's compiler must not be given back by the two-stage
    selection: a sort as long as the catalog, or a second copy of the
    score matrix (a view of it as rows x chunks x L is one: a 625 MB
    relayout) — the temporaries are the scores and small change."""
    from predictionio_tpu.ops import topk
    n_users, n_items, rank = 6_643_669, 2_441_053, 128
    compiled = topk.topk_for_users.lower(
        _s((n_users, rank), jnp.float32, one_chip),
        _s((n_items, rank), jnp.float32, one_chip),
        _s((bucket,), jnp.int32, one_chip), k=K).compile()
    assert not [line for line in compiled.as_text().splitlines()
                if " sort(" in line and f",{n_items}]" in line]
    scores_bytes = 4 * max(bucket, 8) * n_items     # 8 sublanes a tile
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * scores_bytes


@pytest.mark.parametrize("bucket", BUCKETS)
def test_masked_topk_rows_compiles_at_the_benchmark_cells_shape(
        one_chip, no_compile_cache, bucket):
    """The e-commerce cell's program (BENCHMARK.json,
    ecomm-als-amazon-r128: the same factors, one uint32 rule word and
    one eligibility flag an item, 28 categories) at its real size and
    its largest declared exclusion width. As its unmasked twin: no sort
    as long as the catalog. And what the rules must not bring: a second
    copy of the scores. The temporaries are the score matrix (written
    once, the rules applied in the matmul's own output fusion; the
    exclusions overwritten in place, one element a step of a `while`)
    and small change: under 1.1 of it, where one XLA scatter of the
    same indices holds 2.0 (1,250,232,832 B at bucket 64: a relayout to
    a flat copy and back round a sort of all 64 x 4,352 indices)."""
    from predictionio_tpu.ops import topk
    n_users, n_items, rank, words = 6_643_669, 2_441_053, 128, 1
    width = topk.EXCLUDE_WIDTHS[-1]
    compiled = topk.masked_topk_rows.lower(
        _s((n_users, rank), jnp.float32, one_chip),
        _s((n_items, rank), jnp.float32, one_chip),
        _s((words, n_items), jnp.uint32, one_chip),
        _s((n_items,), jnp.bool_, one_chip),
        _s((bucket,), jnp.int32, one_chip),
        _s((bucket, words), jnp.uint32, one_chip),
        _s((bucket, width), jnp.int32, one_chip), k=K).compile()
    text = compiled.as_text()
    assert not [line for line in text.splitlines()
                if " sort(" in line and f",{n_items}]" in line]
    # no scatter op, and no flat copy of the score matrix for one
    assert " scatter(" not in text
    if bucket > 1:      # bucket 1's scores are a flat row as they are
        assert f"f32[{bucket * n_items}]" not in text
    scores_bytes = 4 * max(bucket, 8) * n_items     # 8 sublanes a tile
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.1 * scores_bytes
    # resident: factors + rule words + eligibility, and a flush's
    # arguments: under 0.3 % over the factors
    factors = 4 * (n_users + n_items) * rank
    assert factors < mem.argument_size_in_bytes < 1.003 * factors


@pytest.mark.parametrize("bucket", BUCKETS)
def test_itemset_topk_rows_compiles_at_the_benchmark_cells_shape(
        one_chip, no_compile_cache, bucket):
    """The similar-product cell's program (BENCHMARK.json,
    simprod-als-amazon14-r128: 9,350,000 unit rows of rank 128 on ONE
    chip, one uint32 rule word and one eligibility flag an item) at its
    real size: a 2.39 GB score matrix at bucket 64, four times the
    largest any other one-chip program makes. It compiles and fits; no
    sort is as long as the catalog (`pick` sorts 18,261 chunk maxima a
    row); no scatter and no flat copy of the scores for the exclusions.
    The temporaries: from 5 M items up the TPU compiler holds (8, n)
    row groups beside the score matrix, two of them under the rules
    (1.25 of the scores, where 2,441,053 items hold 1.004): under 1.3,
    so a second copy of the scores would fail this. With the resident
    arrays: under half of the chip's 16 GB."""
    from predictionio_tpu.ops import topk
    n_items, rank, words = 9_350_000, 128, 1
    compiled = topk.itemset_topk_rows.lower(
        _s((n_items, rank), jnp.float32, one_chip),
        _s((words, n_items), jnp.uint32, one_chip),
        _s((n_items,), jnp.bool_, one_chip),
        _s((bucket, topk.QUERY_WIDTH), jnp.int32, one_chip),
        _s((bucket, words), jnp.uint32, one_chip),
        _s((bucket, topk.EXCLUDE_WIDTHS[0]), jnp.int32, one_chip),
        k=K).compile()
    text = compiled.as_text()
    assert not [line for line in text.splitlines()
                if " sort(" in line and f",{n_items}]" in line]
    assert topk.chunk_plan(n_items, K) == (512, 18_261)
    assert " scatter(" not in text
    if bucket > 1:      # bucket 1's scores are a flat row as they are
        assert f"f32[{bucket * n_items}]" not in text
    scores_bytes = 4 * max(bucket, 8) * n_items     # 8 sublanes a tile
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.3 * scores_bytes
    factors = 4 * n_items * rank
    assert factors < mem.argument_size_in_bytes < 1.011 * factors
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 8e9


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------

def test_als_scan_trainer_compiles(topo, no_compile_cache):
    """The scan reference trainer is declarable from shapes alone."""
    from predictionio_tpu.ops import als
    with jax.default_device(topo.devices[0]):
        compiled = als.lower_train_explicit(
            N_USERS, N_ITEMS, RANK, NNZ).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16 * 2 ** 30


def test_als_hybrid_trainer_compiles(one_chip, no_compile_cache):
    """The default hybrid kernel's statics come from data (hot-item
    split, cold-tail plan), so the layout is built on the host — at a
    reduced nnz and user count, with the real rank, item count and
    hot-set width — and the program compiled from its shapes."""
    from predictionio_tpu.data import synthetic
    from predictionio_tpu.ops import als

    n_users = N_USERS // 16
    src = synthetic.chunk_source(1_000_000, seed=7, n_users=n_users,
                                 n_items=N_ITEMS)
    u, i, r = (np.concatenate(c) for c in zip(
        *(src.chunk_codes(c) for c in range(src.n_chunks))))
    data = als.prepare_ratings(u, i, r, n_users=n_users, n_items=N_ITEMS)
    chunk = 1 << 18
    hy = als._hybrid_prepare(data, als._HOT_K, False, 0.0, als._CSRB_B,
                             chunk)

    def shape_of(x):
        return _s(x.shape, x.dtype, one_chip)

    compiled = als._train_hybrid_jit.lower(
        *jax.tree.map(shape_of, (hy.D, hy.hot_ids, *hy.u_tail, *hy.i_tail,
                                 data.by_user.counts, data.by_item.counts)),
        _s((n_users, RANK), jnp.float32, one_chip),
        _s((N_ITEMS, RANK), jnp.float32, one_chip),
        iterations=1, lambda_=0.01, alpha=0.0,
        n_users=n_users, n_items=N_ITEMS, K=hy.K, b=als._CSRB_B,
        u_chunk=hy.u_chunk, i_chunk=hy.i_chunk, reg_scaling="count",
        implicit=False, tuning=als._tuning_key()).compile()
    assert compiled.memory_analysis() is not None


def test_solve_factors_pallas_compiles(one_chip, no_compile_cache):
    from predictionio_tpu.ops import solve_pallas
    compiled = jax.jit(solve_pallas.solve_factors_pallas).lower(
        _s((N_USERS, RANK, RANK), jnp.float32, one_chip),
        _s((N_USERS, RANK), jnp.float32, one_chip),
        _s((N_USERS,), jnp.float32, one_chip)).compile()
    assert _has_kernel(compiled)


# ---------------------------------------------------------------------------
# four chips: row-sharded serving, one program across the mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_sharded_serve_compiles_on_four_chips(mesh4, no_compile_cache,
                                              dtype):
    """fp32 is the kernel the issue names; int8 is what four real chips
    serve by default (auto quantizes AND shards there)."""
    from predictionio_tpu.parallel import serve_dist
    n_dev, bucket = mesh4.devices.size, 64
    rows_u = serve_dist._rows_dev(N_USERS, n_dev)
    rows_i = serve_dist._rows_dev(N_ITEMS, n_dev)
    rows = NamedSharding(mesh4, P(serve_dist.AXIS, None))
    vec = NamedSharding(mesh4, P(serve_dist.AXIS))
    ixs = _s((bucket,), jnp.int32, NamedSharding(mesh4, P()))
    statics = dict(k=K, n_items=N_ITEMS, rows_dev_u=rows_u,
                   rows_dev_i=rows_i, mesh=mesh4)
    if dtype == "int8":
        compiled = serve_dist.topk_for_users_sharded_quant.lower(
            _s((n_dev * rows_u, RANK), jnp.int8, rows),
            _s((n_dev * rows_u,), jnp.float32, vec),
            _s((n_dev * rows_i, RANK), jnp.int8, rows),
            _s((n_dev * rows_i,), jnp.float32, vec),
            ixs, **statics).compile()
    else:
        compiled = serve_dist.topk_for_users_sharded.lower(
            _s((n_dev * rows_u, RANK), jnp.float32, rows),
            _s((n_dev * rows_i, RANK), jnp.float32, rows),
            ixs, **statics).compile()
    # one program across the mesh: the psum and the candidate merge are
    # cross-device collectives (the compiler may turn the small
    # all-gather into an all-reduce), and the factors stay row-sharded
    text = compiled.as_text()
    assert "all-reduce" in text or "all-gather" in text
    assert compiled.input_shardings[0][0].is_equivalent_to(rows, 2)


@pytest.mark.parametrize("bucket", BUCKETS)
def test_sharded_serve_compiles_at_the_benchmark_cells_shape(
        mesh4, no_compile_cache, bucket):
    """The four-chip cell's program (BENCHMARK.json,
    rec-als-amazon14-r128: 20,980,320 x 128 users and 9,350,000 x 128
    items, 15.5 GB of float32 no one chip can hold) at its real size on
    the described 2x2 host: the compiler accepts it, what a device
    holds of it — its blocks of both matrices, the temporaries of a
    flush, the results — fits the chip's 16 GB, and the factors stay
    row-sharded (no device is handed a whole matrix). And what its
    one-chip twin holds, a shard for the catalog: no sort as long as
    the shard, no global index written out beside the scores (an
    s32[b, rows_dev_i]: the whole-shard sort carried one), temporaries
    of the scores and small change (that sort's were three times it);
    and the compiler takes the chunk-fetch kernel at both buckets."""
    from predictionio_tpu.parallel import serve_dist
    n_users, n_items, rank = 20_980_320, 9_350_000, 128
    n_dev = mesh4.devices.size
    rows_u = serve_dist._rows_dev(n_users, n_dev)
    rows_i = serve_dist._rows_dev(n_items, n_dev)
    assert (rows_u * n_dev, rows_i * n_dev) == (n_users, n_items)
    rows = NamedSharding(mesh4, P(serve_dist.AXIS, None))
    compiled = serve_dist.topk_for_users_sharded.lower(
        _s((n_users, rank), jnp.float32, rows),
        _s((n_items, rank), jnp.float32, rows),
        _s((bucket,), jnp.int32, NamedSharding(mesh4, P())),
        k=K, n_items=n_items, rows_dev_u=rows_u, rows_dev_i=rows_i,
        mesh=mesh4).compile()
    mem = compiled.memory_analysis()
    # per device: a quarter of the factors, never the whole
    assert mem.argument_size_in_bytes < 1.01 * (n_users + n_items) * rank
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16e9
    text = compiled.as_text()
    assert "all-reduce" in text or "all-gather" in text
    for sharding in compiled.input_shardings[0][:2]:
        assert sharding.is_equivalent_to(rows, 2)
    assert not [line for line in text.splitlines()
                if (" sort(" in line and f",{rows_i}]" in line)
                or f"s32[{bucket},{rows_i}]" in line]
    scores_bytes = 4 * max(bucket, 8) * rows_i      # 8 sublanes a tile
    assert mem.temp_size_in_bytes < 1.1 * scores_bytes
    # the picked chunks come through serve_dist._fetch_chunks, one op on
    # the device's line, not through a gather's `while` of b*k slices
    assert _has_kernel(compiled) and " while(" not in text
