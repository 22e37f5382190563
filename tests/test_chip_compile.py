"""Ask the chip's compiler before the chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (`v5e:2x2`). These tests compile — from
`ShapeDtypeStruct`s placed on one described device — the jitted
implementations the serving path dispatches, at published widths and on
the dispatcher's own bucket ladder. Nothing runs: a compile that passes
is not a chip run, and says nothing about results or times. What it does
catch is everything interpret mode cannot: block shapes Mosaic refuses,
kernels that need more VMEM than their limit, ops with no TPU lowering.

This is the ONLY file that describes a topology, and it does so inside a
module-scoped fixture (never at import, in a `skipif`, in `parametrize`
arguments or in `conftest.py`): only one process may load the TPU's
library, and every xdist worker imports every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from elasticsearch_tpu.ops import aggs as agg_ops
from elasticsearch_tpu.ops import knn as knn_ops
from elasticsearch_tpu.ops import pallas_ivf_fused as ivf_fused
from elasticsearch_tpu.ops import pallas_knn_binned as binned
from elasticsearch_tpu.ops import pallas_maxsim as maxsim
from elasticsearch_tpu.ops import similarity as sim
from elasticsearch_tpu.ops.knn_ivf import IVFPartitions
from elasticsearch_tpu.parallel import layout
from elasticsearch_tpu.parallel import mesh as mesh_lib
from elasticsearch_tpu.parallel import sharded_knn

N_ROWS = 1 << 20            # BASELINE config 1's corpus: 1,048,576 rows
# the largest bucket the store sends the binned kernel: the per-(field, k)
# CombiningBatcher's batch ceiling (serving/batcher.py max_batch=256)
STORE_MAX_BUCKET = 256


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip; keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _corpus_spec(sh, n, d, dtype, residual=False):
    return knn_ops.Corpus(
        matrix=_sds(sh, (n, d), dtype),
        sq_norms=_sds(sh, (n,), jnp.float32),
        scales=_sds(sh, (n,), jnp.float32),
        num_valid=_sds(sh, (), jnp.int32),
        residual=_sds(sh, (n, d), jnp.int8) if residual else None,
        residual_scales=_sds(sh, (n,), jnp.float32) if residual else None)


def _compile(fn, static_argnames, *args, **statics):
    return jax.jit(fn, static_argnames=static_argnames).lower(
        *args, **statics).compile()


def _has_mosaic_call(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# knn.binned — the Pallas kernel unfiltered cosine/dot kNN rides
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nq", [1, 64, STORE_MAX_BUCKET])
@pytest.mark.parametrize("d", [128, 768])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_binned_compiles_at_published_widths(one_chip, dtype, d, nq):
    assert binned.kernel_holds(d, dtype)
    compiled = _compile(
        binned._binned_impl, ("k", "metric", "interpret"),
        _sds(one_chip, (nq, d), jnp.float32),
        _corpus_spec(one_chip, N_ROWS, d, dtype),
        k=10, metric=sim.COSINE, interpret=False)
    assert _has_mosaic_call(compiled)


def test_binned_rescored_packed_int8_residual_compiles(one_chip):
    compiled = _compile(
        binned._rescored_packed_impl,
        ("k", "metric", "rescore_candidates", "interpret"),
        _sds(one_chip, (64, 768), jnp.float32),
        _corpus_spec(one_chip, N_ROWS, 768, jnp.int8, residual=True),
        k=10, metric=sim.COSINE, rescore_candidates=128, interpret=False)
    assert _has_mosaic_call(compiled)


def test_binned_query_axis_tiles_past_the_store_ceiling(one_chip):
    """Buckets above QUERY_TILE tile the query axis instead of growing
    the [nq, BLOCK_N] temporaries: the dispatcher's top rung compiles
    under the same VMEM limit as the 256 bucket."""
    nq = 2048
    assert nq > binned.QUERY_TILE
    assert (binned.vmem_bytes(nq, 768, 2)
            == binned.vmem_bytes(binned.QUERY_TILE, 768, 2))
    _compile(binned._binned_impl, ("k", "metric", "interpret"),
             _sds(one_chip, (nq, 768), jnp.float32),
             _corpus_spec(one_chip, N_ROWS, 768, jnp.bfloat16),
             k=10, metric=sim.COSINE, interpret=False)


@pytest.mark.parametrize("dtype,itemsize", [(jnp.bfloat16, 2),
                                            (jnp.int8, 1)],
                         ids=["bf16", "int8"])
def test_widest_row_the_rule_admits_compiles(one_chip, dtype, itemsize):
    """`kernel_holds` is an estimate; the compiler is the judge. The
    widest lane-multiple row width the rule admits (up to the mapping's
    4096-dim ceiling) must compile under `vmem_bytes`' limit, and the
    next one up must be refused by the RULE — so the store never sends
    a shape only the compiler would catch."""
    widths = [d for d in range(128, 4096 + 1, 128)
              if binned.kernel_holds(d, dtype)]
    widest = widths[-1]
    assert binned.vmem_bytes(STORE_MAX_BUCKET, widest,
                             itemsize) <= binned.VMEM_LIMIT_CAP
    if widest < 4096:
        assert not binned.kernel_holds(widest + 128, dtype)
        assert not knn_ops.binned_route(N_ROWS, widest + 128, dtype,
                                        sim.COSINE)
    _compile(binned._binned_impl, ("k", "metric", "interpret"),
             _sds(one_chip, (STORE_MAX_BUCKET, widest), jnp.float32),
             _corpus_spec(one_chip, 1 << 17, widest, dtype),
             k=10, metric=sim.COSINE, interpret=False)


# ---------------------------------------------------------------------------
# knn.exact — the filtered / l2 route
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,metric", [(128, sim.COSINE), (960, sim.L2_NORM)])
def test_exact_filtered_route_compiles(one_chip, d, metric):
    nq = 64
    n = N_ROWS if d == 128 else 1 << 18     # GIST-1M-shaped: 262,144 x 960
    _compile(knn_ops._knn_search_impl,
             ("k", "metric", "precision", "block_size"),
             _sds(one_chip, (nq, d), jnp.float32),
             _corpus_spec(one_chip, n, d, jnp.bfloat16),
             _sds(one_chip, (nq, n), jnp.bool_),
             k=10, metric=metric, precision="bf16", block_size=None)


@pytest.mark.parametrize("nq", [1, 8, 64])
def test_filtered_l2_board_of_the_tags_deployment_compiles(one_chip, nq):
    """`yfcc-192-uint8-tags` as `filtered-steady` launches it: l2 over
    327,680 x 192-d bf16 rows (the configuration's `rows`) with a [Q, N]
    mask, the packed board, k on the store's ladder, at the cell's
    smallest, usual and largest batch rung. The whole [64, N] float32
    board and the mask fit the chip beside the 126 MB corpus."""
    from elasticsearch_tpu.ops import dispatch
    n = 327_680
    compiled = _compile(
        knn_ops._knn_search_impl,
        ("k", "metric", "precision", "block_size", "board"),
        _sds(one_chip, (nq, 192), jnp.float32),
        _corpus_spec(one_chip, n, 192, jnp.bfloat16),
        _sds(one_chip, (nq, n), jnp.bool_),
        k=dispatch.bucket_k(10, limit=n), metric=sim.L2_NORM,
        precision="bf16", block_size=None, board=True)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 2 << 30


# ---------------------------------------------------------------------------
# scalar-prefetch kernels: fused IVF probe, MaxSim rescore
# ---------------------------------------------------------------------------

def _ivf_spec(sh, nlist, cap, d, dtype, w):
    return IVFPartitions(
        centroids=_sds(sh, (nlist, d), jnp.float32),
        centroid_sq=_sds(sh, (nlist,), jnp.float32),
        parts=_sds(sh, (nlist, cap, w), dtype),
        part_scales=_sds(sh, (nlist, cap), jnp.float32),
        part_sq=_sds(sh, (nlist, cap), jnp.float32),
        part_rows=_sds(sh, (nlist, cap), jnp.int32))


@pytest.mark.parametrize("dtype,w", [(jnp.bfloat16, 128), (jnp.uint8, 64)],
                         ids=["dense", "int4"])
def test_fused_ivf_probe_compiles(one_chip, dtype, w):
    """One warmed grid point of a 1M-row ivf index: pick_nlist -> 1024
    lists, cap = ceil(1024 * 1.5) = 1536, query bucket 8, nprobe 16."""
    nq, nprobe, nlist, cap, d = 8, 16, 1024, 1536, 128
    compiled = _compile(
        ivf_fused._fused_probe_impl, ("k", "metric", "interpret"),
        _sds(one_chip, (nq, d), jnp.float32),
        _ivf_spec(one_chip, nlist, cap, d, dtype, w),
        _sds(one_chip, (nq, nprobe), jnp.int32),
        k=10, metric=sim.COSINE, interpret=False)
    assert _has_mosaic_call(compiled)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.uint8],
                         ids=["dense", "int4"])
def test_maxsim_rescore_compiles(one_chip, dtype):
    """One warmed grid point of a rank_vectors field: query bucket 8,
    window 64, 32 query tokens, 65,536 docs x 128-token cap x 128 lanes
    (64 packed bytes for int4)."""
    nq, wc, tq, n_pad, cap = 8, 64, 32, 1 << 16, 128
    wd = 64 if dtype == jnp.uint8 else 128
    q = _sds(one_chip, (nq, tq, wd), jnp.float32)
    args = ((None, q, q) if dtype == jnp.uint8 else (q, None, None))
    compiled = _compile(
        maxsim._maxsim_impl, ("interpret",),
        _sds(one_chip, (nq, wc), jnp.int32), *args,
        _sds(one_chip, (n_pad, cap, wd), dtype),
        _sds(one_chip, (n_pad, 1, cap), jnp.float32),
        interpret=False)
    assert _has_mosaic_call(compiled)


# ---------------------------------------------------------------------------
# an x64 agg kernel, and the 4-shard mesh program
# ---------------------------------------------------------------------------

def test_x64_agg_kernel_compiles(one_chip):
    """`aggs.tree_metric` over one histogram level (date_histogram +
    stats sub-agg; `aggs.hist_metric` until ISSUE 36 folded the one-level
    x64 kernels into the tree's) over 131,072 rows, traced under the
    dispatcher's scoped x64 flag: f64 keys and sums, int64 counts —
    types the chip emulates."""
    r, b = 1 << 17, 64
    with jax.enable_x64(True):
        f64 = lambda *shape: _sds(one_chip, shape, jnp.float64)  # noqa: E731
        flag = lambda: _sds(one_chip, (r,), jnp.bool_)           # noqa: E731
        compiled = _compile(
            agg_ops._agg_tree_metric, ("levels", "n_buckets"),
            flag(), f64(2), f64(r), flag(), f64(r), flag(), f64(6),
            levels=("hist",), n_buckets=(b,))
    cnt, total = compiled.out_info[:2]
    assert cnt.dtype == jnp.int64 and cnt.shape == (b + 1,)
    assert total.dtype == jnp.float64


X64, N32 = "x64", "n32"


def _dash_panel_programs(sh):
    """The programs the four panels of `dash-aggs-steady` dispatch over
    `http-logs-dash`'s row bucket of 2^20, by name: (arithmetic, kernel,
    static names, argument specs, statics, dtype and shape of the first
    board). The rungs are the ones the engine forms there: a histogram's
    from the COLUMN's span (1,176 hours -> 2,048, whatever the request's
    range), `status`'s eight values -> 8 (16 where a ninth appears).

    Since ISSUE 36 the cell's columns (whole seconds, integers) run the
    32-bit programs (`n32.*`: int32 boards of (k + 1) lanes a level; a
    metric's is ONE array, the count's row and the sum's limbs: `size`
    up to 2^24 in three of 8 bits). The x64 programs they replaced stay
    what a column of fractions or of a span past 2^31 units runs: one
    level or two of `aggs.tree_counts` / `aggs.tree_metric`."""
    r = 1 << 20
    f64 = lambda *shape: _sds(sh, shape, jnp.float64)  # noqa: E731
    i32 = lambda *shape: _sds(sh, shape, jnp.int32)    # noqa: E731
    flag = _sds(sh, (r,), jnp.bool_)
    ords = i32(r)
    keys, hp, mp, op = f64(r), f64(6), f64(2), f64(1)
    tree = ("levels", "n_buckets")
    counts32 = ("levels", "n_buckets", "form")
    metric32 = counts32 + ("parts", "limb_bits", "n_limbs")
    bits = agg_ops.limb_bits(r)
    limbs = agg_ops.n_limbs_for(1 << 24, bits)
    assert (bits, limbs) == (8, 4)

    def hist(k):
        return {"levels": ("hist",), "n_buckets": (k,)}

    def bounds(k, cols=1):
        return {"levels": ("bounds",), "n_buckets": (k,),
                "form": agg_ops.board_form(k + 1, "bounds", cols)}

    def ordinals(k):
        return {"levels": ("ords",), "n_buckets": (k,),
                "form": agg_ops.board_form(k + 1, "ords")}

    def sum32(statics):
        return dict(statics, parts=("sum",), limb_bits=bits, n_limbs=limbs)

    return {
        "hourly.cal_counts-2048": (
            X64, agg_ops._agg_tree_counts, tree,
            (flag, keys, flag, f64(2048), mp),
            {"levels": ("cal",), "n_buckets": (2048,)},
            (jnp.int64, (2049,))),
        "bytes-by-hour.hist_counts-2048": (
            X64, agg_ops._agg_tree_counts, tree, (flag, keys, flag, hp),
            hist(2048), (jnp.int64, (2049,))),
        "bytes-by-hour.hist_metric-2048": (
            X64, agg_ops._agg_tree_metric, tree,
            (flag, mp, keys, flag, keys, flag, hp), hist(2048),
            (jnp.int64, (2049,))),
        "bytes-by-hour.hist_metric-256": (
            X64, agg_ops._agg_tree_metric, tree,
            (flag, mp, keys, flag, keys, flag, hp), hist(256),
            (jnp.int64, (257,))),
        "bytes-by-hour.total.ord_metric-8": (
            X64, agg_ops._agg_tree_metric, tree,
            (flag, mp, keys, flag, ords, op),
            {"levels": ("ord",), "n_buckets": (8,)}, (jnp.int64, (9,))),
        "status-in-range.ord_counts-8": (
            X64, agg_ops._agg_tree_counts, tree, (flag, ords, op),
            {"levels": ("ord",), "n_buckets": (8,)}, (jnp.int64, (9,))),
        "status-in-range.ord_counts-16": (
            X64, agg_ops._agg_tree_counts, tree, (flag, ords, op),
            {"levels": ("ord",), "n_buckets": (16,)}, (jnp.int64, (17,))),
        "status-by-hour.tree_counts-2048x8": (
            X64, agg_ops._agg_tree_counts, tree,
            (flag, keys, flag, hp, ords, op),
            {"levels": ("hist", "ord"), "n_buckets": (2048, 8)},
            (jnp.int64, (2048 * 8 + 1,))),
        "status-by-hour.tree_counts-32x8": (
            X64, agg_ops._agg_tree_counts, tree,
            (flag, keys, flag, hp, ords, op),
            {"levels": ("hist", "ord"), "n_buckets": (32, 8)},
            (jnp.int64, (32 * 8 + 1,))),
        # hourly's calendar hours and bytes-by-hour's fixed ones: the same
        # program, they differ in their table of bounds alone
        "n32.by-hour.counts-2048": (
            N32, agg_ops._agg_n32_counts, counts32,
            (flag, i32(r), i32(2049)), bounds(2048), (jnp.int32, (2049,))),
        "n32.bytes-by-hour.sum-2048": (
            N32, agg_ops._agg_n32_metric, metric32,
            (flag, i32(r), i32(), i32(r), i32(2049)),
            sum32(bounds(2048, 1 + limbs)),
            (jnp.int32, (1 + limbs, 2049))),
        "n32.bytes-by-hour.total.sum": (
            N32, agg_ops._agg_n32_metric, metric32, (flag, i32(r), i32()),
            sum32({"levels": (), "n_buckets": (), "form": "onehot"}),
            (jnp.int32, (1 + limbs, 1))),
        "n32.status-in-range.counts-8": (
            N32, agg_ops._agg_n32_counts, counts32, (flag, ords),
            ordinals(8), (jnp.int32, (9,))),
        "n32.status-in-range.counts-16": (
            N32, agg_ops._agg_n32_counts, counts32, (flag, ords),
            ordinals(16), (jnp.int32, (17,))),
        "n32.status-by-hour.counts-2048x8": (
            N32, agg_ops._agg_n32_counts, counts32,
            (flag, i32(r), i32(2049), ords),
            {"levels": ("bounds", "ords"), "n_buckets": (2048, 8),
             "form": "onehot"}, (jnp.int32, (2049 * 9,))),
        # shapes the cell does not send: the extrema, and ordinals past
        # the lanes a column at which `board_form` scatters
        "n32.stats-2048": (
            N32, agg_ops._agg_n32_metric, metric32,
            (flag, i32(r), i32(), i32(r), i32(2049)),
            dict(sum32(bounds(2048, 1 + limbs)),
                 parts=("sum", "min", "max")),
            (jnp.int32, (3 + limbs, 2049))),
        "n32.ordinals.counts-65536": (
            N32, agg_ops._agg_n32_counts, counts32, (flag, ords),
            ordinals(65536), (jnp.int32, (65537,))),
    }


@pytest.mark.parametrize("program", [
    "hourly.cal_counts-2048", "bytes-by-hour.hist_counts-2048",
    "bytes-by-hour.hist_metric-2048", "bytes-by-hour.hist_metric-256",
    "bytes-by-hour.total.ord_metric-8", "status-in-range.ord_counts-8",
    "status-in-range.ord_counts-16", "status-by-hour.tree_counts-2048x8",
    "status-by-hour.tree_counts-32x8",
    "n32.by-hour.counts-2048", "n32.bytes-by-hour.sum-2048",
    "n32.bytes-by-hour.total.sum", "n32.status-in-range.counts-8",
    "n32.status-in-range.counts-16", "n32.status-by-hour.counts-2048x8",
    "n32.stats-2048", "n32.ordinals.counts-65536"])
def test_dash_panel_program_compiles_at_the_cells_row_bucket(one_chip,
                                                              program):
    """The programs of the cell `dash-aggs-steady` at its row bucket
    (2^20) and rungs, for the described chip: the x64 scatter programs
    (int64 counts and f64 sums by `.at[].add` over a million rows) and,
    beside them, the 32-bit ones, none of whose boards is 64 bits wide."""
    arithmetic, fn, static_names, args, statics, (dtype, shape) = \
        _dash_panel_programs(one_chip)[program]
    if arithmetic == X64:
        with jax.enable_x64(True):
            compiled = _compile(fn, static_names, *args, **statics)
    else:
        compiled = _compile(fn, static_names, *args, **statics)
        assert agg_ops._grid_n32(statics, [((1 << 20,), "bool", None)])
    boards = jax.tree_util.tree_leaves(compiled.out_info)
    assert boards[0].dtype == dtype and boards[0].shape == shape
    if arithmetic == N32:
        assert all(b.dtype == jnp.int32 for b in boards)
        assert "f64" not in compiled.as_text() \
            and "s64" not in compiled.as_text()


# ---------------------------------------------------------------------------
# the served form: every serving kernel returns ONE packed board
# ---------------------------------------------------------------------------

def _board_binned(sh, nq):
    # the benchmark's corpus: 290,000 x 256-d bf16 rows in 36 kernel tiles
    return _compile(
        binned._binned_impl, ("k", "metric", "interpret", "board"),
        _sds(sh, (nq, 256), jnp.float32),
        _corpus_spec(sh, 36 * binned.BLOCK_N, 256, jnp.bfloat16),
        k=10, metric=sim.COSINE, interpret=False, board=True)


def _board_rescored_packed(sh, nq):
    return _compile(
        binned._rescored_packed_impl,
        ("k", "metric", "rescore_candidates", "interpret", "board"),
        _sds(sh, (nq, 768), jnp.float32),
        _corpus_spec(sh, N_ROWS, 768, jnp.int8, residual=True),
        k=10, metric=sim.COSINE, rescore_candidates=128, interpret=False,
        board=True)


def _board_exact_filtered(sh, nq):
    n = 1 << 18
    return _compile(
        knn_ops._knn_search_impl,
        ("k", "metric", "precision", "block_size", "board"),
        _sds(sh, (nq, 128), jnp.float32),
        _corpus_spec(sh, n, 128, jnp.bfloat16),
        _sds(sh, (nq, n), jnp.bool_),
        k=10, metric=sim.COSINE, precision="bf16", block_size=None,
        board=True)


@pytest.mark.parametrize("nq", [1, 8])
@pytest.mark.parametrize("served", [_board_binned, _board_rescored_packed,
                                    _board_exact_filtered],
                         ids=["knn.binned", "knn.binned_rescored_packed",
                              "knn.exact-filtered"])
def test_served_board_compiles(one_chip, served, nq):
    """The form the store launches (`board=True`): the pair packed into
    one int32 [Q, 2k] array as the program's last operation."""
    (board,) = jax.tree_util.tree_leaves(served(one_chip, nq).out_info)
    assert board.dtype == jnp.int32 and board.shape == (nq, 20)


def test_four_shard_mesh_knn_compiles(topo):
    """`mesh.knn` (as the store launches it) on a Mesh of the four
    described devices: int8 768-d rows sharded over the `shard` axis,
    shard-local exact kNN, the candidate merge an all-gather over ICI."""
    mesh = mesh_lib.make_mesh(num_shards=4, dp=1, devices=topo.devices)
    n, d, nq, k = 1 << 22, 768, 64, 10
    from jax.sharding import NamedSharding
    corpus = layout.shape_specs(sharded_knn.ShardedCorpus(
        jax.ShapeDtypeStruct((n, d), jnp.int8),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((4,), jnp.int32)), mesh)
    queries = jax.ShapeDtypeStruct(
        (nq, d), jnp.float32,
        sharding=NamedSharding(mesh, layout.query_spec(2)))
    compiled = _compile(
        sharded_knn._distributed_knn_impl,
        ("k", "mesh", "metric", "precision", "block_size", "board"),
        queries, corpus, None, k=k, mesh=mesh, metric=sim.COSINE,
        precision="bf16", block_size=None, board=True)
    assert "all-gather" in compiled.as_text()
    # the served form: one packed board, the merged pair side by side
    (board,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert board.dtype == jnp.int32 and board.shape == (nq, 2 * k)
    # every device holds a quarter of the matrix, not the whole
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < (n * d) // 2
