"""Binned-reduction Pallas kNN: the peak-throughput path.

The TPU-KNN recipe (Chern et al., "TPU-KNN: K Nearest Neighbor Search at
Peak FLOP/s", 2022 — PAPERS.md pattern): instead of exact top-k inside the
scan, keep only the max of every BIN_SIZE-column bin — one packed VPU
reduction per tile, fully fused behind the MXU matmul in VMEM — then one
small `lax.top_k` over the [Q, n_bins] candidates. A bin can hold at most
one of the true top-k, so recall@k ≈ 1 - C(k,2)/n_bins (≈0.997 for k=10,
2048 bins over 1M docs); BASELINE's gate is recall@10 ≥ 0.95.

Score+index travel together through the reduction by packing the bin-local
chunk index into the low mantissa bits of the (positively-shifted) f32
score — max over the packed int32 is simultaneously argmax. The chunk-index
pattern (column j belongs to chunk j // 128) is a precomputed [1, BLOCK_N]
input OR-ed in with ONE full-array pass, leaving the 64-deep reduction a
pure `maximum` chain — measured ~2x the per-chunk mask-and-or formulation
on v5e (the reduction is the VPU-bound tail behind the MXU matmul).

int8 corpora run the matmul ON the int8 MXU path (dot_general s8xs8→s32,
~2x bf16 peak on v5e) with per-query and per-row dequant scales applied to
the [Q, BINS] score tile — the corpus is never upcast, so HBM traffic
halves vs bf16.

Grid: one step per corpus tile of BLOCK_N rows; each step writes its
(Q, BINS_PER_TILE) packed maxima to its own output column block, so there is
no cross-step carry at all.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops import similarity as sim
from elasticsearch_tpu.ops import topk as topk_ops
from elasticsearch_tpu.ops.knn import Corpus, _prep_queries

BLOCK_N = 8192
BIN_SIZE = 64
BINS_PER_TILE = BLOCK_N // BIN_SIZE   # 128 — one aligned lane tile
IDX_BITS = 6                          # log2(BIN_SIZE)
MASK = ~((1 << IDX_BITS) - 1)
# cosine scores live in [-1, 1]; dot products are clamped into this window
SHIFT = 4.0
CLAMP = 3.0


def _reduce_packed(p, out_ref):
    """64-deep pure-max chain over lane-aligned [Q, 128] chunks. Mosaic
    cannot lane-split reshapes, but elementwise max of aligned static
    slices is native VPU."""
    acc = p[:, 0:BINS_PER_TILE]
    for t in range(1, BIN_SIZE):
        acc = jnp.maximum(acc, p[:, t * BINS_PER_TILE:(t + 1) * BINS_PER_TILE])
    out_ref[:] = acc


def _make_kernel(clamp: bool):
    def _kernel(q_ref, c_ref, v_ref, t_ref, out_ref):
        """v_ref: {0,1} validity row; t_ref: precomputed chunk-index pattern
        (j // 128 per column). Shift positive so IEEE ordering == integer
        ordering; invalid (padding) columns multiply to 0 and never win."""
        scores = jax.lax.dot_general(
            q_ref[:], c_ref[:], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if clamp:
            scores = jnp.clip(scores, -CLAMP, CLAMP)
        s = (scores + SHIFT) * v_ref[:]
        p = (jax.lax.bitcast_convert_type(s, jnp.int32) & MASK) | t_ref[:]
        _reduce_packed(p, out_ref)

    return _kernel


def _int8_kernel(q_ref, c_ref, qs_ref, vs_ref, t_ref, out_ref):
    """int8 MXU path: s8 x s8 -> s32 matmul, dequant with per-query scale
    (qs_ref [Q, 1]) and per-row scale pre-multiplied into vs_ref
    ([1, BLOCK_N] = row_scale * validity, so padding still zeroes out)."""
    dots = jax.lax.dot_general(
        q_ref[:], c_ref[:], dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    s = dots.astype(jnp.float32) * qs_ref[:]
    s = jnp.clip(s * vs_ref[:] + SHIFT * jnp.minimum(vs_ref[:] * 1e30, 1.0),
                 0.0, SHIFT + CLAMP)
    p = (jax.lax.bitcast_convert_type(s, jnp.int32) & MASK) | t_ref[:]
    _reduce_packed(p, out_ref)


_KERNEL_CLAMPED = _make_kernel(clamp=True)
_KERNEL_COSINE = _make_kernel(clamp=False)


def _bf16x2(x):
    """Split f32 into (hi, lo) bf16 parts with hi + lo ≈ x to ~2^-16
    relative — two full-rate bf16 MXU passes recover near-f32 dot
    precision (the classic bf16x2 trick) without the ~6-pass cost of a
    Precision.HIGHEST f32 matmul on TPU."""
    xf = x.astype(jnp.float32)
    hi = xf.astype(jnp.bfloat16)
    lo = (xf - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _rescore_scores(q, corpus: Corpus, rows):
    """Near-exact scores [Q, C] of the candidate row ids `rows` [Q, C].

    Precision story — this is what makes the "rescoring may only help"
    invariant hold (base picks ⊆ candidate set, and a near-exact
    re-ranking of a superset can only match or beat the base): the query
    is bf16x2-split (error ~2^-16, vs the kernel's int8/bf16-rounded
    query), int8 candidate values in [-127, 127] are EXACT in bf16 so the
    MXU passes introduce no candidate-side error, per-row scales are
    applied to the [Q, C] scores in f32, and the optional residual level
    cuts the remaining int8 quantization error to ~1/127² of max|row|.
    f32-stored corpora split candidates bf16x2 as well (4 passes).
    Candidates stay bf16 end-to-end, so gather bytes are half an f32
    reconstruction.
    """
    q_hi, q_lo = _bf16x2(q)

    def gather(arr):
        # a corpus-aligned array ([N_pad, D] or [N_pad]) to its
        # candidates ([Q, C, D] / [Q, C])
        return arr[rows]

    def dot(c):
        kw = dict(preferred_element_type=jnp.float32)
        return (jnp.einsum("qd,qcd->qc", q_hi, c, **kw)
                + jnp.einsum("qd,qcd->qc", q_lo, c, **kw))

    if corpus.matrix.dtype == jnp.int8:
        s = dot(gather(corpus.matrix).astype(jnp.bfloat16)) \
            * gather(corpus.scales)
        if corpus.residual is not None:
            s = s + dot(gather(corpus.residual).astype(jnp.bfloat16)) \
                * gather(corpus.residual_scales)
        return s
    cand = gather(corpus.matrix)
    if cand.dtype == jnp.bfloat16:
        return dot(cand)
    c_hi, c_lo = _bf16x2(cand)
    return dot(c_hi) + dot(c_lo)


def _decode(packed, k):
    """Packed [Q, n_tiles*BPT] int32 -> (scores [Q,k], global ids [Q,k]).

    Column layout: global id = tile_base + t*BINS_PER_TILE + bin_lane,
    where t is the packed chunk index and bin_lane the output column
    within its tile."""
    ncols = packed.shape[1]
    cols = jnp.arange(ncols, dtype=jnp.int32)[None, :]
    tile_base = (cols // BINS_PER_TILE) * BLOCK_N
    bin_lane = cols % BINS_PER_TILE
    t = packed & ((1 << IDX_BITS) - 1)
    cand_s = jax.lax.bitcast_convert_type(
        packed & jnp.int32(MASK), jnp.float32) - SHIFT
    cand_i = tile_base + t * BINS_PER_TILE + bin_lane
    vals, pos = jax.lax.top_k(cand_s, k)
    return vals, jnp.take_along_axis(cand_i, pos, axis=1)


def _tile_patterns(n_pad: int, num_valid) -> tuple:
    valid = (jnp.arange(n_pad, dtype=jnp.int32)
             < num_valid).astype(jnp.float32).reshape(1, n_pad)
    tpat = jnp.broadcast_to(
        (jnp.arange(BLOCK_N, dtype=jnp.int32)
         // BINS_PER_TILE).reshape(1, BLOCK_N),
        (1, BLOCK_N))
    return valid, tpat


def _binned_impl(queries, corpus, k: int, metric: str, interpret: bool,
                 board: bool = False):
    packed, _q = _binned_packed(queries, corpus, metric, interpret)
    pair = _decode(packed, k)
    return topk_ops.pack_board(*pair) if board else pair


def _grid_binned(statics, sigs) -> bool:
    return (dispatch.is_query_bucket(sigs[0][0][0])
            and dispatch.in_k_grid(int(statics["k"]),
                                   limit=sigs[1][0][0]))


dispatch.DISPATCH.register(
    "knn.binned", _binned_impl,
    static_argnames=("k", "metric", "interpret", "board"),
    grid_check=_grid_binned)


def binned_knn_search(
    queries: jax.Array,
    corpus: Corpus,
    k: int,
    metric: str = sim.COSINE,
    interpret: Optional[bool] = None,
    board: bool = False,
):
    """Approximate (recall ≈ 1 - C(k,2)·BIN_SIZE/N) top-k.

    Supports dot-metric corpora (cosine pre-normalized / dot_product) in
    bf16/f32 or int8 storage; callers route l2 / filtered / tiny corpora
    to the exact XLA path. Returns (raw_scores [Q, k], ids [Q, k]), or
    with `board` the two as one packed array (`topk_ops.pack_board`).
    interpret=None auto-detects (interpret mode off TPU backends).
    """
    return dispatch.call("knn.binned", queries, corpus, k=k, metric=metric,
                         interpret=dispatch.pallas_interpret(interpret),
                         **topk_ops.board_static(board))


def _rescored_packed_impl(queries, corpus, k: int, metric: str,
                          rescore_candidates: int, interpret: bool,
                          board: bool = False):
    packed, q = _binned_packed(queries, corpus, metric, interpret)
    nq, ncols = packed.shape
    cand_s = jax.lax.bitcast_convert_type(
        packed & jnp.int32(MASK), jnp.float32) - SHIFT
    c = min(rescore_candidates, ncols)
    _, pos = jax.lax.top_k(cand_s, c)                        # [Q, C] cols
    sel = jnp.take_along_axis(packed, pos, axis=1)
    tile_base = (pos // BINS_PER_TILE) * BLOCK_N
    lane = pos % BINS_PER_TILE
    t = sel & ((1 << IDX_BITS) - 1)
    rows = tile_base + t * BINS_PER_TILE + lane              # [Q, C]
    scores = _rescore_scores(q, corpus, rows)
    valid = rows < corpus.num_valid
    scores = jnp.where(valid, scores, -jnp.inf)
    vals, p2 = jax.lax.top_k(scores, k)
    ids = jnp.take_along_axis(rows, p2, axis=1)
    return topk_ops.pack_board(vals, ids) if board else (vals, ids)


dispatch.DISPATCH.register(
    "knn.binned_rescored_packed", _rescored_packed_impl,
    static_argnames=("k", "metric", "rescore_candidates", "interpret",
                     "board"),
    grid_check=_grid_binned)


def binned_knn_search_rescored_packed(
    queries: jax.Array,
    corpus: Corpus,
    k: int,
    metric: str = sim.COSINE,
    rescore_candidates: int = 128,
    interpret: Optional[bool] = None,
    board: bool = False,
):
    """Binned pass + re-scoring of the top PACKED candidates with the
    unquantized query.

    The binned kernel keeps one candidate per 64-row bin and (for int8
    corpora) quantizes the query. This reuses the exact winner row each
    packed column already identifies: the top `rescore_candidates`
    columns decode to row ids, and only those rows ([Q, C, D], ~25
    MB/batch at C=128) are re-scored in bf16. Removes the query-side int8
    quantization error; bin-collision loss (second winner inside one
    bin) stays."""
    return dispatch.call("knn.binned_rescored_packed", queries, corpus,
                         k=k, metric=metric,
                         rescore_candidates=rescore_candidates,
                         interpret=dispatch.pallas_interpret(interpret),
                         **topk_ops.board_static(board))


# v5e has 128 MiB of VMEM per core; the compiler's default scoped limit is
# 16 MiB. The kernel asks for what its blocks need (below) and never more
# than this, which leaves the compiler room for its own scratch.
VMEM_LIMIT_CAP = 100 << 20
# Query rows per grid step. Buckets above it tile the query axis (the
# corpus tile stays resident across the inner query steps), so the
# [QUERY_TILE, BLOCK_N] f32/int32 temporaries are bounded whatever the
# bucket.
QUERY_TILE = 256


def vmem_bytes(nq: int, d: int, itemsize: int) -> int:
    """Scoped VMEM one grid step needs: the double-buffered corpus and
    query tiles (lanes pad to 128), the [tq, BLOCK_N] score and packed
    temporaries the 64-deep reduction spills, and the small row/output
    blocks. An estimate from above, held to the chip's compiler in
    tests/test_chip_compile.py: every width `kernel_holds` admits must
    compile under this limit."""
    tq = min(nq, QUERY_TILE)
    d_pad = -(-d // 128) * 128
    tiles = 2 * (BLOCK_N + max(tq, 32)) * d_pad * itemsize
    temps = 3 * max(tq, 8) * BLOCK_N * 4
    rows = 2 * 3 * 8 * BLOCK_N * 4 + 2 * max(tq, 8) * BINS_PER_TILE * 4
    return tiles + temps + rows + (2 << 20)


def kernel_holds(d: int, matrix_dtype) -> bool:
    """Whether the binned kernel can hold a corpus of this row width at
    EVERY query bucket — the routing fact `knn_search_auto`,
    `build_corpus`'s padding and the store's warmup grid all read, so a
    shape the chip's compiler would refuse is never sent (it takes the
    exact path by this rule, not by a caught compile error)."""
    itemsize = 1 if jnp.dtype(matrix_dtype) == jnp.int8 else 2
    return vmem_bytes(QUERY_TILE, d, itemsize) <= VMEM_LIMIT_CAP


def _binned_packed(queries, corpus, metric, interpret):
    n_pad, d = corpus.matrix.shape
    if n_pad % BLOCK_N != 0:
        raise ValueError(f"corpus rows {n_pad} not divisible by {BLOCK_N}")
    q = _prep_queries(queries, metric)
    nq = q.shape[0]
    tq = min(nq, QUERY_TILE)
    if nq % tq != 0:
        raise ValueError(f"query rows {nq} not a multiple of {tq}")
    n_tiles = n_pad // BLOCK_N
    valid, tpat = _tile_patterns(n_pad, corpus.num_valid)
    int8 = corpus.matrix.dtype == jnp.int8
    # grid: corpus tiles outer, query tiles inner — the corpus block index
    # does not change across the inner steps, so each tile is fetched from
    # HBM once however many query tiles score against it
    grid = (n_tiles, nq // tq)
    q_spec = pl.BlockSpec((tq, d), lambda i, j: (j, 0))
    c_spec = pl.BlockSpec((BLOCK_N, d), lambda i, j: (i, 0))
    row_spec = pl.BlockSpec((1, BLOCK_N), lambda i, j: (0, i))
    tpat_spec = pl.BlockSpec((1, BLOCK_N), lambda i, j: (0, 0))
    call = dict(
        grid=grid,
        out_specs=pl.BlockSpec((tq, BINS_PER_TILE), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((nq, n_tiles * BINS_PER_TILE),
                                       jnp.int32),
        interpret=interpret,
        # the kernel's name in the device trace (the HLO line alone is
        # `_binned_impl…` with its shapes)
        name="es_knn_binned_int8" if int8 else "es_knn_binned",
    )
    if not interpret:
        call["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(nq, d, 1 if int8 else 2))

    if int8:
        # symmetric per-query quantization (the codec registry's one
        # int8 recipe, in-trace twin); dequant inside the kernel
        from elasticsearch_tpu.quant import codec as quant_codec
        q8, qscale = quant_codec.quantize_queries_int8_jnp(q)
        row_scale_valid = (corpus.scales.reshape(1, n_pad) * valid)
        packed = pl.pallas_call(
            _int8_kernel,
            in_specs=[q_spec, c_spec,
                      pl.BlockSpec((tq, 1), lambda i, j: (j, 0)),
                      row_spec, tpat_spec],
            **call,
        )(q8, corpus.matrix, qscale.astype(jnp.float32),
          row_scale_valid, tpat)
        return packed, q

    qb = q.astype(jnp.bfloat16)
    mb = corpus.matrix.astype(jnp.bfloat16)
    kernel = _KERNEL_COSINE if metric == sim.COSINE else _KERNEL_CLAMPED
    packed = pl.pallas_call(
        kernel,
        in_specs=[q_spec, c_spec, row_spec, tpat_spec],
        **call,
    )(qb, mb, valid, tpat)
    return packed, q
