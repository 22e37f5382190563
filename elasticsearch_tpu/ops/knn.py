"""Exact kNN as batched matmul + top-k: the north-star device program.

Replaces the reference's O(N·D) per-document scripted loop inside the Lucene
collector (`ScoreScriptUtils.java:151-171` called per doc from
`search/query/QueryPhase.java:171`'s BulkScorer) with one MXU-shaped program:

    scores = queries @ corpus^T          (bf16 MXU, f32 accumulate)
    top-k  = lax.top_k(scores + masks)

Two execution shapes:
  * single-shot for corpora whose [Q, N] score matrix fits comfortably;
  * blocked `lax.scan` over corpus tiles with a running top-k merge, for
    corpora where materializing [Q, N] would blow HBM — the structural
    analog of ring attention's KV rotation, but over corpus blocks
    (SURVEY.md §5.7).

The corpus lives in a `Corpus` pytree built once at index/refresh time
(normalization, squared norms, optional int8 quantization), matching the
reference's encode-at-parse-time design (`DenseVectorFieldMapper.parse`).
"""

from __future__ import annotations

import threading
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops import similarity as sim
from elasticsearch_tpu.ops import topk as topk_ops
from elasticsearch_tpu.ops.quantization import quantize_int8_np
from elasticsearch_tpu.ops.similarity import NEG_INF
from elasticsearch_tpu.quant import codec as quant_codec

LANE = 128  # TPU lane width; corpus rows are padded to a multiple of this.


class Corpus(NamedTuple):
    """Device-resident searchable vector block (a pytree).

    matrix:    [N_pad, D] f32 / bf16 / int8 storage
    sq_norms:  [N_pad] f32 — ||row||^2 (post-normalization for cosine)
    scales:    [N_pad] f32 — int8 per-row scales (all-ones when unquantized)
    num_valid: int32 scalar — rows beyond this are padding and never match
    residual / residual_scales: optional second int8 quantization level
      (row ≈ matrix*scales + residual*residual_scales, error ~1/127² of
      max|row|). The main scan never reads it; rescore variants gather it
      to reconstruct near-exact rows (the ScaNN scan-int8/rescore-float
      recipe, re-shaped so total storage equals bf16 while the scan still
      moves only int8 bytes through HBM).
    """

    matrix: jax.Array
    sq_norms: jax.Array
    scales: jax.Array
    num_valid: jax.Array
    residual: Optional[jax.Array] = None
    residual_scales: Optional[jax.Array] = None


class DeferredCorpus:
    """A `Corpus` that is uploaded on its first use.

    Where every search of a field is answered by the mesh's sharded copy
    (`vectors/store.py` under `search.mesh.enabled: true`), a whole copy
    on one device would only double that device's share. Until
    `resident()` is first called this object reads as the corpus's
    shape: each array field is a `jax.ShapeDtypeStruct` (`.shape` and
    `.dtype` are what the routing and the k clamp read). `resident()`
    builds the arrays once, under a lock; afterwards the fields read as
    the arrays themselves. Hand `resident(corpus)` to a device program,
    never the object."""

    __slots__ = ("_spec", "_build", "_real", "_lock")

    def __init__(self, spec: Corpus, build: Callable[[], Corpus]):
        self._spec = spec
        self._build = build
        self._real: Optional[Corpus] = None
        self._lock = threading.Lock()

    @property
    def built(self) -> bool:
        return self._real is not None

    def resident(self) -> Corpus:
        if self._real is None:
            with self._lock:
                if self._real is None:
                    self._real = self._build()
                    self._build = None
        return self._real

    def __getattr__(self, name):
        # only the Corpus fields reach here (the slots resolve first)
        return getattr(self._real if self._real is not None
                       else self._spec, name)


def resident(corpus):
    """The device arrays of `corpus`, built now if they were deferred."""
    return corpus.resident() if isinstance(corpus, DeferredCorpus) \
        else corpus


def is_resident(corpus) -> bool:
    return not isinstance(corpus, DeferredCorpus) or corpus.built


def pad_rows(n: int, multiple: int = LANE) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def binned_serves(d: int, matrix_dtype, metric: str) -> bool:
    """Can the binned Pallas kernel serve corpora of this row shape here?
    A TPU backend (Mosaic compiles nowhere else), a dot-like metric,
    unpacked storage, and a row width the kernel's VMEM budget holds."""
    from elasticsearch_tpu.ops import pallas_knn_binned as binned
    return (metric in (sim.COSINE, sim.DOT_PRODUCT, sim.MAX_INNER_PRODUCT)
            and jnp.dtype(matrix_dtype) not in (jnp.uint8, jnp.uint32)
            and binned.kernel_holds(d, matrix_dtype)
            and dispatch.is_accelerator_backend())


def binned_route(n_pad: int, d: int, matrix_dtype, metric: str) -> bool:
    """Does the binned kernel serve unfiltered bf16-precision searches of
    THIS corpus: `binned_serves`, and rows tiled to the kernel's block.
    One rule for `knn_search_auto` and the store's warmup grid (and, via
    `binned_serves`, `build_corpus`'s padding). Everything else takes the
    exact path by this rule — never because a kernel call failed."""
    from elasticsearch_tpu.ops import pallas_knn_binned as binned
    return (n_pad % binned.BLOCK_N == 0
            and binned_serves(d, matrix_dtype, metric))


def preferred_pad_multiple(n: int, d: int, dtype: str,
                           metric: str = sim.COSINE) -> int:
    """Pad large corpora to the binned kernel's tile size wherever that
    kernel will serve them (`binned_serves`); everywhere it can't (CPU,
    l2, packed encodings, rows too wide for its VMEM budget), keep
    minimal lane padding — no wasted HBM/FLOPs."""
    from elasticsearch_tpu.ops import pallas_knn_binned as binned
    if n < binned.BLOCK_N or dtype in quant_codec.PACKED_ENCODINGS:
        return LANE
    matrix_dtype = jnp.int8 if dtype == "int8" else jnp.bfloat16
    return (binned.BLOCK_N if binned_serves(d, matrix_dtype, metric)
            else LANE)


def corpus_spec(n: int, d: int, metric: str, dtype: str,
                residual: bool) -> Corpus:
    """The shape `build_corpus` gives n rows of d dims (unpacked
    encodings only), as `jax.ShapeDtypeStruct`s: what a `DeferredCorpus`
    reads as before it is built."""
    n_pad = pad_rows(max(n, 1), preferred_pad_multiple(n, d, dtype, metric))

    def arr(shape, kind):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(kind))

    res = dtype == "int8" and residual
    return Corpus(
        matrix=arr((n_pad, d), quant_codec.MATRIX_DTYPES[dtype]),
        sq_norms=arr((n_pad,), jnp.float32),
        scales=arr((n_pad,), jnp.float32),
        num_valid=arr((), jnp.int32),
        residual=arr((n_pad, d), jnp.int8) if res else None,
        residual_scales=arr((n_pad,), jnp.float32) if res else None)


def build_corpus(
    vectors: np.ndarray,
    metric: str = sim.COSINE,
    dtype: str = "bf16",
    pad_to: Optional[int] = None,
    residual: bool = True,
) -> Corpus:
    """Build the device corpus from raw host vectors.

    dtype: "f32" | "bf16" | "int8" storage for the matrix.
    For cosine, rows are L2-normalized here, once — so query-time work is a
    pure dot product (the reference instead stores the magnitude beside each
    vector and divides per doc per query, `ScoreScriptUtils.java:161`).

    residual: for int8 storage, also keep the second-level int8 residual
    used by the rescore variants (doubles storage to bf16-parity; pass
    False when HBM capacity matters more than rescore headroom).
    int8 quantization happens host-side in numpy — for a 10M x 768 corpus
    the f32 intermediate is ~30 GB and must never be materialized on device.
    """
    vectors = np.asarray(vectors, dtype=np.float32)
    n, d = vectors.shape
    n_pad = pad_to if pad_to is not None else pad_rows(
        max(n, 1), preferred_pad_multiple(n, d, dtype, metric))
    if n_pad < n:
        raise ValueError(f"pad_to {n_pad} < corpus size {n}")

    if metric == sim.COSINE:
        norms = np.linalg.norm(vectors, axis=-1, keepdims=True)
        vectors = vectors / np.maximum(norms, 1e-30)

    padded = np.zeros((n_pad, d), dtype=np.float32)
    padded[:n] = vectors
    # einsum keeps sq_norms temp-free (padded*padded would materialize a
    # second full-size f32 array — ~30 GB at the 10M x 768 scale)
    sq_norms = jnp.asarray(np.einsum("nd,nd->n", padded, padded),
                           dtype=jnp.float32)

    res = res_scales = None
    if dtype in quant_codec.PACKED_ENCODINGS:
        # packed ladder rungs (int4 nibbles / binary sign bits): encode
        # through the codec registry — the one owner of the bit layout
        # (the device kernels unpack with the matching codec helpers)
        if dtype == "binary" and metric in (sim.L2_NORM,
                                            sim.MAX_INNER_PRODUCT):
            raise ValueError(
                "binary encoding scores sign-bit Hamming — incompatible "
                f"with magnitude-dependent {metric} similarity")
        enc = quant_codec.get(dtype).encode_np(padded)
        matrix = jnp.asarray(enc.data)
        scales = jnp.asarray(enc.scales)
    elif dtype == "int8":
        q8, scales_np = quantize_int8_np(padded)
        matrix = jnp.asarray(q8)
        scales = jnp.asarray(scales_np)
        if residual:
            # second level, chunked so the f32 residual temp stays bounded
            r8 = np.empty_like(q8)
            rscales_np = np.empty((n_pad,), dtype=np.float32)
            chunk = max(1, (64 << 20) // max(d * 4, 1))
            for lo in range(0, n_pad, chunk):
                hi = lo + chunk
                res_f = (padded[lo:hi]
                         - q8[lo:hi].astype(np.float32)
                         * scales_np[lo:hi, None])
                r8[lo:hi], rscales_np[lo:hi] = quantize_int8_np(res_f)
            res = jnp.asarray(r8)
            res_scales = jnp.asarray(rscales_np)
    else:
        matrix = jnp.asarray(padded, dtype=jnp.bfloat16 if dtype == "bf16" else jnp.float32)
        scales = jnp.ones((n_pad,), dtype=jnp.float32)

    return Corpus(matrix=matrix, sq_norms=sq_norms, scales=scales,
                  num_valid=jnp.int32(n), residual=res,
                  residual_scales=res_scales)


def corpus_from_encoded(
    data: np.ndarray,
    scales: np.ndarray,
    vectors: np.ndarray,
    metric: str = sim.COSINE,
    dtype: str = "int4",
    pad_to: Optional[int] = None,
) -> Corpus:
    """Build a packed-encoding corpus from ALREADY-ENCODED rows (the
    columnar store's per-segment encoded blocks, `columnar.encoded_rows`)
    — refresh re-encodes only delta segments instead of the whole
    matrix. `vectors` is the raw f32 matrix (for sq-norms); padding rows
    take the codec's encode-of-zeros so the result is byte-identical to
    `build_corpus(vectors, dtype=dtype)`.
    """
    codec = quant_codec.get(dtype)
    vectors = np.asarray(vectors, dtype=np.float32)
    n, d = vectors.shape
    n_pad = pad_to if pad_to is not None else pad_rows(max(n, 1), LANE)
    if n_pad < n:
        raise ValueError(f"pad_to {n_pad} < corpus size {n}")
    # sq-norms in row chunks: the rows themselves are ALREADY encoded,
    # so this must not re-materialize a corpus-sized f32 temp (the whole
    # point of the per-segment encoded blocks); cosine rows are
    # normalized before encoding, so their post-normalization sq-norm is
    # exactly 1 for any non-zero row
    sq_np = np.zeros((n_pad,), dtype=np.float32)
    chunk = max(1, (64 << 20) // max(d * 4, 1))
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        block_sq = np.einsum("nd,nd->n", vectors[lo:hi], vectors[lo:hi])
        if metric == sim.COSINE:
            sq_np[lo:hi] = (block_sq > 0).astype(np.float32)
        else:
            sq_np[lo:hi] = block_sq
    sq_norms = jnp.asarray(sq_np)
    w = codec.packed_width(d)
    pad_enc = codec.encode_np(np.zeros((1, d), dtype=np.float32))
    full_data = np.empty((n_pad, w), dtype=codec.packed_np_dtype)
    full_scales = np.empty((n_pad,), dtype=np.float32)
    full_data[:n] = data.reshape(n, w)
    full_scales[:n] = scales
    full_data[n:] = pad_enc.data[0]
    full_scales[n:] = pad_enc.scales[0]
    return Corpus(matrix=jnp.asarray(full_data),
                  sq_norms=sq_norms,
                  scales=jnp.asarray(full_scales),
                  num_valid=jnp.int32(n))


def _block_scores(queries, matrix, sq_norms, scales, metric: str, precision: str):
    """Raw similarity for one corpus block, handling int8 dequant-after-matmul.

    Queries arrive already metric-prepped (see _prep_queries) — in particular
    cosine queries are unit vectors, so no renormalization happens per block.
    """
    if matrix.dtype == jnp.int8:
        # upcast the int8 rows, delegate to the one authoritative matmul
        # (precision policy lives in sim._matmul), de-scale after
        mat = matrix.astype(jnp.float32 if precision == "f32" else jnp.bfloat16)
        dots = sim._matmul(queries, mat, precision) * scales[None, :]
        if metric == sim.L2_NORM:
            return sim.l2_raw_from_dots(dots, queries, sq_norms)
        return dots
    if matrix.dtype == jnp.uint8:
        # int4 packed nibbles: two half-width matmuls on the (even, odd)
        # level planes — no interleave materializes, the planes unpack
        # in-register ahead of the MXU read
        mm = jnp.float32 if precision == "f32" else jnp.bfloat16
        lo, hi = quant_codec.int4_planes_jnp(matrix, mm)
        q_even, q_odd = quant_codec.split_query_planes_jnp(queries)
        dots = (sim._matmul(q_even, lo, precision)
                + sim._matmul(q_odd, hi, precision)) * scales[None, :]
        if metric == sim.L2_NORM:
            return sim.l2_raw_from_dots(dots, queries, sq_norms)
        return dots
    if matrix.dtype == jnp.uint32:
        # binary sign bits: XOR + popcount pseudo-dots ((D - 2·ham)/D —
        # the 1-bit cosine estimate; two-phase rescore restores exact
        # ordering). l2 is rejected at encode time.
        qbits = quant_codec.pack_sign_bits_jnp(queries)
        return quant_codec.hamming_pseudo_dots_jnp(qbits, matrix)
    return sim.similarity_scores(queries, matrix, sq_norms, metric=metric,
                                 precision=precision, normalize_queries=False)


def _prep_queries(queries, metric: str):
    queries = queries.astype(jnp.float32)
    if metric == sim.COSINE:
        qn = jnp.linalg.norm(queries, axis=-1, keepdims=True)
        queries = queries / jnp.maximum(qn, 1e-30)
    return queries


def knn_search_auto(
    queries: jax.Array,
    corpus: Corpus,
    k: int,
    metric: str = sim.COSINE,
    filter_mask: Optional[jax.Array] = None,
    precision: str = "bf16",
    rescore_candidates: int = 128,
    board: bool = False,
):
    """Route to the kernel that serves this shape. `board`: the result as
    ONE array (`topk_ops.pack_board`, packed inside the same program): the
    form the store's serving call reads back in one crossing.

    Preference order:
      1. binned Pallas kernel where `binned_route` says it serves (TPU,
         dot-like metric, tiled corpus the kernel holds) and the request
         has no filter and k within the candidate budget — recall ≈ 1.0
         for 1M-doc corpora (pallas_knn_binned.py); its speed against the
         exact path on the current chip: not measured. A corpus carrying
         the residual rescore level (index_options.rescore) additionally
         re-ranks the kernel's own top candidates at near-exact
         precision;
      2. exact XLA matmul + lax.top_k (all metrics, filters, any backend).

    The route is a function of shapes and the backend alone. A kernel
    that fails to compile or run raises to the caller (a failed shard in
    the `_search` response), it is never retried on the other route.
    """
    from elasticsearch_tpu.ops import pallas_knn_binned as binned

    n_pad, d = corpus.matrix.shape
    if (filter_mask is None and k <= 64 and precision == "bf16"
            and binned_route(n_pad, d, corpus.matrix.dtype, metric)):
        if corpus.residual is not None:
            # `index_options.rescore_oversample` sizes this window
            # (store-threaded); the old fixed 128 is the
            # default-oversample value
            return binned.binned_knn_search_rescored_packed(
                queries, corpus, k, metric=metric,
                rescore_candidates=rescore_candidates, board=board)
        return binned.binned_knn_search(queries, corpus, k, metric=metric,
                                        board=board)
    return knn_search(queries, corpus, k, metric=metric, filter_mask=filter_mask,
                      precision=precision, board=board)


def _knn_search_impl(
    queries: jax.Array,
    corpus: Corpus,
    filter_mask: Optional[jax.Array],
    k: int,
    metric: str = sim.COSINE,
    precision: str = "bf16",
    block_size: Optional[int] = None,
    board: bool = False,
):
    pair = _knn_pair(queries, corpus, filter_mask, k, metric, precision,
                     block_size)
    return topk_ops.pack_board(*pair) if board else pair


def _knn_pair(queries, corpus, filter_mask, k, metric, precision,
              block_size):
    n_pad = corpus.matrix.shape[0]
    q = _prep_queries(queries, metric)
    # cosine corpus rows are already normalized; its sq_norms are 1 for valid
    # rows, 0 for padding — handled by the validity mask below either way.
    valid = jnp.arange(n_pad, dtype=jnp.int32) < corpus.num_valid
    if filter_mask is not None:
        valid = valid & filter_mask  # broadcasts [N] or [Q, N]

    if block_size is None or block_size >= n_pad:
        # two named scopes, so that a profile splits the [Q, N] board from
        # the selection over it
        with jax.named_scope("es.knn.exact.score"):
            scores = _block_scores(q, corpus.matrix, corpus.sq_norms, corpus.scales, metric, precision)
        with jax.named_scope("es.knn.exact.masked_top_k"):
            return topk_ops.masked_top_k(scores, valid, k)

    # Blocked path: scan corpus tiles with a running top-k. Keeps peak HBM at
    # [Q, block_size] scores instead of [Q, N].
    if n_pad % block_size != 0:
        raise ValueError(f"n_pad {n_pad} not divisible by block_size {block_size}")
    nblocks = n_pad // block_size
    mat = corpus.matrix.reshape(nblocks, block_size, -1)
    sqn = corpus.sq_norms.reshape(nblocks, block_size)
    scl = corpus.scales.reshape(nblocks, block_size)
    if valid.ndim == 1:
        vmask = valid.reshape(nblocks, 1, block_size)
    else:
        vmask = valid.reshape(-1, nblocks, block_size).transpose(1, 0, 2)

    nq = q.shape[0]
    init = (jnp.full((nq, k), NEG_INF, dtype=jnp.float32),
            jnp.zeros((nq, k), dtype=jnp.int32))

    def body(carry, xs):
        best_s, best_i = carry
        block_mat, block_sqn, block_scl, block_valid, block_idx = xs
        s = _block_scores(q, block_mat, block_sqn, block_scl, metric, precision)
        s = jnp.where(block_valid, s, NEG_INF)
        ids = block_idx * block_size + jnp.arange(block_size, dtype=jnp.int32)[None, :]
        ids = jnp.broadcast_to(ids, s.shape)
        cat_s = jnp.concatenate([best_s, s], axis=1)
        cat_i = jnp.concatenate([best_i, ids], axis=1)
        vals, pos = jax.lax.top_k(cat_s, k)
        return (vals, jnp.take_along_axis(cat_i, pos, axis=1)), None

    xs = (mat, sqn, scl, vmask, jnp.arange(nblocks, dtype=jnp.int32))
    (best_s, best_i), _ = jax.lax.scan(body, init, xs)
    return best_s, best_i


def _grid_knn(statics, sigs) -> bool:
    """Closed grid: bucketed query count, k on the ladder (or clamped to
    the corpus), corpus rows lane-padded (they are, by build_corpus)."""
    q_shape = sigs[0][0]          # queries [Q, D]
    n_rows = sigs[1][0][0]        # corpus.matrix [N_pad, D]
    return (dispatch.is_query_bucket(q_shape[0])
            and dispatch.in_k_grid(int(statics["k"]), limit=n_rows)
            and n_rows % LANE == 0)


dispatch.DISPATCH.register(
    "knn.exact", _knn_search_impl,
    static_argnames=("k", "metric", "precision", "block_size", "board"),
    grid_check=_grid_knn)


def knn_search(
    queries: jax.Array,
    corpus: Corpus,
    k: int,
    metric: str = sim.COSINE,
    filter_mask: Optional[jax.Array] = None,
    precision: str = "bf16",
    block_size: Optional[int] = None,
    board: bool = False,
):
    """Exact top-k search of `queries` [Q, D] against `corpus`.

    filter_mask: optional [N_pad] or [Q, N_pad] bool — True = searchable
    (filtered kNN; host-computed bitset from the boolean pre-filter).

    Returns (scores [Q, k] raw similarity, ids [Q, k] int32 row indices).
    Padded / filtered-out rows return score NEG_INF (callers treat those as
    "fewer than k hits"). With `board`, the pair as one packed array.

    Executes through the shape-bucketed dispatch cache (`ops/dispatch.py`):
    serving callers pad queries to pow-2 buckets and round k up the bucket
    ladder, so steady-state traffic never compiles.
    """
    return dispatch.call("knn.exact", queries, corpus, filter_mask,
                         k=k, metric=metric, precision=precision,
                         block_size=block_size,
                         **topk_ops.board_static(board))
