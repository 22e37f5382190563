"""Pallas fused gather+score for IVF probes.

The scan-based probe scorer (`ops/knn_ivf.score_probes`) pays posting-
list materialization: every probe step `jnp.take`s a [Q, cap, D]
partition-tile gather out to HBM before the einsum reads it back — at
nprobe=32, batch=256 that is gigabytes of staged tiles per dispatch.
This kernel fuses the gather INTO the score: the probe ids ride in as a
scalar-prefetch operand (`pltpu.PrefetchScalarGridSpec`), the BlockSpec
index_map selects each (query, probe) step's partition tile directly
out of the resident `parts` array, and the tile is read once, through
VMEM, straight into the MXU matmul — no staged copy exists at any
point. The [Q, nprobe, cap] score board is the only new array.

Variants follow the storage ladder (`quant/codec.py`): f32/bf16 tiles
matmul directly; int8 tiles upcast in-register and de-scale per row;
int4 packed-nibble tiles unpack into (even, odd) level planes against
the matching query planes. Binary stays on the scan path (sign-bit
probes are bandwidth-trivial already). l2 routing stays on the scan
path too — the fused kernel serves the dot-like metrics.

Registered as `ivf.fused_probe` under the same closed-grid predicate as
the scan kernels (bucketed query count, pow-2 nprobe), and kept honest
on CPU by interpret mode (`tests/test_pallas_parity.py` pins program
structure, byte parity vs the scan scorer, validity masking, and the
strict zero-recompile gate).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops import similarity as sim
from elasticsearch_tpu.ops.knn_ivf import IVFPartitions, _grid_ivf
from elasticsearch_tpu.ops.similarity import NEG_INF
from elasticsearch_tpu.quant import codec as quant_codec

# python-float sentinel for in-kernel use (a jnp constant would be a
# captured array, which pallas_call rejects)
_NEG = float(NEG_INF)


def fused_eligible(parts_dtype, metric: str, precision: str = "bf16") -> bool:
    """Can the fused kernel serve this layout? (dtype on the fused
    ladder, dot-like metric, bf16 serving precision). Callers separately
    decide WHETHER to prefer it (accelerator backend, or the
    ES_TPU_IVF_FUSED=1 interpret-mode override for tests/bench)."""
    return (str(parts_dtype) in ("float32", "bfloat16", "int8", "uint8")
            and metric != sim.L2_NORM
            and precision != "f32")


def fused_preferred() -> bool:
    """Route probes through the fused kernel? On by default on real
    accelerator backends (where the staged-gather HBM traffic is the
    cost); ES_TPU_IVF_FUSED=1 forces it in interpret mode, =0 forces it
    off."""
    env = os.environ.get("ES_TPU_IVF_FUSED")
    if env is not None:
        return env != "0"
    return dispatch.is_accelerator_backend()


# ---------------------------------------------------------------------------
# kernel bodies — one (query, probe) tile per grid step
# ---------------------------------------------------------------------------

def _dense_kernel(ids_ref, q_ref, parts_ref, scales_ref, out_ref):
    """f32/bf16/int8 tiles: [1, D] x [cap, D]^T with f32 accumulation
    (int8 tiles upcast in-register to bf16, exact for [-127, 127]).
    `scales_ref` is the per-row dequant scale for int8 and the validity
    row (1/0) otherwise — zero on padding either way, so the same mask
    pins padding slots to NEG_INF before the board leaves the kernel."""
    dots = jax.lax.dot_general(
        q_ref[:].astype(jnp.bfloat16), parts_ref[:].astype(jnp.bfloat16),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    s = dots * scales_ref[:]
    out_ref[:] = jnp.where(scales_ref[:] > 0, s, _NEG)


def _int4_kernel(ids_ref, qe_ref, qo_ref, parts_ref, scales_ref, out_ref):
    """int4 packed-nibble tiles: unpack the (even, odd) level planes
    in-register and run two half-width passes against the matching
    query planes (the codec's one bit layout)."""
    # widen before the nibble split: Mosaic has no 8-bit vector shift
    # ("failed to legalize operation 'arith.shrui'" on i8 lanes)
    tile = parts_ref[:].astype(jnp.int32)
    lo = ((tile & 0x0F) - 8).astype(jnp.bfloat16)
    hi = ((tile >> 4) - 8).astype(jnp.bfloat16)
    dn = (((1,), (1,)), ((), ()))
    dots = (jax.lax.dot_general(qe_ref[:].astype(jnp.bfloat16), lo, dn,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(qo_ref[:].astype(jnp.bfloat16), hi, dn,
                                  preferred_element_type=jnp.float32))
    s = dots * scales_ref[:]
    out_ref[:] = jnp.where(scales_ref[:] > 0, s, _NEG)


def _fused_probe_board(queries, ivf: IVFPartitions, probe_ids,
                       interpret: bool):
    """[Q, nprobe, cap] masked score board, tiles gathered via the
    scalar-prefetched probe ids (one partition tile per grid step).

    Every one-row operand carries a singleton axis ahead of its lanes
    ([nq, 1, d], [nlist, 1, cap], [nq, nprobe, 1, cap]) so the last two
    block dimensions equal the array's — Mosaic refuses a (1, d) block of
    an [nq, d] array (sublane dimension neither 8-divisible nor whole).
    The leading block dimensions are squeezed: the kernel bodies see
    [1, d] / [cap, w] / [1, cap] tiles."""
    nq = queries.shape[0]
    nprobe = probe_ids.shape[1]
    nlist, cap, w = ivf.parts.shape
    out_shape = jax.ShapeDtypeStruct((nq, nprobe, 1, cap), jnp.float32)
    out_spec = pl.BlockSpec((None, None, 1, cap),
                            lambda q, j, ids: (q, j, 0, 0))
    part_spec = pl.BlockSpec((None, cap, w),
                             lambda q, j, ids: (ids[q, j], 0, 0))
    scale_spec = pl.BlockSpec((None, 1, cap),
                              lambda q, j, ids: (ids[q, j], 0, 0))
    scales = ivf.part_scales.reshape(nlist, 1, cap)

    def q_spec(width):
        return pl.BlockSpec((None, 1, width), lambda q, j, ids: (q, 0, 0))

    if ivf.parts.dtype == jnp.uint8:
        qe, qo = quant_codec.split_query_planes_jnp(
            queries.astype(jnp.float32))
        kernel = _int4_kernel
        q_ops = (qe.reshape(nq, 1, w), qo.reshape(nq, 1, w))
    else:
        kernel = _dense_kernel
        q_ops = (queries.astype(jnp.float32).reshape(nq, 1, w),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(nq, nprobe),
        in_specs=[q_spec(w)] * len(q_ops) + [part_spec, scale_spec],
        out_specs=out_spec)
    board = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret, name="es_ivf_fused_probe",
    )(probe_ids, *q_ops, ivf.parts, scales)
    return board.reshape(nq, nprobe, cap)


def _fused_probe_impl(queries, ivf: IVFPartitions, probe_ids, k: int,
                      metric: str = sim.COSINE, interpret: bool = False):
    """Fused board + global top-k merge. The row-id join stays a cheap
    int32 take ([Q, nprobe, cap] ids — ~D× smaller than the vector
    tiles the scan path staged)."""
    board = _fused_probe_board(queries, ivf, probe_ids, interpret)
    nq = board.shape[0]
    rows = jnp.take(ivf.part_rows, probe_ids, axis=0)   # [Q, nprobe, cap]
    flat_s = board.reshape(nq, -1)
    flat_r = rows.reshape(nq, -1)
    flat_s = jnp.where(flat_r >= 0, flat_s, NEG_INF)
    vals, pos = jax.lax.top_k(flat_s, k)
    return vals, jnp.take_along_axis(flat_r, pos, axis=1)


dispatch.DISPATCH.register(
    "ivf.fused_probe", _fused_probe_impl,
    static_argnames=("k", "metric", "interpret"),
    grid_check=_grid_ivf)


def fused_probe_scores(queries, ivf: IVFPartitions, probe_ids, k: int,
                       metric: str = sim.COSINE,
                       interpret: Optional[bool] = None):
    """Score probed partitions with the fused gather+score kernel.

    queries must be metric-prepped (like `knn_ivf.score_probes`);
    probe_ids [Q, nprobe] int32 from `knn_ivf.route`. Returns
    (scores [Q, k], rows [Q, k]) — the `score_probes` contract exactly
    (NEG_INF / -1 padding), pinned by the interpret-mode parity tests.
    """
    return dispatch.call("ivf.fused_probe", queries, ivf, probe_ids,
                         k=k, metric=metric,
                         interpret=dispatch.pallas_interpret(interpret))


def warmup_entries(ivf: IVFPartitions, nprobe: int, dims: int, k_buckets,
                   query_buckets, metric: str = sim.COSINE,
                   interpret: Optional[bool] = None):
    """(kernel, specs, statics) entries pre-compiling the fused probe
    grid over the interactive buckets (the store's router warmup).
    `interpret` defaults through the same resolution serving uses, so
    the warmed programs ARE the ones `fused_probe_scores` dispatches
    (an ES_TPU_IVF_FUSED=1 interpret-mode run warms interpret=True)."""
    parts_spec = dispatch.specs_like(ivf)
    entries = []
    cap = ivf.parts.shape[1]
    interp = dispatch.pallas_interpret(interpret)
    for q in query_buckets:
        qspec = dispatch.query_spec(q, dims)
        pspec = jax.ShapeDtypeStruct((q, nprobe), jnp.int32)
        for k in k_buckets:
            k_b = dispatch.bucket_k(min(k, nprobe * cap),
                                    limit=nprobe * cap)
            entries.append((
                "ivf.fused_probe", (qspec, parts_spec, pspec),
                {"k": k_b, "metric": metric, "interpret": interp}))
    return entries


def warmup_entries_for_index(index, nprobe: int, k_buckets, query_buckets,
                             metric: str = sim.COSINE):
    """SHAPE-ONLY warmup entries derived from an `ann/ivf_index.IVFIndex`
    HOST layout — never touches `device_partitions()`, so scheduling
    warmup on the refresh thread cannot pay (or re-pay, since
    `IVFIndex.add` invalidates the cached upload) the partition-layout
    transfer (the same contract as `sharded_ivf.warmup_entries`)."""
    nlist, cap, dims = index.part_vecs.shape
    part_dtype = {"int8": jnp.int8, "bf16": jnp.bfloat16,
                  "int4": jnp.uint8, "binary": jnp.uint32}.get(
        index.dtype, jnp.float32)
    part_w = dims
    if index.dtype in quant_codec.PACKED_ENCODINGS:
        part_w = quant_codec.get(index.dtype).packed_width(dims)
    spec = IVFPartitions(
        centroids=jax.ShapeDtypeStruct((nlist, dims), jnp.float32),
        centroid_sq=jax.ShapeDtypeStruct((nlist,), jnp.float32),
        parts=jax.ShapeDtypeStruct((nlist, cap, part_w), part_dtype),
        part_scales=jax.ShapeDtypeStruct((nlist, cap), jnp.float32),
        part_sq=jax.ShapeDtypeStruct((nlist, cap), jnp.float32),
        part_rows=jax.ShapeDtypeStruct((nlist, cap), jnp.int32))
    return warmup_entries(spec, nprobe, dims, k_buckets, query_buckets,
                          metric=metric)
