"""Pallas fused gather+MaxSim rescore for late-interaction retrieval.

A late-interaction (ColBERT-style) query holds Tq token vectors and
scores a doc as ``sum_q max_t dot(q_token, doc_token)`` (MaxSim). The
serving shape is two-phase (`vectors/late_interaction.py`): a coarse
single-vector retrieval over pooled doc centroids picks a
top-(k·oversample) candidate window, then THIS kernel rescores the
window against the full token blocks. A scan-based rescore would
`jnp.take` a [Q, W, cap, D] token-tile gather out to HBM before the
matmul reads it back — the exact staging cost `pallas_ivf_fused.py`
killed for IVF probes, reproduced here for candidate docs: the
candidate ids ride in as a scalar-prefetch operand
(`pltpu.PrefetchScalarGridSpec`), the BlockSpec index_map selects each
(query, candidate) step's token tile straight out of the resident
[N_pad, cap, D] block, and the tile flows through VMEM into the MXU
dot. The [Q, W] MaxSim board is the only new array.

Variants follow the storage ladder (`quant/codec.py` via
`quant/tokens.py`): f32/bf16/int8 token tiles matmul directly (int8
upcasts in-register and de-scales per TOKEN row); int4 packed-nibble
tiles unpack into (even, odd) level planes against matching query
planes. Per-token scales are 0 on padding slots (both intra-doc cap
padding and whole padding docs), which pins those lanes to NEG_INF
before the max — and zero-padded QUERY tokens contribute exactly 0.0
to the sum (all their dots are 0, and the max over a doc's valid
tokens of 0 is 0).

Registered as `maxsim.rescore` under its own closed grid (bucketed
query count, candidate window on the k ladder or a LANE multiple,
pow-2 query-token and doc-token caps) with warmup entries; kept honest
on CPU by interpret mode and the jnp reference twin below
(byte-tested in tests/test_late_interaction.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops.similarity import NEG_INF

# python-float sentinel for in-kernel use (a jnp constant would be a
# captured array, which pallas_call rejects)
_NEG = float(NEG_INF)

LANE = 128


# ---------------------------------------------------------------------------
# kernel bodies — one (query, candidate doc) token tile per grid step
# ---------------------------------------------------------------------------

def _max_then_sum(masked):
    """MaxSim reduce of a [Tq, cap] board to [1, 1], rank-2 all the way:
    Mosaic cannot relayout the rank-1 [Tq] vector a plain `max(axis=1)`
    leaves ("Invalid relayout: Non-singleton logical dimension is
    replicated in destination but not in source")."""
    best = jnp.max(masked, axis=1, keepdims=True)       # [Tq, 1]
    return jnp.sum(best, axis=0, keepdims=True)         # [1, 1]


def _dense_kernel(ids_ref, q_ref, toks_ref, scales_ref, out_ref):
    """f32/bf16/int8 token tiles: [Tq, D] x [cap, D]^T with f32
    accumulation (int8 upcasts in-register to bf16, exact for
    [-127, 127]), per-token de-scale, NEG_INF mask on zero-scale
    padding slots, then the MaxSim reduce: max over doc tokens, sum
    over query tokens."""
    dots = jax.lax.dot_general(
        q_ref[:].astype(jnp.bfloat16), toks_ref[:].astype(jnp.bfloat16),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # [Tq, cap]
    s = scales_ref[:]                                   # [1, cap]
    masked = jnp.where(s > 0, dots * s, _NEG)
    out_ref[:] = _max_then_sum(masked)


def _int4_kernel(ids_ref, qe_ref, qo_ref, toks_ref, scales_ref, out_ref):
    """int4 packed-nibble token tiles: unpack the (even, odd) level
    planes in-register and run two half-width passes against the
    matching query planes (the codec's one bit layout), then the same
    masked MaxSim reduce."""
    # widen before the nibble split: Mosaic has no 8-bit vector shift
    tile = toks_ref[:].astype(jnp.int32)
    lo = ((tile & 0x0F) - 8).astype(jnp.bfloat16)
    hi = ((tile >> 4) - 8).astype(jnp.bfloat16)
    dn = (((1,), (1,)), ((), ()))
    dots = (jax.lax.dot_general(qe_ref[:].astype(jnp.bfloat16), lo, dn,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(qo_ref[:].astype(jnp.bfloat16), hi, dn,
                                  preferred_element_type=jnp.float32))
    s = scales_ref[:]
    masked = jnp.where(s > 0, dots * s, _NEG)
    out_ref[:] = _max_then_sum(masked)


def _maxsim_impl(ids, q, qe, qo, toks, scales, interpret: bool):
    """[Q, W] MaxSim board: token tiles gathered via the scalar-
    prefetched candidate ids (one (query, candidate) tile per grid
    step). Dense path passes `q` [Q, Tq, D] with qe/qo None; the int4
    path passes the (even, odd) query planes [Q, Tq, W] with q None."""
    nq, wc = ids.shape
    _n_pad, cap, wd = toks.shape
    # one-row / one-cell operands carry singleton axes (scales arrive
    # resident as [n_pad, 1, cap]; the board leaves as [nq, wc, 1, 1])
    # so the last two block dimensions equal the array's — Mosaic
    # refuses a (1, cap) block of [n_pad, cap] and a (1, 1) block of
    # [nq, wc]. Leading block dimensions are squeezed.
    out_shape = jax.ShapeDtypeStruct((nq, wc, 1, 1), jnp.float32)
    out_spec = pl.BlockSpec((None, None, 1, 1),
                            lambda qi, j, ids_: (qi, j, 0, 0))
    tok_spec = pl.BlockSpec((None, cap, wd),
                            lambda qi, j, ids_: (ids_[qi, j], 0, 0))
    scale_spec = pl.BlockSpec((None, 1, cap),
                              lambda qi, j, ids_: (ids_[qi, j], 0, 0))
    if toks.dtype == jnp.uint8:
        kernel = _int4_kernel
        q_ops = (qe.astype(jnp.float32), qo.astype(jnp.float32))
    else:
        kernel = _dense_kernel
        q_ops = (q.astype(jnp.float32),)
    tq = q_ops[0].shape[1]
    qspec = pl.BlockSpec((None, tq, wd), lambda qi, j, ids_: (qi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(nq, wc),
        in_specs=[qspec] * len(q_ops) + [tok_spec, scale_spec],
        out_specs=out_spec)
    board = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret, name="es_maxsim",
    )(ids, *q_ops, toks, scales)
    return board.reshape(nq, wc)


def _grid_maxsim(statics, sigs) -> bool:
    """Bucketed query count; candidate window on the k ladder or a
    LANE multiple (the coarse phase's bucket_k clamp lands on LANE-
    padded corpus rows); pow-2 query-token pad; pow-2 doc-token cap
    and block count; lane-multiple packed width."""
    nq, wc = sigs[0][0]                     # ids [Q, W]
    tq = sigs[1][0][1]                      # q or qe [Q, Tq, *]
    n_pad, cap, _wd = sigs[-2][0]           # toks [N_pad, cap, W]
    return (dispatch.is_query_bucket(nq)
            and wc >= 1 and (dispatch.in_k_grid(wc) or wc % LANE == 0)
            and tq >= 1 and (tq & (tq - 1)) == 0
            and cap >= 1 and (cap & (cap - 1)) == 0
            and n_pad >= 1 and (n_pad & (n_pad - 1)) == 0)


dispatch.DISPATCH.register(
    "maxsim.rescore", _maxsim_impl,
    static_argnames=("interpret",),
    grid_check=_grid_maxsim)


def _split_token_planes(q):
    """(even, odd) dim planes of a [Q, Tq, D] token batch — the 3-D
    twin of `quant_codec.split_query_planes_jnp` (same bit layout, and
    a reshape rather than a strided index for the same reason)."""
    pairs = q.reshape(q.shape[0], q.shape[1], -1, 2)
    return pairs[..., 0], pairs[..., 1]


def maxsim_rescore(ids, q_tokens, toks, scales,
                   interpret: Optional[bool] = None):
    """Rescore candidate docs `ids` [Q, W] against the resident token
    blocks with the fused gather+MaxSim kernel.

    q_tokens [Q, Tq, D] f32 must be metric-prepped and zero-padded to
    the tile's lane width and a pow-2 Tq; toks/scales are the field's
    [N_pad, cap, W] device tile + [N_pad, 1, cap] per-token scales (the
    singleton axis is the resident layout, see `_maxsim_impl` — a
    per-call reshape would copy the whole array).
    Invalid candidate slots must point at an all-padding doc row (the
    field layout reserves one), which scores NEG_INF. Returns the
    [Q, W] f32 board."""
    if toks.dtype == jnp.uint8:
        qe, qo = _split_token_planes(q_tokens)
        return dispatch.call("maxsim.rescore", ids, None, qe, qo, toks,
                             scales, interpret=dispatch.pallas_interpret(interpret))
    return dispatch.call("maxsim.rescore", ids, q_tokens, None, None, toks,
                         scales, interpret=dispatch.pallas_interpret(interpret))


def maxsim_reference(ids, q_tokens, toks, scales):
    """Reference twin of the fused kernel — IDENTICAL math on
    IDENTICAL shapes: one [Tq, D] x [cap, D] dot per (query, candidate)
    pair, bf16 operands, f32 accumulation, per-token de-scale, NEG_INF
    padding mask, max-then-sum. The per-pair python loop is deliberate:
    a vmapped batch dot lowers to a different XLA contraction tiling
    with much larger drift, while per-pair dots replay the primitive the
    kernel body executes shape-for-shape. Residual few-ULP differences
    remain possible even so (the interpret-mode grid loop can steer XLA
    CPU to a different accumulation order for the same dot), so the
    parity tests pin ordering exactly and scores to tight tolerances —
    the convention test_pallas_parity.py established for the IVF twin."""
    import numpy as np

    ids = np.asarray(ids)
    q_tokens = jnp.asarray(q_tokens, dtype=jnp.float32)
    nq, wc = ids.shape
    int4 = toks.dtype == jnp.uint8
    dn = (((1,), (1,)), ((), ()))
    rows = []
    for qi in range(nq):
        row = []
        qtok = q_tokens[qi]
        if int4:
            qe = qtok[:, 0::2].astype(jnp.bfloat16)
            qo = qtok[:, 1::2].astype(jnp.bfloat16)
        else:
            qb = qtok.astype(jnp.bfloat16)
        for j in range(wc):
            tile = toks[ids[qi, j]]
            s = scales[ids[qi, j]]                      # [1, cap]
            if int4:
                lo = ((tile & jnp.uint8(0x0F)).astype(jnp.int32)
                      - 8).astype(jnp.bfloat16)
                hi = ((tile >> 4).astype(jnp.int32) - 8).astype(jnp.bfloat16)
                dots = (jax.lax.dot_general(
                            qe, lo, dn, preferred_element_type=jnp.float32)
                        + jax.lax.dot_general(
                            qo, hi, dn, preferred_element_type=jnp.float32))
            else:
                dots = jax.lax.dot_general(
                    qb, tile.astype(jnp.bfloat16), dn,
                    preferred_element_type=jnp.float32)
            masked = jnp.where(s > 0, dots * s, _NEG)
            row.append(jnp.sum(jnp.max(masked, axis=1)))
        rows.append(jnp.stack(row))
    return jnp.stack(rows).astype(jnp.float32)


def warmup_entries(n_pad: int, cap: int, packed_w: int, tok_dtype,
                   tq_rungs, w_buckets, query_buckets,
                   interpret: Optional[bool] = None):
    """(kernel, specs, statics) entries pre-compiling the fused MaxSim
    grid over the interactive buckets. `interpret` defaults through the
    same resolution serving uses, so the warmed programs ARE the ones
    `maxsim_rescore` dispatches."""
    entries = []
    interp = dispatch.pallas_interpret(interpret)
    toks_spec = jax.ShapeDtypeStruct((n_pad, cap, packed_w), tok_dtype)
    scales_spec = jax.ShapeDtypeStruct((n_pad, 1, cap), jnp.float32)
    int4 = tok_dtype == jnp.uint8
    for q in query_buckets:
        for tq in tq_rungs:
            qspec = jax.ShapeDtypeStruct(
                (q, tq, packed_w if int4 else packed_w), jnp.float32)
            for w in w_buckets:
                ids_spec = jax.ShapeDtypeStruct((q, w), jnp.int32)
                if int4:
                    args = (ids_spec, None, qspec, qspec, toks_spec,
                            scales_spec)
                else:
                    args = (ids_spec, qspec, None, None, toks_spec,
                            scales_spec)
                entries.append(("maxsim.rescore", args,
                                {"interpret": interp}))
    return entries
