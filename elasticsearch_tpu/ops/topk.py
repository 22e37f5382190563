"""Top-k selection and cross-block merge.

Replaces the reference's per-shard Lucene top-k heaps and the coordinator's
`SearchPhaseController.mergeTopDocs` (`action/search/SearchPhaseController.java:221-243`)
with `lax.top_k` plus a concat-and-reselect merge. `lax.top_k` is stable
(ties resolve to the lower index), so ordering the concatenation by shard
index reproduces the reference's tie-break-by-shard-index semantics.

Outermost calls route through `ops/dispatch.py`'s AOT executable cache
(shape-bucketed, counted); calls from inside an enclosing jit inline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops.similarity import NEG_INF


# ---------------------------------------------------------------------------
# The served board: one array a batch, so its result crosses to the host
# in one read. This module owns the format: kernels pack with `pack_board`,
# `vectors/store.py`'s finalizer and the generational fan-out's legs read
# it back with `split_board`.
# ---------------------------------------------------------------------------

def pack_board(scores: jax.Array, ids: jax.Array) -> jax.Array:
    """(float32 scores [Q, k], int32 ids [Q, k]) -> int32 [Q, 2k]: the
    scores' bit patterns beside the ids. The last operation INSIDE the
    program that computed the pair: no second launch, no arithmetic."""
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32),
         ids.astype(jnp.int32)], axis=1)


def split_board(board):
    """`pack_board` undone, (scores, ids). On the host (a numpy board, as
    the store's finalizer reads it) two views of the one buffer, no copy;
    on a device array two un-synced slices, for a caller that composes
    further device work on the pair (the generational fan-out's legs)."""
    k = board.shape[1] // 2
    scores, ids = board[:, :k], board[:, k:]
    if isinstance(board, np.ndarray):
        return scores.view(np.float32), ids
    return jax.lax.bitcast_convert_type(scores, jnp.float32), ids


def board_static(board: bool) -> dict:
    """The static argument that asks a kernel for `pack_board`'s form. It
    joins a dispatch key only when set, so the pair's keys (and the
    executables every other caller warmed) stay what they were."""
    return {"board": True} if board else {}


def _top_k_impl(scores: jax.Array, k: int):
    return jax.lax.top_k(scores, k)


def _masked_top_k_impl(scores: jax.Array, mask: jax.Array, k: int):
    masked = jnp.where(mask, scores, NEG_INF)
    return jax.lax.top_k(masked, k)


def _merge_top_k_impl(scores_blocks: jax.Array, index_blocks: jax.Array,
                      k: int, board: bool = False):
    b, q, kb = scores_blocks.shape
    flat_scores = jnp.transpose(scores_blocks, (1, 0, 2)).reshape(q, b * kb)
    flat_ids = jnp.transpose(index_blocks, (1, 0, 2)).reshape(q, b * kb)
    vals, pos = jax.lax.top_k(flat_scores, k)
    ids = jnp.take_along_axis(flat_ids, pos, axis=1)
    return pack_board(vals, ids) if board else (vals, ids)


def _grid_topk(statics, sigs) -> bool:
    """k on the ladder (or clamped to the scored width); 2-D score boards
    additionally require a bucketed query count."""
    shape = sigs[0][0]
    n = shape[-1]
    if not dispatch.in_k_grid(int(statics["k"]), limit=n):
        return False
    if len(shape) == 2:
        return dispatch.is_query_bucket(shape[0])
    return True


dispatch.DISPATCH.register("topk.top_k", _top_k_impl,
                           static_argnames=("k",), grid_check=_grid_topk)
dispatch.DISPATCH.register("topk.masked_top_k", _masked_top_k_impl,
                           static_argnames=("k",), grid_check=_grid_topk)
dispatch.DISPATCH.register("topk.merge_top_k", _merge_top_k_impl,
                           static_argnames=("k", "board"))


def top_k(scores: jax.Array, k: int):
    """scores [..., N] → (values [..., k], indices [..., k]) descending."""
    return dispatch.call("topk.top_k", scores, k=k)


def masked_top_k(scores: jax.Array, mask: jax.Array, k: int):
    """Top-k over scores where mask==True; masked-out slots score -inf.

    This is the device half of filtered kNN (BASELINE config 5): the host
    computes the filter bitset from the boolean query, ships it as a packed
    bool array, and the device applies it as an additive mask — the
    reference's collector-level filter composition
    (`BoolQueryBuilder` + `script_score`) doesn't translate to XLA.
    """
    return dispatch.call("topk.masked_top_k", scores, mask, k=k)


def merge_top_k(scores_blocks: jax.Array, index_blocks: jax.Array, k: int,
                board: bool = False):
    """Merge per-block top-k results into a global top-k.

    scores_blocks: [B, Q, k_b] per-block descending scores
    index_blocks:  [B, Q, k_b] matching global doc ids
    Returns (scores [Q, k], ids [Q, k]); with `board`, the two as
    `pack_board`'s one array.

    Concatenation is ordered by block (shard) index, so lax.top_k's stability
    gives the reference's tie-break (`mergeTopDocs:221` breaks equal scores by
    shard index).
    """
    return dispatch.call("topk.merge_top_k", scores_blocks, index_blocks, k=k,
                         **board_static(board))
