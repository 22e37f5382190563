"""Multi-device sharded kNN: scatter-gather as one compiled SPMD program.

The reference's multi-shard search is a coordinator RPC fan-out
(`AbstractSearchAsyncAction.performPhaseOnShard:214`) followed by a
host-side heap merge (`SearchPhaseController.mergeTopDocs:221`). Here the
whole scatter-gather collapses into a single shard_map program:

  1. each mesh column scores its corpus slice (local matmul + top-k),
  2. local doc ids are rebased to global ids via the shard axis index
     (padding rows are masked to -inf / id -1 BEFORE the gather, so a
     ragged shard can never leak aliased ids into the merge),
  3. `lax.all_gather` over the "shard" axis moves the tiny [S, Q, k]
     candidate set across ICI,
  4. every device computes the identical global top-k merge.

No host round-trip, no reduce thread, no `batched_reduce_size` staging — the
merge cost is O(S·Q·k) on ICI, not O(network RPC).

Serving integration (PR 5): the program executes through the shape-bucketed
dispatch cache (`ops/dispatch.py`, kernel ``mesh.knn`` keyed on
(mesh, bucket)), so steady-state sharded traffic never compiles; the
``mesh.append`` kernel writes refresh deltas into each shard's padded
headroom copy-on-write (only the delta crosses PCIe, and the old
buffers are NOT donated — in-flight searches keep a valid snapshot);
and `ShardedFieldState` is the host-side bookkeeping `vectors/store.py`
keeps per mesh-resident field (slot maps, per-shard fill, filter masks).

Sharding over hosts (DCN) uses the same program under multi-process JAX; the
mesh simply spans processes.
"""

from __future__ import annotations

import functools
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops import knn as knn_ops
from elasticsearch_tpu.ops import similarity as sim
from elasticsearch_tpu.ops.similarity import NEG_INF
from elasticsearch_tpu.ops.topk import board_static
from elasticsearch_tpu.parallel import layout
from elasticsearch_tpu.parallel import mesh as mesh_lib


def shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off — the one place the
    package builds sharded programs from (tpulint TPU001)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class ShardedCorpus(NamedTuple):
    """Global-view corpus arrays laid out for a (dp, shard) mesh.

    matrix:    [S * rows_per_shard, D] — row-sharded over "shard"
    sq_norms:  [S * rows_per_shard]
    scales:    [S * rows_per_shard]
    num_valid: [S] int32 — valid row count per shard slice
    """

    matrix: jax.Array
    sq_norms: jax.Array
    scales: jax.Array
    num_valid: jax.Array


class ShardLayout(NamedTuple):
    """Host-side layout metadata (NOT part of the device pytree).

    n_shards:       mesh shard-axis size
    docs_per_shard: contiguous original rows assigned to each shard (balanced)
    rows_per_shard: padded device rows per shard (>= docs_per_shard; the
                    slack is append headroom for the write path)
    """

    n_shards: int
    docs_per_shard: int
    rows_per_shard: int

    def to_original_ids(self, global_ids: np.ndarray) -> np.ndarray:
        """Device global row id → original corpus row index (only valid
        for the contiguous build layout — after device appends the
        `ShardedFieldState.slot_map` is authoritative). id -1 (masked
        padding) maps to -1."""
        per, chunk = self.rows_per_shard, self.docs_per_shard
        ids = (global_ids // per) * chunk + (global_ids % per)
        return np.where(global_ids < 0, -1, ids)

    def to_global_ids(self, original_ids: np.ndarray) -> np.ndarray:
        per, chunk = self.rows_per_shard, self.docs_per_shard
        return (original_ids // chunk) * per + (original_ids % chunk)


def build_sharded_corpus(
    vectors: np.ndarray,
    mesh: Mesh,
    metric: str = sim.COSINE,
    dtype: str = "bf16",
    min_headroom: int = 0,
):
    """Partition host vectors into balanced contiguous chunks across shards.

    Mirrors the reference's fixed-shard-count document routing
    (`OperationRouting`: hash mod num_shards) with balanced range
    partitioning: each shard holds `docs_per_shard` contiguous rows padded to
    `rows_per_shard` device rows (the slack doubles as append headroom).
    Returns (ShardedCorpus, ShardLayout).
    """
    n_shards = mesh.shape[mesh_lib.SHARD_AXIS]
    n, d = vectors.shape
    chunk = (n + n_shards - 1) // n_shards
    per = knn_ops.pad_rows(max(chunk + min_headroom, 1))

    # Build entirely in host numpy, then ONE sharded device_put per array —
    # a jnp.concatenate here would materialize the full matrix on a single
    # device before resharding, OOMing exactly at the corpus scale sharding
    # exists for (30.7 GB corpus vs 16 GB/core HBM).
    matrix_host = np.zeros((n_shards * per, d), dtype=np.float32)
    sq_host = np.zeros(n_shards * per, dtype=np.float32)
    num_valid = np.zeros(n_shards, dtype=np.int32)
    for s in range(n_shards):
        lo, hi = min(s * chunk, n), min((s + 1) * chunk, n)
        block = np.asarray(vectors[lo:hi], dtype=np.float32)
        if metric == sim.COSINE and len(block):
            norms = np.linalg.norm(block, axis=-1, keepdims=True)
            block = block / np.maximum(norms, 1e-30)
        matrix_host[s * per: s * per + (hi - lo)] = block
        sq_host[s * per: s * per + (hi - lo)] = (block * block).sum(axis=-1)
        num_valid[s] = hi - lo

    if dtype == "int8":
        from elasticsearch_tpu.ops.quantization import quantize_int8_np
        matrix_host, scales_host = quantize_int8_np(matrix_host)
    elif dtype in ("int4", "binary"):
        # packed ladder rungs shard exactly like f32 rows: the codec
        # packs per row, so the [S·per, W] matrix and its per-row aux
        # scales both ride the `shard_rows` layout rule unchanged
        from elasticsearch_tpu.quant import codec as quant_codec
        enc = quant_codec.get(dtype).encode_np(matrix_host)
        matrix_host, scales_host = enc.data, enc.scales
    else:
        if dtype == "bf16":
            import ml_dtypes
            matrix_host = matrix_host.astype(ml_dtypes.bfloat16)
        scales_host = np.ones(n_shards * per, dtype=np.float32)
    # ONE rule-driven upload for the whole pytree (parallel/layout.py):
    # rows shard over "shard" and replicate across every dp row, so each
    # dp group holds a complete copy and group views come for free
    corpus = layout.shard_put(
        ShardedCorpus(matrix_host, sq_host, scales_host, num_valid), mesh)
    return corpus, ShardLayout(n_shards, chunk, per)


# ---------------------------------------------------------------------------
# Search program (dispatched: kernel "mesh.knn")
# ---------------------------------------------------------------------------

def _knn_step(q, mat, sqn, scl, nvalid, fmask, *, k, metric, precision,
              block_size, board):
    """Per-shard body: local exact kNN, padding masked OUT before the
    gather (a ragged shard whose num_valid < k would otherwise feed
    aliased padding ids into the merge), then the ICI candidate merge."""
    from elasticsearch_tpu.ops.topk import merge_top_k, pack_board

    # the three scopes name the program's parts in a device trace: the
    # shard's own scoring and top-k, the candidates' way over ICI, and
    # the merge every device makes of them
    with jax.named_scope("es.mesh.score"):
        local = knn_ops.Corpus(mat, sqn, scl, nvalid[0])
        rows_per_shard = mat.shape[0]
        s, i = knn_ops.knn_search(q, local, k, metric=metric,
                                  filter_mask=fmask, precision=precision,
                                  block_size=block_size)
        shard_id = jax.lax.axis_index(mesh_lib.SHARD_AXIS)
        # the local top-k returns NEG_INF for padding/filtered slots but
        # an ARBITRARY row index beside it; pin both so no consumer can
        # alias
        valid = s > NEG_INF
        s = jnp.where(valid, s, -jnp.inf)
        gids = jnp.where(valid, i + shard_id * rows_per_shard,
                         jnp.int32(-1))
    with jax.named_scope("es.mesh.gather"):
        all_s = jax.lax.all_gather(s, mesh_lib.SHARD_AXIS)  # [S, Qdp, k], ICI
        all_i = jax.lax.all_gather(gids, mesh_lib.SHARD_AXIS)
    with jax.named_scope("es.mesh.merge"):
        pair = merge_top_k(all_s, all_i, k)
        # every device holds the merged pair: each packs its own copy
        return pack_board(*pair) if board else pair


def _distributed_knn_impl(queries, corpus, filter_mask, k, mesh,
                          metric=sim.COSINE, precision="bf16",
                          block_size=None, board=False):
    # in_specs from the SAME rule table that laid the corpus out
    # (parallel/layout.py) — specs can't drift from residency, and the
    # dp axis applies here without widening any hand-built spec
    corpus_specs = layout.in_specs_for(corpus)
    out_specs = (layout.query_spec(2) if board
                 else (layout.query_spec(2), layout.query_spec(2)))
    step = functools.partial(_knn_step, k=k, metric=metric,
                             precision=precision, block_size=block_size,
                             board=board)
    if filter_mask is None:
        def step_nf(q, mat, sqn, scl, nvalid):
            return step(q, mat, sqn, scl, nvalid, None)
        fn = shard_map(
            step_nf, mesh=mesh,
            in_specs=(layout.query_spec(2),) + tuple(corpus_specs),
            out_specs=out_specs)
        return fn(queries, corpus.matrix, corpus.sq_norms, corpus.scales,
                  corpus.num_valid)
    fn = shard_map(
        step, mesh=mesh,
        in_specs=(layout.query_spec(2),) + tuple(corpus_specs)
        + (layout.mask_spec(filter_mask.ndim),), out_specs=out_specs)
    return fn(queries, corpus.matrix, corpus.sq_norms, corpus.scales,
              corpus.num_valid, filter_mask)


def _grid_mesh_knn(statics, sigs) -> bool:
    """Closed sharded grid: bucketed query count, k on the ladder (or
    clamped to the per-shard row count), lane-padded shard slices."""
    q_shape = sigs[0][0]                    # queries [Q, D]
    n_rows = sigs[1][0][0]                  # matrix [S * per, D]
    mesh = statics["mesh"]
    n_shards = mesh.shape[mesh_lib.SHARD_AXIS]
    per = n_rows // max(n_shards, 1)
    return (dispatch.is_query_bucket(q_shape[0])
            and dispatch.in_k_grid(int(statics["k"]), limit=per)
            and per % knn_ops.LANE == 0)


dispatch.DISPATCH.register(
    "mesh.knn", _distributed_knn_impl,
    static_argnames=("k", "mesh", "metric", "precision", "block_size",
                     "board"),
    grid_check=_grid_mesh_knn)


def distributed_knn_search(
    queries: jax.Array,
    corpus: ShardedCorpus,
    k: int,
    mesh: Mesh,
    metric: str = sim.COSINE,
    filter_mask: Optional[jax.Array] = None,
    precision: str = "bf16",
    block_size: Optional[int] = None,
    board: bool = False,
):
    """Search queries [Q, D] against a mesh-sharded corpus.

    Q must be divisible by the dp axis size. filter_mask is [S * per] (one
    shared searchable-set) or [Q, S * per] (per-query pre-filters).
    Returns (scores [Q, k], global_ids [Q, k]) fully replicated across the
    mesh; empty/padding slots come back as (-inf, -1). With `board`, the
    pair as one array [Q, 2k] (`topk.pack_board`), packed by the same
    program after its merge: the store's serving form.

    Executes through the shape-bucketed dispatch cache (kernel
    ``mesh.knn``, AOT executables keyed on (mesh, bucket)); calls from
    inside an enclosing jit (the bench scan harness) inline. The launch
    guard serializes the ENQUEUE per device set (collective programs
    that share devices must enqueue in one order) and returns un-synced
    arrays — dispatches on disjoint dp groups overlap end to end.
    """
    with mesh_lib.launch_guard(mesh):
        return dispatch.call("mesh.knn", queries, corpus, filter_mask,
                             k=k, mesh=mesh, metric=metric,
                             precision=precision, block_size=block_size,
                             **board_static(board))


# ---------------------------------------------------------------------------
# Incremental append (dispatched: kernel "mesh.append")
# ---------------------------------------------------------------------------

def _append_impl(matrix, sq_norms, scales, num_valid, new_mat, new_sq,
                 new_scales, new_counts, mesh):
    """Write per-shard delta rows into the padded headroom: refresh
    appends move only the delta across PCIe, never the resident corpus.
    The old buffers are NOT donated (see the registration below) — the
    program produces a fresh corpus pytree so searches in flight against
    the pre-append state keep reading valid arrays."""
    def step(mat, sqn, scl, nv, nmat, nsq, nscl, ncnt):
        m = nmat.shape[0]
        start = nv[0]
        lane = jnp.arange(m, dtype=jnp.int32)
        ok = lane < ncnt[0]
        # out-of-range target rows (beyond this shard's delta count) are
        # DROPPED by the scatter, leaving resident rows untouched
        tgt = jnp.where(ok, start + lane, jnp.int32(mat.shape[0]))
        mat = mat.at[tgt].set(nmat.astype(mat.dtype), mode="drop")
        sqn = sqn.at[tgt].set(nsq, mode="drop")
        scl = scl.at[tgt].set(nscl, mode="drop")
        return mat, sqn, scl, nv + ncnt[0]

    r2, r1 = layout.rows_spec(2), layout.rows_spec(1)
    fn = shard_map(
        step, mesh=mesh,
        in_specs=(r2, r1, r1, r1, r2, r1, r1, r1),
        out_specs=(r2, r1, r1, r1))
    mat, sqn, scl, nv = fn(matrix, sq_norms, scales, num_valid,
                           new_mat, new_sq, new_scales, new_counts)
    return ShardedCorpus(mat, sqn, scl, nv)


def _grid_mesh_append(statics, sigs) -> bool:
    """Delta row count per shard padded to a query-style bucket — refresh
    deltas of any size reuse a small closed set of append programs."""
    n_rows = sigs[4][0][0]                  # new_mat [S * m, D]
    mesh = statics["mesh"]
    n_shards = mesh.shape[mesh_lib.SHARD_AXIS]
    m = n_rows // max(n_shards, 1)
    return dispatch.is_query_bucket(m)


# NO donation: `ShardedFieldState.append` is copy-on-write — searches
# dispatched against the pre-append state mid-refresh still read the old
# buffers, so donating them would hand deleted arrays to a live dispatch
dispatch.DISPATCH.register(
    "mesh.append", _append_impl, static_argnames=("mesh",),
    grid_check=_grid_mesh_append)


# ---------------------------------------------------------------------------
# Host-side field state (the vectors/store.py mesh bookkeeping)
# ---------------------------------------------------------------------------

class ShardedFieldState:
    """One vector field's mesh-resident corpus + host bookkeeping.

    Owns the slot map (device global row -> flat corpus row index), the
    per-shard fill counts the append planner balances against, and the
    filter-mask builder. `append` places refresh deltas into the shards
    with the most headroom and ships ONLY the delta (kernel
    ``mesh.append``); when headroom runs out the caller rebuilds."""

    __slots__ = ("corpus", "layout", "mesh", "metric", "dtype",
                 "slot_map", "shard_counts", "n_rows", "_views",
                 "_views_lock")

    def __init__(self, vectors: np.ndarray, mesh: Mesh, metric: str,
                 dtype: str, min_headroom: Optional[int] = None):
        n = len(vectors)
        n_shards = mesh.shape[mesh_lib.SHARD_AXIS]
        chunk = (n + n_shards - 1) // n_shards
        if min_headroom is None:
            # append headroom: an eighth of the shard (>= one lane tile) —
            # refreshes append in place until the corpus grows 12.5%,
            # then one rebuild re-balances
            min_headroom = max(knn_ops.LANE, chunk // 8)
        self.corpus, self.layout = build_sharded_corpus(
            vectors, mesh, metric=metric, dtype=dtype,
            min_headroom=min_headroom)
        self.mesh = mesh
        self.metric = metric
        self.dtype = dtype
        self.n_rows = n
        self._views = {}
        self._views_lock = threading.Lock()
        per = self.layout.rows_per_shard
        self.slot_map = np.full(n_shards * per, -1, dtype=np.int64)
        self.shard_counts = np.zeros(n_shards, dtype=np.int64)
        for s in range(n_shards):
            lo, hi = min(s * chunk, n), min((s + 1) * chunk, n)
            self.slot_map[s * per: s * per + (hi - lo)] = np.arange(lo, hi)
            self.shard_counts[s] = hi - lo

    @property
    def n_shards(self) -> int:
        return self.layout.n_shards

    def headroom(self) -> int:
        return int((self.layout.rows_per_shard
                    - self.shard_counts).sum())

    def can_append(self, n_new: int) -> bool:
        return n_new <= self.headroom()

    def append(self, new_vectors: np.ndarray) -> "ShardedFieldState":
        """Place `new_vectors` (flat corpus rows n_rows..n_rows+m) into
        per-shard headroom, most-free shards first, and ship ONLY the
        delta with one ``mesh.append`` dispatch.

        Copy-on-write: returns a NEW state and leaves `self` (corpus
        buffers AND slot_map/shard_counts bookkeeping) untouched, so a
        search dispatched against the previously-installed FieldCorpus
        mid-refresh keeps a consistent snapshot. The delta program
        therefore must NOT donate the old buffers — append pays a
        transient second matrix allocation on device, but the host->
        device transfer (the cost that scales with the corpus) stays
        delta-sized."""
        m_total = len(new_vectors)
        if m_total == 0:
            return self
        per = self.layout.rows_per_shard
        S = self.n_shards
        free = per - self.shard_counts
        order = np.argsort(-free, kind="stable")
        counts = np.zeros(S, dtype=np.int64)
        remaining = m_total
        # water-fill: level the most-free shards first so the layout
        # stays balanced under repeated appends
        while remaining > 0:
            target = [s for s in order if free[s] - counts[s] > 0]
            if not target:
                raise ValueError("sharded corpus append exceeds headroom")
            share = max(1, remaining // len(target))
            for s in target:
                take = min(share, int(free[s] - counts[s]), remaining)
                counts[s] += take
                remaining -= take
                if remaining == 0:
                    break

        m_pad = dispatch.bucket_queries(int(counts.max()))
        d = new_vectors.shape[1]
        blocks = np.zeros((S * m_pad, d), dtype=np.float32)
        new_sq = np.zeros(S * m_pad, dtype=np.float32)
        new_scales = np.ones(S * m_pad, dtype=np.float32)
        slot_map = self.slot_map.copy()
        pos = 0
        for s in range(S):
            c = int(counts[s])
            if c == 0:
                continue
            block = np.asarray(new_vectors[pos:pos + c], dtype=np.float32)
            if self.metric == sim.COSINE:
                norms = np.linalg.norm(block, axis=-1, keepdims=True)
                block = block / np.maximum(norms, 1e-30)
            blocks[s * m_pad: s * m_pad + c] = block
            new_sq[s * m_pad: s * m_pad + c] = (block * block).sum(axis=-1)
            start = int(self.shard_counts[s])
            slot_map[s * per + start: s * per + start + c] = \
                np.arange(self.n_rows + pos, self.n_rows + pos + c)
            pos += c
        if self.dtype == "int8":
            from elasticsearch_tpu.ops.quantization import quantize_int8_np
            q8, sc = quantize_int8_np(blocks)
            blocks, new_scales = q8, sc
        elif self.dtype in ("int4", "binary"):
            from elasticsearch_tpu.quant import codec as quant_codec
            enc = quant_codec.get(self.dtype).encode_np(blocks)
            blocks, new_scales = enc.data, enc.scales
        elif self.dtype == "bf16":
            import ml_dtypes
            blocks = blocks.astype(ml_dtypes.bfloat16)
        nm = jax.device_put(blocks, mesh_lib.corpus_sharding(self.mesh))
        nsq = jax.device_put(new_sq, mesh_lib.per_shard_sharding(self.mesh))
        nsc = jax.device_put(new_scales,
                             mesh_lib.per_shard_sharding(self.mesh))
        ncnt = jax.device_put(counts.astype(np.int32),
                              mesh_lib.per_shard_sharding(self.mesh))
        # launch-guarded: the append program shares devices with every
        # in-flight search on this mesh, and interleaved collective
        # enqueues can deadlock the device streams
        with mesh_lib.launch_guard(self.mesh):
            corpus = dispatch.call(
                "mesh.append", self.corpus.matrix, self.corpus.sq_norms,
                self.corpus.scales, self.corpus.num_valid, nm, nsq, nsc,
                ncnt, mesh=self.mesh)
        new = ShardedFieldState.__new__(ShardedFieldState)
        new.corpus = corpus
        new.layout = self.layout
        new.mesh = self.mesh
        new.metric = self.metric
        new.dtype = self.dtype
        new.slot_map = slot_map
        new.shard_counts = self.shard_counts + counts
        new.n_rows = self.n_rows + m_total
        # fresh (empty) dp-group view cache: every replica view of the
        # NEW state derives from ITS corpus pytree, so an install can
        # never leave one dp group serving the pre-append arrays while
        # another serves the post-append ones
        new._views = {}
        new._views_lock = threading.Lock()
        return new

    # ---------------------------------------------------------- serving
    def corpus_for(self, mesh: Mesh) -> ShardedCorpus:
        """The corpus pytree to dispatch on `mesh`: the resident arrays
        for the build mesh, a cached dp-group VIEW for one of its
        submeshes. A view is a rule-driven re-layout (`layout.view_for`)
        of this state's dp-replicated arrays — the group's devices
        already hold every shard, so building one is device-side and
        ~free, and every group reads the SAME immutable snapshot: replica
        consistency is structural, not synchronized."""
        if mesh is self.mesh:
            return self.corpus
        with self._views_lock:
            view = self._views.get(mesh)
            if view is None:
                view = layout.view_for(self.corpus, mesh)
                self._views[mesh] = view
            return view

    def filter_mask(self, allowed_flat: np.ndarray) -> np.ndarray:
        """Map a flat-corpus-row bool mask [n_rows] to the device global
        row space [S * per] via the slot map."""
        m = np.zeros(len(self.slot_map), dtype=bool)
        vs = self.slot_map >= 0
        m[vs] = allowed_flat[self.slot_map[vs]]
        return m

    def map_ids(self, global_ids: np.ndarray) -> np.ndarray:
        """Device global ids -> flat corpus row indices (-1 invalid)."""
        out = np.full(global_ids.shape, -1, dtype=np.int64)
        ok = global_ids >= 0
        out[ok] = self.slot_map[global_ids[ok]]
        return out

    def query_sharding(self, mesh: Optional[Mesh] = None) -> NamedSharding:
        return mesh_lib.query_sharding(mesh if mesh is not None
                                       else self.mesh)

    def mask_sharding(self, ndim: int,
                      mesh: Optional[Mesh] = None) -> NamedSharding:
        mesh = mesh if mesh is not None else self.mesh
        return NamedSharding(mesh, layout.mask_spec(ndim))

    def warmup_entries(self, dims: int, precision: str = "bf16"):
        """(kernel, arg specs, statics) entries pre-compiling the sharded
        serving grid — mirrors `vectors/store._schedule_warmup` but with
        mesh-sharded input layouts baked into the AOT specs, and like it
        in the packed form the store's serving call launches. Every rung
        of the query ladder up to the grid's top is there (a burst forms
        the rungs between as well, and a compile of this program on the
        serving path is seconds), with the precision the caller serves
        in. With dp > 1
        the grid covers BOTH routes the dp-vs-shard router can pick: the
        full-mesh program (query buckets the dp axis divides) and every
        dp-group submesh (all interactive buckets), so strict mode stays
        zero-compile whichever way a dispatch routes."""
        per = self.layout.rows_per_shard
        from elasticsearch_tpu.parallel import policy
        meshes = [self.mesh]
        dp = mesh_lib.dp_size(self.mesh)
        if dp > 1:
            meshes.extend(policy.dp_groups(self.mesh))
        entries = []
        for mesh in meshes:
            corpus_spec = layout.shape_specs(self.corpus, mesh)
            mesh_dp = mesh_lib.dp_size(mesh)
            for q in dispatch.query_buckets_upto(
                    max(dispatch.WARMUP_QUERY_BUCKETS)):
                if q % mesh_dp:
                    continue   # the router never full-meshes this bucket
                qspec = jax.ShapeDtypeStruct(
                    (q, dims), jnp.float32,
                    sharding=mesh_lib.query_sharding(mesh))
                for k in dispatch.WARMUP_K_BUCKETS:
                    k_b = dispatch.bucket_k(min(k, per), limit=per)
                    entries.append((
                        "mesh.knn", (qspec, corpus_spec, None),
                        {"k": k_b, "mesh": mesh, "metric": self.metric,
                         "precision": precision, "block_size": None,
                         "board": True}))
        return entries


def extend_or_build(old_state: Optional[ShardedFieldState],
                    vectors: np.ndarray, prefix_rows: int, mesh: Mesh,
                    metric: str, dtype: str):
    """One owner for the append-vs-rebuild decision both refresh sync
    and the segments merge scheduler make: when `old_state` holds
    exactly the first `prefix_rows` of `vectors` (caller-verified row
    identity) on the same mesh/metric/dtype and its per-shard headroom
    fits the delta, ship ONLY the delta (``mesh.append``,
    copy-on-write); otherwise build the sharded corpus from scratch.
    Returns (state, appended)."""
    n = len(vectors)
    if (old_state is not None and old_state.mesh is mesh
            and old_state.dtype == dtype and old_state.metric == metric
            and old_state.n_rows == prefix_rows and 0 < prefix_rows <= n
            and old_state.can_append(n - prefix_rows)):
        if n == prefix_rows:
            return old_state, True
        return old_state.append(np.asarray(vectors[prefix_rows:],
                                           dtype=np.float32)), True
    return ShardedFieldState(np.asarray(vectors, dtype=np.float32),
                             mesh, metric, dtype), False
