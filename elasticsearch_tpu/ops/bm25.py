"""Device-resident BM25 lexical scoring: tile-padded impacts + batched top-k.

The lexical half of the fused hybrid plan (`search/hybrid_plan.py`). The
round-3 record's one losing row (config 3 hybrid, 7.2 QPS) lost because
BM25 ran per-query in host Python while only the kNN leg rode the device.
Block-max / impact-ordered top-k literature (Ding & Suel, BMW 2011) frames
lexical scoring as bounded linear algebra over quantized impacts — exactly
the shape the MXU already serves for vectors — so this module gives text
fields the same treatment `vectors/store.py` gives `dense_vector`:

* build (at refresh): every posting's full BM25 impact
  ``idf(term) * (k1+1) * tf(freq, len)`` is precomputed ONCE and laid out
  as a tile-padded CSR — postings concatenated term-major, each term's run
  padded to TILE-lane boundaries, so the score stage moves whole
  lane-aligned tiles through HBM with zero per-row gathers (the same
  layout discipline as `ops/knn_ivf.py` partitions). Impacts quantize to
  bf16/int8 for HBM thrift; the default f32 keeps scores bit-identical to
  the host `search/queries.py` BM25 path (`native.bm25_score` computes the
  impacts here too, so even the C++-vs-numpy rounding choice matches).

* search: ONE device dispatch scores a whole batch of queries — a scan
  over each query's term tiles scatter-adds impacts into a [Q, n_slots]
  score board, a parallel match-count board enforces operator/
  minimum_should_match, and `lax.top_k` cuts the ranked window. Ties
  break by ascending row (slots are laid out in ascending global-row
  order), matching `native.topk`'s shard-level convention exactly.

* refresh deltas: per-segment CSR extractions are cached by segment id —
  an append-only refresh (new sealed segments, no new tombstones) only
  tokenizes/extracts the delta segments; impacts are recomputed from the
  cached extractions because idf/avg_len are corpus-global (a cheap
  vectorized pass, grouped by document frequency so `native.bm25_score`
  is called once per distinct df, not once per term).

A numpy host twin (`_score_host`) runs the identical math for corpora
below the device-dispatch break-even (`_prefer_device`), so routing is
invisible to callers.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu import native

TILE = 128

BM25_K1 = 1.2
BM25_B = 0.75


def _pad_query_bucket(tile_ids, boosts, required):
    """Pad a planned query batch up to the dispatch bucket (the jit
    specializes on Q, and a compile per distinct batch size would stall
    serving — same motive as vectors/store._pad_batch). Pad queries
    reference no tiles and require 1 match, so the required-mask keeps
    their whole board at -inf. Shared by the single-board and sharded
    scoring paths so their padding semantics can never diverge.
    Returns (tile_ids, boosts, required, n_pad)."""
    from elasticsearch_tpu.ops import dispatch
    n_real = tile_ids.shape[0]
    n_pad = dispatch.bucket_queries(n_real)
    if n_pad == n_real:
        return tile_ids, boosts, required, n_pad
    pad = n_pad - n_real
    tile_ids = np.concatenate(
        [tile_ids, np.full((pad, tile_ids.shape[1]), -1, dtype=np.int32)])
    boosts = np.concatenate(
        [boosts, np.zeros((pad, boosts.shape[1]), dtype=np.float32)])
    required = np.concatenate([required, np.ones(pad, dtype=np.int32)])
    return tile_ids, boosts, required, n_pad


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


# per-segment postings extraction lives in the shared segment block
# store (`elasticsearch_tpu/columnar/` — `PostingsBlock`): one
# extraction per (segment, field, live-set), shared across fields'
# consumers and evicted with the segment; the private per-instance
# `_seg_cache` dict is gone (tpulint TPU011 keeps it from growing back)


class LexicalField:
    """One text field's tile-padded impact layout over a reader snapshot.

    Host arrays are the source of truth (and the host scoring twin);
    device mirrors upload lazily on the first device-routed dispatch.

    Subclasses retarget the SAME scoring program at other posting
    sources by overriding the kernel/family names plus `sync` and
    `plan_queries` (`ops/sparse.py` does this for `rank_features`
    learned-sparse fields: stored weights become the impacts, query
    token weights fold into the boosts, everything below — boards,
    buckets, mesh twin, tie-breaks — is shared verbatim).
    """

    KERNEL = "bm25.topk"
    MESH_KERNEL = "bm25.mesh_topk"
    FAMILY = "bm25"

    def __init__(self, field: str, dtype: str = "f32"):
        self.field = field
        self.dtype = dtype              # f32 (exact) | bf16 | int8
        self.version: tuple = ()
        self.n_slots = 0
        self.row_map = np.zeros(0, dtype=np.int64)  # slot -> engine global row
        # tile-padded CSR (term-major): [n_tiles, TILE]
        self.tile_slots = np.full((0, TILE), -1, dtype=np.int32)
        self.tile_impacts = np.zeros((0, TILE), dtype=np.float32)
        self.term_tiles: Dict[str, Tuple[int, int]] = {}  # term -> (first, n)
        self.nnz = 0
        # columnar composition summary of the LAST rebuild (profile /
        # stats annotation — the delta-vs-full extraction ledger)
        self.columnar_refresh: dict = {}
        self._device = None             # (slots, impacts[, scales]) jnp arrays
        self._device_version: tuple = ()
        # mesh-replicated tile mirrors, one entry per mesh the router
        # dispatches on (full serving mesh + dp-group submeshes when
        # dp > 1); dropped whole on any corpus version change
        self._device_mesh: dict = {}
        self._device_mesh_version: tuple = ()

    # ------------------------------------------------------------- build
    def sync(self, reader) -> bool:
        """(Re)build from a reader snapshot; returns True if rebuilt.
        Per-segment extractions come from the shared segment block store
        (`columnar.STORE.postings_block`, cached by fingerprint), so
        append-only refreshes pay tokenized extraction only for the
        delta segments."""
        from elasticsearch_tpu import columnar
        version = tuple((v.segment.seg_id, v.segment.num_docs,
                         int(v.live.sum())) for v in reader.views)
        if version == self.version:
            return False
        segs: List = []
        n_cached = n_extracted = 0
        for view in reader.views:
            blk, was_cached = columnar.STORE.postings_block(
                view, self.field)
            if was_cached:
                n_cached += 1
            else:
                n_extracted += 1
            segs.append(blk)
        mode = columnar.STORE.note_composition(
            self.field, "postings", n_cached, n_extracted)
        self.columnar_refresh = {
            "blocks": n_cached + n_extracted, "cached": n_cached,
            "extracted": n_extracted, "mode": mode}

        # dense slot space: segment-major, ascending local order — the
        # row map is therefore ascending iff reader views are base-ordered
        # (they are), which is what makes slot-index tie-breaks equal
        # row tie-breaks
        bases = []
        total = 0
        row_parts = []
        for view, sp in zip(reader.views, segs):
            bases.append(total)
            live_locals = np.nonzero(view.live)[0]
            row_parts.append(live_locals.astype(np.int64)
                            + view.segment.base)
            total += sp.n_live
        self.n_slots = total
        self.row_map = (np.concatenate(row_parts) if row_parts
                        else np.zeros(0, dtype=np.int64))
        lengths = (np.concatenate([sp.lengths for sp in segs])
                   if segs else np.zeros(0, dtype=np.float32))

        # merge terms across segments (slots already ascending per segment
        # and bases ascend, so concatenation keeps ascending order)
        merged: Dict[str, List[Tuple[np.ndarray, np.ndarray]]] = {}
        for base, sp in zip(bases, segs):
            for term, (slots, freqs) in sp.terms.items():
                merged.setdefault(term, []).append((slots + base, freqs))

        # global stats — the SAME quantities bm25_scores() reads live
        n = max(reader.docs_with_field_count(self.field), 1)
        avg_len = reader.avg_field_length(self.field) or 1.0

        terms = sorted(merged)
        ptr = [0]
        slot_parts, freq_parts, dfs = [], [], []
        for t in terms:
            chunks = merged[t]
            s = (np.concatenate([c[0] for c in chunks])
                 if len(chunks) > 1 else chunks[0][0])
            f = (np.concatenate([c[1] for c in chunks])
                 if len(chunks) > 1 else chunks[0][1])
            slot_parts.append(s)
            freq_parts.append(f)
            dfs.append(len(s))
            ptr.append(ptr[-1] + len(s))
        slot_flat = (np.concatenate(slot_parts) if slot_parts
                     else np.zeros(0, dtype=np.int32))
        freq_flat = (np.concatenate(freq_parts) if freq_parts
                     else np.zeros(0, dtype=np.int32))
        self.nnz = len(slot_flat)
        len_flat = lengths[slot_flat] if self.nnz else \
            np.zeros(0, dtype=np.float32)

        # impacts, grouped by distinct df so native.bm25_score (the exact
        # engine the host query path uses) runs once per df value
        impact_flat = np.zeros(self.nnz, dtype=np.float32)
        dfs_arr = np.asarray(dfs, dtype=np.int64)
        import math
        for df in np.unique(dfs_arr):
            idf = math.log(1.0 + (n - int(df) + 0.5) / (int(df) + 0.5))
            t_idx = np.nonzero(dfs_arr == df)[0]
            pieces = [np.arange(ptr[i], ptr[i + 1]) for i in t_idx]
            gather = np.concatenate(pieces)
            impact_flat[gather] = native.bm25_score(
                freq_flat[gather], len_flat[gather], idf, avg_len,
                BM25_K1, BM25_B, 1.0)

        self._install_tiles(terms, dfs, ptr, slot_flat, impact_flat)
        self.version = version
        return True

    def _install_tiles(self, terms, dfs, ptr, slot_flat, impact_flat):
        """Tile-pad term-major flat (slot, impact) runs: each term's run
        rounds up to whole TILE-lane tiles. Shared verbatim with the
        learned-sparse subclass — the layout below the impact math is
        identical by construction."""
        n_tiles_per = [max(1, -(-df // TILE)) if df else 0 for df in dfs]
        total_tiles = sum(n_tiles_per)
        tile_slots = np.full((max(total_tiles, 1), TILE), -1, dtype=np.int32)
        tile_impacts = np.zeros((max(total_tiles, 1), TILE), dtype=np.float32)
        self.term_tiles = {}
        tile = 0
        for i, t in enumerate(terms):
            df = dfs[i]
            if not df:
                continue
            nt = n_tiles_per[i]
            flat_s = tile_slots[tile:tile + nt].reshape(-1)
            flat_i = tile_impacts[tile:tile + nt].reshape(-1)
            flat_s[:df] = slot_flat[ptr[i]:ptr[i + 1]]
            flat_i[:df] = impact_flat[ptr[i]:ptr[i + 1]]
            self.term_tiles[t] = (tile, nt)
            tile += nt
        self.tile_slots = tile_slots[:max(tile, 1)]
        self.tile_impacts = tile_impacts[:max(tile, 1)]

    # ------------------------------------------------------------ search
    def nbytes(self) -> int:
        per = {"f32": 4, "bf16": 2, "int8": 1}[self.dtype]
        return self.tile_slots.size * 4 + self.tile_impacts.size * per

    def _device_arrays(self):
        if self._device is not None and self._device_version == self.version:
            return self._device
        slots = jnp.asarray(self.tile_slots)
        if self.dtype == "bf16":
            impacts = jnp.asarray(self.tile_impacts, dtype=jnp.bfloat16)
            scales = None
        elif self.dtype == "int8":
            # per-tile symmetric scale (the quant codec's int8 recipe at
            # tile granularity: impacts within a tile share one term's
            # idf, so the dynamic range per tile is narrow)
            from elasticsearch_tpu.quant import codec as quant_codec
            enc = quant_codec.get("int8").encode_np(self.tile_impacts)
            impacts = jnp.asarray(enc.data)
            scales = jnp.asarray(enc.scales)
        else:
            impacts = jnp.asarray(self.tile_impacts)
            scales = None
        self._device = (slots, impacts, scales)
        self._device_version = self.version
        return self._device

    def _device_arrays_mesh(self, mesh):
        """Tile mirrors replicated across `mesh` (the sharded kernel
        reads every tile but scatter-adds only its own doc range, so the
        CSR replicates while the score board shards). Cached per mesh —
        the dp-vs-shard router alternates between the full mesh and its
        dp groups, and each must keep its mirror resident. The dict
        holds mesh OBJECTS as keys (not id(mesh)): a GC'd mesh's address
        can be reused by a differently-shaped one."""
        if self._device_mesh_version != self.version:
            self._device_mesh = {}
            self._device_mesh_version = self.version
        cached = self._device_mesh.get(mesh)
        if cached is not None:
            return cached
        import jax
        from jax.sharding import NamedSharding

        from elasticsearch_tpu.parallel import layout
        repl = NamedSharding(mesh, layout.replicated_spec())
        slots, impacts, scales = self._device_arrays()
        arrays = (
            jax.device_put(slots, repl), jax.device_put(impacts, repl),
            None if scales is None else jax.device_put(scales, repl))
        return self._device_mesh.setdefault(mesh, arrays)

    def plan_queries(self, queries: Sequence[Tuple[Sequence[str], float]]
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Resolve (terms, boost) per query to padded tile id / boost
        matrices; per-query required-match counts are the caller's
        business (operator semantics live in the plan layer).

        Every tile of every resolved term is scanned — NO truncation: the
        scan work is O(touched postings), the same bound the host query
        path pays, so dropping tiles would silently change scores without
        saving the corpus-bound part of the cost."""
        per_q: List[List[Tuple[int, float]]] = []
        for terms, boost in queries:
            tiles: List[Tuple[int, float]] = []
            for t in terms:
                span = self.term_tiles.get(t)
                if span is None:
                    continue
                first, nt = span
                tiles.extend((first + j, boost) for j in range(nt))
            per_q.append(tiles)
        m = _pow2(max(max((len(t) for t in per_q), default=1), 1))
        tile_ids = np.full((len(per_q), m), -1, dtype=np.int32)
        boosts = np.zeros((len(per_q), m), dtype=np.float32)
        for qi, tiles in enumerate(per_q):
            for j, (tid, b) in enumerate(tiles):
                tile_ids[qi, j] = tid
                boosts[qi, j] = b
        return tile_ids, boosts, m

    def _score_host(self, tile_ids, boosts, required, k):
        """Numpy twin of the device kernel: identical accumulation order
        (term-major, f32), identical tie-breaks."""
        nq = tile_ids.shape[0]
        out = []
        for qi in range(nq):
            scores = np.zeros(self.n_slots, dtype=np.float32)
            counts = np.zeros(self.n_slots, dtype=np.int32)
            for tid, b in zip(tile_ids[qi], boosts[qi]):
                if tid < 0:
                    continue
                s = self.tile_slots[tid]
                valid = s >= 0
                sv = s[valid]
                scores[sv] += self.tile_impacts[tid][valid] * np.float32(b)
                counts[sv] += 1
            req = int(required[qi])
            elig = np.nonzero(counts >= max(req, 1))[0]
            kk = min(k, len(elig))
            top = native.topk(scores[elig], kk)
            sel = elig[top]
            out.append((self.row_map[sel],
                        scores[sel].astype(np.float32)))
        return out

    def _score_device_mesh(self, tile_ids, boosts, required, k, mesh):
        """Doc-range-sharded SPMD scoring: every shard scatter-adds the
        replicated impact CSR into ITS slot range's board, local top-k,
        all-gather merge (`bm25.mesh_topk`). Bit-identical sums to the
        single-board kernel (same term-major add order per slot), ties
        preserved (merge concatenates ascending shard = ascending slot
        ranges). Returns None when the sharded program can't hold the
        contract (ranked window deeper than a shard's slot range) — the
        caller then runs the single-device board."""
        from elasticsearch_tpu.ops import dispatch
        from elasticsearch_tpu.parallel import mesh as mesh_lib
        from elasticsearch_tpu.parallel import policy

        n_shards = int(mesh.shape[mesh_lib.SHARD_AXIS])
        width = _pow2(max(-(-self.n_slots // n_shards), 1))
        k_req = min(k, max(self.n_slots, 1))
        k_b = dispatch.bucket_k(k_req, limit=width)
        if k_req > width:
            return None
        n_real = tile_ids.shape[0]
        tile_ids, boosts, required, n_pad = _pad_query_bucket(
            tile_ids, boosts, required)
        slots_d, impacts_d, scales_d = self._device_arrays_mesh(mesh)
        # launch-guarded enqueue: collective programs sharing devices
        # must enqueue in one order (parallel/mesh.launch_guard)
        with mesh_lib.launch_guard(mesh):
            vals, gslots = dispatch.call(
                self.MESH_KERNEL, jnp.asarray(tile_ids),
                jnp.asarray(boosts),
                jnp.asarray(required.astype(np.int32)), slots_d,
                impacts_d, scales_d, k=k_b, width=width, mesh=mesh)
        vals = np.asarray(vals)[:, :k_req]
        gslots = np.asarray(gslots)[:, :k_req]
        out = []
        for qi in range(n_real):
            v, si = vals[qi], gslots[qi]
            keep = (v > -np.inf) & (si >= 0) & (si < self.n_slots)
            v, si = v[keep], si[keep]
            out.append((self.row_map[si], v.astype(np.float32)))
        policy.record_leg(self.FAMILY,
                          policy.gather_bytes(n_shards, n_pad, k_b))
        return out

    def _score_device(self, tile_ids, boosts, required, k):
        from elasticsearch_tpu.ops import dispatch
        from elasticsearch_tpu.parallel import policy

        mesh = policy.decide(
            self.FAMILY, self.n_slots,
            batch=dispatch.bucket_queries(tile_ids.shape[0]))
        if mesh is not None:
            out = self._score_device_mesh(tile_ids, boosts, required, k,
                                          mesh)
            if out is not None:
                return out
            # ranked window deeper than one shard's slot range: the
            # sharded merge would be lossy, so this dispatch ran
            # single-device after all — keep the router stats honest
            policy.reclassify_single(
                self.FAMILY + "_window_deeper_than_shard")

        n_real = tile_ids.shape[0]
        tile_ids, boosts, required, n_pad = _pad_query_bucket(
            tile_ids, boosts, required)
        slots_d, impacts_d, scales_d = self._device_arrays()
        # score-board width pads to a pow2 bucket: n_slots changes on
        # every refresh, and a jit re-specialization per refresh would
        # stall the first post-refresh batch for seconds — pad slots
        # score 0 with match-count 0, so the required-mask turns them to
        # -inf and they can never surface
        n_slots_pad = _pow2(max(self.n_slots, 1))
        # window k rounds up the dispatch bucket ladder (one compile per
        # rung, results sliced back down — lax.top_k prefixes are exact)
        k_req = min(k, max(self.n_slots, 1))
        k_b = dispatch.bucket_k(k_req, limit=n_slots_pad)
        # score/count boards are allocated here and DONATED: XLA reuses
        # their HBM for the scan carry instead of holding board + carry
        # live at once — the largest transient of the lexical path
        scores0 = jnp.zeros((n_pad, n_slots_pad + 1), dtype=jnp.float32)
        counts0 = jnp.zeros((n_pad, n_slots_pad + 1), dtype=jnp.int32)
        vals, slot_idx = dispatch.call(
            self.KERNEL, scores0, counts0, jnp.asarray(tile_ids),
            jnp.asarray(boosts), jnp.asarray(required.astype(np.int32)),
            slots_d, impacts_d, scales_d, k=k_b)
        vals = np.asarray(vals)[:, :k_req]
        slot_idx = np.asarray(slot_idx)[:, :k_req]
        out = []
        for qi in range(n_real):
            v, si = vals[qi], slot_idx[qi]
            keep = v > -np.inf
            v, si = v[keep], si[keep]
            out.append((self.row_map[si], v.astype(np.float32)))
        return out

    def search_batch(self, queries, window: int, required=None,
                     route: str = "auto"):
        """Score a batch of (terms, boost) queries; returns per query
        (global rows ranked by (-score, row), f32 scores), len <= window.

        required: per-query minimum matched clauses (operator=and /
        minimum_should_match), default 1.
        """
        if self.n_slots == 0 or not self.term_tiles:
            return [(np.zeros(0, dtype=np.int64),
                     np.zeros(0, dtype=np.float32)) for _ in queries]
        tile_ids, boosts, _m = self.plan_queries(queries)
        if required is None:
            required = np.ones(len(queries), dtype=np.int32)
        else:
            required = np.asarray(required, dtype=np.int32)
        if route == "host" or (route == "auto"
                               and not self._prefer_device(len(queries))):
            res = self._score_host(tile_ids, boosts, required, window)
        else:
            res = self._score_device(tile_ids, boosts, required, window)
        return res[:len(queries)]

    def _prefer_device(self, batch: int) -> bool:
        """Device dispatch pays the fixed round-trip; the host twin pays a
        scan over ~nnz + n_slots per query, priced for the scatter-bound
        lexical shape."""
        from elasticsearch_tpu.ops import dispatch
        host_ms = batch * (self.nnz + self.n_slots) / 2.0e8 * 1000.0
        return host_ms > dispatch.device_overhead_ms()


def _bm25_topk(scores0, counts0, tile_ids, boosts, required, tile_slots,
               tile_impacts, tile_scales, k: int):
    """One-dispatch batched BM25 window: scan each query's term tiles,
    scatter-add impacts into a [Q, n_slots_pad(+1)] score board (slot
    n_slots_pad is the padding trash lane), mask by match count,
    lax.top_k.

    scores0/counts0 are caller-allocated zero boards, DONATED through the
    dispatch layer (`ops/dispatch.py` registers this kernel with
    donate_argnums=(0, 1)): the caller must treat them as consumed. Their
    width is the caller's pow2 bucket over the live-doc count, so
    refreshes don't re-specialize the program; pad slots keep count 0 and
    mask to -inf. Accumulation is term-major in query order — each
    (term, doc) posting lands in exactly one tile, so per-doc adds happen
    in query-term order and the f32 sums are bit-identical to the host
    union-sum fold.
    """
    nq = tile_ids.shape[0]
    n_slots_pad = scores0.shape[1] - 1
    qi = jnp.arange(nq)

    def body(carry, inp):
        scores, counts = carry
        tid, b = inp                                   # [Q], [Q]
        safe = jnp.maximum(tid, 0)
        slots = tile_slots[safe]                       # [Q, TILE]
        imp = tile_impacts[safe].astype(jnp.float32)
        if tile_scales is not None:
            imp = imp * tile_scales[safe][:, None]
        imp = imp * b[:, None]
        valid = (tid >= 0)[:, None] & (slots >= 0)
        tgt = jnp.where(valid, slots, n_slots_pad)
        scores = scores.at[qi[:, None], tgt].add(
            jnp.where(valid, imp, 0.0))
        counts = counts.at[qi[:, None], tgt].add(
            jnp.where(valid, 1, 0))
        return (scores, counts), None

    (scores, counts), _ = jax.lax.scan(
        body, (scores0, counts0), (tile_ids.T, boosts.T))
    sc = scores[:, :n_slots_pad]
    ct = counts[:, :n_slots_pad]
    masked = jnp.where(ct >= jnp.maximum(required, 1)[:, None],
                       sc, -jnp.inf)
    return jax.lax.top_k(masked, k)


def _grid_bm25(statics, sigs) -> bool:
    """Bucketed query count, pow-2 board width (the _pow2(n_slots) pad —
    NOT the query-bucket ladder: tiny corpora legitimately produce 2/4
    wide boards), k on the ladder (or clamped to the board)."""
    from elasticsearch_tpu.ops import dispatch
    nq, width = sigs[0][0]           # scores0 [Q, n_slots_pad + 1]
    w = width - 1
    return (dispatch.is_query_bucket(nq)
            and w >= 1 and (w & (w - 1)) == 0
            and dispatch.in_k_grid(int(statics["k"]), limit=w))


def _bm25_topk_sharded(tile_ids, boosts, required, tile_slots,
                       tile_impacts, tile_scales, k: int, width: int,
                       mesh):
    """Doc-range-sharded BM25 window: shard s owns global slots
    [s*width, (s+1)*width); each shard scans the SAME replicated tiles
    but scatter-adds only its own range into a local [Q, width+1] board
    (allocated in-program — no donated transient), masks by match count,
    takes a local top-k, and the [S, Q, k] candidates merge over ICI.

    Per-slot accumulation order is the single-board kernel's (term-major
    in query order), so scores are bit-identical; the merge concatenates
    shards in ascending slot-range order, so score ties still resolve to
    the ascending global slot — `native.topk`'s convention.

    Cost shape: the tile SCAN is replicated on every shard (only the
    score board and its top-k shard), so this wins on board-bound
    workloads (large n_slots) and is roughly flat on scatter-bound ones;
    partitioning the tiles themselves by doc range is the follow-up that
    would shard the scan too."""
    from elasticsearch_tpu.ops.topk import merge_top_k
    from elasticsearch_tpu.parallel import mesh as mesh_lib
    from elasticsearch_tpu.parallel.sharded_knn import shard_map

    def body_shard(tids, bsts, req, t_slots, t_impacts, t_scales):
        nq = tids.shape[0]
        shard_id = jax.lax.axis_index(mesh_lib.SHARD_AXIS)
        lo = shard_id * width
        qi = jnp.arange(nq)
        scores0 = jnp.zeros((nq, width + 1), dtype=jnp.float32)
        counts0 = jnp.zeros((nq, width + 1), dtype=jnp.int32)

        def step(carry, inp):
            scores, counts = carry
            tid, b = inp
            safe = jnp.maximum(tid, 0)
            slots = t_slots[safe]                      # [Q, TILE] global
            imp = t_impacts[safe].astype(jnp.float32)
            if t_scales is not None:
                imp = imp * t_scales[safe][:, None]
            imp = imp * b[:, None]
            local = slots - lo
            valid = ((tid >= 0)[:, None] & (slots >= 0)
                     & (local >= 0) & (local < width))
            tgt = jnp.where(valid, local, width)
            scores = scores.at[qi[:, None], tgt].add(
                jnp.where(valid, imp, 0.0))
            counts = counts.at[qi[:, None], tgt].add(
                jnp.where(valid, 1, 0))
            return (scores, counts), None

        (scores, counts), _ = jax.lax.scan(
            step, (scores0, counts0), (tids.T, bsts.T))
        sc = scores[:, :width]
        ct = counts[:, :width]
        masked = jnp.where(ct >= jnp.maximum(req, 1)[:, None],
                           sc, -jnp.inf)
        vals, idx = jax.lax.top_k(masked, k)
        gslots = jnp.where(vals > -jnp.inf, idx + lo, -1)
        all_v = jax.lax.all_gather(vals, mesh_lib.SHARD_AXIS)
        all_s = jax.lax.all_gather(gslots, mesh_lib.SHARD_AXIS)
        return merge_top_k(all_v, all_s, k)

    from elasticsearch_tpu.parallel import layout

    # rule-driven specs (parallel/layout.py): query-side inputs split
    # over dp (each dp row scores its batch slice against the full
    # replicated CSR), tiles replicate — the dp axis applies here with
    # no hand-widened specs
    q2, q1 = layout.query_spec(2), layout.query_spec(1)
    repl = layout.replicated_spec()
    in_specs = (q2, q2, q1, repl, repl)
    if tile_scales is None:
        def run(tids, bsts, req, t_slots, t_impacts):
            return body_shard(tids, bsts, req, t_slots, t_impacts, None)
        fn = shard_map(run, mesh=mesh, in_specs=in_specs,
                       out_specs=(q2, q2))
        return fn(tile_ids, boosts, required, tile_slots, tile_impacts)
    # tile_scales is rank-1 [T]: a rank-2 spec would be rejected by
    # shard_map's rank check
    fn = shard_map(body_shard, mesh=mesh,
                   in_specs=in_specs + (repl,), out_specs=(q2, q2))
    return fn(tile_ids, boosts, required, tile_slots, tile_impacts,
              tile_scales)


def _grid_bm25_mesh(statics, sigs) -> bool:
    """Bucketed query count, pow-2 per-shard board width, k on the
    ladder (or clamped to the shard width)."""
    from elasticsearch_tpu.ops import dispatch
    nq = sigs[0][0][0]                # tile_ids [Q, M]
    w = int(statics["width"])
    return (dispatch.is_query_bucket(nq)
            and w >= 1 and (w & (w - 1)) == 0
            and dispatch.in_k_grid(int(statics["k"]), limit=w))


def _register_bm25():
    from elasticsearch_tpu.ops import dispatch
    dispatch.DISPATCH.register("bm25.topk", _bm25_topk,
                               static_argnames=("k",),
                               donate_argnums=(0, 1),
                               grid_check=_grid_bm25)
    dispatch.DISPATCH.register("bm25.mesh_topk", _bm25_topk_sharded,
                               static_argnames=("k", "width", "mesh"),
                               grid_check=_grid_bm25_mesh)


_register_bm25()


class LexicalShard:
    """Per-reader lexical store: one LexicalField per text field, synced
    lazily on first hybrid use (unlike the vector store's eager refresh
    listener — most refreshes never serve a hybrid query, and the build
    is a full tokenized-postings pass)."""

    FIELD_CLS: type = None  # set below (LexicalField) — subclasses override

    def __init__(self, dtype: str = "f32"):
        self.dtype = dtype
        self._fields: Dict[str, LexicalField] = {}
        self._lock = threading.Lock()
        self.stats = {"searches": 0, "queries": 0, "rebuilds": 0,
                      "score_nanos": 0}

    def field(self, reader, name: str) -> LexicalField:
        with self._lock:
            lf = self._fields.get(name)
            if lf is None:
                lf = self.FIELD_CLS(name, dtype=self.dtype)
                self._fields[name] = lf
            if lf.sync(reader):
                self.stats["rebuilds"] += 1
            return lf

    def search_batch(self, reader, field: str, queries, window: int,
                     required=None, route: str = "auto"):
        import time
        lf = self.field(reader, field)
        t0 = time.perf_counter_ns()
        out = lf.search_batch(queries, window, required=required,
                              route=route)
        self.stats["searches"] += 1
        self.stats["queries"] += len(queries)
        self.stats["score_nanos"] += time.perf_counter_ns() - t0
        return out


LexicalShard.FIELD_CLS = LexicalField
