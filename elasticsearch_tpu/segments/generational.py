# tpulint: hot-path
"""Generational corpus + background merge scheduler.

`GenerationalCorpus` is the device-side engine lifecycle the reference
gets from Lucene (PAPER.md, indices/engine layer): refresh SEALS delta
rows into small L0 generations (O(delta), never a corpus re-upload),
deletes flip per-generation tombstone masks, and a budgeted background
merge thread consolidates generations up the tier ladder — copy-on-write
installs, so a search dispatched against the previous generation set
keeps reading valid arrays (the `ShardedFieldState.append` contract,
applied to the whole corpus lifecycle).

The merge scheduler also owns the two expensive stories the refresh
thread must never pay:

* IVF — a merge that produces a new base generation re-enters the
  trained layout via `IVFIndex.clone().add(delta)` (copy-on-write: the
  old router keeps serving mid-merge); when drift trips
  `needs_retrain`, the k-means retrain runs HERE, on the merge thread;
* mesh — L0 generations stay single-device; a merge graduates the new
  base into the sharded serving corpus (`extend_or_build`: delta append
  into per-shard headroom when prefix-compatible, full SPMD build
  otherwise).

Search fans one dispatch per live generation (`segments.knn` for sealed
buckets, the monolithic `knn.exact` grid for the initial base) and fuses
the per-generation boards through the existing `ops/topk.merge_top_k` —
stable concatenation in generation order reproduces the monolithic
tie-break exactly, which is what makes generational serving
byte-identical to the single-corpus path.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops import knn as knn_ops
from elasticsearch_tpu.ops import similarity as sim
from elasticsearch_tpu.ops import topk as topk_ops
from elasticsearch_tpu.segments.generation import (
    Generation, build_generation)
from elasticsearch_tpu.segments.policy import MergeSpec, TieredMergePolicy
from elasticsearch_tpu.telemetry import stage as _stage
from elasticsearch_tpu.vectors import filter_mask

logger = logging.getLogger("elasticsearch_tpu.segments")

_NEG_INF_F32 = float(np.float32(-3.0e38))  # sim.NEG_INF as a host float


class GenerationSet:
    """Immutable snapshot of the live generations (the searchable view).

    The flat logical row space is the concatenation of the generations'
    row maps IN ORDER (tombstoned rows keep their slots — masked, not
    compacted — so positions are stable between merges)."""

    __slots__ = ("generations", "offsets", "row_map", "total_rows",
                 "total_pad", "dead_rows")

    def __init__(self, generations: Sequence[Generation]):
        self.generations = tuple(generations)
        sizes = [g.n_rows for g in self.generations]
        self.offsets = np.concatenate(
            [[0], np.cumsum(sizes)]).astype(np.int64) if sizes \
            else np.zeros(1, dtype=np.int64)
        self.row_map = (np.concatenate([g.row_map
                                        for g in self.generations])
                        if self.generations else np.zeros(0, dtype=np.int64))
        self.total_rows = int(self.offsets[-1])
        self.total_pad = sum(g.n_pad for g in self.generations)
        self.dead_rows = sum(g.dead_rows for g in self.generations)

    @property
    def simple(self) -> bool:
        """One clean generation — serving degenerates to the exact
        monolithic path (same kernels, same warmup grid)."""
        return (len(self.generations) == 1
                and not self.generations[0].has_tombstones)

    @property
    def l0_count(self) -> int:
        return sum(1 for g in self.generations if g.tier == 0)

    def live_row_map(self) -> np.ndarray:
        """Engine rows currently live, in flat order (the refresh
        classifier's baseline)."""
        if self.dead_rows == 0:
            return self.row_map
        return np.concatenate(
            [g.row_map[g.live_mask()] for g in self.generations]) \
            if self.generations else self.row_map

    def gather_rows(self, flat_ids: np.ndarray) -> np.ndarray:
        """Exact f32 host rows for ASCENDING UNIQUE flat row ids,
        resolved per generation through the shared block store
        (`Generation.source`) — the two-phase rescore's candidate
        gather (`quant/rescore.py`). O(window) rows materialize."""
        flat_ids = np.asarray(flat_ids, dtype=np.int64)
        if len(flat_ids) == 0 or not self.generations:
            d = (self.generations[0].source.dims
                 if self.generations else 0)
            return np.zeros((0, d), dtype=np.float32)
        out = np.zeros((len(flat_ids), self.generations[0].source.dims),
                       dtype=np.float32)
        for gen, off in zip(self.generations, self.offsets[:-1]):
            lo = int(off)
            hi = lo + gen.n_rows
            sel = (flat_ids >= lo) & (flat_ids < hi)
            if sel.any():
                out[sel] = gen.source.gather(flat_ids[sel] - lo)
        return out

    # ------------------------------------------------------------ search
    def search_async(self, queries: np.ndarray, n_real: int, k_eff: int,
                     filters: Sequence[Optional[np.ndarray]],
                     metric: str, precision: str,
                     num_candidates: Optional[int] = None,
                     knn_stats: Optional[dict] = None) -> Tuple:
        """Fan one dispatch per generation, fuse via `merge_top_k`.

        queries: [B_pad, D] f32, already padded to the query bucket.
        filters: per-request allowed engine-row arrays (or None), length
        n_real. Returns (board, phases): the un-synced [B_pad, 2·k_t]
        packed board (`topk_ops.pack_board`: scores beside ids in the
        FLAT row space), packed by the merge program itself — the
        caller lands it in one read at response-assembly time
        (`finalize_many`)."""
        import jax.numpy as jnp

        b_pad = len(queries)
        k_t = dispatch.bucket_k(k_eff, limit=self.total_pad)
        any_filter = any(fr is not None for fr in filters)
        qj = jnp.asarray(queries)
        # the batch's filters against every generation's row map, in ONE
        # stage a batch; the legs lay the rows out as their program
        # reads them (padded, or through a mesh's slot map)
        allowed = [None] * len(self.generations)
        if any_filter:
            with _stage("dispatch.mask_build"):
                allowed = [filter_mask.allowed_rows(
                    gen.locator, filters[:n_real], live=gen.live_mask())
                    if gen.n_rows else None for gen in self.generations]
                filter_mask.note_built(
                    filters[:n_real],
                    [gen.locator for gen in self.generations if gen.n_rows])
        board_s: List = []
        board_i: List = []
        legs: List[str] = []
        for gen, off, allow in zip(self.generations, self.offsets[:-1],
                                   allowed):
            if gen.n_rows == 0:
                continue
            s, ids, leg = self._search_generation(
                gen, int(off), qj, queries, b_pad, k_t, allow, metric,
                precision, num_candidates, knn_stats)
            board_s.append(s)
            board_i.append(ids)
            legs.append(leg)
        if not board_s:
            return (topk_ops.pack_board(
                jnp.full((b_pad, k_t), _NEG_INF_F32, dtype=jnp.float32),
                jnp.full((b_pad, k_t), -1, dtype=jnp.int32)),
                {"engine": "tpu_generational", "generations": 0})
        # stable concat in generation order == flat-order tie-break ==
        # the monolithic corpus's lower-row-index tie-break
        board = topk_ops.merge_top_k(jnp.stack(board_s),
                                     jnp.stack(board_i), k=k_t, board=True)
        phases = {"engine": "tpu_generational",
                  "generations": len(self.generations),
                  "l0_generations": self.l0_count,
                  "tombstoned_rows": self.dead_rows,
                  "legs": legs}
        return board, phases

    def _search_generation(self, gen: Generation, off: int, qj,
                           queries: np.ndarray, b_pad: int, k_t: int,
                           allowed: Optional[np.ndarray],
                           metric: str, precision: str,
                           num_candidates: Optional[int],
                           knn_stats: Optional[dict]):
        """One generation's board [B_pad, k_t] in flat ids: mesh / IVF /
        exhaustive leg selection mirrors the monolithic router.
        `allowed`: [n_real, n_rows] bool where the batch carries a filter
        (`filter_mask.allowed_rows`, tombstones already taken out)."""
        import jax.numpy as jnp

        n_pad = gen.n_pad
        need_mask = gen.has_tombstones or allowed is not None
        # -------- IVF leg (graduated base; tombstones drop the router)
        if gen.router is not None and not need_mask:
            reason = gen.router.should_fallback(
                min(k_t, gen.n_rows), False, precision)
            if reason is None:
                return self._ivf_board(gen, off, queries, k_t,
                                       num_candidates, knn_stats)
        # -------- mesh leg (graduated base; masks map via the slot map)
        if gen.mesh_state is not None:
            from elasticsearch_tpu.parallel import policy as mesh_policy
            # batch = the already-padded query bucket: with dp > 1 the
            # policy picks full-mesh vs one dp-group submesh per leg
            mesh = mesh_policy.decide("knn", gen.live_rows,
                                      has_mesh_state=True, batch=b_pad)
            if mesh is not None:
                if k_t <= gen.mesh_state.layout.rows_per_shard:
                    return self._mesh_board(gen, off, queries, b_pad, k_t,
                                            allowed, metric, precision,
                                            knn_stats, mesh)
                mesh_policy.reclassify_single("knn_k_deeper_than_shard")
        # -------- exhaustive leg (un-synced device board)
        k_g = dispatch.bucket_k(min(k_t, n_pad), limit=n_pad)
        mask = None
        if need_mask:
            if allowed is not None:
                m = np.zeros((b_pad, n_pad), dtype=bool)
                m[:len(allowed), :gen.n_rows] = allowed
                filter_mask.note_upload(m)
            else:
                m = np.zeros(n_pad, dtype=bool)
                m[:gen.n_rows] = gen.live_mask()
            mask = jnp.asarray(m)
        # a base the mesh answers defers its single-device copy
        # (`knn_ops.DeferredCorpus`): this leg is its first use
        corpus = knn_ops.resident(gen.corpus)
        if gen.kernel == "knn.exact" and mask is None:
            # the initial base rides the monolithic auto-router (binned
            # Pallas fast path on TPU) in the serving call's packed form,
            # which is the one the store's grid warmed — byte-identical
            # to the pre-generational serving path by construction
            s, ids = topk_ops.split_board(knn_ops.knn_search_auto(
                qj, corpus, k=k_g, metric=metric, precision=precision,
                board=True))
        else:
            s, ids = dispatch.call(gen.kernel, qj, corpus, mask,
                                   k=k_g, metric=metric,
                                   precision=precision, block_size=None)
        ids = ids + np.int32(off)
        if k_g < k_t:
            s = jnp.pad(s, ((0, 0), (0, k_t - k_g)),
                        constant_values=sim.NEG_INF)
            ids = jnp.pad(ids, ((0, 0), (0, k_t - k_g)),
                          constant_values=-1)
        return s, ids, gen.kernel

    def _ivf_board(self, gen: Generation, off: int, queries: np.ndarray,
                   k_t: int, num_candidates: Optional[int],
                   knn_stats: Optional[dict]):
        """Graduated base served through its IVF router (host-synced —
        the router prunes and merges internally)."""
        from elasticsearch_tpu.parallel import policy as mesh_policy

        k_i = dispatch.bucket_k(min(k_t, gen.n_rows), limit=gen.n_rows)
        mesh = mesh_policy.decide("ivf", gen.live_rows,
                                  batch=len(queries))
        scores, rows, _phases = gen.router.search(
            queries, k_i, num_candidates=num_candidates, mesh=mesh)
        scores = np.asarray(scores, dtype=np.float32)
        rows = np.asarray(rows)
        ids = np.where(rows >= 0, rows + off, -1).astype(np.int32)
        if k_i < k_t:
            pad = ((0, 0), (0, k_t - k_i))
            scores = np.pad(scores, pad, constant_values=_NEG_INF_F32)
            ids = np.pad(ids, pad, constant_values=-1)
        if knn_stats is not None:
            knn_stats["ivf_searches"] += 1
            if _phases.get("engine") == "tpu_ivf_mesh":
                knn_stats["mesh_searches"] += 1
        return scores, ids, "ivf"

    def _mesh_board(self, gen: Generation, off: int, queries: np.ndarray,
                    b_pad: int, k_t: int, allowed: Optional[np.ndarray],
                    metric: str, precision: str,
                    knn_stats: Optional[dict], mesh):
        """Graduated base served as ONE SPMD program over its sharded
        copy; tombstones and per-query filters map through the slot map.
        `mesh` is the router's pick — the full serving mesh or a
        dp-group submesh (the group view reads the same immutable
        snapshot, so every replica serves one corpus version). Syncs
        internally (like the monolithic mesh route)."""
        import jax
        import jax.numpy as jnp

        from elasticsearch_tpu.parallel import mesh as mesh_lib
        from elasticsearch_tpu.parallel import policy as mesh_policy
        from elasticsearch_tpu.parallel.sharded_knn import (
            distributed_knn_search)

        ms = gen.mesh_state
        if (mesh is not ms.mesh
                and mesh_lib.shard_size(mesh) != ms.layout.n_shards):
            # policy reconfigured under this graduated base: its layout
            # is baked for its own shard count — serve on the state's
            # mesh until the next graduation rebuilds
            mesh = ms.mesh
        per = ms.layout.rows_per_shard
        k_b = dispatch.bucket_k(min(k_t, per), limit=per)
        mask = None
        if allowed is not None:
            m = filter_mask.through_slots(ms, allowed, b_pad)
            mask = jax.device_put(jnp.asarray(filter_mask.note_upload(m)),
                                  ms.mask_sharding(2, mesh))
        elif gen.has_tombstones:
            mask = jax.device_put(
                jnp.asarray(ms.filter_mask(gen.live_mask())),
                ms.mask_sharding(1, mesh))
        q = jax.device_put(jnp.asarray(queries), ms.query_sharding(mesh))
        # the packed form: the one the mesh's grid warmed, and one read
        scores, gids = topk_ops.split_board(np.asarray(
            distributed_knn_search(
                q, ms.corpus_for(mesh), k_b, mesh, metric=metric,
                filter_mask=mask, precision=precision, board=True)))
        local = ms.map_ids(gids)               # flat rows of this gen
        ids = np.where(local >= 0, local + off, -1).astype(np.int32)
        if k_b < k_t:
            pad = ((0, 0), (0, k_t - k_b))
            scores = np.pad(scores, pad, constant_values=_NEG_INF_F32)
            ids = np.pad(ids, pad, constant_values=-1)
        gather = mesh_policy.gather_bytes(mesh_lib.shard_size(mesh),
                                          b_pad, k_b)
        mesh_policy.record_leg("knn", gather)
        if knn_stats is not None:
            knn_stats["mesh_searches"] += 1
        return scores, ids, "mesh"


class GenerationalCorpus:
    """One vector field's generation lifecycle: the O(delta) refresh
    classifier, the copy-on-write generation set, and the background
    merge scheduler. Thread contract: `_lock` guards the installed set +
    stats; merge EXECUTION runs outside the lock and the install
    validates the merged generations are still the live objects (a
    refresh that tombstoned a victim mid-merge aborts the install — the
    next cycle retries against the fresh set)."""

    def __init__(self, metric: str, dtype: str, rescore: bool, dims: int,
                 policy: Optional[TieredMergePolicy] = None,
                 merge_budget_ms: float = 50.0, background: bool = True,
                 warmup_cb=None, knn_params: Optional[dict] = None,
                 view_cb=None):
        self.metric = metric
        self.dtype = dtype
        self.rescore = bool(rescore)
        self.dims = int(dims)
        self.policy = policy or TieredMergePolicy()
        self.merge_budget_ms = float(merge_budget_ms)
        self.background = bool(background)
        self.warmup_cb = warmup_cb          # callable(entries) or None
        # IVF graduation parameters: engine/nlist/nprobe/recall_target/
        # min_rows (threaded from the store so the merge thread rebuilds
        # routers with the index's own settings)
        self.knn_params = dict(knn_params or {})
        # called (outside the lock) after a merge installs, so the store
        # can refresh its FieldCorpus view and drop stale device refs
        self.view_cb = view_cb
        self._lock = threading.Lock()
        self._set = GenerationSet(())
        self._next_gen_id = 0
        self._merge_thread: Optional[threading.Thread] = None
        self._last_merge_nanos = 0
        self.last_rebuild_reason: Optional[str] = None
        self.stats = {
            "seals": 0, "sealed_rows": 0, "merges": 0, "merge_nanos": 0,
            "merged_rows": 0, "aborted_merges": 0, "tombstone_deletes": 0,
            "ivf_background_builds": 0, "mesh_graduations": 0,
            "dtype_retargets": 0, "dtype_reencodes": 0}

    # ------------------------------------------------------------ set-up
    @classmethod
    def from_monolithic(cls, corpus, row_map: np.ndarray, source,
                        metric: str, dtype: str,
                        rescore: bool, dims: int, router=None,
                        mesh_state=None, **kwargs) -> "GenerationalCorpus":
        """Wrap a legacy full build as generation 0 (kernel `knn.exact`
        — the monolithic grid the store already warms). `source` is the
        columnar RowSource over the build's rows (store-backed on the
        sync path, so the base generation pins nothing); a raw ndarray
        is accepted for direct construction and wrapped (pinning)."""
        from elasticsearch_tpu.columnar import RowSource
        if isinstance(source, np.ndarray):
            source = RowSource.from_array(source)
        gc = cls(metric, dtype, rescore, dims, **kwargs)
        gen = Generation(gc._next_gen_id, corpus,
                         np.asarray(row_map, dtype=np.int64),
                         source, kernel="knn.exact", router=router,
                         mesh_state=mesh_state)
        gc._next_gen_id += 1
        gc._set = GenerationSet((gen,))
        return gc

    def snapshot(self) -> GenerationSet:
        with self._lock:
            return self._set

    # ----------------------------------------------------------- refresh
    def try_incremental(self, view, row_map: np.ndarray,
                        dtype: str, metric: str,
                        rescore: bool) -> Optional[str]:
        """Absorb one refresh as tombstones + an L0 seal. Returns the
        outcome string ("append" / "delete" / "append+delete" / "noop"),
        or None when only a full rebuild can represent the new reader
        (`last_rebuild_reason` says why). O(delta) END TO END: `view` is
        the columnar store's lazy `FieldRowsView` — only the DELTA rows
        ever materialize (a pure append touches the tail blocks alone,
        which the store extracted delta-only too); the host
        classification is one isin pass over the row maps."""
        retargeted = False
        with self._lock:
            cur = self._set
            if not cur.generations:
                self.last_rebuild_reason = "first_build"
                return None
            if metric != self.metric:
                # a metric change re-prepares every row (cosine
                # normalization happens at encode time) — only a
                # rebuild is sound
                self.last_rebuild_reason = "metric_change"
                return None
            if dtype != self.dtype or bool(rescore) != self.rescore:
                # dtype change done on the MERGE thread: future seals
                # encode at the new target immediately; the resident
                # generations keep serving their old encoding until the
                # background merger re-encodes them
                # (`_select` → "dtype_reencode" merges) — the refresh
                # and serving paths never pay a full rebuild for a
                # mapping update
                self.dtype = dtype
                self.rescore = bool(rescore)
                self.stats["dtype_retargets"] += 1
                retargeted = True
            old_rows = cur.row_map
            old_live = cur.live_row_map()
            new = np.asarray(row_map, dtype=np.int64)
            deleted_any = False
            if len(new) >= len(old_live) \
                    and np.array_equal(new[:len(old_live)], old_live):
                # fast path: pure append (the steady-state refresh) —
                # only the tail rows materialize from the block store
                added = new[len(old_live):]
                added_src = view.source_slice(len(old_live))
            else:
                keep = np.isin(new, old_rows)
                added = new[~keep]
                # rows the engine re-based (a host segment merge) look
                # like mass delete+add in a new row space — sealing the
                # whole corpus as a "delta" would double residency, so
                # that shape rebuilds instead
                if len(added) and len(old_rows) \
                        and added.min() <= old_rows.max():
                    self.last_rebuild_reason = "segment_rewrite"
                    return None
                survivors = new[keep]
                still = np.isin(old_live, new)
                if not np.array_equal(old_live[still], survivors):
                    self.last_rebuild_reason = "segment_rewrite"
                    return None
                added_src = view.source_select(~keep)
                gens = []
                for g in cur.generations:
                    gone = g.live_mask() & np.isin(g.row_map, new,
                                                   invert=True)
                    if gone.any():
                        deleted_any = True
                        self.stats["tombstone_deletes"] += int(gone.sum())
                        gens.append(
                            g.with_tombstones(g.tombstones | gone))
                    else:
                        gens.append(g)
                if deleted_any:
                    self._set = GenerationSet(gens)
            gen_id = self._next_gen_id
            self._next_gen_id += 1
        sealed = None
        if len(added):
            # the seal's heavy lifting (f32 copy, normalize, quantize,
            # device upload) runs OUTSIDE the lock — `snapshot()` is on
            # every search dispatch, and stalling it for the seal would
            # feed the build latency straight into search p99 during
            # ingest. Appending at the END of the CURRENT set is safe
            # against a merge installing in between (merges splice
            # interior runs; the tail position is never theirs). The
            # delta gather is the ONLY host materialization this refresh
            # pays; the sealed generation keeps the store-backed source.
            sealed = build_generation(gen_id, added_src.gather(), added,
                                      self.metric, self.dtype,
                                      self.rescore, source=added_src)
            with self._lock:
                self.stats["seals"] += 1
                self.stats["sealed_rows"] += len(added)
                self._set = GenerationSet(self._set.generations
                                          + (sealed,))
        if sealed is not None and self.warmup_cb is not None:
            self.warmup_cb(sealed.warmup_entries(self.dims, self.metric))
        self.notify()
        if sealed is not None and deleted_any:
            outcome = "append+delete"
        elif sealed is not None:
            outcome = "append"
        elif deleted_any:
            outcome = "delete"
        else:
            outcome = "noop"
        if retargeted:
            # the retarget IS a full rebuild avoided, even on an
            # otherwise-noop refresh (the legacy path would have
            # re-encoded the whole corpus on this thread)
            outcome = ("retarget" if outcome == "noop"
                       else outcome + "+retarget")
        return outcome

    # ------------------------------------------------------------ merges
    def _gen_encoding_stale(self, gen: Generation) -> bool:
        """Does this generation still serve a superseded encoding after
        a dtype retarget? (matrix dtype off the target rung, or an int8
        residual level present/absent against the rescore flag)."""
        from elasticsearch_tpu.quant import codec as quant_codec
        if gen.corpus is None:
            return False
        if quant_codec.encoding_of(gen.corpus.matrix.dtype) != self.dtype:
            return True
        if self.dtype == "int8":
            return bool(gen.corpus.residual is not None) != self.rescore
        return False

    def _select(self, gens: Sequence[Generation]) -> Optional[MergeSpec]:
        spec = self.policy.select(gens)
        if spec is not None:
            return spec
        # a tombstoned base dropped its IVF router (dead rows would leak
        # through the partition layout): compact it eagerly so the
        # engine's pruned path comes back without waiting for the GC
        # fraction — in the background, never on the refresh thread
        if (self.knn_params.get("engine") == "tpu_ivf" and gens
                and gens[0].has_tombstones and gens[0].router is None
                and gens[0].live_rows
                >= int(self.knn_params.get("min_rows", 512))):
            return MergeSpec(0, 1, "tombstone_gc")
        # dtype retarget: re-encode superseded generations one at a
        # time on THIS thread — `_build_merged` gathers live rows
        # through the shared block store and seals at the CURRENT
        # target, so a mapping's int8→int4 never full-rebuilds on the
        # refresh or serving path (`segment_counters` dtype_change
        # stays 0)
        for i, g in enumerate(gens):
            if self._gen_encoding_stale(g):
                return MergeSpec(i, i + 1, "dtype_reencode")
        return None

    def merge_pending(self) -> bool:
        with self._lock:
            return self._select(self._set.generations) is not None

    def notify(self) -> None:
        """Kick the background merge thread if work is pending and no
        thread is registered (thread-per-burst: the loop exits when the
        set is steady, so idle corpora hold no threads). The
        registration check is on `is not None` alone — an `is_alive()`
        test would race the window between registering a thread and
        starting it (unstarted threads report not-alive), double-running
        the loop; `_merge_loop` clears the registration in a `finally`,
        so a crashed thread can never wedge merges off."""
        if not self.background:
            return
        with self._lock:
            if self._merge_thread is not None:
                return
            if self._select(self._set.generations) is None:
                return
            t = threading.Thread(target=self._merge_loop, daemon=True,
                                 name="segments-merge")
            self._merge_thread = t
        t.start()

    def _merge_loop(self) -> None:
        budget_ns = max(self.merge_budget_ms, 1.0) * 1e6
        spent = 0.0
        try:
            while self._merge_once():
                spent += self._last_merge_nanos
                if spent > budget_ns:
                    # budget exhausted this cycle: yield to serving (the
                    # merge thread shares host cores with query fan-out)
                    time.sleep(budget_ns / 1e9)
                    spent = 0.0
        finally:
            with self._lock:
                self._merge_thread = None
        # a seal may have landed between the last select and the
        # registration clear; re-kick if so
        self.notify()

    def run_merges(self) -> int:
        """Synchronously drain every pending merge (tests, bench
        determinism). Returns the number of merges executed."""
        n = 0
        while self._merge_once():
            n += 1
        return n

    def force_merge(self) -> bool:
        """Consolidate to a single clean generation (forceMerge(1))."""
        with self._lock:
            spec = TieredMergePolicy.force(self._set.generations)
            victims = (self._set.generations[spec.start:spec.stop]
                       if spec else None)
        if spec is None:
            return False
        return self._execute(spec, victims)

    def drain(self, timeout_s: float = 30.0) -> None:
        """Wait for the background thread to go idle with no pending
        merges (deterministic test/bench checkpoints)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                t = self._merge_thread
                pending = self._select(self._set.generations) is not None
            if t is not None and t.is_alive():
                t.join(0.05)
                continue
            if not pending:
                return
            self.notify()
            time.sleep(0.005)

    def _merge_once(self) -> bool:
        with self._lock:
            spec = self._select(self._set.generations)
            victims = (self._set.generations[spec.start:spec.stop]
                       if spec else None)
        if spec is None:
            self._last_merge_nanos = 0
            return False
        return self._execute(spec, victims)

    def _execute(self, spec: MergeSpec, victims: Tuple[Generation, ...]
                 ) -> bool:
        t0 = time.perf_counter_ns()
        merged = self._build_merged(spec, victims)
        ok = self._install(victims, merged)
        nanos = time.perf_counter_ns() - t0
        self._last_merge_nanos = nanos
        with self._lock:
            if ok:
                self.stats["merges"] += 1
                self.stats["merged_rows"] += merged.n_rows
                if spec.reason == "dtype_reencode":
                    self.stats["dtype_reencodes"] += 1
            else:
                self.stats["aborted_merges"] += 1
            self.stats["merge_nanos"] += nanos
        if ok and self.view_cb is not None:
            self.view_cb(self)
        return ok

    def _build_merged(self, spec: MergeSpec,
                      victims: Tuple[Generation, ...]) -> Generation:
        """Concatenate the victims' LIVE rows and seal the consolidated
        generation; a merge producing the new base (start == 0) also
        graduates it into the IVF layout and the sharded mesh corpus.

        The victim-gather reads live rows THROUGH the shared segment
        block store (each victim's `RowSource`): the f32 concatenation
        is a merge-local transient handed to the corpus build and the
        graduation steps, then dropped — the merged generation keeps
        only the narrowed block references, so merge-input host RAM is
        O(1) in corpus size beyond what the engine segments already
        hold (the pre-columnar path pinned a full `host_vectors` copy
        per generation for its whole lifetime)."""
        from elasticsearch_tpu.columnar import RowSource
        d = self.dims
        src = RowSource.concat(
            [g.source.select(g.live_mask()) for g in victims])
        rows = [g.row_map[g.live_mask()] for g in victims]
        vecs = src.gather()
        if vecs.size == 0:
            vecs = vecs.reshape(0, d)
        rows = (np.concatenate(rows) if rows
                else np.zeros(0, dtype=np.int64))
        with self._lock:
            gen_id = self._next_gen_id
            self._next_gen_id += 1
        merged = build_generation(gen_id, vecs, rows, self.metric,
                                  self.dtype, self.rescore, source=src)
        if spec.start == 0:
            merged.router = self._graduate_ivf(victims[0], merged, vecs)
            merged.mesh_state = self._graduate_mesh(victims[0], merged,
                                                    vecs)
        if self.warmup_cb is not None:
            self.warmup_cb(merged.warmup_entries(self.dims, self.metric))
        return merged

    def _graduate_ivf(self, old_base: Generation, merged: Generation,
                      vecs: np.ndarray):
        """Re-enter the trained IVF layout (clone + add the delta), or
        retrain from scratch — ALWAYS on this merge thread. `vecs` is
        the merge's transient store-read materialization (no
        re-gather, no pinned copy)."""
        params = self.knn_params
        if params.get("engine") != "tpu_ivf":
            return None
        min_rows = int(params.get("min_rows", 512))
        if merged.n_rows < min_rows:
            return None
        old = old_base.router
        if (old is not None and not old_base.has_tombstones
                and old.index.dtype == self.dtype
                and old.index.metric == self.metric
                and not old.index.needs_retrain
                and old_base.n_rows <= merged.n_rows):
            # append-shaped merge: the old base's rows are a stable
            # prefix of the merged generation, so the delta places into
            # the CLONED layout (copy-on-write — the serving router's
            # host mirror and device pytree stay untouched mid-merge)
            idx = old.index.clone()
            idx.add(vecs[old_base.n_rows:],
                    np.arange(old_base.n_rows, merged.n_rows,
                              dtype=np.int32))
            if not idx.needs_retrain:
                return old.with_index(idx)
        # drift / tombstone compaction: full k-means retrain, here on
        # the merge thread — the refresh path never pays it
        from elasticsearch_tpu.ann import IVFRouter, build_ivf_index
        with self._lock:
            self.stats["ivf_background_builds"] += 1
        nlist = params.get("nlist")
        ivf = build_ivf_index(
            vecs, metric=self.metric,
            nlist=int(nlist) if nlist is not None else None,
            dtype=self.dtype, seed=0)
        return IVFRouter(ivf, nprobe=params.get("nprobe", "auto"),
                         recall_target=float(
                             params.get("recall_target", 0.95)))

    def _graduate_mesh(self, old_base: Generation, merged: Generation,
                       vecs: np.ndarray):
        """Graduate the merged base into the sharded serving corpus —
        delta append into per-shard headroom when the old base is a
        clean prefix, full SPMD build otherwise. Eligibility accounts
        the dp-replicated HBM cost of the sharded copy
        (`parallel/policy.eligible`)."""
        from elasticsearch_tpu.parallel import policy as mesh_policy
        from elasticsearch_tpu.vectors.store import device_corpus_nbytes
        if not mesh_policy.eligible(
                merged.n_rows,
                device_bytes=device_corpus_nbytes(
                    merged.n_rows, self.dims, self.dtype)):
            return None
        mesh = mesh_policy.serving_mesh()
        if mesh is None:
            return None
        from elasticsearch_tpu.parallel.sharded_knn import extend_or_build
        old_ms = (old_base.mesh_state
                  if not old_base.has_tombstones else None)
        state, appended = extend_or_build(
            old_ms, vecs, old_base.n_rows, mesh,
            self.metric, self.dtype)
        if not appended:
            with self._lock:
                self.stats["mesh_graduations"] += 1
        return state

    def _install(self, victims: Tuple[Generation, ...],
                 merged: Generation) -> bool:
        """Copy-on-write install: splice `merged` where the victims sit
        in the CURRENT list — identity-validated, so a refresh that
        replaced a victim (tombstones) mid-merge aborts the install
        instead of resurrecting its deleted rows."""
        with self._lock:
            gens = list(self._set.generations)
            try:
                i = gens.index(victims[0])
            except ValueError:
                return False
            if i + len(victims) > len(gens) or any(
                    gens[i + j] is not victims[j]
                    for j in range(len(victims))):
                return False
            gens[i:i + len(victims)] = [merged]
            self._set = GenerationSet(gens)
            return True

    # ------------------------------------------------------------- stats
    def segment_stats(self) -> dict:
        with self._lock:
            s = self._set
            out = dict(self.stats)
        tiers: dict = {}
        for g in s.generations:
            t = tiers.setdefault(str(g.tier), {"generations": 0,
                                               "bytes": 0, "rows": 0,
                                               "tombstoned_rows": 0})
            t["generations"] += 1
            t["bytes"] += g.nbytes
            t["rows"] += g.n_rows
            t["tombstoned_rows"] += g.dead_rows
        out.update({
            "generations": len(s.generations),
            "l0_generations": s.l0_count,
            "tombstoned_rows": s.dead_rows,
            "bytes": sum(g.nbytes for g in s.generations),
            "tiers": tiers})
        return out
