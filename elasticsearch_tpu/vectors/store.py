"""Per-shard device vector store: segments → HBM-resident corpus.

The TPU-side half of `dense_vector` (SURVEY.md §2.8): where the reference
stores one BinaryDocValues blob per doc and scores with a per-doc scripted
loop, this store mirrors each vector field of a shard into a device-resident
`Corpus` (padded matrix + norms + optional int8) rebuilt from the engine's
sealed segments at refresh, with a row map joining device rows back to the
engine's global rows (and thence _id).

Refresh contract: the engine's reader is the source of truth; `sync(reader)`
re-ingests when the segment set or tombstones changed. With generational
segments enabled (`index.segments.enabled`, default on — `segments/`),
a changed field absorbs the refresh as an O(delta) L0 seal plus
per-generation tombstones and a background merge scheduler amortizes
consolidation; the monolithic full build below runs only for first
builds, dtype changes, and engine-level segment rewrites (each counted
and logged — `_nodes/stats indices.segments`).
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

logger = logging.getLogger("elasticsearch_tpu.vectors")

from elasticsearch_tpu.index.mapping import DenseVectorFieldMapper
from elasticsearch_tpu.index.segment import ShardReader
from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops import knn as knn_ops
from elasticsearch_tpu.ops import similarity as sim
from elasticsearch_tpu.ops import topk as topk_ops
from elasticsearch_tpu.quant import rescore as quant_rescore
from elasticsearch_tpu.serving.batcher import IDLE, CombiningBatcher
from elasticsearch_tpu.telemetry import metrics as _telemetry_metrics
from elasticsearch_tpu.telemetry import stage as _stage
from elasticsearch_tpu.vectors import filter_mask

# below this many rows the exhaustive matmul beats IVF routing overhead;
# tpu_ivf fields smaller than this quietly serve exhaustive
IVF_MIN_ROWS = 512

# the precision a search runs in where its caller names none (REST never
# does): what the serving route passes, so what the warm-up grids compile
SERVING_PRECISION = "bf16"

_METRIC_MAP = {
    "cosine": sim.COSINE,
    "dot_product": sim.DOT_PRODUCT,
    "l2_norm": sim.L2_NORM,
    "max_inner_product": sim.MAX_INNER_PRODUCT,
}


class _InflightSlot:
    """One dispatched-not-finalized batch's entry in the store's
    in-flight gauge. Handles carry their slot so the gauge can never
    leak: `finalize_many` releases it on the normal path, and an
    ABANDONED pending handle (a caller that dispatched several legs and
    raised before finalizing them all) releases at GC via ``__del__`` —
    a leaked increment would otherwise bias the dp router toward group
    routes for the process lifetime. release() is idempotent; GC can't
    race an explicit release because ``__del__`` only runs once nothing
    references the handle."""

    __slots__ = ("_store",)

    def __init__(self, store):
        self._store = store

    def release(self) -> None:
        store = self._store
        self._store = None
        if store is not None:
            store._end_dispatch()

    def __del__(self):
        self.release()


class FieldCorpus:
    """Device corpus for one vector field + host-side row maps."""

    __slots__ = ("corpus", "row_map", "_locator", "metric", "dims",
                 "version", "router", "mesh_state", "gens", "encoding",
                 "rescore", "rescore_oversample", "rescore_candidates",
                 "source")

    def __init__(self, corpus, row_map: np.ndarray, metric: str, dims: int,
                 version: tuple, router=None, mesh_state=None,
                 gens=None, encoding: str = "bf16", rescore: bool = False,
                 rescore_oversample: int = 4,
                 rescore_candidates: int = 128, source=None,
                 locator=None):
        self.corpus = corpus          # knn_ops.Corpus (device pytree)
        self.row_map = row_map        # device row -> engine global row
        # engine global row -> device row, for a filter's mask: one pass
        # over the row map, made by `sync` (a view made on the search
        # path is handed the `locator` of the generation whose row map
        # it takes; the flat view of several generations, which no
        # search reads a locator from, is handed none and builds none)
        self._locator = locator
        self.metric = metric
        self.dims = dims
        self.version = version        # cache key: segment/tombstone fingerprint
        self.router = router          # ann.IVFRouter (tpu_ivf engine) or None
        # parallel.sharded_knn.ShardedFieldState: the mesh-resident
        # row-sharded copy + slot maps (None when the mesh router would
        # never pick this corpus)
        self.mesh_state = mesh_state
        # segments.GenerationalCorpus: the live generation lifecycle this
        # view was derived from (None = legacy monolithic field). The
        # serving path re-snapshots per dispatch, so a merge installing
        # mid-flight never invalidates an in-progress search.
        self.gens = gens
        # quantization-ladder state (`elasticsearch_tpu/quant/`): the
        # TARGET storage encoding, whether packed serving runs two-phase
        # (coarse packed top-(k·oversample) + exact f32 rescore of the
        # window), the rescore window sizes, and the columnar RowSource
        # the rescore gathers exact rows through
        self.encoding = encoding
        self.rescore = rescore
        self.rescore_oversample = rescore_oversample
        self.rescore_candidates = rescore_candidates
        self.source = source

    @property
    def locator(self) -> filter_mask.RowLocator:
        if self._locator is None:
            self._locator = filter_mask.RowLocator(self.row_map)
        return self._locator


def _pad_batch(queries: np.ndarray, n_real: int) -> np.ndarray:
    """Pad a coalesced query batch to the dispatch layer's query bucket
    (pow-2): the device jits (exhaustive and IVF alike) specialize on the
    query-count dimension, and a fresh compile per distinct batch size
    would stall serving. Pad results are sliced away by the caller."""
    b_pad = dispatch.bucket_queries(n_real)
    if b_pad != n_real:
        queries = np.concatenate(
            [queries, np.zeros((b_pad - n_real, queries.shape[1]),
                               dtype=np.float32)])
    return queries


def extract_field_rows(reader: ShardReader, field: str
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """(matrix [m, d] f32, row_map [m] engine global rows) for one vector
    field from ONE reader snapshot — now a segment-block-store read
    (`elasticsearch_tpu/columnar/`): per-segment blocks extract once and
    cache by fingerprint, so only delta segments pay extraction. This
    entry MATERIALIZES the full matrix (block concatenation) and exists
    for consumers that genuinely need the whole corpus contiguous (the
    multi-shard mesh layout build in `node.py`); the per-shard sync path
    below reads the lazy `FieldRowsView` instead and stays O(delta) on
    append-only refreshes."""
    from elasticsearch_tpu import columnar
    view = columnar.STORE.vector_view(reader, field)
    return view.matrix(), view.row_map


# index_options.type -> storage encoding (the quant codec ladder); the
# engine half of the mapping lives in `_field_engine`
_OPTION_TYPE_ENCODING = {
    "flat": None, "ivf": None,
    "int8_flat": "int8", "int8_ivf": "int8",
    "int4_flat": "int4", "int4_ivf": "int4",
    "binary_flat": "binary", "binary_ivf": "binary",
}

_DTYPE_ALIASES = {"bfloat16": "bf16", "float32": "f32"}


def device_corpus_nbytes(n_rows: int, dims: int, dtype: str) -> int:
    """Estimated resident device bytes of one field's corpus (packed
    matrix + f32 norms + per-row aux scales, `quant/codec.bytes_per_doc`)
    — the per-field accounting the mesh policy's dp-aware HBM budget
    reads (`parallel/policy.eligible`) and `_nodes/stats indices.knn`
    reports as `bytes_per_doc`."""
    from elasticsearch_tpu.quant import codec as quant_codec
    n = max(int(n_rows), 0)
    name = _DTYPE_ALIASES.get(dtype, dtype)
    try:
        return n * quant_codec.bytes_per_doc(name, int(dims))
    except (KeyError, ValueError):
        return n * (int(dims) * 4 + 4)


class VectorStoreShard:
    def __init__(self, dtype: str = "bf16",
                 knn_engine: str = "tpu", knn_nlist=None,
                 knn_nprobe="auto", knn_recall_target: float = 0.95,
                 warmup: Optional[bool] = None, topup: bool = True,
                 target_batch_latency_ms: float = 2.0,
                 async_depth: int = 2,
                 segments_enabled: bool = True,
                 segments_tier_size: int = 4,
                 segments_max_l0: int = 8,
                 segments_merge_budget_ms: float = 50.0,
                 segments_background_merge: bool = True,
                 semantic_cache_enabled: bool = False,
                 semantic_cache_size: int = 128,
                 semantic_cache_threshold: float = 0.995):
        self.dtype = dtype
        self.knn_engine = knn_engine        # "tpu" (exhaustive) | "tpu_ivf"
        self.knn_nlist = knn_nlist          # None = pick_nlist(n)
        self.knn_nprobe = knn_nprobe        # "auto" | int
        self.knn_recall_target = knn_recall_target
        # None = auto: warm the dispatch grid only where compiles are the
        # serving bottleneck (real accelerator backends) or when forced
        # via ES_TPU_DISPATCH_WARMUP=1 / the node's search.dispatch.warmup
        self.warmup = warmup
        # continuous-batching knobs for the per-(field, k) batchers:
        # bucket top-up window and pipelined dispatch depth. Depth 2
        # (double buffering) holds even on the CPU floor HERE — this
        # batcher's dispatch stage is a thin launch and its finalize is
        # a GIL-releasing device wait, so keeping a second batch in
        # flight feeds the XLA queue (measured: 1cl/4cl closed-loop
        # p99/p50 1.67/1.66 at depth 2 vs 3.06/4.56 at depth 1). The
        # HYBRID executor's scheduler is the one that drops to depth 1
        # on CPU floors — its dispatch stage does real host work.
        self.topup = topup
        self.target_batch_latency_ms = target_batch_latency_ms
        self.async_depth = async_depth
        # generational device segments (elasticsearch_tpu/segments/):
        # refresh seals O(delta) L0 generations instead of rebuilding,
        # deletes tombstone, a background tiered merger consolidates
        # (`index.segments.{enabled,tier_size,max_l0,merge_budget_ms}`)
        self.segments_enabled = segments_enabled
        self.segments_tier_size = segments_tier_size
        self.segments_max_l0 = segments_max_l0
        self.segments_merge_budget_ms = segments_merge_budget_ms
        self.segments_background_merge = segments_background_merge
        self._gens: Dict[str, "GenerationalCorpus"] = {}
        # serializes FieldCorpus view installs between the refresh
        # thread (sync) and the merge thread's view_cb — without it a
        # merge install could clobber a freshly REBUILT field with a
        # view over the superseded GenerationalCorpus (stale row maps)
        self._views_lock = threading.Lock()
        # full-rebuild accounting (the pre-subsystem stall made
        # measurable): every monolithic rebuild of a previously-resident
        # corpus counts here with its reason; incremental refreshes the
        # generational path absorbed count as avoided
        self.segment_counters: Dict[str, object] = {
            "full_rebuilds": 0, "rebuilds_avoided": 0,
            "rebuild_reasons": {}}
        # per-field columnar composition summary of the LAST sync
        # ({blocks, cached, extracted, mode}) — the `columnar`
        # annotation `profile.knn` attaches so the O(delta) refresh
        # claim is inspectable per search
        self.columnar_refresh: Dict[str, dict] = {}
        # per-field quantization-ladder plan (`_encoding_plan`): target
        # encoding + two-phase rescore windows, refreshed every sync
        self._field_plans: Dict[str, dict] = {}
        # device-resident semantic cache (vectors/semantic_cache.py):
        # opt-in ring of recent query embeddings per field, probed with
        # one batched matmul before the full dispatch; invalidated by
        # the field's reader fingerprint (fc.version)
        self.semantic_cache_enabled = semantic_cache_enabled
        self.semantic_cache_size = semantic_cache_size
        self.semantic_cache_threshold = semantic_cache_threshold
        self._sem_caches: Dict[str, object] = {}
        self._fields: Dict[str, FieldCorpus] = {}
        self._batchers: Dict[tuple, CombiningBatcher] = {}
        self._batchers_lock = threading.Lock()
        # live dispatch gauge: how many coalesced batches this shard has
        # in flight (dispatched, not yet finalized). Together with the
        # batchers' queued entries it is the load signal the mesh
        # policy's dp-vs-shard router reads — queued work means a dp
        # group dispatch leaves the other groups free for it
        self._active_lock = threading.Lock()
        self._active_dispatches = 0
        # scheduler counters of batchers retired at refresh (sync drops
        # stale (field, k) variants; their history must not vanish from
        # _nodes/stats)
        self._sched_retired: Dict[str, int] = {}
        # restored IVF layouts (recovery/seed.py): consumed by the next
        # sync's IVF build so a restored/relocated shard re-places rows
        # into the snapshotted centroids instead of re-training k-means
        self._restored_ivf: Dict[str, dict] = {}
        # per-phase serving telemetry (profile "knn" section, _nodes/stats
        # indices.knn). `searches` counts DISPATCHES of the exhaustive
        # routes, one a coalesced batch, not the searches in them: the
        # requests are `scheduler.requests` (the batchers' count)
        self.knn_stats: Dict[str, int] = {
            "searches": 0, "ivf_searches": 0, "fallback_searches": 0,
            "ivf_trains": 0, "ivf_restores": 0,
            "mesh_searches": 0, "fused_probe_searches": 0,
            "rescore_searches": 0, "rescore_window_rows": 0,
            "rescore_promoted": 0, "rescore_nanos": 0,
            "route_nanos": 0, "score_nanos": 0, "merge_nanos": 0,
            "semantic_probes": 0, "semantic_hits": 0,
            "semantic_rejects": 0, "semantic_inserts": 0,
            "semantic_invalidations": 0, "semantic_probe_nanos": 0}
        self.last_knn_phases: dict = {}

    def _field_engine(self, mapper: DenseVectorFieldMapper) -> str:
        """Effective engine for one field: explicit index_options beat the
        index-level `index.knn.engine` setting."""
        otype = (mapper.params.get("index_options") or {}).get("type")
        if otype is not None and otype.endswith("ivf"):
            return "tpu_ivf"
        if otype is not None and otype.endswith("flat"):
            return "tpu"
        return self.knn_engine

    def _encoding_plan(self, field: str, mapper: DenseVectorFieldMapper
                       ) -> dict:
        """Resolve one field's quantization-ladder plan from its
        index_options: storage encoding, two-phase rescore enablement,
        and the rescore window sizes. Unknown `type` values raise a
        mapper error HERE too (defense in depth — the mapper validates
        at parse time, but a store fed a hand-built mapper must not
        silently fall back to f32 flat)."""
        from elasticsearch_tpu.common.errors import MapperParsingError
        from elasticsearch_tpu.quant import rescore as quant_rescore
        opts = mapper.params.get("index_options") or {}
        otype = opts.get("type")
        if otype is not None and otype not in _OPTION_TYPE_ENCODING:
            raise MapperParsingError(
                f"[{field}] unknown index_options type [{otype}]; "
                f"expected one of {sorted(_OPTION_TYPE_ENCODING)}")
        encoding = _OPTION_TYPE_ENCODING.get(otype) or self.dtype
        packed = encoding in ("int4", "binary")
        # packed rungs serve two-phase by default — the recall contract
        # (recall@10 >= 0.95 vs exact f32) is the window's, not the
        # coarse encoding's; int8 `rescore` keeps the device residual
        # path
        rescore = bool(opts.get("rescore", packed))
        oversample = int(opts.get(
            "rescore_oversample",
            quant_rescore.DEFAULT_OVERSAMPLE.get(encoding, 4)))
        return {
            "encoding": encoding,
            "rescore": rescore,
            "rescore_oversample": max(oversample, 1),
            # the int8 residual path's device window (the old fixed 128
            # == default oversample 4 x 32), now `rescore_oversample`-
            # driven — the `"rescore": true` small fix
            "rescore_candidates": max(oversample, 1) * 32,
        }

    # ------------------------------------------------- durable elasticity
    def export_ivf_layout(self) -> Dict[str, dict]:
        """Trained IVF layouts of every field currently routed through
        an IVFIndex (corpus-independent: centroids + shape), for the
        recovery subsystem's shard snapshots."""
        from elasticsearch_tpu.ann.ivf_index import export_layout
        out: Dict[str, dict] = {}
        with self._views_lock:
            fields = dict(self._fields)
        for field, fc in fields.items():
            router = getattr(fc, "router", None)
            index = getattr(router, "index", None)
            if index is not None:
                out[field] = export_layout(index)
        return out

    def restore_ivf_layout(self, layouts: Dict[str, dict]) -> None:
        """Stage restored layouts for the next sync's IVF build (see
        `sync`); unknown/incompatible layouts are simply never consumed
        and the build falls back to training."""
        self._restored_ivf.update(layouts or {})

    @staticmethod
    def _fingerprint(reader: ShardReader, field: str) -> tuple:
        parts = []
        for view in reader.views:
            seg = view.segment
            if field in seg.vectors:
                parts.append((seg.seg_id, seg.num_docs, int(view.live.sum())))
        return tuple(parts)

    def sync(self, reader: ShardReader,
             vector_mappers: Dict[str, DenseVectorFieldMapper]) -> None:
        """Re-ingest vector fields whose segment composition changed.

        Generational path first: an established field absorbs the
        refresh as tombstones + an O(delta) L0 seal
        (`GenerationalCorpus.try_incremental`) — no corpus re-upload, no
        IVF retrain, no mesh rebuild on this thread. Only first builds
        and incompatible reader shapes (dtype change, engine segment
        rewrite) fall through to the monolithic full build, which is
        counted and logged as the rebuild stall it is."""
        from elasticsearch_tpu import columnar
        for field, mapper in vector_mappers.items():
            version = self._fingerprint(reader, field)
            cached = self._fields.get(field)
            plan = self._encoding_plan(field, mapper)
            # a mapping update (dtype rung, rescore window) must re-sync
            # even when the reader fingerprint is unchanged — the
            # generational path absorbs it as a merge-thread re-encode
            # retarget, never a serving-path rebuild
            if (cached is not None and cached.version == version
                    and self._field_plans.get(field) == plan):
                continue
            # block-store read: per-segment extraction is delta-only by
            # construction; nothing corpus-sized materializes unless a
            # monolithic rebuild below actually needs the full matrix
            view = columnar.STORE.vector_view(reader, field)
            row_map = view.row_map
            self.columnar_refresh[field] = view.refresh
            metric = _METRIC_MAP[mapper.similarity]
            # recorded BEFORE the empty-field continue too: the
            # plan-equality short-circuit above must fire for empty
            # fields on the next refresh, not re-sync them forever
            self._field_plans[field] = plan
            if len(row_map) == 0:
                self._fields[field] = FieldCorpus(None, np.zeros(0, dtype=np.int64),
                                                  metric, mapper.dims, version)
                self._gens.pop(field, None)
                continue
            dtype = plan["encoding"]
            opts = mapper.params.get("index_options", {})
            # the residual level is the int8 rung's device-side rescore
            # store; packed rungs rescore host-side through the columnar
            # RowSource instead, so their corpus never carries one
            residual = plan["rescore"] and dtype == "int8"
            gc = self._gens.get(field) if self.segments_enabled else None
            if gc is not None:
                if cached is None or self._reader_prefix_ok(
                        cached.version, version):
                    outcome = gc.try_incremental(
                        view, row_map, dtype=dtype, metric=metric,
                        rescore=residual)
                else:
                    # the engine rewrote segments (merge): row ids were
                    # re-based, so identical ids no longer name
                    # identical docs — only a rebuild is sound
                    gc.last_rebuild_reason = "segment_rewrite"
                    outcome = None
                if outcome is not None:
                    if outcome != "noop":
                        self.segment_counters["rebuilds_avoided"] += 1
                    with self._views_lock:
                        self._fields[field] = self._generational_view(
                            gc, metric, mapper.dims, version, plan=plan)
                    with self._batchers_lock:
                        for key in [k for k in self._batchers
                                    if k[0] == field]:
                            self._retire_sched(self._batchers.pop(key))
                    continue
            rebuild_reason = (gc.last_rebuild_reason if gc is not None
                              else self._rebuild_reason(cached, row_map,
                                                        dtype))
            # monolithic rebuild: the ONE sync shape that materializes
            # the whole matrix (block concatenation — extraction itself
            # was still delta-cached above)
            full = view.matrix()
            from elasticsearch_tpu.parallel import policy as mesh_policy
            mesh_eligible = mesh_policy.eligible(
                len(row_map),
                device_bytes=device_corpus_nbytes(
                    len(row_map), mapper.dims, dtype))
            # `"rescore": true` on the int8 rung additionally keeps the
            # residual rescore level — the analog of Lucene retaining raw
            # f32 vectors beside the quantized copy (reference
            # DenseVectorFieldMapper int8 path), at 2 B/dim total instead
            # of 5. Off by default: int8_flat deployments size HBM against
            # 1 B/dim, and the main scan never reads the residual.
            if dtype in ("int4", "binary"):
                # packed rungs assemble from the columnar store's
                # per-segment ENCODED blocks (cached per fingerprint
                # like the f32 rows — only delta segments re-encode);
                # byte-identical to encoding `full` monolithically
                data, enc_scales, enc_rows, _mode = \
                    columnar.STORE.encoded_rows(reader, field, dtype,
                                                mapper.similarity)
                corpus = knn_ops.corpus_from_encoded(
                    data, enc_scales, full, metric=metric, dtype=dtype)
            elif mesh_eligible and mesh_policy.explicitly_enabled():
                # the operator asked for the mesh and the sharded copy
                # below answers this field: a whole copy on one device
                # beside it would make that device hold twice its share
                # (at a corpus that needs four chips, more than it has).
                # What still needs it builds it on first use
                corpus = self._deferred_corpus(
                    view.as_source(), len(row_map), mapper.dims, metric,
                    dtype, residual)
            else:
                corpus = knn_ops.build_corpus(
                    full, metric=metric, dtype=dtype, residual=residual)
            router = None
            if (self._field_engine(mapper) == "tpu_ivf"
                    and len(row_map) >= IVF_MIN_ROWS):
                # partition layout built from the SAME extraction as the
                # flat corpus, so IVF row ids index the corpus matrix (and
                # row_map) directly; the flat corpus stays resident as the
                # router's exhaustive escape hatch
                from elasticsearch_tpu.ann import (
                    IVFRouter, build_ivf_index)
                old = cached.router if cached is not None else None
                old_n = len(cached.row_map) if cached is not None else 0
                if (old is not None and not old.index.needs_retrain
                        and old.index.dtype == dtype
                        and old.index.metric == metric
                        and 0 < old_n <= len(row_map)
                        and np.array_equal(row_map[:old_n],
                                           cached.row_map)):
                    # append-only refresh (new sealed segments, no
                    # deletes): place only the delta rows into the
                    # existing layout — keeps the trained centroids and
                    # the tuned nprobe instead of retraining k-means on
                    # every refresh. Drift accumulates in the
                    # displacement/spill counters until the retrain
                    # threshold forces the full rebuild below.
                    old.index.add(full[old_n:],
                                  np.arange(old_n, len(row_map),
                                            dtype=np.int32))
                    if not old.index.needs_retrain:
                        router = old
                if router is None:
                    nlist = opts.get("nlist", self.knn_nlist)
                    nprobe = opts.get("nprobe", self.knn_nprobe)
                    ivf = None
                    layout = self._restored_ivf.pop(field, None)
                    if layout is not None:
                        # durable elasticity: a restored/relocated shard
                        # re-places rows into the snapshotted trained
                        # centroids — zero k-means retraining, identical
                        # probe routing (recovery/seed.py installs the
                        # layout before this first sync)
                        from elasticsearch_tpu.ann.ivf_index import (
                            ivf_from_layout, layout_compatible)
                        if layout_compatible(layout, len(row_map),
                                             mapper.dims, metric, dtype):
                            ivf = ivf_from_layout(layout, full)
                            self.knn_stats["ivf_restores"] += 1
                    if ivf is None:
                        ivf = build_ivf_index(
                            full, metric=metric,
                            nlist=int(nlist) if nlist is not None else None,
                            dtype=dtype, seed=0)
                        self.knn_stats["ivf_trains"] += 1
                    router = IVFRouter(
                        ivf, nprobe=nprobe,
                        recall_target=self.knn_recall_target)
            mesh_state = None
            if mesh_eligible:
                from elasticsearch_tpu.parallel.sharded_knn import (
                    extend_or_build)
                mesh = mesh_policy.serving_mesh()
                old_ms = cached.mesh_state if cached is not None else None
                old_n = len(cached.row_map) if cached is not None else 0
                # append-only refresh (new sealed segments, no deletes):
                # ship ONLY the delta rows into the per-shard padded
                # headroom (`mesh.append`, copy-on-write — in-flight
                # searches keep the old state's buffers). Deletes or a
                # mesh/dtype change rebuild the sharded copy.
                prefix = old_n if (old_ms is not None
                                   and 0 < old_n <= len(row_map)
                                   and np.array_equal(row_map[:old_n],
                                                      cached.row_map)) \
                    else 0
                mesh_state, _ = extend_or_build(
                    old_ms if prefix else None, full, prefix, mesh,
                    metric, dtype)
            if (cached is not None and cached.corpus is not None
                    and rebuild_reason is not None):
                self.segment_counters["full_rebuilds"] += 1
                reasons = self.segment_counters["rebuild_reasons"]
                reasons[rebuild_reason] = \
                    reasons.get(rebuild_reason, 0) + 1
                logger.info(
                    "full corpus rebuild for field [%s]: reason=%s "
                    "rows=%d (the generational segments path avoids "
                    "this stall for append/delete refreshes)",
                    field, rebuild_reason, len(row_map))
            gens = None
            if self.segments_enabled:
                from elasticsearch_tpu.segments import (
                    GenerationalCorpus, TieredMergePolicy)
                gens = GenerationalCorpus.from_monolithic(
                    corpus, row_map, view.as_source(), metric, dtype,
                    residual, mapper.dims, router=router,
                    mesh_state=mesh_state,
                    policy=TieredMergePolicy(self.segments_tier_size,
                                             self.segments_max_l0),
                    merge_budget_ms=self.segments_merge_budget_ms,
                    background=self.segments_background_merge,
                    warmup_cb=self._segments_warmup_cb,
                    view_cb=(lambda g, _f=field:
                             self._reinstall_view(_f, g)),
                    knn_params={
                        "engine": self._field_engine(mapper),
                        "nlist": opts.get("nlist", self.knn_nlist),
                        "nprobe": opts.get("nprobe", self.knn_nprobe),
                        "recall_target": self.knn_recall_target,
                        "min_rows": IVF_MIN_ROWS})
            with self._views_lock:
                if gens is not None:
                    self._gens[field] = gens
                self._fields[field] = FieldCorpus(
                    corpus, row_map, metric, mapper.dims, version,
                    router=router, mesh_state=mesh_state, gens=gens,
                    encoding=dtype, rescore=plan["rescore"],
                    rescore_oversample=plan["rescore_oversample"],
                    rescore_candidates=plan["rescore_candidates"],
                    source=view.as_source(),
                    locator=(filter_mask.RowLocator(row_map)
                             if gens is None else
                             gens.snapshot().generations[0].locator))
            with self._batchers_lock:
                for key in [k for k in self._batchers if k[0] == field]:
                    self._retire_sched(self._batchers.pop(key))
            self._schedule_warmup(self._fields[field])

    @staticmethod
    def _deferred_corpus(source, n_rows: int, dims: int, metric: str,
                         dtype: str, residual: bool):
        """The single-device corpus of a field the mesh answers, built on
        first use from the columnar rows (nothing corpus-sized is pinned
        meanwhile). A use is a route that leaves the mesh: k deeper than
        a shard, or the mesh switched off by a later `configure`; each
        build counts as `mesh.single_device_fallbacks`."""
        spec = knn_ops.corpus_spec(n_rows, dims, metric, dtype, residual)
        # registered at 0 here, so that a reader finds "none" and not
        # "no such counter" (resolved by name at each use: a handle would
        # outlive a test's `REGISTRY.reset()`)
        _telemetry_metrics.counter("mesh.single_device_fallbacks")

        def build():
            _telemetry_metrics.counter("mesh.single_device_fallbacks").inc()
            logger.info("building the single-device copy of a mesh-served "
                        "field on first use: rows=%d dims=%d dtype=%s",
                        n_rows, dims, dtype)
            return knn_ops.build_corpus(
                source.gather(), metric=metric, dtype=dtype,
                pad_to=spec.matrix.shape[0], residual=residual)
        return knn_ops.DeferredCorpus(spec, build)

    @staticmethod
    def _reader_prefix_ok(old_version: tuple, new_version: tuple) -> bool:
        """Incremental refreshes require the old reader's segment set to
        be a PREFIX of the new one (same seg ids/sizes, live counts only
        shrinking, new segments appended) — the Lucene NRT contract. An
        engine segment rewrite re-bases rows, so an identical row id no
        longer names an identical doc and the row-id delta classifier
        would silently mis-seal."""
        if len(old_version) > len(new_version):
            return False
        return all(o[0] == n[0] and o[1] == n[1] and o[2] >= n[2]
                   for o, n in zip(old_version, new_version))

    @staticmethod
    def _rebuild_reason(cached: Optional[FieldCorpus],
                        row_map: np.ndarray,
                        dtype: str) -> Optional[str]:
        """Why a monolithic full build is replacing a resident corpus
        (None = first build, not a rebuild) — the pre-subsystem stall
        accounting the generational path is measured against."""
        if cached is None or cached.corpus is None \
                or len(cached.row_map) == 0:
            return None
        from elasticsearch_tpu.quant import codec as quant_codec
        want = quant_codec.MATRIX_DTYPES.get(dtype, dtype)
        if str(cached.corpus.matrix.dtype) != want:
            return "dtype_change"
        old = cached.row_map
        if len(row_map) >= len(old) \
                and np.array_equal(row_map[:len(old)], old):
            # the monolithic path re-uploads the whole corpus for a pure
            # append — the exact headroom-exhaustion stall the
            # generational seal removes
            return "append_headroom"
        if np.isin(old, row_map, invert=True).any():
            return "deletes"
        return "segment_rewrite"

    def _segments_warmup_cb(self, entries) -> None:
        """Pre-compile a freshly sealed/merged generation's search grid
        (policy-gated like every other warmup)."""
        if self.warmup_enabled():
            dispatch.DISPATCH.warmup(entries, background=True)

    def _generational_view(self, gc, metric: str, dims: int,
                           version: tuple,
                           plan: Optional[dict] = None) -> FieldCorpus:
        """FieldCorpus snapshot-view over the current generation set:
        base fields for the single-generation fast path, the FLAT row
        map (concatenated generation row maps — tombstoned slots stay,
        masked at search) for the fan-out path."""
        snap = gc.snapshot()
        base = snap.generations[0]
        plan = plan or {}
        from elasticsearch_tpu.quant import rescore as quant_rescore
        enc = plan.get("encoding", gc.dtype)
        return FieldCorpus(
            base.corpus, snap.row_map, metric, dims, version,
            router=base.router, mesh_state=base.mesh_state, gens=gc,
            encoding=enc,
            rescore=plan.get("rescore", enc in ("int4", "binary")),
            rescore_oversample=plan.get(
                "rescore_oversample",
                quant_rescore.DEFAULT_OVERSAMPLE.get(enc, 4)),
            rescore_candidates=plan.get("rescore_candidates", 128),
            locator=base.locator if len(snap.generations) == 1 else None)

    def _reinstall_view(self, field: str, gc) -> None:
        """Refresh the installed view after a background merge installs
        a new generation set, and retire the field's batchers (their
        closures captured the pre-merge view) — together these drop the
        stale device refs so the pre-merge base corpus can be reclaimed
        once in-flight searches land. Guarded by `_views_lock` against a
        concurrent sync() REBUILD: the install only lands while `gc` is
        still the field's authoritative lifecycle."""
        with self._views_lock:
            if self._gens.get(field) is not gc:
                return
            fc = self._fields.get(field)
            if fc is None or fc.gens is not gc:
                return
            self._fields[field] = self._generational_view(
                gc, fc.metric, fc.dims, fc.version,
                plan=self._field_plans.get(field))
        with self._batchers_lock:
            for key in [k for k in self._batchers if k[0] == field]:
                self._retire_sched(self._batchers.pop(key))

    def segment_stats(self) -> dict:
        """Generational-segment counters for `_nodes/stats
        indices.segments`: rebuilds (+reasons) and rebuilds avoided at
        the store level, generation/tier/merge counters summed over this
        shard's fields."""
        out = {
            "full_rebuilds": self.segment_counters["full_rebuilds"],
            "rebuilds_avoided": self.segment_counters["rebuilds_avoided"],
            "rebuild_reasons": dict(self.segment_counters
                                    ["rebuild_reasons"]),
            "enabled": self.segments_enabled,
        }
        agg: Dict[str, int] = {}
        tiers: Dict[str, dict] = {}
        for gc in list(self._gens.values()):
            st = gc.segment_stats()
            for key, val in st.items():
                if key == "tiers":
                    for t, tv in val.items():
                        slot = tiers.setdefault(
                            t, {k: 0 for k in tv})
                        for k2, v2 in tv.items():
                            slot[k2] += v2
                elif isinstance(val, (int, float)):
                    agg[key] = agg.get(key, 0) + val
        out.update(agg)
        out["tiers"] = tiers
        return out

    def warmup_enabled(self) -> bool:
        return dispatch.warmup_enabled(self.warmup)

    def _schedule_warmup(self, fc: FieldCorpus) -> None:
        """Pre-compile the bucket grid for a freshly-synced corpus on a
        background thread (warmup-at-open): the first real query of any
        interactive bucket then finds its executable cached instead of
        stalling the serving queue behind an XLA compile. Entries mirror
        `knn_search_auto`'s routing, in the packed form (`board`) that
        `_launch_single` asks for, so the warmed program IS the one the
        serving path executes."""
        if fc.corpus is None or not self.warmup_enabled():
            return
        n_pad = fc.corpus.matrix.shape[0]
        packed = str(fc.corpus.matrix.dtype) in ("uint8", "uint32")
        binned_ok = knn_ops.binned_route(
            n_pad, fc.dims, fc.corpus.matrix.dtype, fc.metric)
        entries = []
        # a deferred single-device copy (the mesh answers this field) has
        # no grid to warm: its programs compile if it is ever built
        single = knn_ops.is_resident(fc.corpus)
        corpus_spec = dispatch.specs_like(fc.corpus) if single else None
        query_buckets = dispatch.WARMUP_QUERY_BUCKETS if single else ()
        for q in query_buckets:
            qspec = dispatch.query_spec(q, fc.dims)
            for k in dispatch.WARMUP_K_BUCKETS:
                if packed and fc.rescore:
                    # two-phase fields dispatch the WIDENED coarse k —
                    # warm the programs serving traffic actually runs
                    k = quant_rescore.coarse_window(
                        min(k, n_pad), fc.rescore_oversample, limit=n_pad)
                k_b = dispatch.bucket_k(min(k, n_pad), limit=n_pad)
                if binned_ok and k_b <= 64:
                    if fc.corpus.residual is not None:
                        entries.append((
                            "knn.binned_rescored_packed",
                            (qspec, corpus_spec),
                            {"k": k_b, "metric": fc.metric,
                             "rescore_candidates": fc.rescore_candidates,
                             "interpret": False, "board": True}))
                    else:
                        entries.append((
                            "knn.binned", (qspec, corpus_spec),
                            {"k": k_b, "metric": fc.metric,
                             "interpret": False, "board": True}))
                else:
                    entries.append((
                        "knn.exact", (qspec, corpus_spec, None),
                        {"k": k_b, "metric": fc.metric,
                         "precision": "bf16", "block_size": None,
                         "board": True}))
        if fc.mesh_state is not None:
            # the sharded serving grid pre-compiles alongside the
            # single-device one, so the first mesh-routed query of any
            # interactive bucket finds its SPMD program ready
            entries.extend(fc.mesh_state.warmup_entries(
                fc.dims, precision=SERVING_PRECISION))
        if fc.router is not None:
            from elasticsearch_tpu.parallel import policy as mesh_policy
            from elasticsearch_tpu.parallel import sharded_ivf
            idx = fc.router.index
            mesh = (mesh_policy.serving_mesh()
                    if mesh_policy.eligible(
                        len(fc.row_map),
                        device_bytes=device_corpus_nbytes(
                            len(fc.row_map), fc.dims,
                            str(fc.corpus.matrix.dtype)))
                    else None)
            nprobe_known = (fc.router.nprobe_setting != "auto"
                            or fc.router._tuned_nprobe is not None)
            from elasticsearch_tpu.ops import pallas_ivf_fused as ivf_fused
            from elasticsearch_tpu.quant import codec as quant_codec
            if (idx.total > 0 and nprobe_known
                    and ivf_fused.fused_eligible(
                        quant_codec.MATRIX_DTYPES.get(idx.dtype,
                                                      "float32"),
                        fc.metric)
                    and ivf_fused.fused_preferred()):
                # pre-compile the fused gather+score grid the router
                # will dispatch (single-device probes) — shape-only,
                # so sync never pays the partition-layout upload here
                entries.extend(ivf_fused.warmup_entries_for_index(
                    idx, fc.router.effective_nprobe(10),
                    dispatch.WARMUP_K_BUCKETS,
                    dispatch.WARMUP_QUERY_BUCKETS, metric=fc.metric))
            if mesh is not None and idx.total > 0 and nprobe_known:
                # shape-only: the specs derive from the host layout, so
                # refresh never pays the sharded posting-list upload
                # here (IVFIndex.add invalidates the cached upload, so
                # an eager build would re-transfer the corpus every
                # refresh); an untuned "auto" nprobe is skipped — the
                # tuner runs real searches, far too heavy for warmup
                entries.extend(sharded_ivf.warmup_entries(
                    idx, mesh, fc.router.effective_nprobe(10)))
        dispatch.DISPATCH.warmup(entries, background=True)

    def field(self, name: str) -> Optional[FieldCorpus]:
        return self._fields.get(name)

    def _begin_dispatch(self) -> int:
        """Count this dispatch in flight; returns how many OTHERS were
        already in flight (the dp router's concurrency half of the load
        signal). Mirrored onto the telemetry registry so `_nodes/stats
        telemetry` shows the live in-flight gauge next to the latency
        histograms (resolved per call — a cached Gauge handle would
        detach from the registry across a test-time `reset()`). The
        0 -> 1 edge closes a device-starved interval (`IdleClock`)."""
        _telemetry_metrics.gauge("serving.inflight_dispatches").inc()
        IDLE.begin()
        with self._active_lock:
            n = self._active_dispatches
            self._active_dispatches += 1
            return n

    def _end_dispatch(self) -> None:
        _telemetry_metrics.gauge("serving.inflight_dispatches").dec()
        IDLE.end()
        with self._active_lock:
            self._active_dispatches = max(0, self._active_dispatches - 1)

    def _queued_requests(self) -> int:
        """Requests waiting in this shard's batcher queues (the
        continuous-batching scheduler's live backlog,
        `CombiningBatcher.load()` — the other half of the dp router's
        load signal; in-flight batches are already counted by the
        `_active_dispatches` gauge)."""
        with self._batchers_lock:
            return sum(b.load()["pending"]
                       for b in self._batchers.values())

    def _retire_sched(self, batcher: CombiningBatcher) -> None:
        """Fold a dropped batcher's scheduler counters into the retired
        total (caller holds `_batchers_lock`)."""
        for key, val in batcher.sched.items():
            self._sched_retired[key] = self._sched_retired.get(key, 0) + val

    def scheduler_stats(self) -> Dict[str, int]:
        """Continuous-batching scheduler counters summed over this
        shard's kNN batchers (live + retired): batches, requests,
        top-ups, schedule-time deadline sheds, dispatch/finalize overlap
        hits. Their times are the telemetry stages `serving.queue_wait`,
        `serving.device_dispatch` and `serving.device_sync`."""
        out = dict(self._sched_retired)
        with self._batchers_lock:
            batchers = list(self._batchers.values())
        for b in batchers:
            for key, val in b.sched.items():
                out[key] = out.get(key, 0) + val
        return out

    def field_stats(self) -> Dict[str, dict]:
        """Per-field quantization-ladder stats for `_nodes/stats
        indices.knn.fields`: the serving encoding, device bytes/doc
        (packed row + aux + norms, `quant/codec.bytes_per_doc`), row
        count, and the two-phase rescore window."""
        from elasticsearch_tpu.quant import codec as quant_codec
        out: Dict[str, dict] = {}
        for field, fc in list(self._fields.items()):
            if fc.corpus is None:
                continue
            enc = quant_codec.encoding_of(fc.corpus.matrix.dtype)
            try:
                bpd = quant_codec.bytes_per_doc(enc, fc.dims)
            except (KeyError, ValueError):
                bpd = fc.dims * 4 + 4
            plan = self._field_plans.get(field, {})
            out[field] = {
                "encoding": enc,
                "target_encoding": plan.get("encoding", enc),
                "bytes_per_doc": bpd,
                "rows": len(fc.row_map),
                "device_bytes": device_corpus_nbytes(
                    len(fc.row_map), fc.dims, enc),
                "rescore": bool(fc.rescore),
                "rescore_oversample": (fc.rescore_oversample
                                       if fc.rescore else 0),
            }
        return out

    def search(self, field: str, query_vector: np.ndarray, k: int,
               filter_rows: Optional[np.ndarray] = None,
               precision: str = SERVING_PRECISION,
               num_candidates: Optional[int] = None,
               deadline_at: Optional[float] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k search. Returns (global_rows [m], raw_scores [m]), m <= k
        (padding/filtered slots removed).

        filter_rows: sorted engine global rows allowed to match (pre-filter
        bitset from a boolean query; host → device additive mask).

        Concurrent callers coalesce through a per-(field, k) combining
        batcher into ONE device dispatch (serving/batcher.py) — the
        round-3 path paid a full device round-trip per query.
        """
        fc = self._fields.get(field)
        if fc is None or fc.corpus is None or len(fc.row_map) == 0:
            return np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.float32)

        key = (field, fc.version, k, precision, num_candidates)
        with self._batchers_lock:
            batcher = self._batchers.get(key)
            if batcher is None:
                def execute(reqs, fc=fc, k=k, precision=precision,
                            num_candidates=num_candidates, field=field):
                    return self._execute_batch(fc, k, precision, reqs,
                                               num_candidates=num_candidates,
                                               field=field)

                def dispatch_fn(reqs, fc=fc, k=k, precision=precision,
                                num_candidates=num_candidates, field=field):
                    return self._dispatch_many(
                        fc, k, precision, reqs,
                        num_candidates=num_candidates, field=field)

                # pipelined: the runner holds the batch lock only for the
                # un-synced device dispatch; the d2h sync + row-map join
                # of batch N overlap batch N+1's dispatch
                batcher = CombiningBatcher(
                    execute, dispatch_fn=dispatch_fn,
                    finalize_fn=self.finalize_many,
                    topup=self.topup,
                    target_batch_latency_ms=self.target_batch_latency_ms,
                    async_depth=self.async_depth)
                if len(self._batchers) > 64:  # stale (field, k) variants
                    for stale in self._batchers.values():
                        self._retire_sched(stale)
                    self._batchers.clear()
                self._batchers[key] = batcher
        # deadline_at: the propagated cross-node deadline (monotonic s) —
        # the EDF queue sheds this entry at schedule time if it expires
        # before a runner claims it (EsRejectedExecutionError to the
        # caller, counted in sched["deadline_sheds"])
        IDLE.waiting()
        return batcher.submit(
            (np.asarray(query_vector, dtype=np.float32), filter_rows),
            deadline_at=deadline_at)

    def search_many(self, field: str, requests, k: int,
                    precision: str = SERVING_PRECISION,
                    num_candidates: Optional[int] = None) -> list:
        """Score a whole batch of (query_vector, filter_rows) requests in
        ONE dispatch — the hybrid plan's kNN leg. Where `search` relies on
        concurrent callers colliding in the combining batcher, this entry
        is for a caller that already holds a batch (the hybrid executor's
        runner thread) and wants exactly one device round-trip."""
        return self.finalize_many(
            self.search_many_async(field, requests, k, precision=precision,
                                   num_candidates=num_candidates))

    def search_many_async(self, field: str, requests, k: int,
                          precision: str = SERVING_PRECISION,
                          num_candidates: Optional[int] = None):
        """Launch a whole batch's kNN WITHOUT syncing: route + dispatch
        the device program and return an opaque handle whose un-synced
        arrays `finalize_many` lands later — the hybrid executor's
        pipelined score stage (host RRF/hydrate of batch N overlaps the
        device dispatch of batch N+1). A route that syncs internally
        (IVF) completes here and the handle is already final; results
        are byte-identical either way."""
        fc = self._fields.get(field)
        if fc is None or fc.corpus is None or len(fc.row_map) == 0:
            return ("done", [(np.zeros(0, dtype=np.int64),
                              np.zeros(0, dtype=np.float32))
                             for _ in requests])
        reqs = [(np.asarray(q, dtype=np.float32), fr)
                for q, fr in requests]
        return self._dispatch_many(fc, k, precision, reqs,
                                   num_candidates=num_candidates,
                                   field=field)

    def finalize_many(self, handle) -> list:
        """Land the results of a `search_many_async` handle: ONE
        device→host read of the packed board (`topk_ops.pack_board`,
        its copy started at the launch), the split into scores and ids
        on the host, then the validity mask + row-map join. The blocking
        sync lives HERE, at response-assembly time, never inside the
        dispatch critical section."""
        kind, payload, *rest = handle
        if kind == "done":
            return payload
        if kind == "sem":
            # semantic-cache wrapper: land the miss dispatch, feed the
            # fresh boards back into the ring, splice served + computed
            # results back into request order
            (sem, inner, served, miss_idx, miss_reqs, fc,
             k, precision, num_candidates) = payload
            miss_results = self.finalize_many(inner)
            self.knn_stats["semantic_inserts"] += sem.insert_many(
                miss_reqs, miss_results, fc, k, precision,
                num_candidates)
            out = [None] * (len(miss_idx) + len(served))
            for pos, i in enumerate(miss_idx):
                out[i] = miss_results[pos]
            for i, res in served.items():
                out[i] = res
            return out
        try:
            if kind == "mesh":
                return self._finalize_mesh(payload)
            fc, board, k_eff, n_valid, n_real, rescore_ctx = payload
            scores, ids = self._read_board(board, k_eff)
            with _stage("dispatch.land"):
                if rescore_ctx is not None:
                    # phase two: exact f32 re-rank of the coarse window
                    # (the blocking gather+score lives HERE, at response-
                    # assembly time, with the device sync — never in
                    # dispatch)
                    scores, ids = self._apply_rescore(rescore_ctx, scores,
                                                      ids, n_real)
                return self._land_results(fc, scores, ids, -1e37, n_valid,
                                          n_real)
        finally:
            # every pending handle was counted in flight at dispatch;
            # its slot releases the gauge exactly once
            for slot in rest:
                slot.release()

    @staticmethod
    def _launched(board):
        """The end of a launch: start the board's copy to the host while
        the device still works, so the finalizer's one read finds it on
        its way. Counts the deferred sync, so `_nodes/stats
        indices.dispatch` shows how much serving load pipelines."""
        board.copy_to_host_async()
        dispatch.DISPATCH.note_async()
        return board

    @staticmethod
    def _read_board(board, k_eff: int):
        """A served batch's ONE crossing down, on every exhaustive route:
        `dispatch.sync_wait` is the read, which waits for what is left of
        the device's work and of the copy `_launched` started (every
        crossing more costs a round trip to the device and a hand-over
        of the interpreter lock a batch; an explicit `block_until_ready`
        alone was worth 2.7 ms at the median, PERF.md PR 26), and
        `dispatch.d2h` what is left of bringing the result down, the
        split of the board on the host. Returns (scores, ids), each
        [B_pad, k_eff]."""
        with _stage("dispatch.sync_wait"):
            host = np.asarray(board)
            _telemetry_metrics.counter("dispatch.host_reads").inc()
        with _stage("dispatch.d2h"):
            scores, ids = topk_ops.split_board(host)
            return scores[:, :k_eff], ids[:, :k_eff]

    def _execute_batch(self, fc: FieldCorpus, k: int, precision: str,
                       requests, num_candidates: Optional[int] = None,
                       field: Optional[str] = None) -> list:
        """Serve one coalesced batch of (query_vector, filter_rows)
        synchronously (dispatch + finalize back to back — the combining
        batcher's serial-retry path and the non-pipelined callers)."""
        return self.finalize_many(
            self._dispatch_many(fc, k, precision, requests,
                                num_candidates=num_candidates,
                                field=field))

    def _semantic_cache_for(self, field: Optional[str], fc: FieldCorpus):
        """The field's live SemanticCache, or None (feature off, no
        field identity, or no columnar source to gather exact windows
        through). A ring keyed to a superseded reader fingerprint is
        DROPPED here — refresh/delete/merge each mint a new fc.version,
        so stale entries can never serve rows from an old snapshot."""
        if not self.semantic_cache_enabled or field is None:
            return None
        if fc.source is None and fc.gens is None:
            # no exact row source to build guard windows through
            return None
        from elasticsearch_tpu.vectors import semantic_cache as _semc
        cur = self._sem_caches.get(field)
        if cur is not None and cur.version != fc.version:
            self.knn_stats["semantic_invalidations"] += 1
            cur = None
        if cur is None:
            cur = _semc.SemanticCache(
                self.semantic_cache_size, self.semantic_cache_threshold,
                fc.dims, fc.metric, fc.version)
            self._sem_caches[field] = cur
        return cur

    def _dispatch_many(self, fc: FieldCorpus, k: int, precision: str,
                       requests, num_candidates: Optional[int] = None,
                       field: Optional[str] = None):
        """Dispatch stage of one coalesced batch, fronted by the
        semantic cache when the index opted in: probe the device ring
        first, dispatch only the misses, and hand `finalize_many` a
        handle that splices served and computed boards back into
        request order (and feeds the misses back into the ring)."""
        sem = (self._semantic_cache_for(field, fc) if requests else None)
        served = {}
        if sem is not None:
            served, pstats = sem.probe(requests, k, precision,
                                       num_candidates)
            st = self.knn_stats
            st["semantic_probes"] += pstats["probed"]
            st["semantic_hits"] += pstats["hits"]
            st["semantic_rejects"] += pstats["rejects"]
            st["semantic_probe_nanos"] += pstats["nanos"]
            if len(served) == len(requests):
                # whole batch served from the ring: no device dispatch
                self.last_knn_phases = {
                    "engine": "semantic_cache", "queries": len(requests),
                    "k": int(k)}
                return ("done",
                        [served[i] for i in range(len(requests))])
        miss_idx = [i for i in range(len(requests)) if i not in served]
        miss_reqs = ([requests[i] for i in miss_idx] if served
                     else requests)
        inner = self._dispatch_many_inner(
            fc, k, precision, miss_reqs, num_candidates=num_candidates)
        if sem is None:
            return inner
        return ("sem", (sem, inner, served, miss_idx, miss_reqs, fc,
                        k, precision, num_candidates))

    def _dispatch_many_inner(self, fc: FieldCorpus, k: int,
                             precision: str, requests,
                             num_candidates: Optional[int] = None):
        """Route, build masks, and LAUNCH the device program. The
        exhaustive device paths (single-device AND mesh) return one
        un-synced packed board in the handle; the IVF route syncs
        internally and completes here. Tracks the in-flight gauge the
        dp router reads."""
        # `serving.device_dispatch` in three: `dispatch.prepare` (the
        # host's work on the batch before a byte moves: the in-flight
        # books, stack, route, pad, mask), then what the chosen route
        # does — on the exhaustive device routes `dispatch.h2d` and
        # `dispatch.launch`
        slot = None
        try:
            with _stage("dispatch.prepare"):
                others = self._begin_dispatch()
                slot = _InflightSlot(self)
                filter_mask.note_requests([fr for _, fr in requests])
                launch = self._route_prepared(fc, k, precision, requests,
                                              others, num_candidates)
            handle = launch()
        except BaseException:
            if slot is not None:
                slot.release()
            raise
        if handle[0] == "done":
            slot.release()
            return handle
        # pending handle: the slot rides along so finalize (or GC of an
        # abandoned handle) releases the gauge
        return handle + (slot,)

    def _route_prepared(self, fc: FieldCorpus, k: int, precision: str,
                        requests, others: int,
                        num_candidates: Optional[int]):
        """Pick the batch's route and do its host-side preparation.
        Returns the rest of the dispatch as a call with no arguments."""
        if fc.gens is not None:
            # generational field: serve from the CURRENT copy-on-write
            # snapshot (a background merge may have installed since this
            # view was built). One clean generation degenerates to the
            # monolithic path below on its base corpus — byte-identical
            # to the pre-generational store; anything else fans out.
            snap = fc.gens.snapshot()
            if not snap.simple:
                return functools.partial(
                    self._dispatch_generational, snap, fc, k, precision,
                    requests, num_candidates)
            base = snap.generations[0]
            if base.corpus is not fc.corpus or fc.source is None:
                fc = FieldCorpus(base.corpus, base.row_map, fc.metric,
                                 fc.dims, fc.version, router=base.router,
                                 mesh_state=base.mesh_state,
                                 gens=fc.gens, encoding=fc.encoding,
                                 rescore=fc.rescore,
                                 rescore_oversample=fc.rescore_oversample,
                                 rescore_candidates=fc.rescore_candidates,
                                 source=base.source, locator=base.locator)

        n_valid = len(fc.row_map)
        queries = np.stack([q for q, _ in requests])
        any_filter = any(fr is not None for _, fr in requests)

        # two-phase plan: packed encodings (int4/binary) serve coarse
        # top-(k·oversample) on the packed matrix, then an exact f32
        # rescore of the window at response-assembly time. k widens
        # BEFORE the bucket ladder so the coarse phase stays in-grid.
        k_req = min(k, fc.corpus.matrix.shape[0])
        rescore_ctx = self._rescore_ctx(fc, queries, k_req)
        k_eff = (k_req if rescore_ctx is None
                 else quant_rescore.coarse_window(
                     k_req, fc.rescore_oversample,
                     limit=fc.corpus.matrix.shape[0]))

        self.knn_stats["searches"] += 1
        # cleared up front so a router-less dispatch can never leave a
        # previous query's phase timings behind for the profiler to read
        self.last_knn_phases = {}
        if fc.router is not None:
            reason = fc.router.should_fallback(k_eff, any_filter, precision)
            if reason is None:
                return lambda: ("done", self._execute_ivf(
                    fc, k_eff, n_valid, queries, len(requests),
                    num_candidates, rescore_ctx=rescore_ctx))
            self.knn_stats["fallback_searches"] += 1
            self.last_knn_phases = {"engine": "tpu_exhaustive",
                                    "fallback_reason": reason}

        # mesh router: a corpus past the policy's row floor with a
        # sharded resident copy serves as ONE SPMD program (shard-local
        # matmul + ICI all-gather merge); everything else takes the
        # single-device path below. With dp > 1 the policy also
        # picks the dp-vs-shard split from this batch's bucket and the
        # live load (queued requests + other in-flight dispatches) — a
        # loaded queue routes to one dp group so concurrent batches
        # overlap on disjoint device groups. k deeper than a shard slice
        # can't merge losslessly — those requests stay single-device.
        from elasticsearch_tpu.parallel import policy as mesh_policy
        mesh = mesh_policy.decide(
            "knn", n_valid, has_mesh_state=fc.mesh_state is not None,
            batch=dispatch.bucket_queries(len(requests)),
            queue_depth=others + self._queued_requests())
        if mesh is not None:
            if k_eff <= fc.mesh_state.layout.rows_per_shard:
                return self._prepare_mesh(fc, k_eff, n_valid, queries,
                                          requests, any_filter,
                                          precision, mesh,
                                          rescore_ctx=rescore_ctx)
            mesh_policy.reclassify_single("knn_k_deeper_than_shard")

        queries = _pad_batch(queries, len(requests))
        b_pad = len(queries)
        m = None
        if any_filter:
            with _stage("dispatch.mask_build"):
                # every byte written once: the pad here (requests and
                # rows nobody asked for), the batch's rows whole by
                # `allowed_rows`
                filters = [fr for _, fr in requests]
                m = np.empty((b_pad, fc.corpus.matrix.shape[0]), dtype=bool)
                m[len(filters):] = False
                m[:len(filters), n_valid:] = False
                filter_mask.allowed_rows(fc.locator, filters,
                                         out=m[:len(filters), :n_valid])
                filter_mask.note_built(filters, [fc.locator])
        return functools.partial(self._launch_single, fc, queries, m, k_eff,
                                 n_valid, len(requests), precision,
                                 rescore_ctx)

    def _launch_single(self, fc: FieldCorpus, queries: np.ndarray,
                       m: Optional[np.ndarray], k_eff: int, n_valid: int,
                       n_real: int, precision: str, rescore_ctx):
        """The single-device exhaustive route after its preparation:
        launch WITHOUT syncing. The padded queries ride the launch as
        host numpy (the dispatcher keys them like a device array), so
        `dispatch.h2d` holds only what is uploaded ahead of it: a
        filter's [Q, N] mask, else nothing."""
        import jax

        with _stage("dispatch.h2d"):
            mask = None if m is None else jax.device_put(
                filter_mask.note_upload(m))
        # k rounds up the dispatch bucket ladder so a workload that
        # sweeps k (10, 12, 13, ...) reuses one compiled program per
        # rung; the extra columns slice away at finalize (top-k prefixes
        # are exact)
        k_b = dispatch.bucket_k(k_eff,
                                limit=fc.corpus.matrix.shape[0])
        with _stage("dispatch.launch"):
            board = self._launched(knn_ops.knn_search_auto(
                queries, knn_ops.resident(fc.corpus), k=k_b,
                metric=fc.metric, filter_mask=mask, precision=precision,
                rescore_candidates=fc.rescore_candidates, board=True))
        return ("pending", (fc, board, k_eff, n_valid, n_real,
                            rescore_ctx))

    def _dispatch_generational(self, snap, fc: FieldCorpus, k: int,
                               precision: str, requests,
                               num_candidates: Optional[int]):
        """Fan one dispatch per live generation and fuse through
        `merge_top_k` (`segments/generational.py`) — the serving shape
        between merges: L0 seals and tombstoned generations search as a
        stable-ordered board merge, byte-identical to the monolithic
        corpus. Returns a pending handle whose flat-space board (packed
        by the merge program) lands in `finalize_many` (the snapshot
        rides in the handle, so a merge installing mid-flight cannot
        swap the row map under us)."""
        n_valid = len(snap.row_map)
        queries_real = np.stack([q for q, _ in requests])
        k_req = min(k, snap.total_pad)
        # two-phase when the SNAPSHOT actually serves packed generations
        # (mid-re-encode a still-int8 base stays single-phase and
        # byte-stable; the first packed generation turns the exact
        # rescore on, which also makes the mixed-encoding board merge
        # exact again)
        rescore_ctx = None
        k_eff = k_req
        if fc.rescore and any(
                g.corpus is not None
                and str(g.corpus.matrix.dtype) in ("uint8", "uint32")
                for g in snap.generations):
            rescore_ctx = {"queries": queries_real, "k": k_req,
                           "metric": fc.metric,
                           "gather": snap.gather_rows}
            k_eff = quant_rescore.coarse_window(
                k_req, fc.rescore_oversample, limit=snap.total_pad)
        queries = _pad_batch(queries_real, len(requests))
        self.knn_stats["searches"] += 1
        self.last_knn_phases = {}
        board, phases = snap.search_async(
            queries, len(requests), k_eff, [fr for _, fr in requests],
            fc.metric, precision, num_candidates=num_candidates,
            knn_stats=self.knn_stats)
        self.last_knn_phases = phases
        # un-synced board: the device sync happens at response-assembly
        # time in finalize_many, like the monolithic pipelined path
        return ("pending", (snap, self._launched(board), k_eff, n_valid, len(requests),
                            rescore_ctx))

    @staticmethod
    def _rescore_ctx(fc: FieldCorpus, queries: np.ndarray,
                     k_final: int) -> Optional[dict]:
        """Two-phase rescore context for one coalesced batch, or None
        when this dispatch serves single-phase. Active exactly when the
        SERVING corpus is a packed encoding with rescore on — a field
        mid-re-encode (int8 base still serving after an int8→int4
        mapping change) stays single-phase and byte-stable until the
        merge thread installs the packed generations."""
        if not fc.rescore or fc.source is None:
            return None
        if str(fc.corpus.matrix.dtype) not in ("uint8", "uint32"):
            return None
        return {"queries": queries, "k": k_final, "metric": fc.metric,
                "gather": fc.source.gather}

    def _apply_rescore(self, ctx: dict, scores: np.ndarray,
                       ids: np.ndarray, n_real: int):
        """Run the exact-rescore phase over coarse boards (flat/device
        row ids) and fold the window stats into knn_stats /
        profile.knn."""
        import time as _time

        t0 = _time.perf_counter_ns()
        out_s, out_i, stats = quant_rescore.rescore_boards(
            ctx["queries"][:n_real], scores[:n_real], ids[:n_real],
            ctx["k"], ctx["gather"], ctx["metric"])
        nanos = _time.perf_counter_ns() - t0
        self.knn_stats["rescore_searches"] += 1
        self.knn_stats["rescore_window_rows"] += stats["window"] * n_real
        self.knn_stats["rescore_promoted"] += stats["promoted"]
        self.knn_stats["rescore_nanos"] += nanos
        phases = dict(self.last_knn_phases or {})
        phases["rescore"] = {"window": stats["window"],
                             "promoted": stats["promoted"],
                             "rescore_nanos": nanos}
        self.last_knn_phases = phases
        return out_s, out_i

    @staticmethod
    def _land_results(fc, scores: np.ndarray, ids: np.ndarray,
                      floor: float, n_valid: int, n_real: int) -> list:
        out = []
        for qi in range(n_real):
            sc, rid = scores[qi], ids[qi]
            valid = (sc > floor) & (rid >= 0) & (rid < n_valid)
            sc, rid = sc[valid], rid[valid]
            out.append((fc.row_map[rid], sc.astype(np.float32)))
        return out

    def _prepare_mesh(self, fc: FieldCorpus, k_eff: int, n_valid: int,
                      queries: np.ndarray, requests, any_filter: bool,
                      precision: str, mesh, rescore_ctx=None):
        """One coalesced exact-kNN batch as ONE SPMD program over
        the mesh-resident sharded corpus (`parallel/sharded_knn.py`):
        shard-local matmul + top-k, all-gather candidate merge, k-ladder
        slice-back at finalize. `mesh` is whatever the dp-vs-shard
        router picked — the full serving mesh or one dp-group submesh
        (the corpus view for a group is a free re-layout of the
        dp-replicated arrays). Pads and masks on the host (inside the
        caller's `dispatch.prepare`) and returns the launch, which
        returns an UN-SYNCED handle (one packed board): the device
        sync lands in `_finalize_mesh` at response-assembly time, so
        batch N's merge overlaps batch N+1's dispatch — with dp > 1 the
        overlapping dispatch runs on a DIFFERENT device group, which is
        the replicated mesh's whole throughput story. Result-identical
        to the single-device path (the tier-1 mesh suite pins byte
        parity)."""
        from elasticsearch_tpu.parallel import mesh as mesh_lib

        ms = fc.mesh_state
        if (mesh is not ms.mesh
                and mesh_lib.shard_size(mesh) != ms.layout.n_shards):
            # the policy was reconfigured under this state (its layout
            # is baked for its own shard count): serve on the state's
            # mesh until the next sync rebuilds against the new policy
            mesh = ms.mesh
        queries = _pad_batch(queries, len(requests))
        b_pad = len(queries)
        per = ms.layout.rows_per_shard
        k_b = dispatch.bucket_k(k_eff, limit=per)
        t0 = time.monotonic_ns()
        m = None
        if any_filter:
            with _stage("dispatch.mask_build"):
                filters = [fr for _, fr in requests]
                m = filter_mask.through_slots(ms, filter_mask.allowed_rows(
                    fc.locator, filters), b_pad)
                filter_mask.note_built(filters, [fc.locator])
        return functools.partial(
            self._launch_mesh, fc, ms, mesh, queries, m, k_eff, k_b, b_pad,
            n_valid, len(requests), t0, precision, rescore_ctx)

    def _launch_mesh(self, fc: FieldCorpus, ms, mesh, queries: np.ndarray,
                     m: Optional[np.ndarray], k_eff: int, k_b: int,
                     b_pad: int, n_valid: int, n_real: int, t0: int,
                     precision: str, rescore_ctx):
        import jax

        from elasticsearch_tpu.parallel.sharded_knn import (
            distributed_knn_search)

        # the host arrays go to their devices in ONE placement each (no
        # staging copy on device 0 that is re-laid out from there). The
        # placement stays a call of its own: a sharded argument has to
        # carry its `NamedSharding` into the dispatcher's key, or the
        # warmed executable is missed
        with _stage("dispatch.h2d"):
            mask = None if m is None else jax.device_put(
                filter_mask.note_upload(m), ms.mask_sharding(2, mesh))
            q = jax.device_put(queries, ms.query_sharding(mesh))
        with _stage("dispatch.launch"):
            board = self._launched(distributed_knn_search(
                q, ms.corpus_for(mesh), k_b, mesh, metric=fc.metric,
                filter_mask=mask, precision=precision, board=True))
        return ("mesh", (fc, ms, mesh, board, k_eff, k_b, b_pad,
                         n_valid, n_real, t0, rescore_ctx))

    def _finalize_mesh(self, payload) -> list:
        """Land one mesh dispatch: the one read of its packed board (the
        read is the wait: no `block_until_ready` ahead of it), k
        slice-back, slot-map join, and the router/leg accounting."""
        from elasticsearch_tpu.parallel import mesh as mesh_lib
        from elasticsearch_tpu.parallel import policy as mesh_policy

        (fc, ms, mesh, board, k_eff, k_b, b_pad, n_valid, n_real,
         t0, rescore_ctx) = payload
        scores, gids = self._read_board(board, k_eff)
        rescore_info = None
        with _stage("dispatch.land") as land:
            flat = ms.map_ids(gids)
            if rescore_ctx is not None:
                # exact phase over flat corpus rows (the slot-map join
                # already happened, so the window gathers through the
                # same RowSource as the single-device path)
                scores, flat = self._apply_rescore(rescore_ctx, scores,
                                                   flat, n_real)
                rescore_info = (self.last_knn_phases or {}).get("rescore")
            out = self._land_results(fc, scores, flat, -1e37, n_valid,
                                     n_real)
        # the profile's phase split, from the stages' own clock
        # readings: dispatch start -> board on the host, then the merge
        t1 = land.start_ns
        t2 = t1 + land.nanos
        n_shards = mesh_lib.shard_size(mesh)
        gather = mesh_policy.gather_bytes(n_shards, b_pad, k_b)
        mesh_policy.record_leg("knn", gather)
        self.knn_stats["mesh_searches"] += 1
        self.knn_stats["score_nanos"] += t1 - t0
        self.knn_stats["merge_nanos"] += t2 - t1
        self.last_knn_phases = {
            "engine": "tpu_mesh", "mesh_shards": n_shards,
            "mesh_dp": mesh_lib.dp_size(ms.mesh),
            "dp_group": mesh is not ms.mesh,
            "rows_per_shard": ms.layout.rows_per_shard,
            "collective_bytes": gather,
            "route_nanos": 0, "score_nanos": t1 - t0,
            "merge_nanos": t2 - t1}
        if rescore_ctx is not None and rescore_info is not None:
            self.last_knn_phases["rescore"] = rescore_info
        return out

    def _execute_ivf(self, fc: FieldCorpus, k_eff: int, n_valid: int,
                     queries: np.ndarray, n_real: int,
                     num_candidates: Optional[int],
                     rescore_ctx: Optional[dict] = None) -> list:
        """Serve one coalesced batch through the tpu_ivf router (the
        mesh policy decides single-device vs SPMD execution; packed
        encodings rescore the coarse window exactly before landing)."""
        import time as _time

        from elasticsearch_tpu.parallel import policy as mesh_policy

        queries = _pad_batch(queries, n_real)
        k_b = dispatch.bucket_k(k_eff, limit=len(fc.row_map))
        mesh = mesh_policy.decide("ivf", len(fc.row_map),
                                  batch=len(queries),
                                  queue_depth=self._queued_requests())
        scores, rows, phases = fc.router.search(
            queries, k_b, num_candidates=num_candidates, mesh=mesh)
        scores, rows = scores[:, :k_eff], rows[:, :k_eff]
        phases = dict(phases)
        if rescore_ctx is not None:
            scores, rows = self._apply_rescore(rescore_ctx, scores, rows,
                                               n_real)
            phases["rescore"] = (self.last_knn_phases
                                 or {}).get("rescore")
        t0 = _time.perf_counter_ns()
        out = []
        for qi in range(n_real):
            sc, rid = scores[qi], rows[qi]
            valid = (sc > -1e37) & (rid >= 0) & (rid < n_valid)
            sc, rid = sc[valid], rid[valid]
            out.append((fc.row_map[rid], sc.astype(np.float32)))
        phases["merge_nanos"] += _time.perf_counter_ns() - t0
        self.knn_stats["ivf_searches"] += 1
        if phases.get("engine") == "tpu_ivf_mesh":
            self.knn_stats["mesh_searches"] += 1
        if phases.get("fused_probe"):
            self.knn_stats["fused_probe_searches"] += 1
        for ph in ("route_nanos", "score_nanos", "merge_nanos"):
            self.knn_stats[ph] += phases[ph]
        self.last_knn_phases = phases
        return out
