"""Node: the composition root and API facade.

Re-design of `node/Node.java:275` (layer 3) for a single node: wires
IndicesService, the search coordinator, and the document APIs the REST layer
exposes. The cluster layer (coordination/replication over the transport)
mounts on top of these same internal APIs, mirroring how the reference's
TransportActions call into the node's services.
"""

from __future__ import annotations

import copy
import logging
import threading as _threading
import time
import uuid as _uuid
from typing import Any, Dict, List, Optional

import numpy as np

from elasticsearch_tpu.common.errors import (
    ArrayIndexOutOfBoundsError, DocumentMissingError, IllegalArgumentError,
    IndexNotFoundError, ParsingError, SearchEngineError, VersionConflictError,
)
from elasticsearch_tpu.index.analysis import DEFAULT_REGISTRY
from elasticsearch_tpu.indices.service import (
    SHARD_ROW_SPACE, IndexService, IndicesService,
)
from elasticsearch_tpu.search.service import (
    execute_fetch_phase, execute_query_phase,
)
from elasticsearch_tpu.common.settings import parse_time_value
from elasticsearch_tpu import telemetry as _telemetry
from elasticsearch_tpu.telemetry import trace as _teletrace
from elasticsearch_tpu.version import __version__

logger = logging.getLogger("elasticsearch_tpu.node")

MAX_RESULT_WINDOW_SCROLL = 10_000


class _ShardScopedStore:
    """Vector-store wrapper that drops result rows outside `allowed`
    internal shards — the shard-failure retry path, where the reader omits
    failed shards and a knn clause must not hand back rows the reader
    cannot resolve (a failed shard's hits are simply gone, per the
    reference's partial-results contract)."""

    def __init__(self, inner, allowed: frozenset):
        self._inner = inner
        self._allowed = np.asarray(sorted(allowed), dtype=np.int64)

    def field(self, name):
        return self._inner.field(name)

    def search(self, field, query_vector, k, filter_rows=None,
               precision: str = "bf16", num_candidates=None,
               deadline_at=None):
        rows, scores = self._inner.search(field, query_vector, k,
                                          filter_rows=filter_rows,
                                          precision=precision,
                                          num_candidates=num_candidates,
                                          deadline_at=deadline_at)
        keep = np.isin(rows // SHARD_ROW_SPACE, self._allowed)
        return rows[keep], scores[keep]

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _MultiShardVectorStore:
    """Scatter-gather adapter for multi-shard kNN.

    When the local device mesh can host one column per shard (device
    count >= shard count > 1), searches run as ONE compiled SPMD program:
    each mesh column scores its shard slice and the global top-k merges
    over ICI all_gather (`parallel/sharded_knn.py`) — the compiled
    collapse of `SearchPhaseController.mergeTopDocs:221`. Otherwise the
    host-coordinated fallback runs per-shard device kNN + host merge."""

    def __init__(self, svc: IndexService):
        self.svc = svc
        self._phases: dict = {}

    def field(self, name: str):
        for shard in self.svc.shards:
            fc = shard.vector_store.field(name)
            if fc is not None:
                return fc
        return None

    # -- mesh fast path -----------------------------------------------------
    def _mesh_state(self, field: str):
        """Build (and cache by segment fingerprints) the mesh-sharded
        corpus + row maps for one vector field; None when the mesh path
        does not apply."""
        import jax

        n_shards = len(self.svc.shards)
        if n_shards < 2 or len(jax.devices()) < n_shards:
            return None
        from elasticsearch_tpu.vectors.store import (
            VectorStoreShard, extract_field_rows)
        # one reader snapshot per shard: fingerprints (for cache
        # invalidation), matrices, and row maps all come from the SAME
        # snapshot, so rows can never misalign with doc ids
        readers = [s.engine.acquire_searcher() for s in self.svc.shards]
        version = tuple(VectorStoreShard._fingerprint(r, field)
                        for r in readers)
        cache = self.svc.__dict__.setdefault("_mesh_knn_cache", {})
        cached = cache.get(field)
        if cached is not None and cached["version"] == version:
            return cached
        import jax.numpy as jnp

        from elasticsearch_tpu.index.mapping import DenseVectorFieldMapper
        from elasticsearch_tpu.ops import similarity as sim
        from elasticsearch_tpu.parallel import mesh as mesh_lib
        from elasticsearch_tpu.parallel.sharded_knn import ShardedCorpus
        from elasticsearch_tpu.vectors.store import _METRIC_MAP

        mapper = self.svc.mapper_service.get(field)
        if not isinstance(mapper, DenseVectorFieldMapper):
            return None
        metric = _METRIC_MAP[mapper.similarity]

        # host-side extraction per shard, laid out one shard per mesh
        # column. NOTE: the per-shard device corpora stay resident as the
        # fallback path — on a multi-chip host they all sit on device 0
        # while the mesh copy spreads across chips, so the overlap on any
        # one chip is 1/n_shards of the corpus, not a full double.
        blocks, row_maps = [], []
        for shard, reader in zip(self.svc.shards, readers):
            block, rows = extract_field_rows(reader, field)
            if len(rows) == 0:
                block = np.zeros((0, mapper.dims), dtype=np.float32)
            blocks.append(block)
            row_maps.append(rows + shard.shard_id * SHARD_ROW_SPACE)
        if all(len(b) == 0 for b in blocks):
            return None
        # ONE policy-owned mesh build path (parallel/policy.py): the
        # shard axis is fixed by the engine shard count, but the dp
        # setting and device budget apply exactly as they do for the
        # serving mesh — a `search.mesh.dp` setting can't half-apply
        from elasticsearch_tpu.parallel import policy as mesh_policy
        mesh = mesh_policy.mesh_for_shards(n_shards)
        if mesh is None:
            return None
        from elasticsearch_tpu.ops import knn as knn_ops
        from elasticsearch_tpu.parallel import layout
        per = knn_ops.pad_rows(max(max(len(b) for b in blocks), 1))
        d = mapper.dims
        # dp-aware HBM budget: this upload replicates across every dp
        # group, so it must clear the same search.mesh.hbm_budget_bytes
        # gate the per-shard serving corpus clears (the host-coordinated
        # per-shard fallback below serves instead when it doesn't)
        from elasticsearch_tpu.vectors.store import device_corpus_nbytes
        if not mesh_policy.hbm_allows(
                device_corpus_nbytes(n_shards * per, d, "bf16"), mesh):
            return None
        matrix_host = np.zeros((n_shards * per, d), dtype=np.float32)
        sq_host = np.zeros(n_shards * per, dtype=np.float32)
        num_valid = np.zeros(n_shards, dtype=np.int32)
        for s, block in enumerate(blocks):
            if metric == sim.COSINE and len(block):
                norms = np.linalg.norm(block, axis=-1, keepdims=True)
                block = block / np.maximum(norms, 1e-30)
            matrix_host[s * per: s * per + len(block)] = block
            sq_host[s * per: s * per + len(block)] = \
                (block * block).sum(axis=-1)
            num_valid[s] = len(block)
        import ml_dtypes
        corpus = layout.shard_put(ShardedCorpus(
            matrix=matrix_host.astype(ml_dtypes.bfloat16),
            sq_norms=sq_host,
            scales=np.ones(n_shards * per, dtype=np.float32),
            num_valid=num_valid), mesh)
        state = {"version": version, "mesh": mesh, "corpus": corpus,
                 "row_maps": row_maps, "per": per, "metric": metric,
                 "n_rows": n_shards * per}
        cache[field] = state
        return state

    def _mesh_search(self, state, query_vector, k: int, filter_rows,
                     precision: str):
        import jax
        import jax.numpy as jnp

        from elasticsearch_tpu.ops import dispatch as _dispatch
        from elasticsearch_tpu.parallel import mesh as mesh_lib
        from elasticsearch_tpu.parallel.sharded_knn import (
            distributed_knn_search)

        per = state["per"]
        row_maps = state["row_maps"]
        mask = None
        if filter_rows is not None:
            m = np.zeros(state["n_rows"], dtype=bool)
            for s, rm in enumerate(row_maps):
                allowed = np.isin(rm, filter_rows)
                m[s * per: s * per + len(rm)] = allowed
            mask = jax.device_put(
                jnp.asarray(m),
                mesh_lib.per_shard_sharding(state["mesh"]))
        # the full-mesh program splits queries along dp, so a single
        # query pads up to a dp-divisible bucket (8 covers every pow-2
        # dp on this host); pad rows slice away below
        dp = mesh_lib.dp_size(state["mesh"])
        q_host = np.asarray(query_vector, dtype=np.float32)[None, :]
        if dp > 1:
            q_pad = _dispatch.bucket_queries(max(1, dp))
            q_host = np.concatenate(
                [q_host, np.zeros((q_pad - 1, q_host.shape[1]),
                                  dtype=np.float32)])
        q = jax.device_put(
            jnp.asarray(q_host),
            mesh_lib.query_sharding(state["mesh"]))
        # k rounds up the dispatch ladder so request streams sweeping k
        # reuse one compiled SPMD program per rung (prefixes are exact)
        k_b = _dispatch.bucket_k(min(k, per), limit=per)
        scores, gids = distributed_knn_search(
            q, state["corpus"], k_b, state["mesh"],
            metric=state["metric"], filter_mask=mask, precision=precision)
        scores = np.asarray(scores[0])[:k]
        gids = np.asarray(gids[0])[:k]
        # padding/filtered slots come back (-inf, -1) — masked out
        # before the ICI gather, so no aliased ids can reach this join
        valid = (scores > -1e37) & (gids >= 0)
        scores, gids = scores[valid], gids[valid]
        out_rows = np.empty(len(gids), dtype=np.int64)
        keep = np.ones(len(gids), dtype=bool)
        for i, g in enumerate(gids):
            s, local = int(g) // per, int(g) % per
            if local < len(row_maps[s]):
                out_rows[i] = row_maps[s][local]
            else:
                keep[i] = False
        return out_rows[keep], scores[keep]

    def search(self, field: str, query_vector, k: int, filter_rows=None,
               precision: str = "bf16", num_candidates=None,
               deadline_at=None):
        state = self._mesh_state(field)
        self._phases = {}
        # k beyond the per-shard padded row count cannot merge losslessly
        # in the fused program; such deep k falls back to the host merge
        if state is not None and k <= state["per"]:
            # the fused mesh program has no per-phase split to report
            return self._mesh_search(state, query_vector, k, filter_rows,
                                     precision)
        all_rows, all_scores = [], []
        for shard in self.svc.shards:
            offset = shard.shard_id * SHARD_ROW_SPACE
            frows = None
            if filter_rows is not None:
                local = filter_rows[(filter_rows >= offset)
                                    & (filter_rows < offset + SHARD_ROW_SPACE)] - offset
                frows = local
            rows, scores = shard.vector_store.search(
                field, query_vector, k, filter_rows=frows,
                precision=precision, num_candidates=num_candidates,
                deadline_at=deadline_at)
            if not self._phases:
                # captured per dispatch, NOT scanned lazily later — a
                # later mesh-path query must not inherit these timings
                self._phases = dict(getattr(
                    shard.vector_store, "last_knn_phases", None) or {})
            all_rows.append(rows + offset)
            all_scores.append(scores)
        if not all_rows:
            return np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.float32)
        rows = np.concatenate(all_rows)
        scores = np.concatenate(all_scores)
        # global top-k with shard-order tie-break (stable sort over concat)
        order = np.argsort(-scores, kind="stable")[:k]
        return rows[order], scores[order]

    def search_many(self, field: str, requests, k: int,
                    precision: str = "bf16", num_candidates=None) -> list:
        """Batched kNN for the hybrid executor: the whole request batch
        crosses to the device in ONE dispatch per shard (single-shard
        indices — the common case — pay exactly one round-trip for N
        queries). The mesh fast path stays per-query; it is already one
        compiled program per search."""
        shards = self.svc.shards
        if len(shards) == 1:
            shard = shards[0]
            offset = shard.shard_id * SHARD_ROW_SPACE
            out = shard.vector_store.search_many(
                field, requests, k, precision=precision,
                num_candidates=num_candidates)
            self._phases = dict(getattr(
                shard.vector_store, "last_knn_phases", None) or {})
            return [(rows + offset, scores) for rows, scores in out]
        per_shard = []
        for shard in shards:
            offset = shard.shard_id * SHARD_ROW_SPACE
            reqs = []
            for q, filter_rows in requests:
                frows = None
                if filter_rows is not None:
                    frows = filter_rows[
                        (filter_rows >= offset)
                        & (filter_rows < offset + SHARD_ROW_SPACE)] - offset
                reqs.append((q, frows))
            out = shard.vector_store.search_many(
                field, reqs, k, precision=precision,
                num_candidates=num_candidates)
            per_shard.append([(rows + offset, scores)
                              for rows, scores in out])
        merged = []
        for qi in range(len(requests)):
            rows = np.concatenate([ps[qi][0] for ps in per_shard])
            scores = np.concatenate([ps[qi][1] for ps in per_shard])
            order = np.argsort(-scores, kind="stable")[:k]
            merged.append((rows[order], scores[order]))
        return merged

    def search_many_async(self, field: str, requests, k: int,
                          precision: str = "bf16", num_candidates=None):
        """Pipelined half of `search_many`: launch the batch's device
        dispatch without syncing (single-shard fast path); `finalize_many`
        lands it at response-assembly time. Multi-shard indices fall back
        to the synchronous scatter-gather inside the dispatch stage (the
        host merge needs every shard's results anyway)."""
        shards = self.svc.shards
        if len(shards) == 1:
            shard = shards[0]
            offset = shard.shard_id * SHARD_ROW_SPACE
            handle = shard.vector_store.search_many_async(
                field, requests, k, precision=precision,
                num_candidates=num_candidates)
            self._phases = dict(getattr(
                shard.vector_store, "last_knn_phases", None) or {})
            return ("shard", shard, offset, handle)
        return ("merged", None, 0,
                self.search_many(field, requests, k, precision=precision,
                                 num_candidates=num_candidates))

    def finalize_many(self, handle) -> list:
        kind, shard, offset, payload = handle
        if kind == "merged":
            return payload
        out = shard.vector_store.finalize_many(payload)
        return [(rows + offset, scores) for rows, scores in out]

    @property
    def last_knn_phases(self) -> dict:
        """Engine phase timings captured by this wrapper's most recent
        dispatch (empty for mesh fast-path searches, which have no
        per-phase split)."""
        return self._phases

    @property
    def columnar_refresh(self) -> dict:
        """Per-field segment-block-store refresh ledger, first shard
        that synced the field wins (the `columnar` annotation
        `profile.knn` attaches — see VectorStoreShard.columnar_refresh)."""
        out: dict = {}
        for shard in self.svc.shards:
            for f, info in getattr(shard.vector_store,
                                   "columnar_refresh", {}).items():
                out.setdefault(f, info)
        return out


class Node:
    def __init__(self, data_path: str, node_name: str = "node-0",
                 cluster_name: str = "tpu-search",
                 settings: Optional[dict] = None):
        from elasticsearch_tpu.ingest.service import IngestService
        from elasticsearch_tpu.node_admin import (
            AsyncSearchService, ScrollService, TaskManager, TemplateService,
        )

        self.node_id = _uuid.uuid4().hex[:20]
        self.node_name = node_name
        self.cluster_name = cluster_name
        self.data_path = data_path
        self.indices = IndicesService(data_path)
        self.ingest = IngestService()
        self.scrolls = ScrollService()
        self.async_search = AsyncSearchService()
        self.component_templates: Dict[str, dict] = {}
        self.data_streams: Dict[str, dict] = {}
        self.tasks = TaskManager(self.node_id)
        self.templates = TemplateService()
        from elasticsearch_tpu.script.service import GLOBAL_SCRIPTS
        self.scripts = GLOBAL_SCRIPTS
        import os as _os
        self.scripts.attach_storage(_os.path.join(data_path, "_state",
                                                  "stored_scripts.json"))
        from elasticsearch_tpu.xpack.ilm import IlmService, SlmService
        self.ilm = IlmService(self)
        self.slm = SlmService(self)
        from elasticsearch_tpu.xpack.transform import RollupService, TransformService
        from elasticsearch_tpu.xpack.watcher import WatcherService
        self.watcher = WatcherService(self)
        self.transform = TransformService(self)
        self.rollup = RollupService(self)
        from elasticsearch_tpu.xpack.ccr import CcrService, RemoteClusterService
        self.remotes = RemoteClusterService(self)
        self.ccr = CcrService(self)
        from elasticsearch_tpu.common.breakers import HierarchyCircuitBreakerService
        from elasticsearch_tpu.monitor import SlowLog
        from elasticsearch_tpu.search.caches import NodeCaches
        self.breakers = HierarchyCircuitBreakerService()
        # shard request cache + node query cache (IndicesRequestCache /
        # IndicesQueryCache analogs), shared across this node's shards
        self.caches = NodeCaches()
        from elasticsearch_tpu.common.threadpool import ThreadPool
        self.thread_pool = ThreadPool(settings or {})
        self.search_slow_log = SlowLog("search")
        self.indexing_slow_log = SlowLog("indexing")
        # per-group search counters (SearchRequest `stats` tags ->
        # SearchStats groupStats)
        self._search_groups: Dict[str, int] = {}
        # per-index fused hybrid executors (search/hybrid_plan.py)
        self._hybrid: Dict[str, Any] = {}
        # per-index device aggregation engines (search/agg_plan.py); the
        # lock serializes creation — engines register per-shard refresh
        # listeners, so a lost create-race would leak a permanently
        # resyncing duplicate engine
        self._aggs: Dict[str, Any] = {}
        self._aggs_lock = _threading.Lock()
        self.counters: Dict[str, int] = {"search": 0, "index": 0, "get": 0,
                                         "bulk": 0, "delete": 0}
        # per-index get counts for indices-stats `get` section (GetStats)
        self._index_get_counts: Dict[str, int] = {}
        # cluster-level persistent/transient settings (_cluster/settings API)
        self.cluster_settings: Dict[str, dict] = {"persistent": {},
                                                  "transient": {}}
        # copy: merging keystore secrets into a caller-shared dict would
        # leak plaintext secrets into the caller's object
        self.settings = dict(settings or {})
        # secure settings FIRST: keystore secrets merge under their names
        # without overriding explicit settings, before any service reads
        # them (reference: KeyStoreWrapper loaded in Bootstrap, exposed via
        # Settings#getSecureSettings)
        from elasticsearch_tpu.common.keystore import load_node_keystore
        self.keystore = load_node_keystore(self.settings, data_path)
        if self.keystore is not None:
            for name, value in self.keystore.as_settings().items():
                self.settings.setdefault(name, value)
        # wire remotes from boot settings (cluster.remote.<alias>.seeds);
        # apply_settings isolates + logs per-alias failures itself
        self.remotes.apply_settings(self.settings)
        from elasticsearch_tpu.security import SecurityService, SecurityStore
        from elasticsearch_tpu.security.realms import build_realm_chain
        _sec_store = SecurityStore(
            _os.path.join(data_path, "_state", "security.json"))
        _anon = self.settings.get("xpack.security.authc.anonymous.roles")
        if isinstance(_anon, str):
            _anon = [r.strip() for r in _anon.split(",") if r.strip()]
        self.security = SecurityService(
            _sec_store,
            enabled=bool(self.settings.get("xpack.security.enabled", False)),
            bootstrap_password=str(
                self.settings.get("bootstrap.password", "changeme")),
            realms=build_realm_chain(self.settings, _sec_store, data_path),
            anonymous_roles=_anon)
        from elasticsearch_tpu.xpack.license import LicenseService
        self.license = LicenseService(str(self.settings.get(
            "xpack.license.self_generated.type", "trial")))
        from elasticsearch_tpu.snapshots.service import SnapshotService
        self.snapshots = SnapshotService(self)
        from elasticsearch_tpu.ml import DatafeedService, MlService
        self.ml = MlService(self)
        self.datafeeds = DatafeedService(self)
        from elasticsearch_tpu.xpack.enrich import attach_enrich
        from elasticsearch_tpu.xpack.graph import GraphService
        self.enrich = attach_enrich(self)
        self.graph = GraphService(self)
        from elasticsearch_tpu.xpack.monitoring import MonitoringService
        self.monitoring = MonitoringService(self)
        from elasticsearch_tpu.plugins import PluginsService
        self.plugins = PluginsService(
            self.settings.get("path.plugins",
                              _os.path.join(data_path, "plugins")))
        self.plugins.load_all()
        self.plugins.apply_extensions()
        self.plugins.start_node(self)
        # shape-bucketed kernel dispatch (ops/dispatch.py):
        # search.dispatch.warmup overrides the warmup policy. The
        # persistent compilation cache is the server entry point's to
        # configure (dispatch.configure_compile_cache), not a node setting
        from elasticsearch_tpu.common.settings import setting_bool
        from elasticsearch_tpu.ops import dispatch as _dispatch
        warm = self.settings.get("search.dispatch.warmup")
        self._dispatch_warmup = setting_bool(warm) if warm is not None \
            else None
        if self._dispatch_warmup is not None:
            # the dispatcher (and its warmup policy) is process-wide; a
            # node with no explicit setting must not clobber a policy an
            # earlier in-process node configured
            _dispatch.set_default_warmup(self._dispatch_warmup)
        # mesh serving policy (parallel/policy.py): search.mesh.* settings
        # pick the SPMD shard count and the per-corpus row floor the
        # host-side router applies. Process-wide like the dispatcher —
        # only an explicit setting reconfigures it (same clobber rule as
        # warmup above).
        mesh_keys = ("search.mesh.enabled", "search.mesh.num_shards",
                     "search.mesh.min_rows", "search.mesh.dp",
                     "search.mesh.hbm_budget_bytes")
        if any(self.settings.get(key) is not None for key in mesh_keys):
            from elasticsearch_tpu.parallel import policy as _mesh_policy
            enabled = self.settings.get("search.mesh.enabled")
            num_shards = self.settings.get("search.mesh.num_shards")
            min_rows = self.settings.get("search.mesh.min_rows")
            dp = self.settings.get("search.mesh.dp")
            hbm_budget = self.settings.get("search.mesh.hbm_budget_bytes")
            kwargs = {}
            if enabled is not None:
                kwargs["enabled"] = setting_bool(enabled)
            if num_shards is not None:
                kwargs["num_shards"] = int(num_shards)
            if min_rows is not None:
                kwargs["min_rows"] = int(min_rows)
            if dp is not None:
                kwargs["dp"] = int(dp)
            if hbm_budget is not None:
                kwargs["hbm_budget_bytes"] = int(hbm_budget)
            _mesh_policy.configure(**kwargs)
        # end-to-end telemetry (elasticsearch_tpu/telemetry/): tracer
        # sampling + trace-ring sizing. Process-wide like the dispatcher
        # — only an explicit setting reconfigures (same clobber rule as
        # warmup above).
        _telemetry.configure_from_settings(self.settings)
        # the collector's pauses and the device-starved counters exist
        # from the node's start, so that one that never moved reads 0
        _telemetry.time_gc()
        # the heartbeat (telemetry/beat.py): the wait for the interpreter
        # lock, a stall's record, the profiler's clock anchor
        _telemetry.BEAT.watch(self.thread_pool)
        from elasticsearch_tpu.serving.batcher import IDLE
        IDLE.ensure_counters()
        # set by the server bootstrap after native hardening runs; embedded
        # nodes have no hardening (reference: JNANatives.LOCAL_MLOCKALL)
        self.natives = None
        self.start_time = time.time()

    # ------------------------------------------------------------- documents
    def index_doc(self, index: str, doc_id: Optional[str], body: dict,
                  op_type: str = "index", refresh: Optional[str] = None,
                  routing: Optional[str] = None,
                  if_seq_no: Optional[int] = None,
                  if_primary_term: Optional[int] = None,
                  version: Optional[int] = None,
                  version_type: str = "internal",
                  pipeline: Optional[str] = None) -> dict:
        svc = self.indices.check_open(self._index_or_autocreate(index))
        if pipeline is None:
            pipeline = svc.settings.get("index.default_pipeline")
        if pipeline and pipeline != "_none":
            body = self.ingest.execute(pipeline, svc.name, doc_id, body)
            if body is None:  # dropped by the pipeline
                return {"_index": svc.name, "_id": doc_id, "result": "noop",
                        "_version": -1, "_seq_no": -1, "_primary_term": 0,
                        "_shards": {"total": 0, "successful": 0, "failed": 0}}
        if doc_id is None:
            doc_id = _uuid.uuid4().hex[:20]
            op_type = "create"
        if len(str(doc_id).encode("utf-8")) > 512:
            raise IllegalArgumentError(
                f"id [{doc_id}] is too long, must be no longer than 512 "
                f"bytes but was: {len(str(doc_id).encode('utf-8'))}")
        if op_type == "create" and version_type != "internal":
            raise IllegalArgumentError(
                "create operations only support internal versioning. use "
                "index instead")
        shard = svc.route(doc_id, routing)
        t0 = time.monotonic()
        result = shard.engine.index(
            doc_id, body, op_type=op_type, if_seq_no=if_seq_no,
            if_primary_term=if_primary_term, version=version,
            version_type=version_type, routing=routing)
        self.counters["index"] += 1
        self.indexing_slow_log.maybe_log(
            svc.settings, svc.name, time.monotonic() - t0, source=body)
        self._maybe_refresh(svc, refresh, shard=shard)
        if svc.mapper_service.dirty:
            # persist only on real dynamic-mapping changes, not per document
            self.indices._persist_meta(svc)
            svc.mapper_service.dirty = False
        out = {
            "_index": svc.name, "_id": doc_id, "_version": result.version,
            "result": result.result, "_seq_no": result.seq_no,
            "_primary_term": result.primary_term,
            "_shards": {"total": 1, "successful": 1, "failed": 0},
        }
        if refresh in ("true", "", True):
            # the write itself made changes visible (RestActions
            # forced_refresh flag; wait_for is not "forced")
            out["forced_refresh"] = True
        return out

    def get_doc(self, index: str, doc_id: str, routing: Optional[str] = None,
                source_includes=None, realtime: bool = True) -> dict:
        svc = self.indices.check_open(self.indices.get(index))
        shard = svc.route(doc_id, routing)
        self.counters["get"] += 1
        self._index_get_counts[svc.name] = \
            self._index_get_counts.get(svc.name, 0) + 1
        doc = shard.engine.get(doc_id, realtime=realtime)
        if doc is None:
            return {"_index": svc.name, "_id": doc_id, "found": False}
        out = {"_index": svc.name, "_id": doc_id, "_version": doc["_version"],
               "_seq_no": doc["_seq_no"], "_primary_term": doc["_primary_term"],
               "found": True}
        if svc.mapper_service.source_enabled:
            out["_source"] = doc["_source"]
        if doc.get("_routing") is not None:
            out["_routing"] = doc["_routing"]
        return out

    def delete_doc(self, index: str, doc_id: str, refresh: Optional[str] = None,
                   routing: Optional[str] = None,
                   if_seq_no: Optional[int] = None,
                   if_primary_term: Optional[int] = None,
                   version: Optional[int] = None,
                   version_type: str = "internal") -> dict:
        svc = self.indices.check_open(self.indices.get(index))
        shard = svc.route(doc_id, routing)
        self.counters["delete"] += 1
        result = shard.engine.delete(doc_id, if_seq_no=if_seq_no,
                                     if_primary_term=if_primary_term,
                                     version=version,
                                     version_type=version_type)
        self._maybe_refresh(svc, refresh, shard=shard)
        out = {"_index": svc.name, "_id": doc_id, "_version": result.version,
               "result": "deleted", "_seq_no": result.seq_no,
               "_primary_term": result.primary_term,
               "_shards": {"total": 1, "successful": 1, "failed": 0}}
        if refresh in ("true", "", True):
            out["forced_refresh"] = True
        return out

    _UPDATE_FIELDS = ["doc", "script", "upsert", "doc_as_upsert",
                      "scripted_upsert", "detect_noop", "_source",
                      "if_seq_no", "if_primary_term", "lang"]

    @classmethod
    def _validate_update_body(cls, body: Optional[dict]) -> None:
        import difflib as _difflib
        for k in body or {}:
            if k not in cls._UPDATE_FIELDS:
                close = _difflib.get_close_matches(k, cls._UPDATE_FIELDS,
                                                   n=1)
                hint = f" did you mean [{close[0]}]?" if close else ""
                raise ParsingError(
                    f"[UpdateRequest] unknown field [{k}]{hint}")

    def update_doc(self, index: str, doc_id: str, body: dict,
                   refresh: Optional[str] = None,
                   routing: Optional[str] = None,
                   if_seq_no: Optional[int] = None,
                   if_primary_term: Optional[int] = None,
                   source_filter=None) -> dict:
        """_update API: partial doc merge, script update, upsert.

        Reference: `action/update/UpdateHelper.java`.
        """
        self._validate_update_body(body)
        if source_filter is None and body and "_source" in body:
            # body-level _source is the documented alternative to the
            # query param (UpdateRequest fetchSource)
            source_filter = body["_source"]
        # update auto-creates its index like the index API
        # (TransportUpdateAction routes through auto-create)
        svc = self.indices.check_open(self._index_or_autocreate(index))
        shard = svc.route(doc_id, routing)
        existing = shard.engine.get(doc_id)

        def _with_get(out, src):
            if source_filter is not None and source_filter is not False:
                doc = {"_source": copy.deepcopy(src)}
                self._apply_mget_projection(doc, {}, None, svc.name,
                                            source_filter)
                out["get"] = {"_source": doc.get("_source", {}),
                              "found": True}
            return out

        if existing is None:
            if "upsert" in body:
                out = self.index_doc(svc.name, doc_id, body["upsert"],
                                     refresh=refresh, routing=routing)
                return _with_get(out, body["upsert"])
            if body.get("doc_as_upsert") and "doc" in body:
                out = self.index_doc(svc.name, doc_id, body["doc"],
                                     refresh=refresh, routing=routing)
                return _with_get(out, body["doc"])
            raise DocumentMissingError(f"[{doc_id}]: document missing")
        if if_seq_no is not None and existing["_seq_no"] != if_seq_no or \
                if_primary_term is not None \
                and existing["_primary_term"] != if_primary_term:
            raise VersionConflictError(
                f"[{doc_id}]: version conflict, required seqNo "
                f"[{if_seq_no}], primary term [{if_primary_term}], "
                f"current document has seqNo [{existing['_seq_no']}] and "
                f"primary term [{existing['_primary_term']}]")
        source = copy.deepcopy(existing["_source"])
        if "doc" in body:
            _deep_merge(source, body["doc"])
            if body.get("detect_noop", True) \
                    and source == existing["_source"]:
                return _with_get({
                    "_index": svc.name, "_id": doc_id,
                    "_version": existing["_version"], "result": "noop",
                    "_seq_no": existing["_seq_no"],
                    "_primary_term": existing["_primary_term"],
                    "_shards": {"total": 0, "successful": 0,
                                "failed": 0}}, source)
        elif "script" in body:
            verdict: Dict[str, Any] = {}
            source = _apply_update_script(source, body["script"],
                                          ctx_extra=verdict)
            op = verdict.get("op", "index")
            if op == "none":
                # script vetoed the update (UpdateHelper: ctx.op = 'none')
                return {"_index": index, "_id": doc_id,
                        "_version": existing["_version"],
                        "result": "noop",
                        "_seq_no": existing["_seq_no"],
                        "_primary_term": existing["_primary_term"],
                        "_shards": {"total": 0, "successful": 0, "failed": 0}}
            if op == "delete":
                out = self.delete_doc(index, doc_id, refresh=refresh,
                                      routing=routing)
                out["result"] = "deleted"
                return out
        else:
            raise IllegalArgumentError("update requires [doc] or [script]")
        out = self.index_doc(svc.name, doc_id, source, refresh=refresh,
                             routing=routing,
                             if_seq_no=existing["_seq_no"],
                             if_primary_term=existing["_primary_term"])
        out["result"] = "updated"
        return _with_get(out, source)

    def mget(self, body: dict, default_index: Optional[str] = None,
             stored_fields=None, realtime: bool = True,
             refresh: bool = False, source_filter=None) -> dict:
        """_mget (reference: TransportMultiGetAction / MultiGetRequest).

        Validation aggregates per-item failures into one
        action_request_validation_exception; a missing index or document
        yields {found: false}, while a multi-index alias yields a per-doc
        error with root_cause (`MultiGetRequest.java` add() validation +
        TransportMultiGetAction per-item failure handling)."""
        from elasticsearch_tpu.common.errors import (
            ActionRequestValidationError, IllegalArgumentError,
            IndexNotFoundError)
        body = body or {}
        items: List[dict] = []
        verrs: List[str] = []
        for spec in body.get("docs") or []:
            index = spec.get("_index", default_index)
            if not index:
                verrs.append("index is missing")
            if "_id" not in spec:
                verrs.append("id is missing")
            if index and "_id" in spec:
                items.append({**spec, "_index": index})
        for doc_id in body.get("ids") or []:
            if not default_index:
                verrs.append("index is missing")
            else:
                items.append({"_index": default_index, "_id": doc_id})
        if not items and not verrs:
            verrs.append("no documents to get")
        if verrs:
            raise ActionRequestValidationError.of(verrs)

        docs = []
        refreshed = set()
        for spec in items:
            index = spec["_index"]
            doc_id = str(spec["_id"])
            routing = spec.get("routing")
            routing = str(routing) if routing is not None else None
            try:
                if refresh and index not in refreshed:
                    self.indices.get(index).refresh()
                    refreshed.add(index)
                doc = self.get_doc(index, doc_id, routing=routing,
                                   realtime=realtime)
            except IndexNotFoundError:
                docs.append({"_index": index, "_id": doc_id, "found": False})
                continue
            except IllegalArgumentError as e:
                docs.append({"_index": index, "_id": doc_id,
                             "error": e.to_wrapped_dict()})
                continue
            except SearchEngineError as e:
                docs.append({"_index": index, "_id": doc_id,
                             "error": e.to_dict()})
                continue
            self._apply_mget_projection(doc, spec, stored_fields, index,
                                        source_filter)
            docs.append(doc)
        return {"docs": docs}

    def _apply_mget_projection(self, doc: dict, spec: dict, req_stored_fields,
                               index: str, req_source=None) -> None:
        """stored_fields + per-doc _source filtering on a fetched doc."""
        from elasticsearch_tpu.search.service import _filter_source, _get_path
        if "_source" not in spec and req_source is not None:
            spec = {**spec, "_source": req_source}
        sf = spec.get("stored_fields", req_stored_fields)
        if sf:
            sf = [sf] if isinstance(sf, str) else list(sf)
            svc = self.indices.get(index)
            fields = {}
            for fname in sf:
                if fname.startswith("_"):
                    continue  # metadata fields ride at the top level
                mapper = svc.mapper_service.get(fname)
                if mapper is None or not mapper.params.get("store"):
                    continue
                val = _get_path(doc.get("_source") or {}, fname)
                if val is not None:
                    fields[fname] = val if isinstance(val, list) else [val]
            if fields:
                doc["fields"] = fields
            # stored_fields suppress _source unless the caller asked for
            # it explicitly (via the list or a truthy _source param)
            if "_source" not in sf and spec.get("_source") in (None, False):
                doc.pop("_source", None)
        src_spec = spec.get("_source")
        if src_spec is False:
            doc.pop("_source", None)
        elif isinstance(src_spec, (list, str)):
            inc = [src_spec] if isinstance(src_spec, str) else src_spec
            if doc.get("_source") is not None:
                doc["_source"] = _filter_source(doc["_source"], inc, [])
        elif isinstance(src_spec, dict):
            inc = src_spec.get("include", src_spec.get("includes", [])) or []
            exc = src_spec.get("exclude", src_spec.get("excludes", [])) or []
            inc = [inc] if isinstance(inc, str) else inc
            exc = [exc] if isinstance(exc, str) else exc
            if doc.get("_source") is not None:
                doc["_source"] = _filter_source(doc["_source"], inc, exc)

    def bulk(self, operations: List[dict], default_index: Optional[str] = None,
             refresh: Optional[str] = None, source_filter=None) -> dict:
        """_bulk: list of {action: meta} / source pairs already decoded.

        Reference: `TransportBulkAction` §3.3 — here single-node, grouped by
        shard implicitly by the engine's per-shard lock.
        """
        self.counters["bulk"] += 1
        # parse-time validation of every action line BEFORE any item
        # executes: a rejected request must not be partially applied
        # (BulkRequestParser rejects during parsing)
        ln = 0
        for j, line in enumerate(operations):
            if j != ln:
                continue
            if not isinstance(line, dict) or len(line) != 1:
                # the reference names the parser state it hit
                if isinstance(line, dict) and len(line) > 1:
                    expected, found = "END_OBJECT", "FIELD_NAME"
                elif isinstance(line, dict):
                    expected, found = "FIELD_NAME", "END_OBJECT"
                else:
                    expected, found = "START_OBJECT", "VALUE_STRING"
                raise IllegalArgumentError(
                    f"Malformed action/metadata line [{j + 1}], expected "
                    f"{expected} but found [{found}]")
            ((act, m),) = line.items()
            if act not in ("index", "create", "update", "delete") \
                    or not isinstance(m, dict):
                raise IllegalArgumentError(
                    f"Malformed action/metadata line [{j + 1}], found "
                    f"[{act}]")
            for dep in ("_version", "_routing", "_parent", "fields",
                        "_version_type", "_retry_on_conflict"):
                if dep in m:
                    raise IllegalArgumentError(
                        f"Action/metadata line [{j + 1}] contains an "
                        f"unknown parameter [{dep}]")
            ln += 1 if act == "delete" else 2
        items = []
        errors = False
        touched = set()
        i = 0
        while i < len(operations):
            action_line = operations[i]
            i += 1
            ((action, meta),) = action_line.items()
            index = meta.get("_index", default_index)
            doc_id = meta.get("_id")
            if doc_id is not None:
                doc_id = str(doc_id)  # numeric ids arrive as JSON numbers
            routing = meta.get("routing")
            if_seq_no = meta.get("if_seq_no")
            if_primary_term = meta.get("if_primary_term")
            try:
                if action in ("index", "create"):
                    source = operations[i]
                    i += 1
                    if doc_id == "":
                        raise IllegalArgumentError(
                            "if _id is specified it must not be empty")
                    op_type = "create" if action == "create" \
                        else meta.get("op_type", "index")
                    resp = self.index_doc(
                        index, doc_id, source, op_type=op_type,
                        routing=routing, if_seq_no=if_seq_no,
                        if_primary_term=if_primary_term,
                        version=meta.get("version"),
                        version_type=meta.get("version_type", "internal"))
                    status = 201 if resp["result"] == "created" else 200
                    # `index` + op_type create reports under `create`
                    # (BulkItemResponse opType rendering)
                    action = "create" if op_type == "create" else action
                elif action == "update":
                    body = operations[i]
                    i += 1
                    if doc_id == "":
                        raise IllegalArgumentError(
                            "if _id is specified it must not be empty")
                    src_spec = (body.pop("_source", None)
                                if isinstance(body, dict) else None)
                    if src_spec is None:
                        src_spec = meta.get("_source", source_filter)
                    resp = self.update_doc(index, doc_id, body,
                                           routing=routing,
                                           if_seq_no=if_seq_no,
                                           if_primary_term=if_primary_term,
                                           source_filter=src_spec)
                    status = 200
                elif action == "delete":
                    resp = self.delete_doc(
                        index, doc_id, routing=routing,
                        if_seq_no=if_seq_no,
                        if_primary_term=if_primary_term,
                        version=meta.get("version"),
                        version_type=meta.get("version_type", "internal"))
                    status = 200
                else:
                    raise IllegalArgumentError(
                        f"Malformed action/metadata line, found [{action}]")
                touched.add(resp["_index"])
                items.append({action: {**resp, "status": status}})
            except SearchEngineError as e:
                errors = True
                if action in ("index", "create", "update") and i <= len(operations):
                    pass
                items.append({action: {"_index": index, "_id": doc_id,
                                       "status": e.status, "error": e.to_dict()}})
        if refresh in ("true", "wait_for", True, ""):
            self._refresh_indices(touched)
        if refresh in ("true", "", True):
            for item in items:
                for inner in item.values():
                    if "error" not in inner:
                        inner["forced_refresh"] = True
        return {"took": 0, "errors": errors, "items": items}

    def _index_or_autocreate(self, index: str) -> IndexService:
        if not self.indices.exists(index):
            # auto-create applying matching templates (reference:
            # TransportBulkAction auto-create + MetaDataIndexTemplateService)
            resolved = self.templates.resolve(index)
            return self.indices.create_index(
                index, settings=resolved["settings"] or None,
                mappings=resolved["mappings"] if resolved["mappings"]["properties"] else None,
                aliases=resolved["aliases"] or None)
        return self.indices.get(index)

    def create_index_with_templates(self, name: str, settings=None,
                                    mappings=None, aliases=None) -> IndexService:
        """Explicit create: template values apply under the request's own."""
        resolved = self.templates.resolve(name)
        merged_settings = dict(resolved["settings"])
        if settings:
            merged_settings.update(settings)
        merged_mappings = {"properties": dict(resolved["mappings"]["properties"])}
        for k, v in ((mappings or {}).get("properties") or {}).items():
            merged_mappings["properties"][k] = v
        for meta_key in ("dynamic", "_source", "_meta", "_routing"):
            if mappings and meta_key in mappings:
                merged_mappings[meta_key] = mappings[meta_key]
        merged_aliases = dict(resolved["aliases"])
        merged_aliases.update(aliases or {})
        return self.indices.create_index(
            name, settings=merged_settings or None,
            mappings=merged_mappings if merged_mappings["properties"] or mappings else mappings,
            aliases=merged_aliases or None)

    def _expand_collapse_inner_hits(self, readers, body, collapse_spec,
                                    hits) -> None:
        from elasticsearch_tpu.index.mapping import AliasFieldMapper
        from elasticsearch_tpu.search.service import (
            execute_fetch_phase, execute_query_phase)

        inner = collapse_spec.get("inner_hits")
        specs = inner if isinstance(inner, list) else [inner]
        cfield = collapse_spec["field"]
        for hit in hits:
            vals = (hit.get("fields") or {}).get(cfield)
            gv = vals[0] if vals else None
            for spec in specs:
                name = spec.get("name", cfield)
                want = int(spec.get("size", 3))
                merged = []
                total = 0
                for svc, reader, store in readers:
                    read_field = cfield
                    raw_m = svc.mapper_service.get_raw(cfield) \
                        if hasattr(svc.mapper_service, "get_raw") \
                        else svc.mapper_service.get(cfield)
                    if isinstance(raw_m, AliasFieldMapper):
                        read_field = (raw_m.params or {}).get("path", cfield)
                    sub_body = {"query": {"bool": {
                        "must": [body["query"]] if body.get("query") else [],
                        "filter": [{"term": {read_field: gv}}]}},
                        "size": want}
                    for key in ("sort", "version", "seq_no_primary_term",
                                "docvalue_fields", "_source"):
                        if spec.get(key) is not None:
                            sub_body[key] = spec[key]
                    sub_result = execute_query_phase(
                        reader, svc.mapper_service, sub_body,
                        vector_store=store, index_name=svc.name)
                    total += sub_result.total_hits
                    sub_hits = execute_fetch_phase(
                        reader, svc.mapper_service, sub_body, sub_result,
                        index_name=svc.name,
                        index_settings=svc.settings.as_flat_dict())
                    merged.extend(sub_hits)
                if spec.get("sort") is None:
                    merged.sort(key=lambda h: -(h.get("_score") or 0.0))
                else:
                    merged.sort(key=lambda h: tuple(h.get("sort") or []))
                hit.setdefault("inner_hits", {})[name] = {"hits": {
                    "total": {"value": total, "relation": "eq"},
                    "max_score": (merged[0].get("_score")
                                  if merged else None),
                    "hits": merged[:want]}}

    def _search_rrf(self, index_expr: Optional[str], body: dict,
                    rrf: dict, ignore_throttled: bool) -> dict:
        """Reciprocal-rank fusion at the coordinator (BASELINE config 3:
        hybrid BM25 + kNN; the reference's designated fusion point is the
        rescore boundary — RRF composes the ranked lists instead:
        score(d) = Σ_lists 1 / (rank_constant + rank_list(d))).

        Sub-searches come from `sub_searches: [{query}, ...]` or, in the
        common hybrid shape, the top-level `query` plus `knn` clauses.
        """
        rank_constant = int(rrf.get("rank_constant", 60))
        window = int(rrf.get("rank_window_size", rrf.get("window_size", 100)))
        size = int(body.get("size", 10))
        frm = int(body.get("from", 0) or 0)
        body = self._rewrite_terms_lookup(body)

        sub_queries: List[dict] = []
        if body.get("sub_searches"):
            sub_queries = [s.get("query", {"match_all": {}})
                           for s in body["sub_searches"]]
        else:
            if body.get("query") is not None:
                sub_queries.append(body["query"])
            if body.get("knn") is not None:
                knn = body["knn"]
                # a knn LIST is one ranked list per clause (matching the
                # fused plan's leg expansion — hybrid_plan._sub_queries_of)
                if isinstance(knn, list):
                    sub_queries.extend({"knn": spec} for spec in knn)
                else:
                    sub_queries.append({"knn": knn})
        if len(sub_queries) < 2:
            raise IllegalArgumentError(
                "[rrf] requires at least 2 ranked lists (sub_searches, or "
                "query + knn)")

        passthrough = {k: v for k, v in body.items()
                       if k in ("_source", "docvalue_fields", "highlight")}
        start = time.perf_counter()

        # Fast path (single index): run the sub-searches as QUERY PHASES
        # only, fuse ranks on row ids, and fetch just the final `size` docs
        # — the query-then-fetch shape (SearchPhaseController), vs. the
        # general path below that materializes `window` full hits per list.
        try:
            services = self.indices.resolve_open(index_expr) \
                if index_expr and ":" not in index_expr else []
        except SearchEngineError:
            services = []
        from elasticsearch_tpu.common.settings import setting_bool
        if len(services) == 1 \
                and not setting_bool(services[0].settings.get("index.frozen")) \
                and "highlight" not in body:  # highlighting needs the
            # per-sub-search query context — the general path keeps it
            from elasticsearch_tpu.search.service import (
                ShardSearchResult, execute_fetch_phase, execute_query_phase)

            svc = services[0]
            if not body.get("__rrf_two_phase__"):
                # fused hybrid plan: whole queries coalesce through the
                # bounded per-index batcher, legs score in one device
                # dispatch each, RRF fuses vectorized. The inline
                # two-phase path below stays as the parity oracle
                # (tests/test_hybrid_plan.py proves byte-identical
                # results) and the escape hatch.
                resp = self._hybrid_executor(svc).submit(body)
                # the hybrid device path must feed the same telemetry
                # surfaces as the host query path: e2e latency histogram
                # + per-index slow log with phase breakdown and trace.
                # The executor ships the breakdown on a private key so
                # UNPROFILED breaches carry it too; pop it before the
                # response reaches the client.
                phases = resp.pop("_took_phases", None)
                took_s = time.perf_counter() - start
                _telemetry.stage_done("search.took", start * 1e9,
                                      (start + took_s) * 1e9)
                _task = _teletrace.current_task()
                self.search_slow_log.maybe_log(
                    svc.settings, svc.name, took_s,
                    source={"rank": {"rrf": rrf}},
                    opaque_id=getattr(_task, "opaque_id", None),
                    trace=_teletrace.current_trace(),
                    phases=phases)
                return resp
            reader = svc.combined_reader()
            store = _MultiShardVectorStore(svc)
            breaker_bytes = reader.num_docs * 16
            self.breakers.add_estimate("request", breaker_bytes, "<rrf>")
            try:
                fused_rows: Dict[int, float] = {}
                for q in sub_queries:
                    result = execute_query_phase(
                        reader, svc.mapper_service,
                        {"query": q, "size": window},
                        vector_store=store, query_cache=self.caches.query,
                        index_settings=svc.settings.as_flat_dict(),
                        max_buckets=self._max_buckets(),
                        allow_expensive=self._allow_expensive(),
                        index_name=svc.name)
                    for rank_pos, row in enumerate(result.rows):
                        row = int(row)
                        fused_rows[row] = fused_rows.get(row, 0.0) + 1.0 / (
                            rank_constant + rank_pos + 1)
                ordered = sorted(fused_rows.items(),
                                 key=lambda kv: (-kv[1], kv[0]))
                top = ordered[frm:frm + size]
                final = ShardSearchResult(
                    0, np.asarray([r for r, _ in top], dtype=np.int64),
                    np.asarray([s for _, s in top], dtype=np.float32),
                    None, len(fused_rows), "eq", None,
                    top[0][1] if top else None)
                hits = execute_fetch_phase(reader, svc.mapper_service,
                                           {**passthrough, "size": size},
                                           final, index_name=svc.name)
            finally:
                self.breakers.release("request", breaker_bytes)
            for h, (_, score) in zip(hits, top):
                h["_score"] = score
            return {"took": int((time.perf_counter() - start) * 1000),
                    "timed_out": False,
                    "hits": {"total": {"value": len(fused_rows),
                                       "relation": "eq"},
                             "max_score": hits[0]["_score"] if hits else None,
                             "hits": hits}}

        fused: Dict[tuple, float] = {}
        hit_by_key: Dict[tuple, dict] = {}
        for q in sub_queries:
            sub_body = {"query": q, "size": window, **passthrough}
            resp = self.search(index_expr, sub_body,
                               ignore_throttled=ignore_throttled)
            for rank_pos, hit in enumerate(resp["hits"]["hits"]):
                key = (hit["_index"], hit["_id"])
                fused[key] = fused.get(key, 0.0) + 1.0 / (
                    rank_constant + rank_pos + 1)
                hit_by_key.setdefault(key, hit)
        ordered = sorted(fused.items(), key=lambda kv: (-kv[1], kv[0]))
        hits = []
        for key, score in ordered[frm:frm + size]:
            hit = dict(hit_by_key[key])
            hit["_score"] = score
            hit.pop("sort", None)
            hits.append(hit)
        return {"took": int((time.perf_counter() - start) * 1000),
                "timed_out": False,
                "hits": {"total": {"value": len(fused), "relation": "eq"},
                         "max_score": hits[0]["_score"] if hits else None,
                         "hits": hits}}

    def _evict_stale_hybrid(self) -> None:
        """Drop executors whose IndexService is no longer live (index
        deleted or recreated): they pin the closed service's engines and
        the lexical store's tile/device arrays, and their counters must
        not keep flowing into _nodes/stats. Swept from every hybrid
        entry point because deletion has several paths (REST, cascades,
        ILM) and none of them knows about this cache."""
        for name, ex in list(self._hybrid.items()):
            if self.indices.indices.get(name) is not ex.svc:
                del self._hybrid[name]

    def _evict_stale_aggs(self) -> None:
        """Same sweep for device-agg engines: a deleted/recreated index's
        engine pins its columnar store and pollutes _nodes/stats."""
        for name, (svc, _eng) in list(self._aggs.items()):
            if self.indices.indices.get(name) is not svc:
                del self._aggs[name]

    def _agg_engine(self, svc):
        """Per-index device aggregation engine (search/agg_plan.py),
        created lazily like the hybrid executor; None when device aggs
        are disabled (`search.aggs.device_enabled: false`). A refresh
        listener resyncs warm columns in the background so a dashboard's
        first post-refresh query doesn't pay the column rebuild inline —
        the agg-store analog of `vectors/store.sync` at refresh."""
        from elasticsearch_tpu.common.settings import setting_bool
        enabled = self.settings.get("search.aggs.device_enabled")
        if enabled is not None and not setting_bool(enabled):
            return None
        with self._aggs_lock:
            self._evict_stale_aggs()
            cached = self._aggs.get(svc.name)
            if cached is not None and cached[0] is svc:
                return cached[1]
            from elasticsearch_tpu.search.agg_plan import AggEngine
            router = self.settings.get("search.aggs.cost_router")
            engine = AggEngine(svc.mapper_service,
                               warmup=self._dispatch_warmup,
                               cost_router=(self._agg_cost_router()
                                            if router is None
                                            or setting_bool(router)
                                            else False))

            def _resync(_reader, svc=svc, engine=engine):
                def run():
                    try:
                        reader = svc.combined_reader()
                        for field in engine.store.fields():
                            col = engine.store.column(reader, field)
                            engine.store.schedule_warmup(col)
                    except Exception:  # pragma: no cover - background
                        pass
                if engine.store.fields():
                    _threading.Thread(target=run, daemon=True,
                                      name="agg-column-resync").start()

            for shard in svc.shards:
                shard.engine.add_refresh_listener(_resync)
            self._aggs[svc.name] = (svc, engine)
            return engine

    def _agg_cost_router(self):
        """The node's ONE shared cost router, disk-backed at
        `<data>/_state/agg_router.json`: every index's agg engine trains
        the same per-node EWMA tables, each observation persists them,
        and a restart seeds them back instead of re-probing cold (the
        PR 19 leftover — `router_restores` counts the seeded families)."""
        router = getattr(self, "_agg_router", None)
        if router is None:
            import os as _os

            from elasticsearch_tpu.search.agg_plan import CostRouter
            state_dir = _os.path.join(self.indices.data_path, "_state")
            _os.makedirs(state_dir, exist_ok=True)
            router = CostRouter(
                persist_path=_os.path.join(state_dir, "agg_router.json"))
            self._agg_router = router
        return router

    def _aggs_stats_section(self) -> dict:
        """Device-aggregation counters summed over local indices
        (`_nodes/stats indices.aggs`): per-node device vs host-fallback
        routing (with reasons), agg-plan cache hit rate, cumulative
        device/assembly time, mesh dispatches, and columnar-store
        footprint."""
        out = {"searches": 0, "device_nodes": 0, "host_nodes": 0,
               "plan_cache_hits": 0, "plan_cache_misses": 0,
               "device_nanos": 0, "assemble_nanos": 0, "host_nanos": 0,
               "mesh_dispatches": 0, "router_host_routed": 0,
               "router_probes": 0, "router_restores": 0,
               "fallback_reasons": {},
               "columns": 0, "column_bytes": 0, "column_rebuilds": 0}
        router = getattr(self, "_agg_router", None)
        if router is not None:
            out["router_restores"] = router.restores
        with self._aggs_lock:
            self._evict_stale_aggs()
            engines = [eng for _svc, eng in self._aggs.values()]
        for eng in engines:
            for key in ("searches", "device_nodes", "host_nodes",
                        "plan_cache_hits", "plan_cache_misses",
                        "device_nanos", "assemble_nanos", "host_nanos",
                        "mesh_dispatches", "router_host_routed",
                        "router_probes"):
                out[key] += eng.stats.get(key, 0)
            # per-reason entries are {count, docs[, observed_max]}: doc
            # totals rank reasons by routed WORK, observed_max sizes
            # ladder growth (e.g. the ordinal count that busted the grid)
            for reason, ent in eng.stats.get("fallback_reasons",
                                             {}).items():
                agg = out["fallback_reasons"].setdefault(
                    reason, {"count": 0, "docs": 0})
                agg["count"] += ent["count"]
                agg["docs"] += ent["docs"]
                if "observed_max" in ent:
                    agg["observed_max"] = max(ent["observed_max"],
                                              agg.get("observed_max", 0))
            out["columns"] += eng.store.stats.get("columns", 0)
            out["column_bytes"] += eng.store.stats.get("bytes", 0)
            out["column_rebuilds"] += eng.store.stats.get("rebuilds", 0)
        return out

    def _hybrid_executor(self, svc):
        """Per-index fused hybrid serving path (plan cache + bounded
        combining queue), created lazily; replaced when the index is
        recreated under the same name."""
        from elasticsearch_tpu.common.settings import setting_bool
        from elasticsearch_tpu.ops import dispatch as _dispatch
        from elasticsearch_tpu.search.hybrid_plan import HybridExecutor
        self._evict_stale_hybrid()
        ex = self._hybrid.get(svc.name)
        if ex is None or ex.svc is not svc:
            s = self.settings
            # dispatch/finalize overlap only pays where device compute
            # runs on separate silicon: depth 2 on accelerator backends,
            # 1 on CPU floors (measured: a second in-flight dispatch on
            # the CPU backend contends with batch N's finalize for the
            # same cores and only adds tail — hybrid closed-loop p99/p50
            # 3.28 at depth 2 vs 2.76 at depth 1, same throughput)
            depth_default = 2 if _dispatch.is_accelerator_backend() else 1
            ex = HybridExecutor(
                self, svc,
                max_batch=int(s.get("search.hybrid.max_batch", 64)),
                max_queue_depth=int(
                    s.get("search.hybrid.max_queue_depth", 256)),
                deadline_ms=float(
                    s.get("search.hybrid.queue_deadline_ms", 10_000)),
                topup=setting_bool(s.get("search.hybrid.topup", True)),
                target_batch_latency_ms=float(
                    s.get("search.hybrid.target_batch_latency_ms", 2.0)),
                async_depth=int(s.get("search.hybrid.async_depth",
                                      depth_default)))
            self._hybrid[svc.name] = ex
        return ex

    def _hybrid_stats_section(self) -> dict:
        """Fused-hybrid serving counters summed over local indices:
        searches/batches through the plan executor, plan-cache hit rate,
        admission-control shedding, the closed-loop tail attribution
        (queue-wait vs device dispatch+sync vs hydrate), and the
        continuous batcher's scheduler counters (topups,
        deadline_sheds, overlap_hits)."""
        out = {"searches": 0, "batches": 0, "plan_cache_hits": 0,
               "plan_cache_misses": 0, "plan_nanos": 0, "score_nanos": 0,
               "fuse_nanos": 0, "hydrate_nanos": 0, "queue_wait_nanos": 0,
               "dispatch_nanos": 0, "sync_nanos": 0, "rejected_depth": 0,
               "shed_deadline": 0, "max_queue_depth_seen": 0,
               "request_cache_hits": 0, "request_cache_misses": 0,
               "request_cache_stores": 0,
               "scheduler": {"topups": 0, "deadline_sheds": 0,
                             "overlap_hits": 0, "pipelined_batches": 0},
               "sparse": {"searches": 0, "queries": 0, "rebuilds": 0,
                          "score_nanos": 0, "grid_fallbacks": 0},
               "late_interaction": {"searches": 0, "queries": 0,
                                    "rebuilds": 0, "score_nanos": 0,
                                    "grid_fallbacks": 0, "fields": {}}}
        self._evict_stale_hybrid()
        for ex in self._hybrid.values():
            for key in ("searches", "batches", "plan_cache_hits",
                        "plan_cache_misses", "plan_nanos", "score_nanos",
                        "fuse_nanos", "hydrate_nanos", "queue_wait_nanos",
                        "dispatch_nanos", "sync_nanos",
                        "request_cache_hits", "request_cache_misses",
                        "request_cache_stores"):
                out[key] += ex.stats.get(key, 0)
            for key in ("searches", "queries", "rebuilds", "score_nanos"):
                out["sparse"][key] += ex.sparse.stats.get(key, 0)
                out["late_interaction"][key] += ex.late.stats.get(key, 0)
            out["sparse"]["grid_fallbacks"] += ex.stats.get(
                "sparse_grid_fallbacks", 0)
            out["late_interaction"]["grid_fallbacks"] += ex.stats.get(
                "maxsim_grid_fallbacks", 0)
            out["late_interaction"]["fields"].update(ex.late.field_stats())
            bs = ex.batcher.stats
            out["rejected_depth"] += bs.get("rejected_depth", 0)
            out["shed_deadline"] += bs.get("shed_deadline", 0)
            out["max_queue_depth_seen"] = max(
                out["max_queue_depth_seen"], bs.get("max_depth_seen", 0))
            for key, val in ex.scheduler_snapshot().items():
                out["scheduler"][key] += val
        return out

    def _run_query_phase(self, svc, reader, store, body, use_partial_aggs,
                         frozen):
        """One index's query phase. Frozen indices run on the
        single-threaded search_throttled pool (queue 100): cold data may
        be searched, never at the expense of hot traffic (x-pack
        frozen-indices + ThreadPool.java:129)."""
        kwargs = dict(vector_store=store, partial_aggs=use_partial_aggs,
                      query_cache=self.caches.query,
                      index_settings=svc.settings.as_flat_dict(),
                      max_buckets=self._max_buckets(),
                      allow_expensive=self._allow_expensive(),
                      index_name=svc.name,
                      agg_engine=self._agg_engine(svc))
        from elasticsearch_tpu.search.service import execute_query_phase
        if frozen:
            return self.thread_pool.submit(
                "search_throttled", execute_query_phase,
                reader, svc.mapper_service, body, **kwargs).result()
        return execute_query_phase(reader, svc.mapper_service, body, **kwargs)

    @staticmethod
    def _maybe_refresh(svc: IndexService, refresh, shard=None) -> None:
        # a doc-level ?refresh=true refreshes only the TARGET shard
        # (TransportShardBulkAction) — other shards' unrefreshed
        # tombstones/docs must stay invisible
        if refresh in ("true", "wait_for", True, ""):
            if shard is not None:
                shard.engine.refresh()
            else:
                svc.refresh()

    def _refresh_indices(self, names) -> None:
        """Refresh hook for bulk epilogues — overridden by the clustered
        deployment to broadcast instead of touching local services."""
        for name in names:
            self.indices.get(name).refresh()

    # ---------------------------------------------------------------- search
    def search(self, index_expr: Optional[str], body: Optional[dict],
               ignore_throttled: bool = True,
               ignore_unavailable: bool = False,
               allow_no_indices: bool = True,
               expand_wildcards: Optional[str] = None) -> dict:
        body = body or {}
        rank = body.get("rank")
        if isinstance(rank, dict) and "rrf" in rank:
            return self._search_rrf(index_expr, body, rank["rrf"] or {},
                                    ignore_throttled)
        # cross-cluster search: split `alias:index` parts, fan out, merge
        # (reference: TransportSearchAction + SearchResponseMerger)
        if index_expr and ":" in index_expr:
            from elasticsearch_tpu.xpack.ccr import merge_ccs_responses
            local_expr, remote_exprs = self.remotes.split_indices(index_expr)
            remote_resps, clusters = self.remotes.search_remotes(
                remote_exprs, body)
            local_resp = self.search(local_expr, body) if local_expr else None
            return merge_ccs_responses(local_resp, remote_resps, body,
                                       clusters)
        start = time.perf_counter()
        body = self._rewrite_terms_lookup(body)
        if ignore_unavailable and index_expr:
            # IndicesOptions.lenientExpandOpen: missing/closed concrete
            # names silently drop from the target set
            kept = []
            for part in index_expr.split(","):
                part = part.strip()
                try:
                    for svc in self.indices.resolve(part):
                        if not svc.closed:
                            kept.append(svc.name)
                except SearchEngineError:
                    continue
            services = self.indices.resolve_open(",".join(kept)) \
                if kept else []
        else:
            ew = {t.strip() for t in str(expand_wildcards or "open").split(",")
                  if t.strip()}
            if ew & {"closed", "all"}:
                # expand_wildcards=closed surfaces closed matches, and a
                # closed index in the target set is an error
                # (IndicesOptions.forbidClosedIndices for search)
                services = self.indices.resolve(index_expr,
                                                expand_closed=True)
                for svc in services:
                    self.indices.check_open(svc)
            else:
                services = self.indices.resolve_open(index_expr)
        if not allow_no_indices and not services and index_expr \
                and "*" in index_expr:
            raise IndexNotFoundError(index_expr)
        if ignore_throttled:
            # frozen indices sit out of normal searches unless the caller
            # passes ignore_throttled=false (reference:
            # x-pack/plugin/frozen-indices + search_throttled pool)
            from elasticsearch_tpu.common.settings import setting_bool
            services = [s for s in services
                        if not setting_bool(s.settings.get("index.frozen"))]
        readers = []
        for svc in services:
            reader = svc.combined_reader()
            store = _MultiShardVectorStore(svc)
            readers.append((svc, reader, store))

        # request breaker accounts the candidate working set (reference:
        # QueryPhase checks the request breaker while collecting)
        breaker_bytes = sum(r.num_docs for _, r, _ in readers) * 16
        self.breakers.add_estimate("request", breaker_bytes, "<search>")

        profile_enabled = bool(body.get("profile"))
        profile_shards = []
        # execute per index, merge across indices by score/sort; with >1
        # index the aggs travel as mergeable partial states and are
        # finalized once after the reduce (agg_partials, the
        # InternalAggregation.reduce analog)
        # indices_boost: per-index score multipliers, resolved up front so
        # unknown names fail the request (SearchRequest#indicesBoost)
        boosts: Dict[str, float] = {}
        ib = body.get("indices_boost")
        if ib:
            entries = ib.items() if isinstance(ib, dict) else \
                [e for d in ib for e in d.items()]
            for expr, boost in entries:
                matched = self.indices.resolve(expr, expand_hidden=True) \
                    if ("*" in expr or self.indices.exists(expr)) else []
                if not matched:
                    if ignore_unavailable:
                        continue
                    raise IndexNotFoundError(expr)
                for svc in matched:
                    boosts.setdefault(svc.name, float(boost))

        aggs_spec = body.get("aggs") or body.get("aggregations")
        if aggs_spec:
            # builder-time validation (the reference rejects bad agg params
            # at request parse, even when zero shards participate)
            from elasticsearch_tpu.search.aggregations import validate_aggs

            def _field_type(f):
                for svc in services:
                    m = svc.mapper_service.get(f)
                    if m is not None:
                        return m.type_name
                return None
            validate_aggs(aggs_spec, _field_type)
        use_partial_aggs = bool(aggs_spec) and len(readers) > 1
        all_hits = []
        total = 0
        relation = "eq"
        max_score = None
        merged_aggs = None
        phase_nanos = {"query_nanos": 0, "fetch_nanos": 0, "merge_nanos": 0}
        # the phases' stages, filed together with `search.took` at the
        # end (the filing code then runs once a request, back to back)
        phase_stages: list = []
        shard_failures: List[dict] = []
        pre_filter = body.pop("__pre_filter_shard_size__", None)
        skipped_shards = 0
        try:
            for svc, reader, store in readers:
                if pre_filter is not None and body.get("query") is not None \
                        and not _has_global_agg(body.get("aggs")
                                                or body.get("aggregations")):
                    from elasticsearch_tpu.search.caches import can_match
                    if not can_match(reader, svc.mapper_service, body):
                        # can_match pre-filter: provably-empty shards are
                        # SKIPPED, not executed (CanMatchPreFilterSearchPhase)
                        skipped_shards += svc.num_shards
                        continue
                q_start = time.perf_counter_ns()
                if profile_enabled:
                    # per-shard dispatch trace: which shape bucket every
                    # device kernel hit and what compiling cost (empty in
                    # steady state; `profile.dispatch` renders it)
                    from elasticsearch_tpu.ops import dispatch as _dispatch
                    _dispatch.DISPATCH.record_events(True)
                # shard request cache: query-phase results keyed on the
                # reader CONTENT fingerprint (search/caches.reader_
                # fingerprint) — a refresh that changed nothing keeps
                # its hits, any ingest/delete/merge invalidates. Two
                # rungs share the policy: the legacy host rung (size=0
                # aggs/counts, the device-agg engine's dashboard shape)
                # and the device rung (kNN-bearing bodies, size > 0 —
                # the query phase IS the device dispatch there).
                from elasticsearch_tpu.search.caches import (
                    reader_fingerprint)
                cache_key = None
                cache_used = None
                cache_hit = False
                result = None
                # device rung first: it claims every knn-bearing body
                # (flag-opted-in ones included), so the host rung keeps
                # its original host-side population (size=0 aggs/counts)
                if self._device_request_cache_enabled() \
                        and self.caches.device_request.device_cacheable(
                            body):
                    cache_used = self.caches.device_request
                elif self.caches.request.cacheable_tracked(body):
                    cache_used = self.caches.request
                if cache_used is not None:
                    # partial vs finalized agg trees differ per request shape
                    # (multi-index searches ship partials); max_buckets is
                    # dynamic, so a changed limit must miss the cache, and a
                    # mesh-policy reconfigure must miss rather than serve a
                    # result (and its routing diagnostics) computed under
                    # the old serving config
                    from elasticsearch_tpu.parallel import policy as _policy
                    cache_key = cache_used.key(
                        (svc.name, svc.uuid, use_partial_aggs,
                         self._max_buckets(), self._allow_expensive(),
                         _policy.config_epoch()),
                        reader_fingerprint(reader), body)
                    result = cache_used.get(cache_key)
                    cache_hit = result is not None
                if result is None:
                    from elasticsearch_tpu.common.settings import setting_bool
                    frozen = setting_bool(svc.settings.get("index.frozen"))
                    try:
                        result = self._run_query_phase(
                            svc, reader, store, body, use_partial_aggs,
                            frozen)
                    except ArrayIndexOutOfBoundsError as e:
                        # execution-class failure inside an aggregator
                        # (HDR percentiles fed a negative). The fused
                        # single-node pass spans every internal shard, but
                        # the reference fails at SHARD granularity: probe
                        # each shard alone — only shards whose MATCHED
                        # docs trip the aggregator fail — then retry the
                        # fused pass without them (partial response).
                        all_ids = frozenset(
                            s.shard_id for s in svc.shards)
                        failed = set()
                        for s in svc.shards:
                            probe_reader = svc.combined_reader(
                                exclude_shards=all_ids - {s.shard_id})
                            probe_store = _ShardScopedStore(
                                store, frozenset({s.shard_id}))
                            try:
                                self._run_query_phase(
                                    svc, probe_reader, probe_store, body,
                                    use_partial_aggs, frozen)
                            except ArrayIndexOutOfBoundsError:
                                failed.add(s.shard_id)
                        if not failed:
                            # combined raised but no single shard does —
                            # cannot attribute; fail them all
                            failed = set(all_ids)
                        for sid in sorted(failed):
                            shard_failures.append({
                                "shard": sid, "index": svc.name,
                                "node": self.node_id,
                                "reason": e.to_dict()})
                        if len(failed) >= svc.num_shards:
                            continue
                        reader = svc.combined_reader(
                            exclude_shards=frozenset(failed))
                        result = self._run_query_phase(
                            svc, reader,
                            _ShardScopedStore(store, all_ids - failed),
                            body, use_partial_aggs, frozen)
                        cache_key = None  # partial result: never cache
                    except SearchEngineError:
                        raise  # the request's own fault, with its status
                    except Exception as e:
                        # anything else the query phase raises — a kernel
                        # the device refused, a dispatch that died — fails
                        # THIS index's shards in the response (the
                        # reference's per-shard failure), it is never
                        # answered from another route behind the caller
                        logger.exception(
                            "query phase failed on index [%s]", svc.name)
                        for s in svc.shards:
                            shard_failures.append({
                                "shard": s.shard_id, "index": svc.name,
                                "node": self.node_id,
                                "reason": {
                                    "type": "shard_execution_exception",
                                    "reason": f"{type(e).__name__}: {e}"}})
                        continue
                    if cache_key is not None:
                        cache_used.put(cache_key, result)
                q_nanos = time.perf_counter_ns() - q_start
                phase_nanos["query_nanos"] += q_nanos
                phase_stages.append(("search.query", q_start,
                                     q_start + q_nanos, svc.name))
                for f in getattr(result, "failures", None) or []:
                    f = dict(f)
                    f["index"] = svc.name
                    f["node"] = self.node_id
                    shard_failures.append(f)
                total += result.total_hits
                if result.total_relation == "gte":
                    relation = "gte"
                factor = boosts.get(svc.name, 1.0)
                if result.max_score is not None:
                    max_score = max(max_score or -1e30,
                                    result.max_score * factor)
                f_start = time.perf_counter_ns()
                hits = execute_fetch_phase(
                    reader, svc.mapper_service, body, result,
                    index_name=svc.name,
                    index_settings=svc.settings.as_flat_dict())
                f_nanos = time.perf_counter_ns() - f_start
                phase_nanos["fetch_nanos"] += f_nanos
                phase_stages.append(("search.fetch", f_start,
                                     f_start + f_nanos, svc.name))
                for h, score, sv in zip(hits, result.scores,
                                        result.sort_values or [None] * len(hits)):
                    if factor != 1.0 and h.get("_score") is not None:
                        h["_score"] = float(h["_score"]) * factor
                    all_hits.append((h, float(score) * factor, sv))
                if result.aggregations is not None:
                    if merged_aggs is None:
                        merged_aggs = result.aggregations
                    else:
                        from elasticsearch_tpu.search.agg_partials import (
                            merge_partial_aggs,
                        )
                        merged_aggs = merge_partial_aggs(
                            merged_aggs, result.aggregations, aggs_spec)
                if profile_enabled:
                    from elasticsearch_tpu.ops import dispatch as _dispatch
                    from elasticsearch_tpu.search.profile import shard_profile
                    events = _dispatch.DISPATCH.drain_events()
                    _dispatch.DISPATCH.record_events(False)
                    cache_note = None
                    if cache_used is not None:
                        cache_note = {
                            "rung": ("shard_request"
                                     if cache_used is self.caches.request
                                     else "device_request"),
                            "hit": cache_hit}
                    profile_shards.append(shard_profile(
                        svc.name, body, q_nanos, f_nanos,
                        result.total_hits,
                        knn_phases=result.knn_phases,
                        dispatch_events=events,
                        aggs_profile=result.aggs_profile,
                        cache=cache_note))
        finally:
            self.breakers.release("request", breaker_bytes)
            if profile_enabled:
                # a query-phase error must not leave the thread-local
                # dispatch trace recording into later requests
                from elasticsearch_tpu.ops import dispatch as _dispatch
                _dispatch.DISPATCH.record_events(False)
        n_shards_total = sum(s.num_shards for s, _, _ in readers)
        if shard_failures and n_shards_total \
                and len(shard_failures) >= n_shards_total - skipped_shards:
            # every executed shard failed: the whole phase fails
            # (SearchPhaseExecutionException "all shards failed")
            from elasticsearch_tpu.common.errors import (
                SearchPhaseExecutionError,
            )
            raise SearchPhaseExecutionError("query", "all shards failed",
                                            shard_failures)
        self.counters["search"] += 1
        for g in body.get("stats") or []:
            self._search_groups[str(g)] = \
                self._search_groups.get(str(g), 0) + 1

        m_start = time.perf_counter_ns()
        sort_spec = body.get("sort")
        if sort_spec:
            all_hits.sort(key=lambda t: _sort_key_tuple(t[2], body))
        else:
            all_hits.sort(key=lambda t: -t[1])
        phase_nanos["merge_nanos"] = time.perf_counter_ns() - m_start
        phase_stages.append(("search.merge", m_start,
                             m_start + phase_nanos["merge_nanos"], None))
        collapse_spec = body.get("collapse")
        if collapse_spec and len(readers) > 1:
            # cross-index collapse: per-index phases deduped their own
            # groups; the merged ranking dedupes across indices by the
            # group value each hit carries in `fields`
            seen_groups = set()
            deduped = []
            for t in all_hits:
                vals = (t[0].get("fields") or {}).get(collapse_spec["field"])
                key = vals[0] if vals else None
                if key in seen_groups:
                    continue
                seen_groups.add(key)
                deduped.append(t)
            all_hits = deduped
        frm = int(body.get("from", 0) or 0)
        size = int(body.get("size", 10) if body.get("size") is not None else 10)
        window = all_hits[frm:frm + size]
        if collapse_spec and collapse_spec.get("inner_hits") \
                and len(readers) > 1:
            # inner_hits expand across EVERY index (ExpandSearchPhase runs
            # one multi-index sub-search per collapsed hit); the per-index
            # fetch saw only its own shard
            self._expand_collapse_inner_hits(readers, body, collapse_spec,
                                             [t[0] for t in window])

        resp = {
            "took": int((time.perf_counter() - start) * 1000),
            "timed_out": False,
            "_shards": {"total": sum(s.num_shards for s, _, _ in readers),
                        "successful": sum(s.num_shards for s, _, _ in readers)
                        - len(shard_failures),
                        "skipped": skipped_shards,
                        "failed": len(shard_failures),
                        **({"failures": shard_failures}
                           if shard_failures else {})},
            "hits": {
                "total": {"value": total, "relation": relation},
                "max_score": max_score,
                "hits": [h for h, _, _ in window],
            },
        }
        brs = body.get("batched_reduce_size")
        n_sh = resp["_shards"]["total"]
        if brs and int(brs) < n_sh:
            # phases: one partial reduce per filled buffer + the final
            # reduce (QueryPhaseResultConsumer counting)
            resp["num_reduce_phases"] = -(-n_sh // int(brs)) + 1
        if body.get("track_total_hits") is False \
                or body.get("track_total_hits") == -1:
            # hit counting disabled (false or the -1 sentinel): no total
            # in the response (RestSearchAction)
            del resp["hits"]["total"]
        else:
            track = body.get("track_total_hits")
            if isinstance(track, int) and not isinstance(track, bool) \
                    and total > track:
                # coordinator-level cap: per-index phases may each be under
                # the limit while the summed total crosses it
                resp["hits"]["total"] = {"value": track, "relation": "gte"}
        if merged_aggs is not None:
            if use_partial_aggs:
                from elasticsearch_tpu.search.agg_partials import finalize_aggs
                merged_aggs = finalize_aggs(merged_aggs, aggs_spec)
            resp["aggregations"] = merged_aggs
        if profile_enabled:
            resp["profile"] = {"shards": profile_shards}
        # slow log (reference: SearchSlowLog thresholds per index) —
        # breaches carry the phase breakdown, the caller's X-Opaque-ID,
        # and this request's trace (id + top spans) when sampled
        took_s = time.perf_counter() - start
        for name, begun, ended, index in phase_stages:
            _telemetry.stage_done(name, begun, ended, index=index)
        _telemetry.stage_done("search.took", start * 1e9,
                              (start + took_s) * 1e9)
        _task = _teletrace.current_task()
        for svc, _, _ in readers:
            self.search_slow_log.maybe_log(
                svc.settings, svc.name, took_s, source=body.get("query"),
                opaque_id=getattr(_task, "opaque_id", None),
                trace=_teletrace.current_trace(),
                phases=dict(phase_nanos))

        suggest_spec = body.get("suggest")
        if suggest_spec:
            from elasticsearch_tpu.search.extras import execute_suggest
            from elasticsearch_tpu.search.queries import SearchContext
            merged_suggest: Dict[str, list] = {}
            for svc, reader, _ in readers:
                ctx = SearchContext(reader, svc.mapper_service)
                for name, entries in execute_suggest(
                        ctx, suggest_spec, index_name=svc.name).items():
                    if name not in merged_suggest:
                        merged_suggest[name] = entries
                    else:
                        for a, b in zip(merged_suggest[name], entries):
                            a["options"] = sorted(
                                a["options"] + b["options"],
                                key=lambda o: -o.get("score", o.get("_score", 0.0)))
            resp["suggest"] = merged_suggest
        return resp

    # ----------------------------------------------------------------- scroll
    def search_scroll_start(self, index_expr: Optional[str], body: Optional[dict],
                            keep_alive: str = "1m",
                            ignore_throttled: bool = True) -> dict:
        """Initial search with ?scroll=: snapshot all matching docs in order,
        return the first page + a scroll id."""
        body = self._rewrite_terms_lookup(dict(body or {}))
        if body.get("collapse") is not None:
            raise IllegalArgumentError(
                "cannot use `collapse` in a scroll context")
        size = int(body.get("size", 10) if body.get("size") is not None else 10)
        entries = []  # (svc, reader, row, score, sort_values)
        total = 0
        from elasticsearch_tpu.common.settings import setting_bool
        services = self.indices.resolve_open(index_expr)
        for svc in services:
            mrw = int(svc.settings.get("index.max_result_window", 10_000))
            if size > mrw:
                raise IllegalArgumentError(
                    f"Batch size is too large, size must be less than or "
                    f"equal to: [{mrw}] but was [{size}]. Scroll batch "
                    f"sizes cost as much memory as result windows so they "
                    f"are controlled by the [index.max_result_window] index "
                    f"level setting.")
        if ignore_throttled:
            services = [s for s in services
                        if not setting_bool(s.settings.get("index.frozen"))]
        # scroll slicing (search/slice/SliceBuilder) is applied inside
        # execute_query_phase: shard-level when max <= shards, hashed
        # _id terms otherwise
        for svc in services:
            reader = svc.combined_reader()
            store = _MultiShardVectorStore(svc)
            # scroll snapshots EVERY matching doc — deep pagination past the
            # 10k window is the point of scrolling
            big = dict(body)
            big["size"] = max(reader.num_docs, 1)
            big["__unbounded_window__"] = True
            big["track_total_hits"] = True
            big.pop("from", None)
            result = execute_query_phase(
                reader, svc.mapper_service, big, vector_store=store,
                index_settings=svc.settings.as_flat_dict(),
                index_name=svc.name)
            kept_rows = list(range(len(result.rows)))
            total += result.total_hits
            for i in kept_rows:
                row = result.rows[i]
                sv = result.sort_values[i] if result.sort_values is not None else None
                entries.append((svc, reader, int(row), float(result.scores[i]), sv))
        if body.get("sort"):
            entries.sort(key=lambda t: _sort_key_tuple(t[4], body))
        else:
            entries.sort(key=lambda t: -t[3])
        keep_s = parse_time_value(keep_alive, "scroll")
        scroll_id = self.scrolls.create(entries, body, keep_s)
        sc = self.scrolls.get(scroll_id)
        sc.total = total
        resp = self._scroll_page(sc, size)
        resp["_scroll_id"] = scroll_id
        return resp

    def search_scroll_next(self, scroll_id: str,
                           keep_alive: Optional[str] = None) -> dict:
        sc = self.scrolls.get(scroll_id)
        if keep_alive:
            sc.keep_alive = parse_time_value(keep_alive, "scroll")
        size = int(sc.body.get("size", 10) if sc.body.get("size") is not None else 10)
        resp = self._scroll_page(sc, size)
        resp["_scroll_id"] = scroll_id
        return resp

    def _scroll_page(self, sc, size: int) -> dict:
        page = sc.slices[sc.cursor: sc.cursor + size]
        sc.cursor += len(page)
        hits = []
        for svc, reader, row, score, sv in page:
            hit = {"_index": svc.name, "_id": reader.get_id(row),
                   "_score": score if not sc.body.get("sort") else None,
                   "_source": reader.get_source(row)}
            if sv is not None:
                hit["sort"] = list(sv)
            hits.append(hit)
        total = getattr(sc, "total", len(sc.slices))
        return {"took": 0, "timed_out": False,
                "_shards": {"total": 1, "successful": 1, "skipped": 0, "failed": 0},
                "hits": {"total": {"value": total, "relation": "eq"},
                         "max_score": None, "hits": hits}}

    def pending_cluster_tasks(self) -> list:
        return []

    def clear_scroll(self, scroll_id: str) -> dict:
        freed = 1 if self.scrolls.delete(scroll_id) else 0
        return {"succeeded": True, "num_freed": freed}

    def clear_all_scrolls(self) -> dict:
        return {"succeeded": True, "num_freed": self.scrolls.delete_all()}

    def count(self, index_expr: Optional[str], body: Optional[dict]) -> dict:
        body = self._rewrite_terms_lookup(dict(body or {}))
        body["size"] = 0
        body.pop("sort", None)
        total = 0
        for svc in self.indices.resolve_open(index_expr):
            reader = svc.combined_reader()
            result = execute_query_phase(
                reader, svc.mapper_service,
                {**body, "track_total_hits": True},
                vector_store=_MultiShardVectorStore(svc),
                index_name=svc.name)
            total += result.total_hits
        return {"count": total, "_shards": {"total": 1, "successful": 1,
                                            "skipped": 0, "failed": 0}}

    def msearch(self, lines: List[dict]) -> dict:
        responses = []
        i = 0
        while i < len(lines):
            header = lines[i]
            i += 1
            body = lines[i] if i < len(lines) else {}
            i += 1
            try:
                resp = self.search(header.get("index"), body)
                resp["status"] = 200
                responses.append(resp)
            except SearchEngineError as e:
                responses.append({"error": e.to_wrapped_dict(),
                                  "status": e.status})
        return {"took": 0, "responses": responses}

    def analyze(self, body: dict, index: Optional[str] = None) -> dict:
        from elasticsearch_tpu.index.analysis import (
            Analyzer, _as_list, _builtin_filter, _builtin_tokenizer,
            _build_filter, _build_tokenizer,
        )
        text = body.get("text", "")
        texts = text if isinstance(text, list) else [text]
        registry = DEFAULT_REGISTRY
        max_tokens = 10_000
        if index and self.indices.exists(index):
            # index-scoped: custom analyzers from index.analysis.* settings
            svc = self.indices.get(index)
            registry = svc.analysis_registry
            max_tokens = int(svc.settings.get(
                "index.analyze.max_token_count", 10_000))

        custom = "tokenizer" in body or "filter" in body \
            or "char_filter" in body
        filters = []
        filter_names = []
        if custom:
            tok_spec = body.get("tokenizer", "keyword")
            if isinstance(tok_spec, dict):
                tokenizer = _build_tokenizer(tok_spec)
                tok_name = tok_spec.get("type", "custom")
            else:
                tokenizer = _builtin_tokenizer(str(tok_spec))
                tok_name = str(tok_spec)
            for f in _as_list(body.get("filter", [])) \
                    if not isinstance(body.get("filter"), dict) \
                    else [body["filter"]]:
                if isinstance(f, dict):
                    filters.append(_build_filter(f))
                    filter_names.append(f.get("type", "custom"))
                else:
                    filters.append(_builtin_filter(str(f)))
                    filter_names.append(str(f))
            analyzer = Analyzer("__custom__", tokenizer, filters)
            analyzer_name = None
        else:
            analyzer_name = body.get("analyzer", "standard")
            analyzer = registry.get(analyzer_name)

        def _render(toks, pos_base=0):
            return [{"token": t.term, "start_offset": t.start_offset,
                     "end_offset": t.end_offset, "type": "<ALPHANUM>",
                     "position": pos_base + t.position} for t in toks]

        tokens = []
        tokenizer_tokens = []
        pos = 0
        for t in texts:
            text_tokens = analyzer.analyze(str(t))
            if len(tokens) + len(text_tokens) > max_tokens:
                raise IllegalArgumentError(
                    f"The number of tokens produced by calling _analyze "
                    f"has exceeded the allowed maximum of [{max_tokens}]. "
                    f"This limit can be set by changing the "
                    f"[index.analyze.max_token_count] index level setting.")
            tokens.extend(_render(text_tokens, pos))
            if custom:
                tokenizer_tokens.extend(_render(analyzer.tokenizer(str(t)),
                                                pos))
            # position gap of 1 between texts, like multi-valued fields
            pos += len(text_tokens) + 1
        if body.get("explain"):
            if custom:
                detail = {"custom_analyzer": True,
                          "tokenizer": {"name": tok_name,
                                        "tokens": tokenizer_tokens}}
                if filter_names:
                    detail["tokenfilters"] = [
                        {"name": n, "tokens": tokens}
                        for n in filter_names]
                return {"detail": detail}
            return {"detail": {"custom_analyzer": False,
                               "analyzer": {"name": analyzer_name,
                                            "tokens": tokens}}}
        return {"tokens": tokens}

    # ----------------------------------------------------------------- stats
    def _rewrite_terms_lookup(self, body: dict) -> dict:
        """Coordinator rewrite of terms-lookup clauses: fetch the source
        doc ONCE and inline its values (reference:
        TermsQueryBuilder.doRewrite + GetRequest on the coordinator)."""
        def has_terms(node):
            # cheap key scan — str()/dumps of a body holding a dense query
            # vector costs more than the whole rewrite
            if isinstance(node, dict):
                if "terms" in node:
                    return True
                return any(has_terms(v) for v in node.values())
            if isinstance(node, list) and node \
                    and isinstance(node[0], (dict, list)):
                return any(has_terms(i) for i in node)
            return False

        scope = {k: (body or {}).get(k)
                 for k in ("query", "aggs", "aggregations")
                 if (body or {}).get(k) is not None}
        if not scope or not has_terms(scope):
            return body
        import copy as _copy
        from elasticsearch_tpu.search.service import _get_path
        body = dict(body)
        for k in scope:
            body[k] = _copy.deepcopy(body[k])

        def walk(node):
            if isinstance(node, dict):
                t = node.get("terms")
                if isinstance(t, dict):
                    for f, v in list(t.items()):
                        if f in ("boost", "_name") or not isinstance(v, dict):
                            continue
                        if "index" not in v:
                            continue
                        doc = self.get_doc(v["index"], str(v.get("id")),
                                           routing=v.get("routing"))
                        vals = _get_path(doc.get("_source") or {},
                                         str(v.get("path", "")))
                        t[f] = (vals if isinstance(vals, list)
                                else [vals] if vals is not None else [])
                for val in node.values():
                    walk(val)
            elif isinstance(node, list):
                for item in node:
                    walk(item)
        for k in scope:
            walk(body[k])
        return body

    def _cluster_setting(self, key: str):
        """Dynamic cluster setting lookup, transient before persistent
        (ClusterSettings precedence); accepts flat or nested storage."""
        for scope in ("transient", "persistent"):
            s = self.cluster_settings.get(scope, {})
            v = s.get(key)
            if v is None:
                node = s
                for part in key.split("."):
                    node = node.get(part) if isinstance(node, dict) else None
                v = node
            if v is not None:
                return v
        return None

    def _allow_expensive(self) -> bool:
        v = self._cluster_setting("search.allow_expensive_queries")
        return v is None or str(v).lower() != "false"

    def _device_request_cache_enabled(self) -> bool:
        """`search.request_cache.device_paths` (default on): the shard
        request cache rung on the fused device paths — hybrid executor
        responses and kNN/device-agg query-phase results. Dynamic
        cluster setting wins over the node setting, so a live cluster
        can turn the rung off without restart."""
        v = self._cluster_setting("search.request_cache.device_paths")
        if v is None:
            v = self.settings.get("search.request_cache.device_paths")
        return v is None or str(v).lower() != "false"

    def _max_buckets(self) -> Optional[int]:
        v = self._cluster_setting("search.max_buckets")
        return int(v) if v is not None else None

    def cluster_health(self, index: Optional[str] = None,
                       level: str = "cluster",
                       expand_wildcards: str = "all") -> dict:
        """Single-node health: replicas can never assign, so a replicated
        index makes the cluster yellow (ClusterStateHealth semantics).
        Closed indices count too (replicated in 8.0); health defaults to
        expanding BOTH open and closed wildcards."""
        tokens = {t for t in str(expand_wildcards).split(",") if t}
        want_open = bool(tokens & {"open", "all"})
        want_closed = bool(tokens & {"closed", "all"})
        missing_concrete = False
        if index:
            import fnmatch as _fn
            services = []
            for part in index.split(","):
                part = part.strip()
                matched = False
                for name, svc in self.indices.indices.items():
                    if not (_fn.fnmatch(name, part) if "*" in part
                            else name == part):
                        continue
                    if svc.closed and not want_closed and "*" in part:
                        continue
                    if not svc.closed and not want_open and "*" in part:
                        continue
                    services.append(svc)
                    matched = True
                # a concrete index that doesn't exist makes health RED and
                # the request time out (ClusterStateHealth: nonexistent
                # index -> red, TransportClusterHealthAction waits -> 408)
                if not matched and "*" not in part:
                    missing_concrete = True
        else:
            services = [s for s in self.indices.indices.values()
                        if (s.closed and want_closed)
                        or (not s.closed and want_open)]
        seen = set()
        services = [s for s in services
                    if s.name not in seen and not seen.add(s.name)]
        shards = sum(s.num_shards for s in services)
        unassigned = sum(s.num_shards * s.num_replicas for s in services)
        total = shards + unassigned
        out = {
            "cluster_name": self.cluster_name,
            "status": "yellow" if unassigned else "green",
            "timed_out": False, "number_of_nodes": 1,
            "number_of_data_nodes": 1, "active_primary_shards": shards,
            "active_shards": shards, "relocating_shards": 0,
            "initializing_shards": 0, "unassigned_shards": unassigned,
            "delayed_unassigned_shards": 0, "number_of_pending_tasks": 0,
            "number_of_in_flight_fetch": 0, "task_max_waiting_in_queue_millis": 0,
            "active_shards_percent_as_number":
                (shards / total * 100.0) if total else 100.0,
        }
        if missing_concrete:
            out["status"] = "red"
            out["timed_out"] = True
        if level in ("indices", "shards"):
            indices_out = {}
            for svc in services:
                un = svc.num_shards * svc.num_replicas
                entry = {
                    "status": "yellow" if un else "green",
                    "number_of_shards": svc.num_shards,
                    "number_of_replicas": svc.num_replicas,
                    "active_primary_shards": svc.num_shards,
                    "active_shards": svc.num_shards,
                    "relocating_shards": 0, "initializing_shards": 0,
                    "unassigned_shards": un,
                }
                if level == "shards":
                    entry["shards"] = {
                        str(s.shard_id): {
                            "status": "yellow" if svc.num_replicas
                            else "green",
                            "primary_active": True,
                            "active_shards": 1,
                            "relocating_shards": 0,
                            "initializing_shards": 0,
                            "unassigned_shards": svc.num_replicas,
                        } for s in svc.shards}
                indices_out[svc.name] = entry
            out["indices"] = indices_out
        return out

    # metric flag -> response section key (RestIndicesStatsAction METRICS;
    # the `merge` flag renders as `merges`)
    _STATS_METRIC_TO_SECTION = {
        "docs": "docs", "store": "store", "indexing": "indexing",
        "get": "get", "search": "search", "merge": "merges",
        "refresh": "refresh", "flush": "flush", "warmer": "warmer",
        "query_cache": "query_cache", "fielddata": "fielddata",
        "completion": "completion", "segments": "segments",
        "translog": "translog", "request_cache": "request_cache",
        "recovery": "recovery", "bulk": "bulk",
    }

    @staticmethod
    def _fielddata_bytes(shard_list, field: str) -> int:
        """On-demand fielddata size estimate: the inverted doc-values the
        reference builds lazily for text fielddata (terms + entries)."""
        total = 0
        for shard in shard_list:
            reader = shard.engine.acquire_searcher()
            for view in reader.views:
                postings = view.segment.postings.get(field) or {}
                for term, p in postings.items():
                    total += len(str(term)) * 2 + 8 * p.doc_freq
        return total

    def index_stats(self, name: Optional[str] = None,
                    metrics: Optional[List[str]] = None,
                    level: str = "indices",
                    fields: Optional[str] = None,
                    fielddata_fields: Optional[str] = None,
                    completion_fields: Optional[str] = None,
                    groups: Optional[str] = None,
                    include_segment_file_sizes: bool = False,
                    include_unloaded_segments: bool = False,
                    forbid_closed_indices: bool = True,
                    expand_hidden: bool = False) -> dict:
        """`GET [/{index}]/_stats[/{metric}]` (IndicesStatsAction):
        per-index stat sections with metric filtering, level=cluster/
        indices/shards, fields/groups breakdowns; `_shards.total` counts
        primaries + configured replicas."""
        import difflib as _difflib
        import fnmatch as _fn
        if metrics and not any(m in ("_all", "*") for m in metrics):
            keep = set()
            for m in metrics:
                section = self._STATS_METRIC_TO_SECTION.get(m)
                if section is None:
                    close = _difflib.get_close_matches(
                        m, self._STATS_METRIC_TO_SECTION, n=1)
                    hint = f" -> did you mean [{close[0]}]?" if close else ""
                    raise IllegalArgumentError(
                        f"request [/_stats/{m}] contains unrecognized "
                        f"metric: [{m}]{hint}")
                keep.add(section)
        else:
            keep = set(self._STATS_METRIC_TO_SECTION.values())

        services = list(self.indices.resolve(name,
                                             expand_hidden=expand_hidden))
        if not forbid_closed_indices:
            have = {s.name for s in services}
            services += [s for s in self.indices.indices.values()
                         if s.closed and s.name not in have]
        else:
            services = [s for s in services if not s.closed]

        def _match_any(field, patterns):
            return any(_fn.fnmatchcase(field, p.strip())
                       for p in str(patterns).split(","))

        import os as _os

        def shard_sections(svc, shard_list) -> dict:
            closed = svc.closed
            docs = sum(s.engine.doc_count() for s in shard_list)
            segs = 0 if closed and not include_unloaded_segments else \
                sum(len(s.engine.segments) for s in shard_list)
            # size counts the operation files only: the checkpoint file's
            # length varies with digit counts and would break the
            # size-returns-to-creation invariant the reference suite pins
            tlog_bytes = 0
            for s in shard_list:
                tdir = _os.path.join(s.engine.path, "translog")
                if _os.path.isdir(tdir):
                    tlog_bytes += sum(
                        _os.path.getsize(_os.path.join(tdir, f))
                        for f in _os.listdir(tdir) if f.endswith(".tlog"))
            tlog_ops = sum(len(s.engine.translog.read_ops())
                           for s in shard_list) \
                if "translog" in keep else 0
            uncommitted = sum(
                max(s.engine.local_checkpoint
                    - (s.engine.last_commit_checkpoint
                       if s.engine.last_commit_checkpoint is not None
                       else -1), 0)
                for s in shard_list)
            ops_total = sum(s.engine.local_checkpoint + 1
                            for s in shard_list)
            # fielddata / completion on-demand sizes with per-field
            # breakdowns controlled by the fields params — only computed
            # when the section is requested (full postings walk)
            fd_fields: Dict[str, int] = {}
            comp_fields: Dict[str, int] = {}
            if keep & {"fielddata", "completion"}:
                loaded = getattr(svc.mapper_service,
                                 "loaded_fielddata", set())
                for path, mapper in svc.mapper_service.all_mappers():
                    t = getattr(mapper, "type_name", None)
                    fd_capable = (t == "keyword"
                                  or (t == "text"
                                      and mapper.params.get("fielddata")))
                    if fd_capable and "fielddata" in keep:
                        # fielddata/global-ordinals are built LAZILY: bytes
                        # appear only once an aggregation actually loaded
                        # the field (map execution hint never does)
                        fd_fields[path] = self._fielddata_bytes(
                            shard_list, path) if path in loaded else 0
                    elif t == "completion" and "completion" in keep:
                        comp_fields[path] = max(
                            self._fielddata_bytes(shard_list, path),
                            64 * docs)
            fielddata = {"memory_size_in_bytes": sum(fd_fields.values()),
                         "evictions": 0}
            fd_pat = fielddata_fields if fielddata_fields is not None \
                else fields
            if fd_pat is not None:
                fielddata["fields"] = {
                    f: {"memory_size_in_bytes": b}
                    for f, b in fd_fields.items() if _match_any(f, fd_pat)}
            completion = {"size_in_bytes": sum(comp_fields.values())}
            comp_pat = completion_fields if completion_fields is not None \
                else fields
            if comp_pat is not None:
                completion["fields"] = {
                    f: {"size_in_bytes": b}
                    for f, b in comp_fields.items()
                    if _match_any(f, comp_pat)}
            search_sec = {"query_total": 0, "query_time_in_millis": 0,
                          "fetch_total": 0, "open_contexts": 0}
            segments_sec = {"count": segs, "memory_in_bytes": 0,
                            "index_writer_memory_in_bytes": 0,
                            "version_map_memory_in_bytes": 0,
                            "fixed_bit_set_memory_in_bytes": 0}
            if include_segment_file_sizes:
                segments_sec["file_sizes"] = {
                    "seg": {"size_in_bytes": max(
                        sum(_dir_size(s.engine.path) for s in shard_list)
                        - tlog_bytes, 1),
                        "description": "segment data"}}
            newest = max((_os.path.getmtime(_os.path.join(
                s.engine.path, "translog"))
                for s in shard_list
                if _os.path.isdir(_os.path.join(s.engine.path, "translog"))),
                default=time.time())
            full = {
                "docs": {"count": docs, "deleted": 0},
                "store": {"size_in_bytes": max(
                    sum(_dir_size(s.engine.path) for s in shard_list)
                    - tlog_bytes, 0),
                    "reserved_in_bytes": 0},
                "indexing": {"index_total": ops_total, "index_failed": 0,
                             "delete_total": 0, "index_time_in_millis": 0},
                "get": {"total": self._index_get_counts.get(svc.name, 0),
                        "missing_total": 0, "time_in_millis": 0},
                "search": search_sec,
                "merges": {"total": 0, "total_docs": 0,
                           "total_size_in_bytes": 0,
                           "total_time_in_millis": 0},
                "refresh": {"total": 0, "external_total": 0,
                            "total_time_in_millis": 0},
                "flush": {"total": getattr(svc, "flush_count", 0),
                          "periodic": 0,
                          "total_time_in_millis": 0},
                "warmer": {"current": 0, "total": 0,
                           "total_time_in_millis": 0},
                "segments": segments_sec,
                "translog": {"operations": tlog_ops if not closed else 0,
                             "size_in_bytes": tlog_bytes,
                             "uncommitted_operations":
                                 uncommitted if not closed else 0,
                             "uncommitted_size_in_bytes": tlog_bytes,
                             "earliest_last_modified_age":
                                 max(int((time.time() - newest) * 1000), 0)},
                "query_cache": {"memory_size_in_bytes": 0, "hit_count": 0,
                                "miss_count": 0, "evictions": 0},
                "request_cache": {"memory_size_in_bytes": 0, "hit_count": 0,
                                  "miss_count": 0, "evictions": 0},
                "fielddata": fielddata,
                "completion": completion,
                "recovery": {"current_as_source": 0,
                             "current_as_target": 0},
                "bulk": {"total_operations": 0,
                         "total_time_in_millis": 0},
            }
            return {k: v for k, v in full.items() if k in keep}

        indices_out = {}
        total_shards = 0
        successful = 0
        agg: dict = {}
        for svc in services:
            total_shards += svc.num_shards * (1 + svc.num_replicas)
            successful += svc.num_shards
            sections = shard_sections(svc, svc.shards)
            entry = {"uuid": svc.uuid,
                     "primaries": sections,
                     "total": sections}
            if level == "shards":
                entry["shards"] = {
                    str(s.shard_id): [{
                        **shard_sections(svc, [s]),
                        "routing": {"state": "STARTED", "primary": True,
                                    "node": self.node_id},
                        "commit": {"id": f"{svc.uuid}-{s.shard_id}",
                                   "generation": 1, "num_docs":
                                       s.engine.doc_count(),
                                   "user_data": {}},
                        "seq_no": {"max_seq_no": s.engine.local_checkpoint,
                                   "local_checkpoint":
                                       s.engine.local_checkpoint,
                                   "global_checkpoint":
                                       s.engine.local_checkpoint},
                    }] for s in svc.shards}
            indices_out[svc.name] = entry
            _deep_merge_add(agg, sections)
        # node-global counters attributed once at the _all level
        if "search" in keep and "search" in agg:
            agg["search"]["query_total"] = max(
                self.counters.get("search", 0),
                agg["search"].get("query_total", 0))
            if groups is not None:
                agg["search"]["groups"] = {
                    g: {"query_total": n, "query_time_in_millis": 0,
                        "fetch_total": n}
                    for g, n in self._search_groups.items()
                    if _match_any(g, groups) and n > 0}
        if "query_cache" in keep and "query_cache" in agg:
            agg["query_cache"].update(
                memory_size_in_bytes=self.caches.query.bytes,
                hit_count=self.caches.query.hits,
                miss_count=self.caches.query.misses,
                evictions=self.caches.query.evictions)
        if "request_cache" in keep and "request_cache" in agg:
            # both rungs of the shard request cache: the legacy host
            # path and the device-path cache (hybrid/kNN/device-agg);
            # bytes are the LruCache's tracked approximation, not 0
            host, dev = self.caches.request, self.caches.device_request
            agg["request_cache"].update(
                memory_size_in_bytes=host.bytes + dev.bytes,
                hit_count=host.hits + dev.hits,
                miss_count=host.misses + dev.misses,
                evictions=host.evictions + dev.evictions,
                skipped_uncacheable=(host.skipped_uncacheable
                                     + dev.skipped_uncacheable))
        if "bulk" in keep and "bulk" in agg:
            # node-global counter: once at _all, not summed per index
            agg["bulk"]["total_operations"] = self.counters.get("bulk", 0)
        out = {"_shards": {"total": total_shards, "successful": successful,
                           "failed": 0},
               "_all": {"primaries": agg, "total": agg}}
        if level != "cluster":
            out["indices"] = indices_out
        return out

    # -------------------------------------------------- node-level admin APIs
    # The per-node sections below are the "nodeOperation" halves of the
    # reference's TransportNodesAction pattern: REST handlers call the
    # *_api envelope methods, which the clustered deployment overrides with
    # a transport fan-out + merge (cluster/rest_node.py) while these local
    # collectors run unchanged on every node.

    def local_node_info(self) -> dict:
        natives = getattr(self, "natives", None)
        nested_settings: dict = {"client": {"type": "node"},
                                 "node": {"name": self.node_name},
                                 "cluster": {"name": self.cluster_name}}
        for key, value in (self.settings or {}).items():
            node_ = nested_settings
            parts = str(key).split(".")
            for part in parts[:-1]:
                nxt = node_.setdefault(part, {})
                if not isinstance(nxt, dict):
                    break
                node_ = nxt
            else:
                node_[parts[-1]] = value
        return {"name": self.node_name, "version": __version__,
                "roles": ["master", "data", "ingest"],
                "settings": nested_settings,
                "process": {
                    "mlockall": bool(natives and natives.memory_locked),
                    "seccomp": bool(natives and natives.seccomp_installed)},
                "plugins": self.plugins.info()}

    def local_node_stats(self, level: str = None,
                         include_segment_file_sizes: bool = False) -> dict:
        from elasticsearch_tpu.monitor.probes import (
            fs_probe, os_probe, process_probe, runtime_probe,
        )
        def _index_section(svc):
            segs = sum(len(sh.engine.acquire_searcher().views)
                       for sh in svc.shards)
            return {
                "docs": {"count": svc.doc_count(), "deleted": 0},
                "store": {"size_in_bytes": svc.store_size_bytes()
                          if hasattr(svc, "store_size_bytes") else 0},
                "segments": {"count": segs},
            }

        indices_section = {
            "docs": {"count": sum(
                s.doc_count()
                for s in self.indices.indices.values())},
            "store": {"size_in_bytes": sum(
                getattr(s, "store_size_bytes", lambda: 0)()
                for s in self.indices.indices.values())},
            "segments": {"count": sum(
                len(sh.engine.acquire_searcher().views)
                for s in self.indices.indices.values()
                for sh in s.shards),
                "device": self._device_segments_section(),
                **({"file_sizes": {"columns": {"size_in_bytes": 0}}}
                   if include_segment_file_sizes else {})},
            "get": {"total": self.counters.get("get", 0)},
            "merges": {"total": self.counters.get("merge", 0)},
            "recovery": self._recovery_section(),
            "translog": {"operations": 0},
            "fielddata": {"memory_size_in_bytes": 0, "evictions": 0},
            "completion": {"size_in_bytes": 0},
            "refresh": {"total": self.counters.get("refresh", 0)},
            "flush": {"total": self.counters.get("flush", 0)},
            "warmer": {"total": 0},
            "search": {"query_total": self.counters.get("search", 0)},
            "indexing": {"index_total":
                         self.counters.get("index", 0)},
            "request_cache": {
                "memory_size_in_bytes": (self.caches.request.bytes
                                         + self.caches.device_request.bytes),
                "hit_count": (self.caches.request.hits
                              + self.caches.device_request.hits),
                "miss_count": (self.caches.request.misses
                               + self.caches.device_request.misses),
                "evictions": (self.caches.request.evictions
                              + self.caches.device_request.evictions),
                "skipped_uncacheable": (
                    self.caches.request.skipped_uncacheable
                    + self.caches.device_request.skipped_uncacheable),
                # per-rung breakdown: `device` is the fused hybrid /
                # kNN / device-agg request cache (fingerprint-keyed),
                # the top-level counters remain the combined view
                "host": self.caches.request.stats(),
                "device": self.caches.device_request.stats()},
            "query_cache": {
                "memory_size_in_bytes": self.caches.query.bytes,
                "hit_count": self.caches.query.hits,
                "miss_count": self.caches.query.misses,
                "evictions": self.caches.query.evictions},
            "knn": self._knn_stats_section(),
            "hybrid": self._hybrid_stats_section(),
            "aggs": self._aggs_stats_section(),
            "dispatch": self._dispatch_stats_section(),
            "mesh": self._mesh_stats_section(),
            "columnar": self._columnar_stats_section(),
            "slowlog": {"search": self.search_slow_log.stats(),
                        "indexing": self.indexing_slow_log.stats()}}
        discovery_section = {
            "cluster_state_queue": {"total": 0, "pending": 0,
                                    "committed": 0},
            "published_cluster_states": {"full_states": 0,
                                         "incompatible_diffs": 0,
                                         "compatible_diffs": 0}}
        if level in ("indices", "shards"):
            # per-index breakdown (`?level=indices` —
            # NodeIndicesStats.toXContent level handling)
            indices_section["indices"] = {
                name: _index_section(svc)
                for name, svc in self.indices.indices.items()}
        return {"name": self.node_name,
                "roles": ["data", "ingest", "master"],
                "jvm": runtime_probe(),
                "os": os_probe(),
                "fs": fs_probe(self.indices.data_path),
                "process": process_probe(),
                "indices": indices_section,
                "device": self._device_stats_section(),
                "discovery": discovery_section,
                "breakers": self.breakers.stats(),
                "thread_pool": self.thread_pool.stats(),
                "telemetry": self._telemetry_stats_section()}

    @staticmethod
    def _device_stats_section() -> dict:
        """What the kernels ran on, as JAX reports it: platform, device
        kind and count, each device's `memory_stats()` (where the
        backend keeps them), and the measured dispatch overhead
        (`ops/dispatch.device_overhead_ms`; null until a BM25 search
        has needed it)."""
        import jax

        from elasticsearch_tpu.ops import dispatch
        devices = jax.devices()
        memory = []
        for d in devices:
            ms = d.memory_stats() or {}
            memory.append({k: int(ms[k]) for k in
                           ("bytes_in_use", "peak_bytes_in_use",
                            "bytes_limit") if k in ms})
        return {"platform": devices[0].platform,
                "device_kind": devices[0].device_kind,
                "count": len(devices),
                "memory": memory,
                "cost_model": {
                    "device_overhead_ms": dispatch._overhead_ms}}

    def _recovery_section(self) -> dict:
        """`indices.recovery` for a single node: block-level restore
        accounting folded over every index restored from a repository
        (recovery/progress.py shape; cluster nodes report live peer
        recoveries through the same keys via `recovery_summary`)."""
        done = reused = shipped = bytes_shipped = 0
        for svc in self.indices.indices.values():
            for st in (getattr(svc, "recovery_block_stats", None)
                       or {}).values():
                done += 1
                reused += int(st.get("blocks_reused", 0))
                shipped += int(st.get("blocks_shipped", 0))
                bytes_shipped += int(st.get("bytes_shipped", 0))
        from elasticsearch_tpu.recovery.snapshot import NODE_STREAM_LIMITER
        streams = dict(NODE_STREAM_LIMITER.stats)
        streams["max_streams"] = NODE_STREAM_LIMITER.max_streams
        streams["max_bytes_per_sec"] = NODE_STREAM_LIMITER.max_bytes_per_sec
        return {"current_as_source": 0, "current_as_target": 0,
                "completed": done, "blocks_reused": reused,
                "blocks_shipped": shipped, "bytes_shipped": bytes_shipped,
                "throttle_time_in_millis":
                    int(streams["throttle_time_in_millis"]),
                # bounded-concurrency snapshot block upload + per-node
                # byte-rate throttle (recovery/snapshot.py limiter)
                "snapshot_streams": streams,
                "attempts": 0, "retries": 0, "giveups": 0}

    def _device_segments_section(self) -> dict:
        """Generational device-corpus counters summed over local shards
        (`elasticsearch_tpu/segments/`): generation counts/bytes per
        tier, seals, merges run + merge nanos, tombstoned rows, and the
        full-rebuild accounting (rebuilds by reason vs rebuilds the
        incremental path avoided) — the before/after ledger of the
        write-while-search stall."""
        out: dict = {"full_rebuilds": 0, "rebuilds_avoided": 0,
                     "rebuild_reasons": {}, "tiers": {}}
        for svc in self.indices.indices.values():
            for shard in svc.shards:
                stats_fn = getattr(shard.vector_store, "segment_stats",
                                   None)
                if stats_fn is None:
                    continue
                for key, val in stats_fn().items():
                    if key in ("rebuild_reasons", "tiers"):
                        slot = out[key]
                        for k2, v2 in val.items():
                            if isinstance(v2, dict):
                                tier = slot.setdefault(
                                    k2, {k3: 0 for k3 in v2})
                                for k3, v3 in v2.items():
                                    tier[k3] += v3
                            else:
                                slot[k2] = slot.get(k2, 0) + v2
                    elif isinstance(val, bool):
                        out[key] = out.get(key, False) or val
                    elif isinstance(val, (int, float)):
                        out[key] = out.get(key, 0) + val
        return out

    @staticmethod
    def _columnar_stats_section() -> dict:
        """Segment block store counters (`elasticsearch_tpu/columnar/`):
        live per-field block counts/bytes, cache hits vs extractions
        (+ extract nanos), evictions, and the delta-vs-full composition
        ledger — the counter form of the O(delta) refresh claim.
        Process-wide like the dispatch section: one block per (segment,
        field, kind) serves every consumer on this node."""
        from elasticsearch_tpu import columnar
        return columnar.STORE.stats()

    @staticmethod
    def _dispatch_stats_section() -> dict:
        """Shape-bucketed kernel dispatch counters (`ops/dispatch.py`):
        executable-cache hits/misses, compiles and cumulative compile
        time, warmup/out-of-grid compiles, plus the per-bucket breakdown.
        The process-wide dispatcher serves every index on this node, so
        this section is node-level by construction (like the query
        cache)."""
        from elasticsearch_tpu.ops import dispatch
        return dispatch.stats(per_bucket=True)

    @staticmethod
    def _mesh_stats_section() -> dict:
        """Mesh-sharded serving counters (`parallel/policy.py`): shard
        count, the host router's mesh-vs-single-device decisions (with
        reasons), and per-leg SPMD timings + analytic all-gather bytes.
        Process-wide like the dispatch section — one physical mesh serves
        every index on this node."""
        from elasticsearch_tpu.parallel import policy
        return policy.stats()

    def _knn_stats_section(self) -> dict:
        """Vector-search engine counters summed over local shards:
        `searches` (DISPATCHES of the exhaustive routes: one a coalesced
        batch, not the searches in it — those are `scheduler.requests`),
        how many took the pruned tpu_ivf path vs fell back to
        exhaustive (or rode the SPMD mesh), fused-probe dispatches and
        two-phase rescore window stats (the quant subsystem's serving
        counters), cumulative per-phase device time, the per-field
        encoding/bytes-per-doc ladder breakdown, and the per-(field, k)
        continuous-batching scheduler counters (batches / requests /
        topups / overlap; their times are the telemetry stages)."""
        out = {"searches": 0, "ivf_searches": 0, "fallback_searches": 0,
               "mesh_searches": 0,
               # no store counts this any more: a constant 0 kept for
               # benchmark/kinds/knn.py, knn_int8.py and chip_smoke.py,
               # which read the key and compare it with 0 (ROADMAP D13)
               "host_mirror_searches": 0,
               "fused_probe_searches": 0,
               "rescore_searches": 0, "rescore_window_rows": 0,
               "rescore_promoted": 0, "rescore_nanos": 0,
               "route_nanos": 0, "score_nanos": 0, "merge_nanos": 0,
               "semantic_probes": 0, "semantic_hits": 0,
               "semantic_rejects": 0, "semantic_inserts": 0,
               "semantic_invalidations": 0, "semantic_probe_nanos": 0}
        sched: dict = {}
        fields: dict = {}
        for svc in self.indices.indices.values():
            for shard in svc.shards:
                stats = getattr(shard.vector_store, "knn_stats", None)
                if stats:
                    for key in out:
                        out[key] += stats.get(key, 0)
                sched_fn = getattr(shard.vector_store, "scheduler_stats",
                                   None)
                if sched_fn is not None:
                    for key, val in sched_fn().items():
                        sched[key] = sched.get(key, 0) + val
                fields_fn = getattr(shard.vector_store, "field_stats",
                                    None)
                if fields_fn is not None:
                    for field, fs in fields_fn().items():
                        slot = fields.get(field)
                        if slot is None:
                            fields[field] = dict(fs)
                        else:
                            # shards of one field share the encoding
                            # plan; the size halves sum
                            for key in ("rows", "device_bytes"):
                                slot[key] = (slot.get(key, 0)
                                             + fs.get(key, 0))
        out["scheduler"] = sched
        out["fields"] = fields
        return out

    @staticmethod
    def _telemetry_stats_section() -> dict:
        """Live percentile surfaces (`_nodes/stats telemetry`): the
        process-wide metrics registry's histograms (end-to-end search
        latency, queue wait, device dispatch/sync, fan-out leg latency —
        p50/p90/p99/p999 each, no bench harness required) plus the
        tracer's sampling/ring counters and the heartbeat's stalls (the
        two counters and the newest records of what held the server).
        Process-wide like the dispatch section. The device-starved
        counters are booked up to this read first, so that two reads
        bracket exactly their window."""
        from elasticsearch_tpu.serving.batcher import IDLE
        from elasticsearch_tpu.telemetry import BEAT, REGISTRY, TRACER
        IDLE.flush()
        return {**REGISTRY.snapshot(), "tracing": TRACER.snapshot(),
                "stalls": BEAT.snapshot()}

    def local_traces_section(self, limit: int = 50) -> dict:
        """This node's completed-trace ring (`GET _nodes/traces`): most
        recent first, filtered to traces/segments that completed on THIS
        node (the tracer is process-wide; a simulated multi-node process
        shares one ring with per-node attribution)."""
        from elasticsearch_tpu.telemetry import TRACER
        return {"name": self.node_name,
                "traces": TRACER.traces(node_id=self.node_id,
                                        limit=limit)}

    def local_hot_threads(self, interval_s: float = 0.05,
                          top_n: int = 3) -> str:
        from elasticsearch_tpu.monitor import hot_threads_report
        return hot_threads_report(interval_s=min(interval_s, 0.5),
                                  top_n=top_n,
                                  node_name=self.node_name)

    def local_tasks_section(self, actions: Optional[str] = None) -> dict:
        return {"name": self.node_name,
                "roles": ["data", "ingest", "master"],
                "tasks": {t.task_id: t.to_dict(self.node_id)
                          for t in self.tasks.list_tasks(actions)}}

    @staticmethod
    def _matches_csv_patterns(name: str, patterns_csv) -> bool:
        from elasticsearch_tpu.common.patterns import matches_csv_patterns
        return matches_csv_patterns(name, patterns_csv)

    def local_cat_threadpool_rows(self, pool_filter=None) -> list:
        import os as _os
        info = self.thread_pool.info()
        rows = []
        for name, s in sorted(self.thread_pool.stats().items()):
            if not self._matches_csv_patterns(name, pool_filter):
                continue
            meta = info.get(name, {})
            ptype = meta.get("type", "fixed")
            threads = meta.get("size", 0)
            scaling = ptype == "scaling"
            rows.append([self.node_name, self.node_id, self.node_id,
                         _os.getpid(), "127.0.0.1", "127.0.0.1",
                         9300, name, ptype, s["active"],
                         s.get("threads", 0), s["queue"],
                         meta.get("queue_size", -1),
                         s["rejected"], s.get("largest", 0),
                         s.get("completed", 0),
                         1 if scaling else "", threads if scaling else "",
                         "" if scaling else threads,
                         "5m" if scaling else ""])
        return rows

    def cat_threadpool_rows_api(self, pool_filter=None) -> list:
        return self.local_cat_threadpool_rows(pool_filter)

    def local_cat_nodeattrs_rows(self) -> list:
        import os as _os
        attrs = dict(getattr(self, "node_attrs", {}) or {})
        return [[self.node_name, self.node_id, _os.getpid(),
                 "127.0.0.1", "127.0.0.1", 9300, k, v]
                for k, v in sorted(attrs.items())]

    def cat_nodeattrs_rows_api(self) -> list:
        return self.local_cat_nodeattrs_rows()

    def local_cat_fielddata_rows(self, field_filter=None) -> list:
        """Plain-value rows (size as int — the REST handler applies the cat
        Bytes wrapper; wrappers don't survive the transport)."""
        rows = []
        seen = set()
        for svc in self.indices.indices.values():
            for path, mapper in svc.mapper_service.all_mappers():
                if mapper.type_name != "text" \
                        or not mapper.params.get("fielddata"):
                    continue
                if not self._matches_csv_patterns(path, field_filter):
                    continue
                if path in seen:
                    continue
                seen.add(path)
                size = max(svc.doc_count() * 32, 1)
                rows.append([self.node_id, "127.0.0.1", "127.0.0.1",
                             self.node_name, path, size])
        return rows

    def cat_fielddata_rows_api(self, field_filter=None) -> list:
        return self.local_cat_fielddata_rows(field_filter)

    def local_cat_tasks_rows(self) -> list:
        """Plain-value rows (running time in ns — handler applies Millis)."""
        me = self.tasks.register("cluster:monitor/tasks/lists", "cat tasks")
        try:
            rows = []
            for t in self.tasks.list_tasks():
                d = t.to_dict(self.node_id)
                rows.append([d["action"], t.task_id, "-", d["type"],
                             d["start_time_in_millis"],
                             d["running_time_in_nanos"],
                             "127.0.0.1", self.node_name,
                             d["description"] or "-"])
        finally:
            self.tasks.unregister(me)
        return rows

    def cat_tasks_rows_api(self) -> list:
        return self.local_cat_tasks_rows()

    def termvectors_api(self, index: str, doc_id, spec: dict) -> dict:
        """TermVectorsService analog: per-field term/position/offset stats.

        Field statistics come from the READER (sum_doc_freq = Σ doc_freq of
        the field's distinct indexed terms), not from the one document.
        realtime=false reads only refreshed segments (found: false for docs
        sitting in the unrefreshed buffer)."""
        spec = spec or {}
        svc = self.indices.get(index)
        reader = svc.combined_reader()
        realtime = spec.get("realtime", True)
        if isinstance(realtime, str):
            realtime = realtime not in ("false", "0")
        source = None
        if doc_id is not None:
            if not realtime:
                visible = any(reader.get_id(int(r)) == str(doc_id)
                              for r in reader.live_global_rows())
                if not visible:
                    return {"_index": index, "_id": doc_id, "_version": 1,
                            "found": False, "took": 0}
            got = self.get_doc(index, str(doc_id))
            if not got.get("found"):
                return {"_index": index, "_id": doc_id, "found": False,
                        "took": 0}
            source = got["_source"]
        else:
            source = spec.get("doc") or {}
        fields = spec.get("fields")
        want_stats = spec.get("term_statistics") in (True, "true", "")
        out_fields = {}
        for fname, value in (source or {}).items():
            if fields and fname not in fields:
                continue
            mapper = svc.mapper_service.get(fname)
            if mapper is None or not hasattr(mapper, "analyze") \
                    or getattr(mapper, "type_name", "") not in ("text",):
                continue
            tokens = mapper.analyze(str(value))
            text_lower = str(value).lower()
            terms: Dict[str, dict] = {}
            cursor = 0
            for pos, t in enumerate(tokens):
                start = text_lower.find(str(t).lower(), cursor)
                end = start + len(str(t)) if start >= 0 else -1
                if start >= 0:
                    cursor = end
                entry = terms.setdefault(t, {"term_freq": 0, "tokens": []})
                entry["term_freq"] += 1
                tok = {"position": pos}
                if start >= 0:
                    tok["start_offset"] = start
                    tok["end_offset"] = end
                entry["tokens"].append(tok)
            if want_stats:
                for t, entry in terms.items():
                    entry["doc_freq"] = reader.doc_freq(fname, t)
                    ttf = 0
                    for view in reader.views:
                        p = view.segment.postings.get(fname, {}).get(t)
                        if p is not None:
                            ttf += int(p.freqs.sum())
                    entry["ttf"] = ttf
            # field statistics describe the INDEX, not this document
            distinct = set()
            for view in reader.views:
                distinct.update(view.segment.postings.get(fname, {}).keys())
            sum_doc_freq = sum(reader.doc_freq(fname, t) for t in distinct)
            out_fields[fname] = {
                "field_statistics": {
                    "sum_doc_freq": sum_doc_freq,
                    "doc_count": reader.docs_with_field_count(fname),
                    "sum_ttf": reader.total_term_count(fname)},
                "terms": terms}
        return {"_index": index, "_id": doc_id, "_version": 1, "found": True,
                "took": 0, "term_vectors": out_fields}

    def _nodes_envelope(self, nodes: dict, failed: int = 0) -> dict:
        return {"_nodes": {"total": len(nodes) + failed,
                           "successful": len(nodes), "failed": failed},
                "cluster_name": self.cluster_name, "nodes": nodes}

    def nodes_info_api(self) -> dict:
        return self._nodes_envelope({self.node_id: self.local_node_info()})

    def nodes_stats_api(self, level: str = None,
                        include_segment_file_sizes: bool = False) -> dict:
        return self._nodes_envelope(
            {self.node_id: self.local_node_stats(
                level, include_segment_file_sizes)})

    def hot_threads_api(self, interval_s: float = 0.05,
                        top_n: int = 3) -> str:
        return self.local_hot_threads(interval_s, top_n=top_n)

    def traces_api(self, limit: int = 50) -> dict:
        return self._nodes_envelope(
            {self.node_id: self.local_traces_section(limit)})

    def tasks_list_api(self, actions: Optional[str] = None) -> dict:
        return {"nodes": {self.node_id: self.local_tasks_section(actions)}}

    def task_get_api(self, task_id: str) -> dict:
        t = self.tasks.get(task_id)
        return {"completed": False, "task": t.to_dict(self.node_id)}

    def task_cancel_api(self, task_id: str) -> dict:
        t = self.tasks.cancel(task_id)
        return {"nodes": {self.node_id: {
            "tasks": {t.task_id: t.to_dict(self.node_id)}}}}

    def close(self):
        self.ml.close_all()
        self.plugins.remove_extensions()
        for alias in list(self.remotes.remotes):
            self.remotes.unregister(alias)
        self.indices.close()
        self.thread_pool.shutdown()


# ---------------------------------------------------------------------------

def _has_global_agg(aggs) -> bool:
    """Aggregations that need EVERY shard disable can_match skipping:
    `global` aggs and min_doc_count:0 bucket aggs (the reference's
    SearchSourceBuilder#aggregations rewrite check)."""
    for spec in (aggs or {}).values():
        if not isinstance(spec, dict):
            continue
        if "global" in spec:
            return True
        for kind, body in spec.items():
            if kind in ("aggs", "aggregations", "meta"):
                continue
            if isinstance(body, dict) \
                    and str(body.get("min_doc_count")) == "0":
                return True
        if _has_global_agg(spec.get("aggs") or spec.get("aggregations")):
            return True
    return False


def _dir_size(path: str) -> int:
    import os as _os
    total = 0
    for root, _dirs, files in _os.walk(path):
        for f in files:
            try:
                total += _os.path.getsize(_os.path.join(root, f))
            except OSError:
                pass
    return total


def _deep_merge_add(dst: dict, src: dict) -> None:
    """Numeric stat sections sum; nested dicts merge recursively."""
    for k, v in src.items():
        if isinstance(v, dict):
            _deep_merge_add(dst.setdefault(k, {}), v)
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            dst[k] = dst.get(k, 0) + v
        else:
            dst.setdefault(k, v)


def _deep_merge(dst: dict, src: dict) -> None:
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _deep_merge(dst[k], v)
        else:
            dst[k] = v


def _apply_update_script(source: dict, script_spec, ctx_extra=None) -> dict:
    """Update scripts run through the sandboxed Painless interpreter
    (script/painless.py): `ctx._source.*` mutation, loops, conditionals,
    list/map methods, user functions. Returns the mutated source; the
    script's operation verdict lands in ctx['op'] (UpdateHelper honors
    'none'/'delete'). Raises on compile/sandbox violations."""
    from elasticsearch_tpu.script.painless import (
        FrozenParams, compile_painless, execute,
    )

    if isinstance(script_spec, str):
        script_spec = {"source": script_spec}
    if isinstance(script_spec, dict) and "id" in script_spec and "source" not in script_spec:
        from elasticsearch_tpu.script.service import GLOBAL_SCRIPTS
        resolved = GLOBAL_SCRIPTS.resolve(script_spec)
        if resolved["lang"] == "mustache":
            raise IllegalArgumentError(
                f"stored script [{script_spec['id']}] is a [mustache] template, "
                "not usable as an update script")
        script_spec = {"source": resolved["source"],
                       "params": script_spec.get("params", {})}
    src = script_spec.get("source", "")
    params = script_spec.get("params", {})
    ctx_obj = {"_source": source, "op": "index"}
    if ctx_extra:
        ctx_obj.update(ctx_extra)
    try:
        program = compile_painless(src)
    except Exception as e:
        raise IllegalArgumentError(f"compile error in update script: {e}")
    execute(program, {"ctx": ctx_obj, "params": FrozenParams(params)})
    if ctx_extra is not None:
        ctx_extra["op"] = ctx_obj.get("op", "index")
    return source


def _sort_key_tuple(sort_values, body):
    sort = body.get("sort")
    if isinstance(sort, (str, dict)):
        sort = [sort]
    keys = []
    for spec, v in zip(sort or [], sort_values or []):
        direction = "asc"
        if isinstance(spec, dict):
            ((_, o),) = spec.items()
            direction = o if isinstance(o, str) else o.get("order", "asc")
        if isinstance(v, str):
            keys.append(v if direction == "asc" else _InvStr(v))
        elif v is None:
            # missing sorts last regardless of direction; _MissingLast
            # compares greater than both floats and strings so mixed-type
            # columns (string field absent on some docs) don't TypeError
            keys.append(_MISSING_SENTINEL)
        else:
            keys.append(float(v) if direction == "asc" else -float(v))
    return tuple(keys)


class _InvStr:
    """Inverted string ordering for desc sorts in tuple keys."""

    __slots__ = ("s",)

    def __init__(self, s):
        self.s = s

    def __lt__(self, other):
        if isinstance(other, _MissingLast):
            return True
        return self.s > other.s

    def __eq__(self, other):
        return isinstance(other, _InvStr) and self.s == other.s


class _MissingLast:
    """Compares greater than every other sort key (missing sorts last)."""

    __slots__ = ()

    def __lt__(self, other):
        return False

    def __gt__(self, other):
        return not isinstance(other, _MissingLast)

    def __eq__(self, other):
        return isinstance(other, _MissingLast)


_MISSING_SENTINEL = _MissingLast()


# cross-index / cross-shard agg merging lives in search/agg_partials.py:
# shards emit mergeable partial states, the coordinator reduces + finalizes
# (InternalAggregation.reduce analog)
