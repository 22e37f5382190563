"""A full cluster-aware node: coordination + shard lifecycle + replication +
distributed search.

This composes the layers the reference wires in `node/Node.java`:

- `IndicesClusterStateService.applyClusterState` (reference `:210`): on every
  committed cluster state, diff the routing table against local shards —
  create INITIALIZING copies assigned here (primaries activate the
  replication tracker; replicas run ops-based peer recovery from the
  primary), promote on failover, remove unassigned copies.
- `TransportReplicationAction` / `ReplicationOperation` (§3.3): writes route
  to the primary, execute under the primary term, fan out to in-sync replica
  copies, and acknowledge when all copies respond; a failed copy is reported
  to the master (`shard_failed`) which reroutes.
- Peer recovery (§3.5): ops-based phase 2 from the primary's translog when
  retention covers the gap; otherwise phase 1 copies the primary's commit
  files in CRC-framed chunks under a retention lease
  (`RecoverySourceHandler.java:262,274,290`).
- Two-phase scatter-gather search (§3.2): the coordinating node fans
  QUERY-phase requests (rows+scores+sort+partial aggs only) to the
  latency-ranked copy of each shard, folds responses through a streaming
  bounded reduce, then FETCH round-trips for the global window's documents
  — the host-RPC analog of the compiled ICI merge in
  `parallel/sharded_knn.py`.

Transport/scheduler are injected (same API as testing.deterministic), so the
whole stack runs under the deterministic simulator or a real asyncio TCP
transport unchanged.
"""

from __future__ import annotations

import os
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from elasticsearch_tpu.cluster import allocation
from elasticsearch_tpu.cluster.coordination import (
    FOLLOWER, LEADER, Coordinator,
)
from elasticsearch_tpu.cluster.gateway import FilePersistedState
from elasticsearch_tpu.cluster.routing import shard_id_for
from elasticsearch_tpu.cluster.state import (
    ClusterState, DiscoveryNode, ShardRoutingEntry,
)
from elasticsearch_tpu.common.errors import (
    IllegalArgumentError, IndexNotFoundError, SearchContextMissingError,
    SearchEngineError,
)
from elasticsearch_tpu.common.threadpool import EsRejectedExecutionError
from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.serving import fanout as fanout_lib
from elasticsearch_tpu.serving.fanout import ScatterGather
from elasticsearch_tpu import telemetry
from elasticsearch_tpu.telemetry import trace as telemetry_trace
from elasticsearch_tpu.index.mapping import MapperService
from elasticsearch_tpu.index.seqno import ReplicationTracker
from elasticsearch_tpu.search.service import (
    execute_fetch_phase, execute_query_phase,
)
from elasticsearch_tpu.vectors.store import VectorStoreShard

# transport actions (reference: action names in TransportService registry)
WRITE_PRIMARY = "indices:data/write/primary"
WRITE_REPLICA = "indices:data/write/replica"
QUERY_SHARD = "indices:data/read/query"
FETCH_SHARD = "indices:data/read/fetch"
CAN_MATCH_SHARD = "indices:data/read/search[can_match]"
SCROLL_CREATE = "indices:data/read/scroll[create]"
SCROLL_FETCH = "indices:data/read/scroll[fetch]"
SCROLL_FREE = "indices:data/read/scroll[free]"
SCROLL_NEXT = "indices:data/read/scroll[next]"
SCROLL_CLEAR = "indices:data/read/scroll[clear]"
SCROLL_CLEAR_ALL = "indices:data/read/scroll[clear_all]"
RECOVERY_START = "internal:index/shard/recovery/start_recovery"
RECOVERY_FILE_CHUNK = "internal:index/shard/recovery/file_chunk"
NODES_DISPATCH = "cluster:monitor/nodes/dispatch"
MASTER_CREATE_INDEX = "cluster:admin/indices/create"
MASTER_DELETE_INDEX = "cluster:admin/indices/delete"
MASTER_SHARD_STARTED = "internal:cluster/shard/started"
MASTER_SHARD_FAILED = "internal:cluster/shard/failure"
MASTER_UPDATE_SETTINGS = "cluster:admin/settings/update"
MASTER_PUT_REGISTRY = "cluster:admin/registry/update"
MASTER_PUT_PERSISTENT_TASK = "cluster:admin/persistent/update"

# cluster-state metadata key for persistent background tasks (the
# reference's PersistentTasksCustomMetaData): task_id -> {params,
# interval_ms, assigned_node} — the master assigns each task to exactly
# one live node and reassigns on node-leave
PERSISTENT_TASKS_KEY = "__persistent_tasks__"

# cluster-state metadata key for replicated registries (ingest pipelines,
# templates, stored scripts — the reference stores these in MetaData customs:
# IngestMetadata / IndexTemplateMetaData / ScriptMetaData). Index names may
# not start with "_", so the key cannot collide.
REGISTRIES_KEY = "_registries"


class LocalShard:
    def __init__(self, routing: ShardRoutingEntry, engine: Engine,
                 mapper_service: MapperService, index_settings=None):
        self.routing = routing
        self.mapper_service = mapper_service
        self.tracker = ReplicationTracker(routing.allocation_id)
        s = index_settings or {}
        try:
            from elasticsearch_tpu.indices.service import (
                validate_knn_settings)
            knn_engine, knn_nlist, knn_nprobe = validate_knn_settings(s)
        except Exception:
            # settings are validated at create-index; a bad value that
            # slipped into replicated state (older master) must degrade
            # to the exhaustive default, never crash the state applier
            knn_engine, knn_nlist, knn_nprobe = "tpu", None, "auto"
        from elasticsearch_tpu.common.settings import setting_bool
        try:
            from elasticsearch_tpu.indices.service import (
                validate_segments_settings)
            segments_settings = validate_segments_settings(s)
        except Exception:
            # same degradation contract as the knn settings above: a bad
            # replicated value must not crash the state applier
            segments_settings = {}
        try:
            from elasticsearch_tpu.indices.service import (
                validate_semantic_cache_settings)
            semantic_cache_settings = validate_semantic_cache_settings(s)
        except Exception:
            # same degradation contract: bad replicated value -> feature
            # stays off, never crash the state applier
            semantic_cache_settings = {}
        # lightweight-shard materialization (lazy device store): the
        # VectorStoreShard — batcher threads, device mirrors, IVF state —
        # is only built when the index actually has vector fields or a
        # recovery seed to apply. A text-only shard on a 3-node cluster
        # costs a bare engine, not 3x device-store setup.
        self._vector_store: Optional[VectorStoreShard] = None
        self._vector_store_kwargs = dict(
            dtype=s.get("index.knn.vector_dtype", "bf16"),
            knn_engine=knn_engine, knn_nlist=knn_nlist,
            knn_nprobe=knn_nprobe,
            topup=setting_bool(s.get("index.knn.topup", True)),
            target_batch_latency_ms=float(
                s.get("index.knn.target_batch_latency_ms", 2.0)),
            async_depth=int(s.get("index.knn.async_depth", 2)),
            **segments_settings, **semantic_cache_settings)
        self._attach_engine(engine)

    @property
    def vector_store(self) -> VectorStoreShard:
        if self._vector_store is None:
            self._vector_store = VectorStoreShard(
                **self._vector_store_kwargs)
        return self._vector_store

    def _attach_engine(self, engine: Engine) -> None:
        self.engine = engine
        engine.retained_seq_no_provider = self._min_retained_seq_no
        # restored/recovered engines carry a seed sidecar (columnar
        # blocks + IVF layout); apply it BEFORE the first vector sync so
        # block recovery never re-encodes or re-trains (recovery/seed.py)
        from elasticsearch_tpu.recovery import seed as recovery_seed
        if (self.mapper_service.vector_fields()
                or recovery_seed.has_sidecar(engine.path)):
            recovery_seed.maybe_apply(engine, self.vector_store)
        engine.add_refresh_listener(self._sync_vectors)
        self._sync_vectors(engine.acquire_searcher())

    def _min_retained_seq_no(self) -> int:
        try:
            return self.tracker.min_retained_seq_no()
        except Exception:
            return self.engine.local_checkpoint + 1

    def replace_engine(self, engine: Engine) -> None:
        """Swap in a recovered engine (post phase-1 file copy)."""
        self._attach_engine(engine)

    def _sync_vectors(self, reader):
        vf = self.mapper_service.vector_fields()
        if vf:
            self.vector_store.sync(reader, vf)

    def active_vector_store(self) -> Optional[VectorStoreShard]:
        """The device store when this shard serves vectors; None for a
        text-only shard, so the query path never materializes the lazy
        store just to ignore it."""
        if self._vector_store is not None:
            return self._vector_store
        return self.vector_store if self.mapper_service.vector_fields() \
            else None


class ClusterNode:
    def __init__(self, node_id: str, data_path: str, transport, scheduler,
                 seed_peers: List[str], initial_state: ClusterState,
                 rng=None, address: str = "",
                 attributes: Optional[Dict[str, str]] = None,
                 roles: Optional[Set[str]] = None):
        self.node_id = node_id
        self.data_path = data_path
        self.transport = transport
        self.scheduler = scheduler
        self.local_shards: Dict[Tuple[str, int], LocalShard] = {}
        # pinned per-shard scroll reader contexts (data side) and merged
        # scroll cursors (coordinator side)
        self._shard_scrolls: Dict[str, dict] = {}
        self._client_scrolls: Dict[str, dict] = {}
        # persistent-task execution (PersistentTasksExecutor registry):
        # task_id -> tick callable, supplied by the composition root
        self.persistent_task_executors: Dict[str, Callable[[], None]] = {}
        # generic routed-action layer (TransportNodesAction analog): named
        # local collectors the REST layer registers; NODES_DISPATCH fans a
        # named op out to every node and merges per-node sections
        self.node_collectors: Dict[str, Callable[[dict], Any]] = {}
        self.dispatch_executor: Optional[Callable[[Callable], Any]] = None
        self._running_ptasks: Set[str] = set()
        self.mappers: Dict[str, MapperService] = {}
        from elasticsearch_tpu.search.caches import NodeCaches
        self.caches = NodeCaches()
        # observers of every applied cluster state (registry sync, etc.)
        self.state_listeners: List[Callable[[ClusterState], None]] = []
        # cross-node serving counters (serving/fanout.py): coordinator-side
        # per-phase fan-out accounting + data-plane remote-shed tallies;
        # surfaced through `_nodes/stats fanout` and `profile.fanout`
        self.fanout_stats = fanout_lib.FanoutStats()
        # unified dispatch cost router (serving/router.py): queue wait +
        # transport RTT EWMA + device-leg estimate per candidate route.
        # The RTT feed exists only on the TCP transport; the sim
        # transport's cost collapses to the classic ARS ranking.
        from elasticsearch_tpu.serving import router as router_lib
        self._router = router_lib.DispatchRouter(
            node_id, rtt_provider=getattr(transport, "rtt_ms", None))
        # ARS back-compat alias: tests and the bench harness read/pop
        # this dict directly — it IS the router's service-time EWMA table
        self._ars_ewma = self._router.service_ewma
        # roles gate allocation: a coordinating-only node (no "data")
        # never receives shard copies — the multi-process bench joins its
        # in-parent coordinator this way so every search leg is remote
        node = DiscoveryNode(node_id, address=address, roles=roles,
                             attributes=attributes)
        # durable gateway: term + last-accepted state survive full-cluster
        # restarts (PersistedClusterStateService/GatewayMetaState analog);
        # initial_state seeds only a never-booted node
        persisted = FilePersistedState(data_path, initial_state=initial_state)
        self.coordinator = Coordinator(
            node, persisted, transport, scheduler,
            seed_peers=seed_peers, on_committed=self.apply_cluster_state, rng=rng)
        self.coordinator.membership_listener = self._on_membership_change
        self._register_handlers()
        # cluster-state-driven snapshot/restore lifecycle (SnapshotsService/
        # SnapshotShardsService/RestoreService analogs); data-plane hooks
        # are installed by the REST layer
        from elasticsearch_tpu.cluster.snapshots import (
            ClusterSnapshotLifecycle)
        self.snapshot_lifecycle = ClusterSnapshotLifecycle(self)
        self.shard_restore_hook: Optional[Callable] = None
        # durable elasticity (recovery/): node-local content-addressed
        # block cache — peer recoveries diff the source manifest against
        # it, so retries resume from the last acked block and a restored
        # shard's blocks never re-ship
        from elasticsearch_tpu.recovery.peer import BlockCache
        self.block_cache = BlockCache(os.path.join(data_path, "_blocks"))
        # per-recovery progress (allocation_id -> recovery/progress.py
        # dict, kept after completion for `_cat/recovery`) + lifetime
        # retry counters for `_nodes/stats indices.recovery`
        self.recoveries: Dict[str, dict] = {}
        self.recovery_stats = {"attempts": 0, "retries": 0,
                               "giveups": 0, "completed": 0}
        self._recovery_attempts: Dict[str, int] = {}
        self._recovery_pending: Set[str] = set()
        self._recovery_sources: Set[str] = set()

    # ------------------------------------------------------------------ admin
    def start(self):
        self.coordinator.start()
        self._schedule_scroll_reaper()

    def _schedule_scroll_reaper(self):
        """Periodic keepalive reaper for abandoned scroll contexts
        (reference: SearchService's KEEPALIVE_INTERVAL Reaper job)."""
        def tick():
            self._reap_shard_scrolls()
            now = time.time()
            for sid in [s for s, st in self._client_scrolls.items()
                        if st["expiry"] < now]:
                self._client_scrolls.pop(sid, None)
            self._schedule_scroll_reaper()
        try:
            self.scheduler.schedule_in(60_000, tick, "scroll_reaper")
        except Exception:
            pass  # deterministic test schedulers may be closed

    def stop(self):
        self.coordinator.stop()
        for shard in self.local_shards.values():
            shard.engine.close()

    @property
    def cluster_state(self) -> ClusterState:
        return self.coordinator.committed_state

    @property
    def is_master(self) -> bool:
        return self.coordinator.mode == LEADER

    # ------------------------------------------------- master-side state tasks
    def _on_membership_change(self, state: ClusterState, added: Set[str],
                              removed: Set[str]) -> ClusterState:
        for nid in sorted(removed):  # deterministic under any hash seed
            state = allocation.node_left(state, nid)
        if added:
            state = allocation.reroute(state)
            # a fresh node is empty: move shards onto it until node weights
            # converge (BalancedShardsAllocator.balance on reroute)
            state = allocation.rebalance(state)
        # persistent tasks on departed nodes reassign immediately
        # (PersistentTasksClusterService.shouldReassignPersistentTasks)
        state = self._reassign_persistent_tasks(state)
        return state

    @staticmethod
    def _reassign_persistent_tasks(state: ClusterState) -> ClusterState:
        tasks = state.metadata.get(PERSISTENT_TASKS_KEY)
        if not tasks:
            return state
        live = sorted(state.nodes)
        if not live:
            return state
        loads = {n: 0 for n in live}
        for t in tasks.values():
            if t.get("assigned_node") in loads:
                loads[t["assigned_node"]] += 1
        changed = False
        new_tasks = {}
        for tid in sorted(tasks):
            t = dict(tasks[tid])
            if t.get("assigned_node") not in loads:
                target = min(live, key=lambda n: (loads[n], n))
                t["assigned_node"] = target
                loads[target] += 1
                changed = True
            new_tasks[tid] = t
        if not changed:
            return state
        return state.with_(metadata={**state.metadata,
                                     PERSISTENT_TASKS_KEY: new_tasks})

    def _master_put_persistent_task(self, sender, request, respond):
        self._require_master()
        tid = request["task_id"]

        def update(base: ClusterState) -> ClusterState:
            tasks = {k: dict(v) for k, v in
                     (base.metadata.get(PERSISTENT_TASKS_KEY) or {}).items()}
            if request.get("remove"):
                if tid not in tasks:
                    return base
                tasks.pop(tid)
            else:
                if tid in tasks:
                    return base  # idempotent registration
                tasks[tid] = {"params": request.get("params") or {},
                              "interval_ms": int(request.get(
                                  "interval_ms", 1000)),
                              "assigned_node": None}
            state = base.with_(metadata={**base.metadata,
                                         PERSISTENT_TASKS_KEY: tasks})
            return self._reassign_persistent_tasks(state)

        self._publish_then_respond(update, respond, {"acknowledged": True},
                                   source=f"persistent-task [{tid}]")

    def client_register_persistent_task(self, task_id: str,
                                        params: Optional[dict] = None,
                                        interval_ms: int = 1000,
                                        on_done: Optional[Callable] = None,
                                        on_failure: Optional[Callable] = None
                                        ) -> None:
        self._send_to_master(MASTER_PUT_PERSISTENT_TASK,
                             {"task_id": task_id, "params": params,
                              "interval_ms": interval_ms},
                             on_response=on_done or (lambda r: None),
                             on_failure=on_failure)

    def client_remove_persistent_task(self, task_id: str,
                                      on_done: Optional[Callable] = None,
                                      on_failure: Optional[Callable] = None
                                      ) -> None:
        self._send_to_master(MASTER_PUT_PERSISTENT_TASK,
                             {"task_id": task_id, "remove": True},
                             on_response=on_done or (lambda r: None),
                             on_failure=on_failure)

    # node-side execution: a ticker per task assigned to THIS node,
    # started/stopped as committed states change ownership
    def _sync_persistent_tasks(self, state: ClusterState) -> None:
        tasks = state.metadata.get(PERSISTENT_TASKS_KEY) or {}
        mine = {tid for tid, t in tasks.items()
                if t.get("assigned_node") == self.node_id
                and tid in self.persistent_task_executors}
        for tid in mine - self._running_ptasks:
            self._running_ptasks.add(tid)
            interval = int(tasks[tid].get("interval_ms", 1000))
            self._schedule_ptask_tick(tid, interval)
        # tasks no longer mine stop at their next tick check (the loop
        # discards itself from _running_ptasks there — removing here
        # could double-schedule on a fast unassign/reassign cycle)

    def _schedule_ptask_tick(self, tid: str, interval: int) -> None:
        def tick():
            tasks = self.cluster_state.metadata.get(
                PERSISTENT_TASKS_KEY) or {}
            t = tasks.get(tid)
            if t is None or t.get("assigned_node") != self.node_id \
                    or self.coordinator.stopped \
                    or self.node_id not in self.cluster_state.nodes:
                self._running_ptasks.discard(tid)
                return
            # partition guard: a node cut off from the master may hold a
            # stale assignment while a new owner starts; once fault
            # detection demotes this node to CANDIDATE it pauses execution
            # (keeps the loop) until it rejoins — bounding dual execution
            # to the detection window, like the reference's reassignment
            has_cluster = self.coordinator.mode in (LEADER, FOLLOWER)
            fn = self.persistent_task_executors.get(tid)
            if fn is not None and has_cluster:
                try:
                    fn()
                except Exception:
                    pass  # a failing feature tick must not kill the loop
            # interval is re-read so a remove + re-register with a new
            # cadence takes effect at the next tick
            self._schedule_ptask_tick(
                tid, int(t.get("interval_ms", interval)))
        self.scheduler.schedule_in(interval, tick,
                                   f"persistent_task:{tid}:{self.node_id}")

    def _require_master(self):
        if self.coordinator.mode != LEADER:
            # raising fails the transport call → sender's retry loop finds
            # the new master (reference: NotMasterException)
            raise SearchEngineError(f"[{self.node_id}] is not the elected master")

    def _master_create_index(self, sender, request, respond):
        self._require_master()
        name = request["index"]
        # same name rules as the single-node path — in particular no "_"
        # prefix, which is what keeps reserved metadata sections
        # (REGISTRIES_KEY) unreachable as indices
        from elasticsearch_tpu.indices.service import (
            IndicesService, validate_knn_settings,
            validate_semantic_cache_settings)
        IndicesService.validate_index_name(name)
        validate_knn_settings(dict(request.get("settings") or {}))
        validate_semantic_cache_settings(dict(request.get("settings") or {}))

        def update(base: ClusterState) -> ClusterState:
            if name in base.metadata:
                return base
            settings = dict(request.get("settings") or {})
            settings.setdefault("index.number_of_shards", 1)
            settings.setdefault("index.number_of_replicas", 1)
            meta = dict(base.metadata)
            meta[name] = {"settings": settings,
                          "mappings": request.get("mappings") or {"properties": {}}}
            state = base.with_(metadata=meta)
            return allocation.allocate_new_index(
                state, name, int(settings["index.number_of_shards"]),
                int(settings["index.number_of_replicas"]))

        self._publish_then_respond(update, respond, {"acknowledged": True},
                                   source=f"create-index [{name}]")

    def _publish_then_respond(self, update, respond, result: dict,
                              source: str = "cluster-state-update") -> None:
        """Ack only after COMMIT (MasterService publish listener): a stale
        leader's rejected publish must surface as a retryable non-ack, not
        a false acknowledged=true. Updates route through the batching task
        queue, so concurrent submissions coalesce into one publication."""
        def on_committed(ok: bool):
            respond(result if ok else {"__not_committed__": True})

        self.coordinator.submit_state_update(source, update, on_committed)

    def _master_delete_index(self, sender, request, respond):
        self._require_master()
        name = request["index"]
        self._publish_then_respond(
            lambda base: allocation.remove_index(base, name)
            if name in base.metadata else base,
            respond, {"acknowledged": True})

    def _master_update_settings(self, sender, request, respond):
        """`PUT /_cluster/settings` persistent settings: merged into the
        state, then reroute+rebalance so allocation filters / watermarks /
        enable flags take effect immediately (TransportClusterUpdateSettings
        Action reroutes after applying)."""
        self._require_master()
        updates = dict(request.get("persistent") or {})

        def update(base: ClusterState) -> ClusterState:
            merged = dict(base.settings)
            for k, v in updates.items():
                if v is None:
                    merged.pop(k, None)
                else:
                    merged[k] = v
            state = base.with_(settings=merged)
            state = allocation.reroute(state)
            return allocation.rebalance(state)

        self._publish_then_respond(update, respond,
                                   {"acknowledged": True,
                                    "persistent": updates})

    def client_update_settings(self, persistent: dict,
                               on_done: Optional[Callable] = None,
                               on_failure: Optional[Callable] = None) -> None:
        self._send_to_master(MASTER_UPDATE_SETTINGS,
                             {"persistent": persistent},
                             on_response=on_done or (lambda r: None),
                             on_failure=on_failure)

    def _master_put_registry(self, sender, request, respond):
        """Replicated registries (pipelines/templates/scripts): every
        mutation is a cluster-state update, so every node sees the same
        registry (IngestMetadata/ScriptMetaData analogs)."""
        self._require_master()
        section, key = request["section"], request["key"]
        value = request.get("value")

        def update(base: ClusterState) -> ClusterState:
            meta = dict(base.metadata)
            regs = {k: dict(v) for k, v in
                    (meta.get(REGISTRIES_KEY) or {}).items()}
            sec = regs.setdefault(section, {})
            if value is None:
                sec.pop(key, None)
            else:
                sec[key] = value
            meta[REGISTRIES_KEY] = regs
            return base.with_(metadata=meta)

        self._publish_then_respond(update, respond, {"acknowledged": True})

    def client_put_registry(self, section: str, key: str, value,
                            on_done: Optional[Callable] = None,
                            on_failure: Optional[Callable] = None) -> None:
        self._send_to_master(MASTER_PUT_REGISTRY,
                             {"section": section, "key": key, "value": value},
                             on_response=on_done or (lambda r: None),
                             on_failure=on_failure)

    def _master_shard_started(self, sender, request, respond):
        self._require_master()
        aid = request["allocation_id"]
        self._publish_then_respond(
            lambda base: allocation.shard_started(base, aid),
            respond, {"ack": True})

    def _master_shard_failed(self, sender, request, respond):
        self._require_master()
        aid = request["allocation_id"]
        self._publish_then_respond(
            lambda base: allocation.shard_failed(base, aid),
            respond, {"ack": True})

    def _send_to_master(self, action: str, request: dict,
                        on_response=None, on_failure=None, retries: int = 60):
        """Master-node action with retry-until-master-known semantics
        (reference: TransportMasterNodeAction observes cluster state and
        retries on NotMasterException / no-master). APPLICATION errors
        (validation etc.) propagate immediately — only master-unavailable
        conditions retry."""
        master = self.cluster_state.master_node_id
        if self.is_master:
            master = self.node_id

        def retry(err=None):
            # a 4xx from the master is the answer, not a reason to re-ask
            status = int(getattr(err, "status", 500)) if err is not None else 500
            if err is not None and 400 <= status < 500 \
                    and "not the elected master" not in str(err):
                if on_failure:
                    on_failure(err)
                return
            if retries <= 0:
                if on_failure:
                    on_failure(SearchEngineError("no elected master"))
                return
            self.scheduler.schedule_in(
                500, lambda: self._send_to_master(action, request, on_response,
                                                  on_failure, retries - 1),
                f"master_retry:{action}")

        if master is None:
            retry()
            return

        def on_resp(resp):
            # the master acked receipt but its publication failed to commit
            # (stepped down mid-publish): retry against the next master
            if isinstance(resp, dict) and resp.get("__not_committed__"):
                retry()
            elif on_response is not None:
                on_response(resp)

        self.transport.send(self.node_id, master, action, request,
                            on_response=on_resp, on_failure=retry)

    # --------------------------------------------------- cluster state applier
    def apply_cluster_state(self, state: ClusterState) -> None:
        """IndicesClusterStateService.applyClusterState analog."""
        # learn peer transport addresses from the published node set, so
        # every node can dial every other (NodeConnectionsService analog);
        # the deterministic test transport routes by id and has no addresses
        add_addr = getattr(self.transport, "add_peer_address", None)
        if add_addr is not None:
            for n in state.nodes.values():
                if n.address and n.node_id != self.node_id:
                    host, _, port = n.address.rpartition(":")
                    if host and port.isdigit():
                        add_addr(n.node_id, host, int(port))

        my_entries = {(r.index, r.shard): r for r in state.routing
                      if r.node_id == self.node_id}

        # remove shards no longer assigned here — including copies reassigned
        # to this node under a NEW allocation_id: the stale engine must go so
        # the create loop below builds the new copy and runs its recovery
        for key in list(self.local_shards):
            mine = my_entries.get(key)
            if mine is None or mine.allocation_id != self.local_shards[key].routing.allocation_id:
                shard = self.local_shards.pop(key)
                shard.engine.close()

        # create / update assigned shards
        for key, entry in my_entries.items():
            index, shard_id = key
            meta = state.metadata.get(index)
            if meta is None:
                continue
            local = self.local_shards.get(key)
            if local is None:
                if index not in self.mappers:
                    from elasticsearch_tpu.index.analysis import (
                        AnalysisRegistry)
                    self.mappers[index] = MapperService(
                        meta.get("mappings") or {"properties": {}},
                        registry=AnalysisRegistry.from_index_settings(
                            meta.get("settings") or {}))
                mapper = self.mappers[index]
                path = os.path.join(self.data_path, index, str(shard_id),
                                    entry.allocation_id.replace("/", "_").replace("#", "_"))
                if entry.primary:
                    # snapshot restore: materialize the shard's files from
                    # the repository BEFORE the engine opens, so the new
                    # primary boots from the snapshotted commit
                    # (RestoreService: restore is a recovery source)
                    from elasticsearch_tpu.cluster.snapshots import (
                        RESTORE_IN_PROGRESS)
                    restore = (state.metadata.get(RESTORE_IN_PROGRESS)
                               or {}).get(index)
                    if restore is not None and self.shard_restore_hook:
                        try:
                            self.shard_restore_hook(restore, index, shard_id,
                                                    path)
                        except Exception as e:
                            self._send_to_master(
                                MASTER_SHARD_FAILED,
                                {"allocation_id": entry.allocation_id,
                                 "reason": f"restore failed: {e}"})
                            continue
                engine = Engine(path, mapper, translog_sync="async")
                local = LocalShard(entry, engine, mapper,
                                   index_settings=meta.get("settings"))
                self.local_shards[key] = local
                if entry.primary:
                    local.tracker.activate_primary_mode(engine.local_checkpoint)
                    self._send_to_master(MASTER_SHARD_STARTED,
                                         {"allocation_id": entry.allocation_id})
                else:
                    self._start_replica_recovery(local, state)
            else:
                was_primary = local.routing.primary
                local.routing = entry
                if entry.primary and not was_primary:
                    # failover promotion (reference: IndexShard#activateWithPrimaryContext)
                    local.tracker = ReplicationTracker(entry.allocation_id)
                    local.tracker.activate_primary_mode(local.engine.local_checkpoint)

        self._sync_persistent_tasks(state)
        for listener in self.state_listeners:
            try:
                listener(state)
            except Exception:
                pass  # a listener bug must not break shard application

    def _start_replica_recovery(self, local: LocalShard, state: ClusterState) -> None:
        entry = local.routing
        prog = self._track_recovery(local)
        self.recovery_stats["attempts"] += 1
        self._recovery_attempts[entry.allocation_id] = \
            self._recovery_attempts.get(entry.allocation_id, 0) + 1
        prog["attempts"] = self._recovery_attempts[entry.allocation_id]
        primary = state.primary_of(entry.index, entry.shard)
        if primary is None or primary.node_id is None:
            # counting this as an attempt keeps the backoff escalating
            # (and eventually gives up -> master reroutes) instead of
            # polling a missing primary at the base interval forever
            self._schedule_recovery_retry(entry, "no active primary")
            return
        prog["source_node"] = primary.node_id

        def on_ops(response):
            if "phase1" in response:
                # translog can't cover the gap: ship the missing blocks
                # first (RecoverySourceHandler.java:262 phase1, at block
                # rather than file granularity), then re-enter ops
                # recovery from the block checkpoint
                self._run_phase1(local, primary.node_id, response["phase1"])
                return
            from elasticsearch_tpu.recovery import progress as rp
            prog["stage"] = rp.STAGE_TRANSLOG
            for op in response["ops"]:
                self._apply_replica_op(local, op)
            prog["ops_replayed"] += len(response["ops"])
            self._finalize_recovery(local, prog)

        def on_fail(_err):
            # primary not ready yet (e.g. promotion not applied there) or the
            # request raced a topology change: retry while still INITIALIZING
            self._schedule_recovery_retry(entry, str(_err))

        self.transport.send(
            self.node_id, primary.node_id, RECOVERY_START,
            {"index": entry.index, "shard": entry.shard,
             "allocation_id": entry.allocation_id,
             "from_seq_no": local.engine.local_checkpoint + 1},
            on_response=on_ops, on_failure=on_fail)
        # dropped-message safety net: if neither response nor failure arrives
        # (partition during recovery), retry while still INITIALIZING
        self.scheduler.schedule_in(
            5000, lambda: self._recovery_watchdog(entry),
            f"recovery_timeout:{entry.allocation_id}")

    def _track_recovery(self, local: LocalShard) -> dict:
        """The progress record for one recovery target (created once per
        allocation; retries mutate the same record)."""
        from elasticsearch_tpu.recovery import progress as rp
        entry = local.routing
        prog = self.recoveries.get(entry.allocation_id)
        if prog is None:
            rtype = "RELOCATION" if entry.relocation_source else "PEER"
            prog = rp.new_progress(entry.index, entry.shard,
                                   entry.allocation_id, rtype,
                                   target_node=self.node_id,
                                   now_ms=int(time.time() * 1000))
            self.recoveries[entry.allocation_id] = prog
        return prog

    def _finalize_recovery(self, local: LocalShard, prog: dict) -> None:
        """Refresh + (for relocations) warm the device path, then report
        started — reference: IndexShard#finalizeRecovery refreshes before
        POST_RECOVERY, so a post-failover copy never serves 0 docs while
        waiting for the next user refresh."""
        from elasticsearch_tpu.recovery import progress as rp
        entry = local.routing
        prog["stage"] = rp.STAGE_FINALIZE
        local.engine.refresh()
        if entry.relocation_source is not None:
            # live relocation: compile the dispatch grid and touch the
            # device arrays through the real serving entry BEFORE routing
            # flips to this copy — the first user search lands warm
            from elasticsearch_tpu.recovery import relocation
            prog["warm"] = relocation.warm_handoff(local)
        prog["stage"] = rp.STAGE_DONE
        prog["stop_ms"] = int(time.time() * 1000)
        self.recovery_stats["completed"] += 1
        self._recovery_attempts.pop(entry.allocation_id, None)
        self._send_to_master(MASTER_SHARD_STARTED,
                             {"allocation_id": entry.allocation_id})

    # recovery retry policy: jittered exponential backoff, capped, with a
    # bounded attempt count — a permanently failing copy is reported to
    # the master (giveup -> reroute) instead of retrying at a fixed
    # interval forever
    _RECOVERY_RETRY_BASE_MS = 500
    _RECOVERY_RETRY_CAP_MS = 30_000
    _RECOVERY_MAX_ATTEMPTS = 10

    def _schedule_recovery_retry(self, entry: ShardRoutingEntry,
                                 reason: str = "") -> None:
        alloc = entry.allocation_id
        local = self.local_shards.get((entry.index, entry.shard))
        if local is None or local.routing.allocation_id != alloc \
                or local.routing.state != ShardRoutingEntry.INITIALIZING:
            return
        n = self._recovery_attempts.get(alloc, 0)
        if n >= self._RECOVERY_MAX_ATTEMPTS:
            self.recovery_stats["giveups"] += 1
            prog = self.recoveries.get(alloc)
            if prog is not None:
                prog["stop_ms"] = int(time.time() * 1000)
            self._recovery_attempts.pop(alloc, None)
            self._send_to_master(
                MASTER_SHARD_FAILED,
                {"allocation_id": alloc,
                 "reason": f"recovery gave up after {n} attempts: {reason}"})
            return
        if alloc in self._recovery_pending:
            return  # a retry is already scheduled; don't stack them
        delay = min(self._RECOVERY_RETRY_CAP_MS,
                    self._RECOVERY_RETRY_BASE_MS << n)
        # deterministic jitter (±25%): decorrelates a herd of replicas
        # retrying against one reborn primary without wall clock or the
        # process hash seed (which would break the simulator's replay)
        span = delay // 2
        delay = delay - span // 2 + \
            zlib.crc32(f"{alloc}:{n}".encode()) % (span + 1)
        self.recovery_stats["retries"] += 1
        prog = self.recoveries.get(alloc)
        if prog is not None:
            prog["throttle_ms"] += delay
        self._recovery_pending.add(alloc)
        self.scheduler.schedule_in(delay,
                                   lambda: self._retry_recovery(entry),
                                   f"recovery_retry:{alloc}")

    def _recovery_watchdog(self, entry: ShardRoutingEntry) -> None:
        """Dropped-message backstop. Unlike a real retry it must not act
        when the recovery finished or a backoff retry is already queued —
        otherwise it would double-fire attempts and defeat the backoff."""
        from elasticsearch_tpu.recovery import progress as rp
        prog = self.recoveries.get(entry.allocation_id)
        if prog is not None and prog["stage"] == rp.STAGE_DONE:
            return
        if entry.allocation_id in self._recovery_pending:
            return
        self._retry_recovery(entry)

    def recovery_summary(self) -> dict:
        """`_nodes/stats indices.recovery` section for this node."""
        from elasticsearch_tpu.recovery import progress as rp
        from elasticsearch_tpu.recovery.snapshot import NODE_STREAM_LIMITER
        out = rp.summarize(self.recoveries.values(), self.recovery_stats,
                           current_as_source=len(self._recovery_sources))
        streams = dict(NODE_STREAM_LIMITER.stats)
        streams["max_streams"] = NODE_STREAM_LIMITER.max_streams
        streams["max_bytes_per_sec"] = NODE_STREAM_LIMITER.max_bytes_per_sec
        # bounded-concurrency snapshot block upload + per-node byte-rate
        # throttle (recovery/snapshot.py limiter)
        out["snapshot_streams"] = streams
        out["throttle_time_in_millis"] = int(
            streams["throttle_time_in_millis"])
        return out

    def _run_phase1(self, local: LocalShard, primary_node: str,
                    phase1: dict) -> None:
        """Target side of block recovery (PeerRecoveryTargetService
        analog): diff the source's block manifest against the node block
        cache, pull ONLY the missing blocks in CRC-framed chunks (each
        landing in the cache as soon as it verifies — a retry after a
        dead source resumes from the last acked block for free), then
        assemble the shard and resume ops recovery from the block
        checkpoint."""
        import base64
        import shutil
        import zlib as _zlib

        from elasticsearch_tpu.recovery import progress as rp
        from elasticsearch_tpu.recovery.manifest import (
            diff_entries, manifest_totals)
        from elasticsearch_tpu.recovery.snapshot import assemble_shard

        entry = local.routing
        prog = self._track_recovery(local)
        entries = list(phase1.get("blocks", []))
        meta = phase1.get("meta")
        if not entries or meta is None:
            return self._schedule_recovery_retry(entry, "empty phase1 manifest")
        missing, _present = diff_entries(entries, self.block_cache.held())
        need, seen = [], set()
        for e in missing:
            if e["digest"] not in seen:
                seen.add(e["digest"])
                need.append(e)
        totals = manifest_totals(entries)
        prog["stage"] = rp.STAGE_BLOCKS
        prog["blocks_total"] = totals["blocks_total"]
        prog["bytes_total"] = totals["bytes_total"]
        prog["blocks_reused"] = totals["blocks_total"] - len(need)
        state = {"idx": 0, "offset": 0, "buf": []}

        def fail(reason):
            self._schedule_recovery_retry(entry, reason)

        def next_block():
            if local.routing.allocation_id != entry.allocation_id:
                return
            if state["idx"] >= len(need):
                return finish()
            e = need[state["idx"]]
            if self.block_cache.has(e["digest"]):
                # landed via a concurrent restore or an earlier attempt
                state["idx"] += 1
                state["offset"] = 0
                state["buf"] = []
                return next_block()
            # budgeted single-RPC (PR-12 ScatterGather): a source that
            # dies mid-transfer resolves as a failure, never a hang
            self._send_guarded(
                primary_node, RECOVERY_FILE_CHUNK,
                {"index": entry.index, "shard": entry.shard,
                 "allocation_id": entry.allocation_id,
                 "digest": e["digest"], "offset": state["offset"]},
                on_chunk, lambda err: fail(str(err)),
                budget_ms=self._REPLICATION_BUDGET_MS, phase="recovery")

        def on_chunk(resp):
            e = need[state["idx"]]
            data = base64.b64decode(resp["data"])
            if (_zlib.crc32(data) & 0xFFFFFFFF) != resp["crc32"]:
                return fail("chunk crc mismatch")
            state["buf"].append(data)
            state["offset"] += len(data)
            if resp.get("last") or state["offset"] >= e["size"]:
                blob = b"".join(state["buf"])
                try:
                    # content-addressed write verifies the digest; a
                    # torn/corrupt transfer is rejected and retried
                    self.block_cache.put(e["digest"], blob)
                except ValueError:
                    return fail(
                        f"block {e['digest'][:8]} failed digest verification")
                prog["blocks_shipped"] += 1
                prog["bytes_shipped"] += len(blob)
                state["idx"] += 1
                state["offset"] = 0
                state["buf"] = []
            next_block()

        def finish():
            # stage every block in memory first (digest-verified reads) so
            # the engine swap below can't strand the shard half-assembled
            blocks = {}
            for e in entries:
                data = self.block_cache.get(e["digest"])
                if data is None:
                    return fail(f"cache lost block {e['digest'][:8]}")
                blocks[e["digest"]] = data
            path = local.engine.path
            local.engine.close()
            for name in os.listdir(path):
                full = os.path.join(path, name)
                if os.path.isdir(full):
                    shutil.rmtree(full, ignore_errors=True)
                else:
                    os.unlink(full)
            assemble_shard(path, entries, meta, blocks.__getitem__)
            engine = Engine(path, local.mapper_service,
                            translog_sync="async")
            local.replace_engine(engine)
            prog["stage"] = rp.STAGE_TRANSLOG
            self._start_replica_recovery(local, self.cluster_state)

        next_block()

    def _retry_recovery(self, entry: ShardRoutingEntry) -> None:
        self._recovery_pending.discard(entry.allocation_id)
        local = self.local_shards.get((entry.index, entry.shard))
        if local is not None and local.routing.allocation_id == entry.allocation_id \
                and local.routing.state == ShardRoutingEntry.INITIALIZING:
            self._start_replica_recovery(local, self.cluster_state)

    def _on_recovery_start(self, sender, request, respond):
        """Primary side (RecoverySourceHandler.recoverToTarget analog):
        ops-only replay when the translog still covers the gap, else a
        phase-1 manifest — commit files snapshotted under a per-recovery
        dir so concurrent flushes can't mutate what the target is copying,
        with a retention lease pinning post-commit history until phase 2."""
        key = (request["index"], request["shard"])
        local = self.local_shards.get(key)
        if local is None or not local.routing.primary:
            raise SearchEngineError(f"not primary for {key}")
        alloc = request["allocation_id"]
        from_seq = int(request.get("from_seq_no", 0))

        if not local.engine.can_replay_from(from_seq):
            respond({"phase1": self._prepare_phase1(local, alloc)})
            return

        ops = local.engine.translog.read_ops(from_seq)
        local.tracker.init_tracking(alloc)
        local.tracker.mark_in_sync(alloc, local.engine.local_checkpoint)
        self._cleanup_phase1(local, alloc)
        respond({"ops": ops, "global_checkpoint": local.tracker.global_checkpoint})

    _RECOVERY_CHUNK = 1 << 20

    def _phase1_dir(self, local: LocalShard, alloc: str) -> str:
        safe = alloc.replace("/", "_").replace("#", "_")
        return os.path.join(local.engine.path, f"_recovery_{safe}")

    def _prepare_phase1(self, local: LocalShard, alloc: str) -> dict:
        """Flush, collect the shard into content-addressed blocks staged
        under a per-recovery dir (so concurrent flushes can't mutate what
        the target is copying), lease the history above the commit
        (RecoverySourceHandler.java:262 phase1 + CcrRetentionLeases-style
        lease so a concurrent flush cannot trim phase-2 ops)."""
        import shutil

        from elasticsearch_tpu.recovery.snapshot import collect_shard_blocks

        engine = local.engine
        engine.flush()
        lease_id = f"peer_recovery/{alloc}"
        retaining = (engine.last_commit_checkpoint or -1) + 1
        try:
            local.tracker.add_retention_lease(lease_id, retaining,
                                              "peer_recovery")
        except IllegalArgumentError:
            local.tracker.renew_retention_lease(lease_id, retaining)
        entries, payloads, meta = collect_shard_blocks(
            engine, getattr(local, "vector_store", None))
        snap_dir = self._phase1_dir(local, alloc)
        shutil.rmtree(snap_dir, ignore_errors=True)
        os.makedirs(snap_dir, exist_ok=True)
        for digest, data in payloads.items():
            with open(os.path.join(snap_dir, digest), "wb") as f:
                f.write(data)
        self._recovery_sources.add(alloc)
        return {"blocks": entries, "meta": meta,
                "from_seq_no": (engine.last_commit_checkpoint or -1) + 1}

    def _cleanup_phase1(self, local: LocalShard, alloc: str) -> None:
        import shutil
        shutil.rmtree(self._phase1_dir(local, alloc), ignore_errors=True)
        self._recovery_sources.discard(alloc)
        try:
            local.tracker.remove_retention_lease(f"peer_recovery/{alloc}")
        except Exception:
            pass

    def _on_recovery_file_chunk(self, sender, request, respond):
        """Primary side: serve one CRC-framed chunk of a staged block,
        addressed by content digest (MultiFileTransfer /
        RecoverySourceHandler.sendFiles analog)."""
        key = (request["index"], request["shard"])
        local = self.local_shards.get(key)
        if local is None or not local.routing.primary:
            raise SearchEngineError(f"not primary for {key}")
        from elasticsearch_tpu.recovery.peer import safe_digest
        snap_dir = self._phase1_dir(local, request["allocation_id"])
        path = os.path.join(snap_dir, safe_digest(request["digest"]))
        offset = int(request["offset"])
        with open(path, "rb") as f:
            f.seek(offset)
            data = f.read(self._RECOVERY_CHUNK)
        import base64
        import zlib as _zlib
        respond({"digest": request["digest"], "offset": offset,
                 "data": base64.b64encode(data).decode("ascii"),
                 "crc32": _zlib.crc32(data) & 0xFFFFFFFF,
                 "last": offset + len(data) >= os.path.getsize(path)})

    # ------------------------------------------------------------- write path
    def client_write(self, index: str, op: dict,
                     on_done: Callable[[dict], None],
                     on_failure: Optional[Callable[[Exception], None]] = None) -> None:
        """op: {type: index|delete, id, source?}; routes to the primary."""
        state = self.cluster_state
        meta = state.metadata.get(index)
        if meta is None:
            (on_failure or on_done)(IndexNotFoundError(index)
                                    if on_failure else {"error": "index_not_found"})
            return
        num_shards = int(meta["settings"].get("index.number_of_shards", 1))
        sid = shard_id_for(op.get("routing") or op["id"], num_shards)
        primary = state.primary_of(index, sid)
        if primary is None or primary.node_id is None:
            if on_failure:
                on_failure(SearchEngineError(f"no active primary for [{index}][{sid}]"))
            return
        request = {"index": index, "shard": sid, "op": op}
        if primary.node_id == self.node_id:
            self._on_write_primary(self.node_id, request, on_done)
        else:
            self._send_guarded(primary.node_id, WRITE_PRIMARY, request,
                               on_done, on_failure, phase="write_forward")

    def _on_write_primary(self, sender, request, respond):
        key = (request["index"], request["shard"])
        local = self.local_shards.get(key)
        if local is None or not local.routing.primary:
            raise SearchEngineError(f"[{key}] not primary on [{self.node_id}]")
        op = request["op"]
        if op["type"] == "index":
            result = local.engine.index(
                op["id"], op["source"],
                op_type=op.get("op_type", "index"),
                routing=op.get("routing"),
                if_seq_no=op.get("if_seq_no"),
                if_primary_term=op.get("if_primary_term"),
                version=op.get("version"),
                version_type=op.get("version_type", "internal"))
        else:
            result = local.engine.delete(
                op["id"], if_seq_no=op.get("if_seq_no"),
                if_primary_term=op.get("if_primary_term"))
        local.tracker.update_local_checkpoint(local.routing.allocation_id,
                                              local.engine.local_checkpoint)

        state = self.cluster_state
        # fan out to every ASSIGNED copy, INITIALIZING included — a copy mid-
        # recovery must see concurrent ops or they are silently lost when it
        # is later promoted (reference: ReplicationOperation replicates to
        # the tracked set, not just started copies; replica engines dedup by
        # seq_no so recovery-replay overlap is safe)
        replicas = [r for r in state.replicas_of(*key)
                    if r.state in (ShardRoutingEntry.STARTED,
                                   ShardRoutingEntry.INITIALIZING) and r.node_id]
        response = {"_index": request["index"], "_shard": request["shard"],
                    "_id": op["id"], "_seq_no": result.seq_no,
                    "_primary_term": result.primary_term,
                    "_version": result.version, "result": result.result}
        if not replicas:
            respond(response)
            return

        def one_done(outcome, resp, _err, rep):
            if outcome == fanout_lib.OK and isinstance(resp, dict) \
                    and "local_checkpoint" in resp:
                # replica acks carry their local checkpoint: feed the
                # primary's tracker so the global checkpoint advances
                # (ReplicationTracker.java:996 updateLocalCheckpoint) —
                # flush-time translog trimming keys off it via
                # min_retained_seq_no
                try:
                    local.tracker.update_local_checkpoint(
                        rep.allocation_id, int(resp["local_checkpoint"]))
                except Exception:
                    pass
                return
            if outcome != fanout_lib.OK:
                # replica failed to apply, or never answered inside the
                # replication budget (silent partition): ask the master to
                # fail that copy, then ack (reference: ReplicationOperation
                # #onPrimaryOperationFailure; the timed-out case is the
                # unbounded-wait fix — a dropped replica ack must not hang
                # the client write forever)
                self._send_to_master(MASTER_SHARD_FAILED,
                                     {"allocation_id": rep.allocation_id})

        sg = ScatterGather(self.scheduler, phase="replication",
                           budget_ms=self._REPLICATION_BUDGET_MS,
                           stats=self.fanout_stats,
                           on_done=lambda _s: respond(response))
        replica_req = {"index": request["index"], "shard": request["shard"],
                       "op": op, "seq_no": result.seq_no,
                       "primary_term": result.primary_term,
                       "version": result.version,
                       "global_checkpoint": local.tracker.global_checkpoint}
        for rep in replicas:
            def send(on_resp, on_fail, rep=rep):
                self.transport.send(self.node_id, rep.node_id, WRITE_REPLICA,
                                    replica_req, on_response=on_resp,
                                    on_failure=on_fail)
            sg.launch(rep.allocation_id, rep.node_id, send,
                      on_item=lambda o, r, e, rep=rep: one_done(o, r, e, rep))
        sg.seal()

    # replication fan-out budget: the backstop for a replica that neither
    # acks nor fails (silent partition) — the copy is reported failed and
    # the write acks, instead of hanging the client forever
    _REPLICATION_BUDGET_MS = 30_000

    def _send_guarded(self, target: str, action: str, request: dict,
                      on_response, on_failure,
                      budget_ms: Optional[int] = None,
                      phase: str = "forward") -> None:
        """Single-RPC forward with the same no-hang guarantee as the
        fan-outs: a silently dropped response resolves as a failure after
        `budget_ms` (a one-item ScatterGather — the write-to-primary and
        scroll-owner forwards hung forever on a dead target otherwise)."""
        if budget_ms is None:
            budget_ms = self._BROADCAST_BUDGET_MS

        def item(outcome, payload, err):
            if outcome == fanout_lib.OK:
                on_response(payload)
            elif on_failure is not None:
                if err is None:
                    err = SearchEngineError(
                        f"[{action}] to [{target}] got no response in "
                        f"{budget_ms}ms")
                on_failure(err)

        sg = ScatterGather(self.scheduler, phase=phase,
                           budget_ms=budget_ms, stats=self.fanout_stats,
                           on_done=None)
        sg.launch(action, target,
                  lambda ok, fail: self.transport.send(
                      self.node_id, target, action, request,
                      on_response=ok, on_failure=fail),
                  on_item=item)
        sg.seal()
    # scroll create/fetch and broadcast admin fan-outs share one generous
    # backstop budget: these are correctness timers (never hang on a dead
    # node), not latency budgets
    _SCROLL_BUDGET_MS = 30_000
    _BROADCAST_BUDGET_MS = 30_000

    def _on_write_replica(self, sender, request, respond):
        key = (request["index"], request["shard"])
        local = self.local_shards.get(key)
        if local is None:
            raise SearchEngineError(f"no shard {key} on [{self.node_id}]")
        self._apply_replica_op(local, {**request["op"],
                                       "seq_no": request["seq_no"],
                                       "primary_term": request["primary_term"],
                                       "version": request["version"]})
        local.tracker.update_global_checkpoint_on_replica(
            request.get("global_checkpoint", -1))
        respond({"ack": True, "local_checkpoint": local.engine.local_checkpoint})

    def _apply_replica_op(self, local: LocalShard, op: dict) -> None:
        if op.get("type", op.get("op")) in ("index", None):
            local.engine.index(op["id"], op.get("source") or {},
                               seq_no=op["seq_no"],
                               primary_term=op.get("primary_term"),
                               version=op.get("version"), origin="replica",
                               routing=op.get("routing"))
        else:
            try:
                local.engine.delete(op["id"], seq_no=op["seq_no"],
                                    primary_term=op.get("primary_term"),
                                    version=op.get("version"), origin="replica")
            except SearchEngineError:
                pass

    # ------------------------------------------------------------ search path
    def _select_copy(self, copies: List[ShardRoutingEntry],
                     sid: int) -> ShardRoutingEntry:
        """Adaptive replica selection through the unified dispatch cost
        router (SearchExecutionStatsCollector analog): lowest estimated
        queue-wait + RTT + device-leg cost wins; unmeasured nodes rank
        first so every copy gets probed, ties rotate by shard."""
        return self._router.select_copy(copies, sid)

    def _ars_observe(self, node_id: str, took_ms: float) -> None:
        self._router.observe(node_id, float(took_ms))

    def resolve_indices(self, expression: Optional[str]) -> List[str]:
        """Index-name expression → concrete index names from the cluster
        metadata (IndexNameExpressionResolver analog: csv, wildcards,
        _all)."""
        import fnmatch
        # "_"-prefixed keys are reserved metadata sections, not indices
        meta = {n: m for n, m in self.cluster_state.metadata.items()
                if not n.startswith("_")}
        if expression in (None, "", "_all", "*"):
            return sorted(meta)
        out: List[str] = []
        for part in str(expression).split(","):
            part = part.strip()
            if not part:
                continue
            if "*" in part:
                out.extend(n for n in sorted(meta)
                           if fnmatch.fnmatch(n, part) and n not in out)
            elif part in meta:
                if part not in out:
                    out.append(part)
            else:
                # a missing CONCRETE name is an error, not a silent skip
                # (IndexNameExpressionResolver: only wildcards may match
                # nothing)
                raise IndexNotFoundError(part)
        return out

    def client_search(self, index: Optional[str], body: dict,
                      on_done: Callable[[dict], None],
                      telemetry_ctx=None) -> None:
        """Two-phase query-then-fetch scatter-gather with a STREAMING
        incremental reduce (AbstractSearchAsyncAction + QueryPhaseResult
        Consumer:619): the query phase returns (row, score, sort) tuples
        only; per-shard responses fold into a bounded top-(from+size)
        accumulator and batched agg reduce as they arrive, so coordinator
        memory is independent of size x shards; the fetch phase then
        round-trips only for the global window's rows. `index` may be a
        multi-index expression; targets span every resolved index."""
        state = self.cluster_state
        try:
            names = self.resolve_indices(index)
        except IndexNotFoundError as e:
            on_done({"error": {"type": "index_not_found_exception",
                               "reason": str(e)}, "status": 404})
            return
        if not names:
            if index in (None, "", "_all", "*") or "*" in str(index):
                on_done({"took": 0, "timed_out": False,
                         "_shards": {"total": 0, "successful": 0,
                                     "skipped": 0, "failed": 0},
                         "hits": {"total": {"value": 0, "relation": "eq"},
                                  "max_score": None, "hits": []}})
            else:
                on_done({"error": {"type": "index_not_found_exception",
                                   "reason": f"no such index [{index}]"},
                         "status": 404})
            return
        targets: List[Tuple[str, ShardRoutingEntry]] = []
        unsearchable = 0  # red shards: no STARTED copy anywhere
        total_shards = 0
        for name in names:
            num_shards = int(state.metadata[name]["settings"].get(
                "index.number_of_shards", 1))
            total_shards += num_shards
            for sid in range(num_shards):
                copies = [r for r in state.routing
                          if r.index == name and r.shard == sid
                          and r.state == ShardRoutingEntry.STARTED and r.node_id]
                if not copies:
                    unsearchable += 1
                    continue
                targets.append((name, self._select_copy(copies, sid)))
        if not targets:
            # all-red expression: same response CONTRACT as the normal
            # path (took/timed_out/skipped present, red shards counted
            # failed) — the old early return omitted half the _shards
            # object and disagreed in shape with every other response
            on_done({"took": 0, "timed_out": False,
                     "_shards": {"total": total_shards, "successful": 0,
                                 "skipped": 0, "failed": unsearchable},
                     "hits": {"total": {"value": 0, "relation": "eq"},
                              "max_score": None, "hits": []}})
            return

        fan = self._fanout_context(body, telemetry_ctx=telemetry_ctx)

        # can_match pre-filter round (CanMatchPreFilterSearchPhase.java:57):
        # above the threshold, a lightweight range-vs-field-stats RPC prunes
        # shards that provably cannot match before the query phase fans out.
        # Time-range queries prefilter at ANY fan-out width (the reference's
        # default-on-range behavior): the field-stats min/max comparison is
        # exactly the evidence class those queries prune on, and a dashboard
        # time window typically rules out most of a rolling-index target set
        explicit = body.get("pre_filter_shard_size")
        prefilter_size = int(explicit) if explicit is not None else 128
        from elasticsearch_tpu.search.caches import has_range_clauses
        auto_range = (explicit is None
                      and has_range_clauses(body.get("query")))
        if body.get("query") is not None \
                and (len(targets) > prefilter_size
                     or (auto_range and len(targets) > 1)):
            self._can_match_phase(
                body, targets,
                lambda kept, skipped: self._query_phase(
                    body, kept, skipped, total_shards, unsearchable,
                    on_done, fan), fan)
        else:
            self._query_phase(body, targets, 0, total_shards,
                              unsearchable, on_done, fan)

    def _fanout_context(self, body: dict, telemetry_ctx=None) -> dict:
        """Per-request fan-out plan: budgets from the `search.fanout.*`
        cluster settings, the ABSOLUTE deadline from the request's
        `timeout` (propagated into every per-shard sub-request so remote
        admission layers shed on it), the partial-results policy
        (`allow_partial_search_results` overrides the cluster default),
        and the request's trace context (`telemetry.capture()` from the
        REST thread — the coordinator runs on the scheduler thread, so
        thread-locals cannot carry it here)."""
        from elasticsearch_tpu.common.settings import (
            parse_time_value, setting_bool)
        budgets = fanout_lib.budgets_from_settings(
            self.cluster_state.settings)
        started_ms = self.scheduler.now_ms
        deadline_at_ms = None
        timeout = body.get("timeout")
        if timeout not in (None, "", -1, "-1"):
            t_s = parse_time_value(timeout, "timeout")
            if t_s > 0:
                deadline_at_ms = started_ms + int(t_s * 1000)
        partial = budgets["partial_results"]
        if body.get("allow_partial_search_results") is not None:
            partial = setting_bool(body["allow_partial_search_results"])
        trace, trace_parent = None, None
        if telemetry_ctx is not None:
            trace, trace_parent = telemetry_ctx[0], telemetry_ctx[1]
        return {"budgets": budgets, "deadline_at_ms": deadline_at_ms,
                "started_ms": started_ms, "partial": partial,
                "profile": bool(body.get("profile")), "phases": {},
                "trace": trace, "trace_parent": trace_parent}

    def _phase_budget(self, fan: dict, base_budget_ms: int) -> int:
        """Per-shard timer budget for the NEXT phase: the configured phase
        budget, tightened by the request deadline — plus the grace window,
        so a remote's own deadline shed (cheap, attributed) beats the
        coordinator's backstop timer for live-but-slow nodes."""
        if fan["deadline_at_ms"] is None:
            return int(base_budget_ms)
        remaining = max(fan["deadline_at_ms"] - self.scheduler.now_ms, 0)
        return int(min(base_budget_ms,
                       remaining + fan["budgets"]["deadline_grace_ms"]))

    def _phase_deadline_ms(self, fan: dict, base_budget_ms: int) -> int:
        """Absolute deadline stamped on this phase's sub-requests: the
        request's own deadline when it has one, else the phase budget's
        end — either way every sub-request carries an absolute deadline,
        so a remote node never does work whose answer nobody will read."""
        if fan["deadline_at_ms"] is not None:
            return fan["deadline_at_ms"]
        return self.scheduler.now_ms + int(base_budget_ms)

    def _can_match_phase(self, body, targets, proceed, fan):
        flags = {}

        def finish(_summary):
            kept = [(n, e) for n, e in targets
                    if flags.get((n, e.shard), True)]
            skipped = len(targets) - len(kept)
            if not kept:
                # keep one shard so the response still carries proper
                # formatting (reference keeps the first skipped shard)
                kept, skipped = targets[:1], len(targets) - 1
            # pruning yield of the round, next to its launched/ok/failed
            # counters in _nodes/stats `fanout.phases.can_match`
            pc = self.fanout_stats.phase("can_match")
            pc["skipped_shards"] = pc.get("skipped_shards", 0) + skipped
            proceed(kept, skipped)

        # an unresponsive shard defaults to can_match=True (never prune on
        # missing evidence), so timeouts here only cost the pruning win
        sg = ScatterGather(
            self.scheduler, phase="can_match",
            budget_ms=self._phase_budget(
                fan, fan["budgets"]["query_budget_ms"]),
            stats=self.fanout_stats, on_done=finish,
            trace=fan.get("trace"), trace_parent=fan.get("trace_parent"))

        def fold(outcome, resp, _err, name, entry):
            if outcome == fanout_lib.OK and isinstance(resp, dict) \
                    and "can_match" in resp:
                flags[(name, entry.shard)] = bool(resp["can_match"])

        for name, entry in targets:
            req = {"index": name, "shard": entry.shard, "body": body}

            def send(on_resp, on_fail, name=name, entry=entry, req=req):
                if entry.node_id == self.node_id:
                    try:
                        self._on_can_match_shard(self.node_id, req, on_resp)
                    except Exception as e:
                        on_fail(e)
                else:
                    self.transport.send(
                        self.node_id, entry.node_id, CAN_MATCH_SHARD, req,
                        on_response=on_resp, on_failure=on_fail)

            sg.launch((name, entry.shard), entry.node_id, send,
                      on_item=lambda o, r, e, n=name, en=entry:
                      fold(o, r, e, n, en))
        sg.seal()

    def _query_phase(self, body, targets, skipped, num_shards,
                     unsearchable, on_done, fan):
        from elasticsearch_tpu.node import _sort_key_tuple
        from elasticsearch_tpu.search.agg_partials import (
            finalize_aggs, merge_partial_aggs,
        )

        frm = int(body.get("from", 0) or 0)
        size = int(body.get("size", 10) if body.get("size") is not None else 10)
        window = frm + size
        aggs_spec = body.get("aggs") or body.get("aggregations")
        batched_reduce = max(int(body.get("batched_reduce_size", 512)), 2)
        sort_key = ((lambda e: (_sort_key_tuple(e[1], body), e[2]))
                    if body.get("sort")
                    else (lambda e: (-e[0], e[2])))

        # streaming accumulator: top-`window` (score, sort, (index, shard),
        # row, node_id) entries + batched partial-agg buffer
        acc = {"top": [], "agg_buffer": [], "aggs": None, "total": 0,
               "relation": "eq", "max_score": None, "failed": 0,
               "successful": 0, "skipped": skipped, "timed_out": False}

        def fold_aggs(force=False):
            buf = acc["agg_buffer"]
            if not buf or (len(buf) < batched_reduce and not force):
                return
            merged = acc["aggs"]
            for tree in buf:
                merged = tree if merged is None else \
                    merge_partial_aggs(merged, tree, aggs_spec)
            acc["aggs"] = merged
            acc["agg_buffer"] = []

        def fold(outcome, resp, _err, name, entry):
            if isinstance(resp, dict) and "_spans" in resp:
                # the remote's trace segment rode back on the response:
                # fold its spans into the coordinator's trace (their
                # parent ids point at this leg's span, so the merged
                # tree needs no rewriting)
                spans = resp.pop("_spans")
                if fan.get("trace") is not None:
                    fan["trace"].absorb(spans)
            if outcome != fanout_lib.OK:
                # failed / per-shard timer expired / shed at the remote's
                # admission layer: the shard contributed nothing — count
                # it failed, and carry the timeout semantics forward
                acc["failed"] += 1
                if outcome in (fanout_lib.TIMED_OUT, fanout_lib.SHED):
                    acc["timed_out"] = True
                return
            acc["successful"] += 1
            acc["total"] += resp["total"]
            if resp.get("relation") == "gte":
                acc["relation"] = "gte"
            if resp.get("max_score") is not None:
                acc["max_score"] = max(acc["max_score"] or -1e30,
                                       resp["max_score"])
            svs = resp["sort_values"] or [None] * len(resp["rows"])
            entries = [(s, sv, (name, resp["shard"]), row, entry.node_id)
                       for row, s, sv in zip(resp["rows"], resp["scores"], svs)]
            # bounded merge: never hold more than 2*window entries
            acc["top"] = sorted(acc["top"] + entries, key=sort_key)[:window]
            if resp.get("aggregations") is not None:
                acc["agg_buffer"].append(resp["aggregations"])
                fold_aggs()

        fan_trace = fan.get("trace")
        # stage `phase.query`: filed by query_done below on EVERY
        # completion path (ScatterGather's on_done is structural — the
        # sweep timer guarantees it); per-leg spans parent under its
        # span, whose id therefore exists from the start
        q_start = time.monotonic_ns()
        q_id = telemetry.new_span_id() if fan_trace is not None else None

        def query_done(summary):
            telemetry.stage_done(
                "phase.query", q_start, time.monotonic_ns(),
                (fan_trace, fan.get("trace_parent"), None),
                status="timeout" if summary["any_timed_out"] else "ok",
                span_id=q_id, targets=len(targets))
            fold_aggs(force=True)
            fan["phases"]["query"] = summary
            if not fan["partial"] and (summary["any_timed_out"]
                                       or acc["failed"] > 0):
                # allow_partial_search_results=false: a timed-out or
                # failed shard fails the whole request (reference:
                # SearchPhaseExecutionException)
                on_done({"error": {
                    "type": "search_phase_execution_exception",
                    "reason": f"{acc['failed']} of {len(targets)} shards "
                              "failed and partial results are disallowed",
                    "phase": "query"}, "status": 503})
                return
            self._fetch_phase(body, acc, num_shards,
                              unsearchable, frm, on_done,
                              finalize_aggs, aggs_spec, fan)

        budgets = fan["budgets"]
        sg = ScatterGather(
            self.scheduler, phase="query",
            budget_ms=self._phase_budget(fan, budgets["query_budget_ms"]),
            stats=self.fanout_stats, observe=self._ars_observe,
            on_done=query_done,
            trace=fan_trace, trace_parent=q_id)
        deadline_ms = self._phase_deadline_ms(fan,
                                              budgets["query_budget_ms"])

        for name, entry in targets:
            req = fanout_lib.attach_deadline(
                {"index": name, "shard": entry.shard, "body": body},
                deadline_ms, self.scheduler.now_ms)

            def send(on_resp, on_fail, entry=entry, req=req):
                if entry.node_id == self.node_id:
                    try:
                        self._on_query_shard(self.node_id, req, on_resp)
                    except Exception as e:
                        on_fail(e)
                else:
                    self.transport.send(
                        self.node_id, entry.node_id, QUERY_SHARD, req,
                        on_response=on_resp, on_failure=on_fail)

            # `request=req` rides the trace context on the deadline
            # envelope, parenting the remote's spans under this leg
            sg.launch((name, entry.shard), entry.node_id, send,
                      on_item=lambda o, r, e, n=name, en=entry:
                      fold(o, r, e, n, en),
                      request=req)
        sg.seal()

    def _fetch_phase(self, body, acc, num_shards,
                     unsearchable, frm, on_done, finalize_aggs, aggs_spec,
                     fan):
        """Second round-trip: materialize _source/highlight for the global
        window only (FetchSearchPhase.java:47), under the fetch-phase
        budget — a dead node can drop hits from the window but never hang
        the response."""
        window_entries = acc["top"][frm:]
        partial_fanin = acc["timed_out"] or acc["failed"] > 0
        out = {
            "took": 0, "timed_out": acc["timed_out"],
            # skipped shards count as successful (SearchResponse: skipped
            # is a subset of successful)
            "_shards": {"total": num_shards,
                        "successful": acc["successful"] + acc.get("skipped", 0),
                        "skipped": acc.get("skipped", 0),
                        "failed": acc["failed"] + unsearchable},
            "hits": {"total": {"value": acc["total"],
                               # a partial fan-in's total only counts the
                               # shards that answered: the true total is
                               # at least this (reference: partial
                               # responses report a lower bound)
                               "relation": "gte" if partial_fanin
                               and acc["successful"] > 0
                               else acc["relation"]},
                     "max_score": acc["max_score"], "hits": []},
        }
        if acc["aggs"] is not None:
            out["aggregations"] = finalize_aggs(acc["aggs"], aggs_spec)

        def finish_response():
            out["took"] = max(self.scheduler.now_ms - fan["started_ms"], 0)
            if out["timed_out"]:
                self.fanout_stats.partial_responses += 1
            from elasticsearch_tpu.search.profile import fanout_profile
            phases = fanout_profile(fan["phases"])
            # private key (popped by the REST layer): the coordinator
            # slow log needs the phase breakdown on EVERY breach, not
            # just on profiled requests
            out["_took_phases"] = phases
            if fan["profile"]:
                out.setdefault("profile", {})["fanout"] = phases
            on_done(out)

        if not window_entries:
            finish_response()
            return

        # group window rows by (index, shard, node)
        by_shard: Dict[Tuple[str, int, str], List[int]] = {}
        for pos, (score, sv, ishard, row, node_id) in enumerate(window_entries):
            by_shard.setdefault((ishard[0], ishard[1], node_id), []).append(pos)
        hits: List[Optional[dict]] = [None] * len(window_entries)

        fan_trace = fan.get("trace")
        # stage `phase.fetch`: filed by fetch_done on every completion
        # path below
        f_start = time.monotonic_ns()
        f_id = telemetry.new_span_id() if fan_trace is not None else None

        def fetch_done(summary):
            telemetry.stage_done(
                "phase.fetch", f_start, time.monotonic_ns(),
                (fan_trace, fan.get("trace_parent"), None),
                status="timeout" if summary["any_timed_out"] else "ok",
                span_id=f_id, targets=len(by_shard))
            fan["phases"]["fetch"] = summary
            out["hits"]["hits"] = [h for h in hits if h is not None]
            finish_response()

        # the request deadline governs QUERY work (the expensive scan);
        # fetch hydrates the window those shards already won and runs
        # under its OWN budget — tightening it by an expired request
        # deadline would shed every hydration and turn partial results
        # into zero hits, defeating the whole partial-results contract
        budgets = fan["budgets"]
        sg = ScatterGather(
            self.scheduler, phase="fetch",
            budget_ms=budgets["fetch_budget_ms"],
            stats=self.fanout_stats, observe=self._ars_observe,
            on_done=fetch_done,
            trace=fan_trace, trace_parent=f_id)
        deadline_ms = self.scheduler.now_ms + budgets["fetch_budget_ms"]

        def fold(outcome, resp, _err, positions):
            if isinstance(resp, dict) and "_spans" in resp:
                spans = resp.pop("_spans")
                if fan_trace is not None:
                    fan_trace.absorb(spans)
            if outcome == fanout_lib.OK:
                for p, hit in zip(positions, resp["hits"]):
                    hits[p] = hit
                return
            out["_shards"]["failed"] += 1
            if outcome in (fanout_lib.TIMED_OUT, fanout_lib.SHED):
                out["timed_out"] = True

        for key, positions in by_shard.items():
            name, shard, node_id = key
            req = fanout_lib.attach_deadline(
                {"index": name, "shard": shard,
                 "rows": [window_entries[p][3] for p in positions],
                 "scores": [window_entries[p][0] for p in positions],
                 "sort_values": [window_entries[p][1] for p in positions],
                 "body": body},
                deadline_ms, self.scheduler.now_ms)

            def send(on_resp, on_fail, node_id=node_id, req=req):
                if node_id == self.node_id:
                    try:
                        self._on_fetch_shard(self.node_id, req, on_resp)
                    except Exception as e:
                        on_fail(e)
                else:
                    self.transport.send(self.node_id, node_id, FETCH_SHARD,
                                        req, on_response=on_resp,
                                        on_failure=on_fail)

            sg.launch(key, node_id, send,
                      on_item=lambda o, r, e, positions=positions:
                      fold(o, r, e, positions),
                      request=req)
        sg.seal()

    def _on_query_shard(self, sender, request, respond):
        """QUERY phase only: (row, score, sort) tuples + partial aggs —
        per-shard network payload independent of the fetch weight
        (QuerySearchResult analog); _source travels in the fetch phase."""
        from elasticsearch_tpu.search.caches import RequestCache

        key = (request["index"], request["shard"])
        local = self.local_shards.get(key)
        if local is None:
            raise SearchEngineError(f"no shard {key} on [{self.node_id}]")
        body = request["body"]

        # trace segment (telemetry): the envelope carried the
        # coordinator's trace context — open a segment with the SAME
        # trace id whose spans parent under the coordinator's leg span.
        # The segment lands in THIS node's ring (per-node attribution in
        # `_nodes/traces`) and its spans ride back on the response for
        # the coordinator to absorb into the one request trace.
        tctx = fanout_lib.trace_ctx_of(request)
        rtrace = None
        if tctx is not None and tctx.get("trace_id"):
            rtrace = telemetry_trace.TRACER.start_remote(
                f"shard.query[{request['index']}][{request['shard']}]",
                node_id=self.node_id, trace_id=tctx["trace_id"],
                parent_span_id=tctx.get("parent_span_id"),
                opaque_id=tctx.get("opaque_id"))

        def answer(payload: dict, status: str = "ok") -> None:
            if rtrace is not None:
                telemetry_trace.TRACER.finish(
                    rtrace, status=None if status == "ok" else status)
                # never mutate a possibly-cached payload: spans go on a
                # copy
                payload = {**payload, "_spans": rtrace.span_dicts()}
            respond(payload)

        # propagated deadline (serving/fanout.py): the coordinator stamped
        # this sub-request with the request's ABSOLUTE deadline. Convert
        # the remaining budget to this process's monotonic clock and hand
        # it to the execution path — device-work legs feed it into the
        # continuous batcher's EDF queue, so an overloaded or late shard
        # sheds at ITS OWN admission layer instead of making the
        # coordinator time out. An already-expired pure-host request is
        # shed right here (no batcher to do it).
        deadline_at = None
        remaining = fanout_lib.remaining_ms(request, self.scheduler.now_ms)
        if remaining is not None:
            has_device_leg = body.get("knn") is not None or (
                isinstance(body.get("query"), dict)
                and "knn" in body["query"])
            if remaining <= 0 and not has_device_leg:
                self.fanout_stats.remote["sheds_admission"] += 1
                answer(fanout_lib.shed_response(request["shard"],
                                                "admission"),
                       status="shed")
                return
            deadline_at = time.monotonic() + remaining / 1000.0

        reader = local.engine.acquire_searcher()
        # shard request cache: whole serialized query-phase responses for
        # size=0 requests, keyed on the reader CONTENT fingerprint
        # (IndicesRequestCache; a no-op refresh keeps its entries)
        cache_key = None
        if self.caches.request.cacheable_tracked(body):
            from elasticsearch_tpu.search.caches import reader_fingerprint
            cache_key = self.caches.request.key(
                key, reader_fingerprint(reader), body)
            cached = self.caches.request.get(cache_key)
            if cached is not None:
                answer(cached)
                return
        # aggs leave the shard as mergeable partial states (HLL/t-digest/
        # sum-count pairs); the coordinator reduce finalizes them
        # (InternalAggregation.reduce analog)
        try:
            # the segment rides the thread for the synchronous execute:
            # the vector-store batcher's queue entries capture it here,
            # so remote queue-wait / dispatch / device-sync spans land in
            # this segment with zero extra plumbing
            with telemetry_trace.use(trace=rtrace):
                t0 = time.perf_counter_ns()
                result = execute_query_phase(
                    reader, local.mapper_service, body,
                    shard_id=request["shard"],
                    vector_store=local.active_vector_store(),
                    partial_aggs=True,
                    query_cache=self.caches.query,
                    deadline_at=deadline_at)
                telemetry.stage_done("shard.query_phase", t0,
                                     time.perf_counter_ns())
        except EsRejectedExecutionError:
            # the continuous batcher's EDF queue shed the device leg on
            # the propagated deadline — exactly the remote-admission shed
            # the fan-out exists to produce. Answer with the structured
            # rejection so the coordinator attributes it (deadline, not
            # node death).
            self.fanout_stats.remote["sheds_batcher"] += 1
            answer(fanout_lib.shed_response(request["shard"],
                                            "batcher_edf"),
                   status="shed")
            return
        except BaseException:
            # an erroring shard must not leak its trace segment (the
            # leaked-span class TPU012 polices): finish it with error
            # status so it still lands in this node's ring, then let the
            # failure travel to the coordinator's on_failure as before
            if rtrace is not None:
                telemetry_trace.TRACER.finish(rtrace, status="error")
            raise
        response = {
            "shard": request["shard"],
            "total": result.total_hits,
            "relation": result.total_relation,
            "max_score": result.max_score,
            "rows": [int(r) for r in result.rows],
            "scores": [float(s) for s in result.scores],
            "sort_values": [list(sv) for sv in result.sort_values]
            if result.sort_values is not None else None,
            "aggregations": result.aggregations,
        }
        if cache_key is not None:
            self.caches.request.put(cache_key, response)
        answer(response)

    def _on_can_match_shard(self, sender, request, respond):
        """Lightweight pre-filter: range-vs-field-stats only, no query
        execution (SearchService#canMatch)."""
        from elasticsearch_tpu.search.caches import can_match

        key = (request["index"], request["shard"])
        local = self.local_shards.get(key)
        if local is None:
            raise SearchEngineError(f"no shard {key} on [{self.node_id}]")
        reader = local.engine.acquire_searcher()
        respond({"shard": request["shard"],
                 "can_match": can_match(reader, local.mapper_service,
                                        request["body"])})

    # ------------------------------------------------------------ scroll
    # Per-shard pinned reader contexts with keepalives (reference:
    # SearchService.createContext + LegacyReaderContext for scrolls,
    # SearchScrollAsyncAction on the coordinator). The shard holds the
    # full sorted row snapshot; the coordinator pulls windows per page,
    # so deep pagination never materializes the corpus anywhere.

    def _on_scroll_create(self, sender, request, respond):
        import uuid as _uuid

        key = (request["index"], request["shard"])
        local = self.local_shards.get(key)
        if local is None:
            raise SearchEngineError(f"no shard {key} on [{self.node_id}]")
        body = dict(request["body"])
        reader = local.engine.acquire_searcher()
        body["size"] = reader.num_docs  # snapshot the full shard ordering
        body["from"] = 0
        body["__unbounded_window__"] = True  # scroll bypasses
        # index.max_result_window: depth is bounded per page, not in total
        body["track_total_hits"] = True  # scrolls always count accurately
        body.pop("aggs", None)
        body.pop("aggregations", None)
        result = execute_query_phase(reader, local.mapper_service, body,
                                     shard_id=request["shard"],
                                     vector_store=local.active_vector_store(),
                                     query_cache=self.caches.query)
        ctx_id = _uuid.uuid4().hex
        keep_s = float(request.get("keep_alive_s", 300))
        self._shard_scrolls[ctx_id] = {
            "index": request["index"], "shard": request["shard"],
            "reader": reader, "body": request["body"],
            "rows": result.rows, "scores": result.scores,
            "sort_values": result.sort_values,
            "expiry": time.time() + keep_s, "keep_s": keep_s,
        }
        respond({"ctx_id": ctx_id, "total": result.total_hits,
                 "relation": result.total_relation,
                 "max_score": result.max_score})

    def _reap_shard_scrolls(self) -> None:
        now = time.time()
        for cid in [c for c, s in self._shard_scrolls.items()
                    if s["expiry"] < now]:
            self._shard_scrolls.pop(cid, None)

    def _on_scroll_fetch(self, sender, request, respond):
        import numpy as np

        from elasticsearch_tpu.search.service import ShardSearchResult

        self._reap_shard_scrolls()
        ctx = self._shard_scrolls.get(request["ctx_id"])
        if ctx is None:
            raise SearchContextMissingError(
                f"No search context found for id [{request['ctx_id']}]")
        if request.get("keep_alive_s"):
            ctx["keep_s"] = float(request["keep_alive_s"])
        ctx["expiry"] = time.time() + ctx["keep_s"]
        pos = int(request["pos"])
        count = int(request["count"])
        rows = ctx["rows"][pos:pos + count]
        scores = ctx["scores"][pos:pos + count]
        svs = ctx["sort_values"][pos:pos + count] \
            if ctx["sort_values"] is not None else None
        result = ShardSearchResult(
            shard_id=ctx["shard"],
            rows=np.asarray(rows, dtype=np.int64),
            scores=np.asarray(scores, dtype=np.float32),
            sort_values=svs, total_hits=len(rows), total_relation="eq",
            aggregations=None, max_score=None)
        hits = execute_fetch_phase(ctx["reader"], self.local_shards[
            (ctx["index"], ctx["shard"])].mapper_service,
            ctx["body"], result, index_name=ctx["index"])
        respond({"hits": hits,
                 "scores": [float(s) for s in scores],
                 "sort_values": [list(sv) if sv is not None else None
                                 for sv in svs] if svs is not None else None,
                 "exhausted": pos + count >= len(ctx["rows"])})

    def _on_scroll_free(self, sender, request, respond):
        freed = self._shard_scrolls.pop(request["ctx_id"], None) is not None
        respond({"freed": freed})

    def client_scroll_start(self, index: Optional[str], body: dict,
                            keep_alive_s: float,
                            on_done: Callable[[dict], None]) -> None:
        """Open per-shard scroll contexts on every target shard, then
        serve the first page through the merged cursor."""
        import uuid as _uuid

        state = self.cluster_state
        try:
            names = self.resolve_indices(index)
        except IndexNotFoundError as e:
            on_done({"error": {"type": "index_not_found_exception",
                               "reason": str(e)}, "status": 404})
            return
        targets: List[Tuple[str, ShardRoutingEntry]] = []
        for name in names:
            num_shards = int(state.metadata[name]["settings"].get(
                "index.number_of_shards", 1))
            for sid in range(num_shards):
                copies = [r for r in state.routing
                          if r.index == name and r.shard == sid
                          and r.state == ShardRoutingEntry.STARTED
                          and r.node_id]
                if copies:
                    targets.append((name, self._select_copy(copies, sid)))
        if not targets:
            on_done({"_scroll_id": _uuid.uuid4().hex, "took": 0,
                     "timed_out": False,
                     "_shards": {"total": 0, "successful": 0, "skipped": 0,
                                 "failed": 0},
                     "hits": {"total": {"value": 0, "relation": "eq"},
                              "max_score": None, "hits": []}})
            return
        size = int(body.get("size", 10) if body.get("size") is not None
                   else 10)
        # the id carries the coordinating node so ANY node can serve or
        # clear it (the reference encodes context locations in the id)
        scroll_id = f"{self.node_id}~{_uuid.uuid4().hex}"
        sstate = {
            "body": body, "size": size, "keep_s": keep_alive_s,
            "expiry": time.time() + keep_alive_s,
            "total": 0, "relation": "eq", "max_score": None,
            "shards": [],  # {node, ctx, pos, buffer, exhausted, failed}
        }
        failed_creates = {"n": 0}

        def created(outcome, resp, entry):
            if outcome == fanout_lib.OK and isinstance(resp, dict) \
                    and "ctx_id" in resp:
                sstate["total"] += int(resp.get("total", 0))
                if resp.get("relation") == "gte":
                    sstate["relation"] = "gte"
                ms = resp.get("max_score")
                if ms is not None:
                    sstate["max_score"] = max(sstate["max_score"] or -1e30,
                                              ms)
                sstate["shards"].append({
                    "node": entry.node_id, "ctx": resp["ctx_id"],
                    "pos": 0, "buffer": [], "exhausted": False,
                    "failed": False})
            else:
                failed_creates["n"] += 1

        def creates_done(_summary):
            self._client_scrolls[scroll_id] = sstate
            self._scroll_page(scroll_id, sstate, failed_creates["n"],
                              on_done)

        sg = ScatterGather(self.scheduler, phase="scroll_create",
                           budget_ms=self._SCROLL_BUDGET_MS,
                           stats=self.fanout_stats, on_done=creates_done)
        for name, entry in targets:
            req = {"index": name, "shard": entry.shard, "body": body,
                   "keep_alive_s": keep_alive_s}

            def send(on_resp, on_fail, entry=entry, req=req):
                if entry.node_id == self.node_id:
                    try:
                        self._on_scroll_create(self.node_id, req, on_resp)
                    except Exception as e:
                        on_fail(e)
                else:
                    self.transport.send(
                        self.node_id, entry.node_id, SCROLL_CREATE, req,
                        on_response=on_resp, on_failure=on_fail)

            sg.launch((name, entry.shard), entry.node_id, send,
                      on_item=lambda o, r, e, en=entry: created(o, r, en))
        sg.seal()

    def _scroll_page(self, scroll_id: str, sstate: dict, failed: int,
                     on_done: Callable[[dict], None]) -> None:
        """Fill per-shard buffers to >= size (or exhaustion), then emit the
        globally-ordered next page (SearchScrollQueryThenFetchAsyncAction's
        lastEmittedDoc accounting, done with per-shard cursors)."""
        from elasticsearch_tpu.node import _sort_key_tuple

        size = sstate["size"]
        body = sstate["body"]
        need = [sh for sh in sstate["shards"]
                if not sh["exhausted"] and not sh["failed"]
                and len(sh["buffer"]) < size]
        if not need:
            # keep untouched-but-live shard contexts alive: a shard whose
            # buffer stays full would otherwise never see a fetch and
            # could expire mid-scroll (keepalive piggyback, count=0)
            for sh in sstate["shards"]:
                if sh["exhausted"] or sh["failed"]:
                    continue
                if len(sh["buffer"]) >= size:
                    req = {"ctx_id": sh["ctx"], "pos": sh["pos"],
                           "count": 0, "keep_alive_s": sstate["keep_s"]}
                    if sh["node"] == self.node_id:
                        try:
                            self._on_scroll_fetch(self.node_id, req,
                                                  lambda _r: None)
                        except Exception:
                            pass
                    else:
                        self.transport.send(
                            self.node_id, sh["node"], SCROLL_FETCH, req,
                            on_response=lambda _r: None,
                            on_failure=lambda _e: None)
            # merge: pick the top `size` across buffers
            sort_spec = body.get("sort")

            def rank(item):
                _hit, score, sv = item
                if sort_spec:
                    return _sort_key_tuple(sv, body)
                return (-(score if score is not None else -1e30),)
            candidates = []
            for sh in sstate["shards"]:
                for item in sh["buffer"]:
                    candidates.append((rank(item), sh, item))
            candidates.sort(key=lambda t: t[0])
            page = candidates[:size]
            for _, sh, item in page:
                sh["buffer"].remove(item)
            hits = [item[0] for _, _, item in page]
            runtime_failed = sum(1 for sh in sstate["shards"]
                                 if sh["failed"])
            shards_total = len(sstate["shards"]) + failed
            on_done({"_scroll_id": scroll_id, "took": 0,
                     "timed_out": False,
                     "_shards": {"total": shards_total,
                                 "successful": len(sstate["shards"])
                                 - runtime_failed,
                                 "skipped": 0,
                                 "failed": failed + runtime_failed},
                     "hits": {"total": {"value": sstate["total"],
                                        "relation": sstate["relation"]},
                              "max_score": sstate["max_score"],
                              "hits": hits}})
            return
        def fetched(outcome, resp, sh):
            if outcome == fanout_lib.OK and isinstance(resp, dict) \
                    and "hits" in resp:
                svs = resp.get("sort_values")
                for i, h in enumerate(resp["hits"]):
                    sh["buffer"].append(
                        (h, resp["scores"][i] if resp.get("scores") else None,
                         tuple(svs[i]) if svs is not None
                         and svs[i] is not None else None))
                sh["pos"] += len(resp["hits"])
                if resp.get("exhausted"):
                    sh["exhausted"] = True
            else:
                # a shard that failed OR never answered inside the budget
                # stops contributing to the scroll; remaining shards keep
                # paging (same partial semantics as the search fan-out)
                sh["failed"] = True

        sg = ScatterGather(
            self.scheduler, phase="scroll_fetch",
            budget_ms=self._SCROLL_BUDGET_MS, stats=self.fanout_stats,
            on_done=lambda _s: self._scroll_page(scroll_id, sstate,
                                                 failed, on_done))
        for sh in need:
            req = {"ctx_id": sh["ctx"], "pos": sh["pos"],
                   "count": max(size, 1),
                   "keep_alive_s": sstate["keep_s"]}

            def send(on_resp, on_fail, sh=sh, req=req):
                if sh["node"] == self.node_id:
                    try:
                        self._on_scroll_fetch(self.node_id, req, on_resp)
                    except Exception as e:
                        on_fail(e)
                else:
                    self.transport.send(
                        self.node_id, sh["node"], SCROLL_FETCH, req,
                        on_response=on_resp, on_failure=on_fail)

            sg.launch(sh["ctx"], sh["node"], send,
                      on_item=lambda o, r, e, s=sh: fetched(o, r, s))
        sg.seal()

    def _scroll_owner(self, scroll_id: str) -> Optional[str]:
        owner = scroll_id.split("~", 1)[0] if "~" in scroll_id else None
        if owner and owner != self.node_id \
                and owner in self.cluster_state.nodes:
            return owner
        return None

    def client_scroll_next(self, scroll_id: str,
                           keep_alive_s: Optional[float],
                           on_done: Callable[[dict], None]) -> None:
        owner = self._scroll_owner(scroll_id)
        if owner:
            self._send_guarded(
                owner, SCROLL_NEXT,
                {"scroll_id": scroll_id, "keep_alive_s": keep_alive_s},
                on_done,
                lambda e: on_done({"error": {
                    "type": "search_context_missing_exception",
                    "reason": str(e)}, "status": 404}),
                phase="scroll_forward")
            return
        sstate = self._client_scrolls.get(scroll_id)
        if sstate is None or sstate["expiry"] < time.time():
            self._client_scrolls.pop(scroll_id, None)
            on_done({"error": {
                "type": "search_context_missing_exception",
                "reason": f"No search context found for id [{scroll_id}]"},
                "status": 404})
            return
        if keep_alive_s:
            sstate["keep_s"] = keep_alive_s
        sstate["expiry"] = time.time() + sstate["keep_s"]
        self._scroll_page(scroll_id, sstate, 0, on_done)

    def _on_scroll_clear_all(self, sender, request, respond):
        """Free every scroll THIS node coordinates (one leg of the
        cluster-wide _all broadcast)."""
        ids = list(self._client_scrolls)
        pending = {"count": len(ids), "freed": 0}
        if not ids:
            respond({"num_freed": 0})
            return

        def one(resp):
            pending["freed"] += int((resp or {}).get("num_freed", 0))
            pending["count"] -= 1
            if pending["count"] == 0:
                respond({"num_freed": pending["freed"]})

        for sid in ids:
            self.client_scroll_clear(sid, one)

    def client_scroll_clear_all(self, on_done: Callable[[dict], None]) -> None:
        """Broadcast _all scroll clearing to every node (any node may be
        coordinating scrolls the client started elsewhere)."""
        nodes = sorted(self.cluster_state.nodes) or [self.node_id]
        freed = {"n": 0}

        def one(outcome, resp, _err):
            if outcome == fanout_lib.OK:
                freed["n"] += int((resp or {}).get("num_freed", 0))

        sg = ScatterGather(
            self.scheduler, phase="scroll_clear",
            budget_ms=self._BROADCAST_BUDGET_MS, stats=self.fanout_stats,
            on_done=lambda _s: on_done({"succeeded": True,
                                        "num_freed": freed["n"]}))
        for nid in nodes:
            def send(on_resp, on_fail, nid=nid):
                if nid == self.node_id:
                    try:
                        self._on_scroll_clear_all(self.node_id, {}, on_resp)
                    except Exception as e:
                        on_fail(e)
                else:
                    self.transport.send(
                        self.node_id, nid, SCROLL_CLEAR_ALL, {},
                        on_response=on_resp, on_failure=on_fail)

            sg.launch(nid, nid, send, on_item=one)
        sg.seal()

    def client_scroll_clear(self, scroll_id: str,
                            on_done: Callable[[dict], None]) -> None:
        owner = self._scroll_owner(scroll_id)
        if owner:
            self._send_guarded(
                owner, SCROLL_CLEAR, {"scroll_id": scroll_id},
                on_done,
                lambda e: on_done({"succeeded": True, "num_freed": 0}),
                phase="scroll_forward")
            return
        sstate = self._client_scrolls.pop(scroll_id, None)
        if sstate is None:
            on_done({"succeeded": True, "num_freed": 0})
            return
        shards = [sh for sh in sstate["shards"] if not sh["failed"]]
        if not shards:
            on_done({"succeeded": True, "num_freed": 0})
            return
        freed = {"n": 0}

        def one(outcome, resp, _err):
            if outcome == fanout_lib.OK and isinstance(resp, dict) \
                    and resp.get("freed"):
                freed["n"] += 1

        sg = ScatterGather(
            self.scheduler, phase="scroll_clear",
            budget_ms=self._BROADCAST_BUDGET_MS, stats=self.fanout_stats,
            on_done=lambda _s: on_done({"succeeded": True,
                                        "num_freed": freed["n"]}))
        for sh in shards:
            req = {"ctx_id": sh["ctx"]}

            def send(on_resp, on_fail, sh=sh, req=req):
                if sh["node"] == self.node_id:
                    try:
                        self._on_scroll_free(self.node_id, req, on_resp)
                    except Exception as e:
                        on_fail(e)
                else:
                    self.transport.send(
                        self.node_id, sh["node"], SCROLL_FREE, req,
                        on_response=on_resp, on_failure=on_fail)

            sg.launch(sh["ctx"], sh["node"], send, on_item=one)
        sg.seal()

    def _on_fetch_shard(self, sender, request, respond):
        """FETCH phase: materialize hits for the coordinator's global
        window rows (FetchSearchPhase / SearchService.executeFetchPhase)."""
        import numpy as np

        from elasticsearch_tpu.search.service import ShardSearchResult

        key = (request["index"], request["shard"])
        local = self.local_shards.get(key)
        if local is None:
            raise SearchEngineError(f"no shard {key} on [{self.node_id}]")
        tctx = fanout_lib.trace_ctx_of(request)
        rtrace = None
        if tctx is not None and tctx.get("trace_id"):
            rtrace = telemetry_trace.TRACER.start_remote(
                f"shard.fetch[{request['index']}][{request['shard']}]",
                node_id=self.node_id, trace_id=tctx["trace_id"],
                parent_span_id=tctx.get("parent_span_id"),
                opaque_id=tctx.get("opaque_id"))

        def answer(payload: dict, status: str = "ok") -> None:
            if rtrace is not None:
                telemetry_trace.TRACER.finish(
                    rtrace, status=None if status == "ok" else status)
                payload = {**payload, "_spans": rtrace.span_dicts()}
            respond(payload)

        # propagated-deadline admission: a fetch arriving past the
        # request's deadline hydrates hits nobody will read — shed it
        remaining = fanout_lib.remaining_ms(request, self.scheduler.now_ms)
        if remaining is not None and remaining <= 0:
            self.fanout_stats.remote["sheds_admission"] += 1
            answer(fanout_lib.shed_response(request["shard"], "admission"),
                   status="shed")
            return
        body = request["body"]
        reader = local.engine.acquire_searcher()
        svs = request.get("sort_values")
        result = ShardSearchResult(
            shard_id=request["shard"],
            rows=np.asarray(request["rows"], dtype=np.int64),
            scores=np.asarray(request["scores"], dtype=np.float32),
            sort_values=[tuple(sv) if sv is not None else None for sv in svs]
            if svs is not None and any(sv is not None for sv in svs) else None,
            total_hits=len(request["rows"]), total_relation="eq",
            aggregations=None, max_score=None)
        t0 = time.perf_counter_ns()
        try:
            hits = execute_fetch_phase(reader, local.mapper_service, body,
                                       result,
                                       index_name=request["index"])
        except BaseException:
            # same no-leak rule as the query side: an erroring fetch
            # finishes its segment with error status before propagating
            if rtrace is not None:
                telemetry_trace.TRACER.finish(rtrace, status="error")
            raise
        telemetry.stage_done(
            "shard.hydrate", t0, time.perf_counter_ns(),
            (rtrace, rtrace.root.span_id if rtrace is not None else None,
             None), hits=len(hits))
        answer({"hits": hits})

    def client_get(self, index: str, doc_id: str,
                   on_done: Callable[[dict], None],
                   routing: Optional[str] = None) -> None:
        state = self.cluster_state
        meta = state.metadata.get(index)
        if meta is None:
            on_done({"found": False, "error": "index_not_found"})
            return
        num_shards = int(meta["settings"].get("index.number_of_shards", 1))
        sid = shard_id_for(routing if routing is not None else doc_id,
                           num_shards)
        primary = state.primary_of(index, sid)
        if primary is None:
            on_done({"found": False, "error": "no_primary"})
            return

        request = {"index": index, "shard": sid, "id": doc_id}
        if primary.node_id == self.node_id:
            self._on_get(self.node_id, request, on_done)
        else:
            self._send_guarded(primary.node_id, "indices:data/read/get",
                               request, on_done,
                               lambda e: on_done({"found": False,
                                                  "error": str(e)}),
                               phase="get_forward")

    def _on_get(self, sender, request, respond):
        local = self.local_shards.get((request["index"], request["shard"]))
        if local is None:
            respond({"found": False})
            return
        doc = local.engine.get(request["id"])
        if doc is None:
            respond({"_index": request["index"], "_id": request["id"], "found": False})
        else:
            out = {"_index": request["index"], "_id": request["id"],
                   "found": True, "_source": doc["_source"],
                   "_seq_no": doc["_seq_no"], "_version": doc["_version"],
                   "_primary_term": doc.get("_primary_term", 1)}
            if doc.get("_routing") is not None:
                out["_routing"] = doc["_routing"]
            respond(out)

    def refresh_all(self) -> None:
        for shard in self.local_shards.values():
            shard.engine.refresh()

    # ------------------------------------------------------------------ wiring
    def _register_handlers(self):
        t = self.transport
        me = self.node_id
        t.register(me, WRITE_PRIMARY, self._on_write_primary)
        t.register(me, WRITE_REPLICA, self._on_write_replica)
        t.register(me, QUERY_SHARD, self._on_query_shard)
        t.register(me, FETCH_SHARD, self._on_fetch_shard)
        t.register(me, CAN_MATCH_SHARD, self._on_can_match_shard)
        t.register(me, SCROLL_CREATE, self._on_scroll_create)
        t.register(me, SCROLL_FETCH, self._on_scroll_fetch)
        t.register(me, SCROLL_FREE, self._on_scroll_free)
        t.register(me, SCROLL_NEXT,
                   lambda s, req, respond: self.client_scroll_next(
                       req["scroll_id"], req.get("keep_alive_s"), respond))
        t.register(me, SCROLL_CLEAR,
                   lambda s, req, respond: self.client_scroll_clear(
                       req["scroll_id"], respond))
        t.register(me, SCROLL_CLEAR_ALL, self._on_scroll_clear_all)
        t.register(me, "indices:data/read/get", self._on_get)
        t.register(me, "indices:admin/refresh", self._on_refresh)
        t.register(me, RECOVERY_START, self._on_recovery_start)
        t.register(me, RECOVERY_FILE_CHUNK, self._on_recovery_file_chunk)
        t.register(me, MASTER_CREATE_INDEX, self._master_create_index)
        t.register(me, MASTER_DELETE_INDEX, self._master_delete_index)
        t.register(me, MASTER_SHARD_STARTED, self._master_shard_started)
        t.register(me, MASTER_SHARD_FAILED, self._master_shard_failed)
        t.register(me, MASTER_UPDATE_SETTINGS, self._master_update_settings)
        t.register(me, MASTER_PUT_REGISTRY, self._master_put_registry)
        t.register(me, MASTER_PUT_PERSISTENT_TASK,
                   self._master_put_persistent_task)
        t.register(me, NODES_DISPATCH, self._on_nodes_dispatch)

    # routed actions ----------------------------------------------------------
    def _on_nodes_dispatch(self, sender, request, respond):
        """Run a named registered collector locally and respond with its
        section — the nodeOperation half of TransportNodesAction."""
        op = (request or {}).get("op")
        fn = self.node_collectors.get(op)
        if fn is None:
            respond({"error": {"type": "unknown_dispatch_op",
                               "reason": f"no collector [{op}]"}})
            return
        params = (request or {}).get("params") or {}

        def work():
            try:
                out = {"result": fn(params)}
            except Exception as e:  # surface to the caller, never hang
                out = {"error": {"type": type(e).__name__, "reason": str(e),
                                 "status": int(getattr(e, "status", 500))}}
            loop = getattr(self.transport, "loop", None)
            if loop is not None:
                loop.call_soon_threadsafe(respond, out)
            else:  # simulator transport: synchronous respond
                respond(out)

        if self.dispatch_executor is not None:
            # collectors may block (hot-threads sampling, fs probes): run on
            # the generic pool, never on the event loop
            self.dispatch_executor(work)
        else:
            work()

    def _transport_send(self, target: str, action: str, request: dict,
                        on_response, on_failure,
                        timeout_ms: Optional[int]) -> None:
        """send() with timeout when the transport supports it (the
        deterministic sim transport's send has no timeout kwarg)."""
        if not hasattr(self, "_send_takes_timeout"):
            import inspect
            self._send_takes_timeout = "timeout_ms" in                 inspect.signature(self.transport.send).parameters
        if self._send_takes_timeout:
            self.transport.send(self.node_id, target, action, request,
                                on_response=on_response,
                                on_failure=on_failure, timeout_ms=timeout_ms)
        else:
            self.transport.send(self.node_id, target, action, request,
                                on_response=on_response,
                                on_failure=on_failure)

    def fanout_nodes(self, op: str, params: Optional[dict] = None,
                     on_done: Optional[Callable] = None,
                     timeout_ms: int = 10000) -> None:
        """Broadcast a named collector op to every cluster node and merge:
        on_done({"results": {node_id: section}, "failures": {node_id: err}}).
        Unreachable nodes become failures, not errors — the merged response
        reports partial coverage the way TransportNodesAction does."""
        targets = list(self.cluster_state.nodes.keys()) or [self.node_id]
        results: Dict[str, Any] = {}
        failures: Dict[str, Any] = {}
        remaining = {"n": len(targets)}

        def finish_one():
            remaining["n"] -= 1
            if remaining["n"] == 0 and on_done is not None:
                on_done({"results": results, "failures": failures})

        def callbacks(nid):
            def on_resp(resp):
                if isinstance(resp, dict) and resp.get("error") is not None:
                    failures[nid] = resp["error"]
                else:
                    results[nid] = (resp or {}).get("result")
                finish_one()

            def on_fail(err):
                failures[nid] = {"type": "node_unreachable",
                                 "reason": str(err)}
                finish_one()

            return on_resp, on_fail

        for nid in targets:
            on_resp, on_fail = callbacks(nid)
            self._transport_send(nid, NODES_DISPATCH,
                                 {"op": op, "params": params or {}},
                                 on_resp, on_fail, timeout_ms)

    def dispatch_to_node(self, node_id: str, op: str,
                         params: Optional[dict] = None,
                         on_done: Optional[Callable] = None,
                         on_failure: Optional[Callable] = None,
                         timeout_ms: int = 10000) -> None:
        """Run a named collector op on ONE node (task get/cancel routing)."""
        def on_resp(resp):
            if isinstance(resp, dict) and resp.get("error") is not None:
                err = resp["error"]
                # rebuild the remote's error class so error.type/status
                # round-trip (clustered /_tasks/{id} must 404 with
                # resource_not_found_exception, as single-node does)
                from elasticsearch_tpu.common import errors as _errors
                cls = getattr(_errors, str(err.get("type", "")),
                              SearchEngineError)
                if not (isinstance(cls, type)
                        and issubclass(cls, SearchEngineError)):
                    cls = SearchEngineError
                exc = cls(err.get("reason", str(err)))
                exc.status = int(err.get("status", getattr(cls, "status", 500)))
                if on_failure:
                    on_failure(exc)
                return
            if on_done:
                on_done((resp or {}).get("result"))

        self._transport_send(node_id, NODES_DISPATCH,
                             {"op": op, "params": params or {}},
                             on_resp, on_failure, timeout_ms)

    # client admin helpers ----------------------------------------------------
    def client_create_index(self, name: str, settings: Optional[dict] = None,
                            mappings: Optional[dict] = None,
                            on_done: Optional[Callable] = None,
                            on_failure: Optional[Callable] = None) -> None:
        self._send_to_master(MASTER_CREATE_INDEX,
                             {"index": name, "settings": settings,
                              "mappings": mappings},
                             on_response=on_done or (lambda r: None),
                             on_failure=on_failure)

    def client_delete_index(self, name: str, on_done: Optional[Callable] = None,
                            on_failure: Optional[Callable] = None) -> None:
        self._send_to_master(MASTER_DELETE_INDEX, {"index": name},
                             on_response=on_done or (lambda r: None),
                             on_failure=on_failure)

    def _on_refresh(self, sender, request, respond):
        index = (request or {}).get("index")
        for (idx, _sid), shard in self.local_shards.items():
            if index is None or idx == index:
                shard.engine.refresh()
        respond({"ack": True})

    def client_refresh(self, index: Optional[str],
                       on_done: Callable[[dict], None]) -> None:
        """Cluster-wide refresh: broadcast to every node holding shards
        (RefreshAction broadcast-by-node analog)."""
        state = self.cluster_state
        targets = sorted({n for n in state.nodes})
        if not targets:
            targets = [self.node_id]
        counts = {"ok": 0, "failed": 0}

        def one(outcome, _resp, _err):
            # an unreachable or unresponsive node means its shards were
            # NOT refreshed — the response must say so, not claim success
            # (RefreshAction reports per-shard failures)
            counts["ok" if outcome == fanout_lib.OK else "failed"] += 1

        sg = ScatterGather(
            self.scheduler, phase="refresh",
            budget_ms=self._BROADCAST_BUDGET_MS, stats=self.fanout_stats,
            on_done=lambda _s: on_done(
                {"_shards": {"total": len(targets),
                             "successful": counts["ok"],
                             "failed": counts["failed"]}}))
        for t in targets:
            def send(on_resp, on_fail, t=t):
                if t == self.node_id:
                    try:
                        self._on_refresh(self.node_id, {"index": index},
                                         on_resp)
                    except Exception as e:
                        on_fail(e)
                else:
                    self.transport.send(self.node_id, t,
                                        "indices:admin/refresh",
                                        {"index": index},
                                        on_response=on_resp,
                                        on_failure=on_fail)

            sg.launch(t, t, send, on_item=one)
        sg.seal()
