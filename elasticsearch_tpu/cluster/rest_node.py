"""ClusterAwareNode: ONE feature surface for both deployment shapes.

The reference has a single execution path — every REST handler drives a
TransportAction, and a one-node cluster is just a cluster (`node/Node.java`
wires the same ActionModule either way). Round 1 here grew two worlds: the
full-featured single-node `Node` and a CRUD+search-only `ClusterNode`
(VERDICT "two worlds, one brain").

This class collapses them for the REST surface: it IS a `Node` (every
registered handler — templates, ingest pipelines, analyze, scripts, cat
APIs, xpack features — keeps working), but the DATA PATH overrides
delegate to the cluster layer:

- document writes/deletes route to the shard's primary and replicate
  (`ClusterNode.client_write`)
- GETs route to the primary (realtime)
- searches/counts/msearch run the distributed two-phase scatter-gather
  with streaming reduce and partial-agg merging (`client_search`)
- index create/delete/refresh and cluster settings go through the master

Registries (ingest pipelines, templates, stored scripts) replicate
through cluster state (`_wire_replicated_registries`), so a PUT on any
node is visible cluster-wide after publication.
"""

from __future__ import annotations

import functools
import threading
import time as _time
from typing import Any, Dict, List, Optional

from elasticsearch_tpu.common.errors import (
    IllegalArgumentError, IndexNotFoundError, SearchEngineError,
)
from elasticsearch_tpu.node import Node
from elasticsearch_tpu import telemetry as _telemetry
from elasticsearch_tpu.telemetry import trace as _teletrace


def _parse_keepalive_s(value: Optional[str]) -> float:
    """'1m' / '30s' -> seconds (TimeValue parsing)."""
    if not value:
        return 300.0
    from elasticsearch_tpu.common.settings import parse_time_value
    return float(parse_time_value(str(value), "scroll"))


def _empty_search_response() -> dict:
    return {"took": 0, "timed_out": False,
            "_shards": {"total": 0, "successful": 0, "skipped": 0,
                        "failed": 0},
            "hits": {"total": {"value": 0, "relation": "eq"},
                     "max_score": None, "hits": []}}


class ClusterCallError(SearchEngineError):
    status = 503


class ClusterAwareNode(Node):
    def __init__(self, data_path: str, cluster_node, loop,
                 node_name: str = "node-0", cluster_name: str = "tpu-search",
                 settings: Optional[dict] = None):
        super().__init__(data_path, node_name=node_name,
                         cluster_name=cluster_name, settings=settings)
        self.cluster = cluster_node
        self.loop = loop
        # one identity: the REST layer, task manager, and cluster layer must
        # agree on this node's id (task ids embed it; fan-out responses key
        # on it)
        self.node_id = cluster_node.node_id
        self.tasks.node_id = cluster_node.node_id
        self._wire_replicated_registries()
        self._wire_persistent_features()
        self._wire_node_dispatch()
        self._wire_cluster_snapshots()
        self._wire_replicated_jobs()

    def _wire_persistent_features(self) -> None:
        """Background features run as cluster-assigned persistent tasks
        (PersistentTasksClusterService): the master picks exactly ONE node
        to tick ILM / SLM / watcher, with reassignment on node-leave —
        instead of every node ticking its own copy."""
        from elasticsearch_tpu.xpack.watcher import WatcherService

        def _bg(fn):
            # ticks fire on the event loop; the feature work itself (which
            # may write through the cluster and block on the loop) runs on
            # the generic pool — running it inline would deadlock
            def tick():
                try:
                    self.thread_pool.submit("generic", fn)
                except Exception:
                    pass
            return tick

        self.cluster.persistent_task_executors.update({
            "watcher": _bg(lambda: self.watcher.run_once()),
            "ilm": _bg(lambda: self.ilm.run_once()),
            "slm": _bg(lambda: self.slm.run_once()),
            "rollup": _bg(lambda: self.rollup.run_once()),
            "transform": _bg(lambda: self.transform.run_once()),
        })

        # watches replicate through cluster state like the other
        # registries, so the assigned executor node sees every watch
        watcher = self.watcher
        orig_put_watch = WatcherService.put_watch.__get__(watcher)
        orig_del_watch = WatcherService.delete_watch.__get__(watcher)
        node = self

        record = functools.partial(self._record_registry, "watches")

        def put_watch(watch_id, body, active=True):
            WatcherService.validate_watch(body)
            created = watch_id not in watcher.watches
            value = {"body": body, "active": active}
            node._call(node.cluster.client_put_registry,
                       "watches", watch_id, value)
            out = orig_put_watch(watch_id, body, active=active)
            record(watch_id, value)
            # the registry sync may have applied the watch an instant
            # before the local call: report created from the pre-call view
            out["created"] = created
            return out

        def delete_watch(watch_id):
            watcher.get_watch(watch_id)  # 404 before cluster traffic
            node._call(node.cluster.client_put_registry,
                       "watches", watch_id, None)
            try:
                orig_del_watch(watch_id)
            except Exception:
                pass  # the registry sync may have removed it already
            record(watch_id, None)

        watcher.put_watch = put_watch
        watcher.delete_watch = delete_watch
        self._registry_originals["watch"] = \
            lambda key, value: orig_put_watch(
                key, value["body"], active=value.get("active", True))
        self._registry_originals["del_watch"] = orig_del_watch
        self._registry_sections = getattr(self, "_registry_sections", ()) + (
            ("watches", self._registry_originals["watch"],
             self._registry_originals["del_watch"]),)

    def _wire_replicated_jobs(self) -> None:
        """Rollup jobs and transforms replicate like watches: the config
        AND run-state travel through cluster state, so whichever node holds
        the persistent task (incl. after an owner dies) ticks them
        (RollupJobTask / TransformTask as persistent tasks)."""
        node = self

        def _wrap(service, section, put_name, start_name, stop_name,
                  del_name, state_key, jobs_attr):
            orig_put = getattr(service, put_name)
            orig_start = getattr(service, start_name)
            orig_stop = getattr(service, stop_name)
            orig_del = getattr(service, del_name)
            configs = getattr(service, jobs_attr)

            def current_value(job_id):
                run = service.state.get(job_id, {}).get(state_key, "stopped")
                return {"config": configs.get(job_id), "run_state": run}

            def replicate(job_id, value):
                node._call(node.cluster.client_put_registry,
                           section, job_id, value)
                node._record_registry(section, job_id, value)

            def rput(job_id, body):
                had = job_id in configs
                orig_put(job_id, body)  # validate + apply locally
                try:
                    replicate(job_id, current_value(job_id))
                except Exception:
                    # failed publish must not leave this node diverged
                    if not had:
                        configs.pop(job_id, None)
                        service.state.pop(job_id, None)
                    raise

            def rstart(job_id):
                out = orig_start(job_id)
                # replicate the POST-call state (a batch transform may have
                # already completed and flipped back to stopped)
                replicate(job_id, current_value(job_id))
                return out

            def rstop(job_id):
                out = orig_stop(job_id)
                replicate(job_id, current_value(job_id))
                return out

            def rdel(job_id):
                if job_id not in configs:
                    orig_del(job_id)  # surface the native 404
                    return
                saved_cfg = configs.get(job_id)
                saved_state = dict(service.state.get(job_id) or {})
                orig_del(job_id)
                try:
                    replicate(job_id, None)
                except Exception:
                    configs[job_id] = saved_cfg
                    service.state[job_id] = saved_state
                    raise

            def sync_put(key, value):
                cfg = (value or {}).get("config")
                if cfg is None:
                    return
                try:
                    orig_put(key, cfg)
                except Exception:
                    pass  # already known locally: just apply run state
                if key in service.state:
                    service.state[key][state_key] = \
                        (value or {}).get("run_state", "stopped")

            def sync_del(key):
                try:
                    orig_del(key)
                except Exception:
                    pass

            setattr(service, put_name, rput)
            setattr(service, start_name, rstart)
            setattr(service, stop_name, rstop)
            setattr(service, del_name, rdel)
            self._registry_sections = getattr(
                self, "_registry_sections", ()) + (
                (section, sync_put, sync_del),)

        _wrap(self.rollup, "rollup_jobs", "put_job", "start_job",
              "stop_job", "delete_job", "job_state", "jobs")
        _wrap(self.transform, "transforms", "put", "start", "stop",
              "delete", "state", "transforms")

    def register_builtin_persistent_tasks(self) -> None:
        """Called once post-boot: idempotent registrations (the master's
        task-update no-ops when the id exists)."""
        for tid, interval in (("watcher", 1000), ("ilm", 30_000),
                              ("slm", 60_000), ("rollup", 2000),
                              ("transform", 2000)):
            self.cluster.client_register_persistent_task(
                tid, interval_ms=interval, on_done=lambda r: None,
                on_failure=lambda e: None)

    # --------------------------------------------------- replicated registries
    def _wire_node_dispatch(self) -> None:
        """Register this node's local collectors for the generic routed
        action layer (TransportNodesAction analog): every node serves the
        same named ops; the *_api overrides below fan them out and merge,
        so `_nodes/stats` on node B reflects node A."""
        c = self.cluster

        def _cancel(p):
            t = self.tasks.cancel(p["task_id"])
            return {self.cluster.node_id: {
                "tasks": {t.task_id: t.to_dict(self.cluster.node_id)}}}

        def _stats(p):
            st = {**self.local_node_stats(
                p.get("level"), bool(p.get("include_segment_file_sizes"))),
                "fanout": self.cluster.fanout_stats.snapshot()}
            # block-level recovery progress (peer recovery, relocation,
            # restore) replaces the single-node stub: live targets,
            # sources serving phase 1, reused/shipped blocks, backoff
            # throttle time and retry/giveup counters
            st.setdefault("indices", {})["recovery"] = c.recovery_summary()
            return st

        c.node_collectors.update({
            "info": lambda p: self.local_node_info(),
            # the cross-node serving path's counters ride the stats
            # section: coordinator-side per-phase fan-out tallies +
            # data-plane remote deadline sheds (serving/fanout.py)
            "stats": _stats,
            "hot_threads": lambda p: self.local_hot_threads(
                float(p.get("interval_s", 0.05)),
                top_n=int(p.get("top_n", 3))),
            "traces": lambda p: self.local_traces_section(
                int(p.get("limit", 50))),
            "tasks": lambda p: self.local_tasks_section(p.get("actions")),
            "task_get": lambda p: {
                "completed": False,
                "task": self.tasks.get(p["task_id"]).to_dict(
                    self.cluster.node_id)},
            "task_cancel": _cancel,
            "cat_thread_pool": lambda p: self.local_cat_threadpool_rows(
                p.get("pool_filter")),
            "cat_nodeattrs": lambda p: self.local_cat_nodeattrs_rows(),
            "cat_fielddata": lambda p: self.local_cat_fielddata_rows(
                p.get("field_filter")),
            "cat_tasks": lambda p: self.local_cat_tasks_rows(),
        })
        c.dispatch_executor = functools.partial(
            self.thread_pool.submit, "generic")

    def _fanout(self, op: str, params: Optional[dict] = None,
                timeout: float = 20.0) -> dict:
        return self._call(self.cluster.fanout_nodes, op, params,
                          timeout=timeout)

    def nodes_info_api(self) -> dict:
        out = self._fanout("info")
        return self._nodes_envelope(out["results"],
                                    failed=len(out["failures"]))

    def nodes_stats_api(self, level: str = None,
                        include_segment_file_sizes: bool = False) -> dict:
        out = self._fanout("stats", {
            "level": level,
            "include_segment_file_sizes": include_segment_file_sizes})
        return self._nodes_envelope(out["results"],
                                    failed=len(out["failures"]))

    def hot_threads_api(self, interval_s: float = 0.05,
                        top_n: int = 3) -> str:
        out = self._fanout("hot_threads", {"interval_s": interval_s,
                                           "top_n": top_n})
        return "\n".join(out["results"][nid]
                          for nid in sorted(out["results"]))

    def traces_api(self, limit: int = 50) -> dict:
        """Cluster `GET _nodes/traces`: every node's completed-trace
        ring, merged under the standard `_nodes` envelope — a cross-node
        search shows its coordinator trace on the coordinating node and
        its shard segments on each data node, joined by trace_id."""
        out = self._fanout("traces", {"limit": limit})
        return self._nodes_envelope(out["results"],
                                    failed=len(out["failures"]))

    def tasks_list_api(self, actions=None) -> dict:
        out = self._fanout("tasks", {"actions": actions})
        resp = {"nodes": out["results"]}
        if out["failures"]:
            resp["node_failures"] = [
                {"type": f.get("type", "failed_node_exception"),
                 "reason": f.get("reason", str(f)), "node_id": nid}
                for nid, f in sorted(out["failures"].items())]
        return resp

    def _task_owner(self, task_id: str) -> str:
        owner = str(task_id).rsplit(":", 1)[0]
        if owner not in self.cluster.cluster_state.nodes:
            from elasticsearch_tpu.common.errors import ResourceNotFoundError
            raise ResourceNotFoundError(f"task [{task_id}] isn't running and "
                                        "hasn't stored its results")
        return owner

    def task_get_api(self, task_id: str) -> dict:
        return self._call(self.cluster.dispatch_to_node,
                          self._task_owner(task_id), "task_get",
                          {"task_id": task_id}, timeout=20.0)

    def task_cancel_api(self, task_id: str) -> dict:
        nodes = self._call(self.cluster.dispatch_to_node,
                           self._task_owner(task_id), "task_cancel",
                           {"task_id": task_id}, timeout=20.0)
        return {"nodes": nodes}

    def _wire_cluster_snapshots(self) -> None:
        """Route snapshot/restore through the cluster-state lifecycle
        (cluster/snapshots.py): repositories replicate like the other
        registries; create/restore become master state updates; this node
        contributes the data-plane hooks (blob IO, shard access)."""
        import os
        import time as _time

        from elasticsearch_tpu.cluster.snapshots import (
            RESTORE_IN_PROGRESS, SNAPSHOTS_IN_PROGRESS)
        from elasticsearch_tpu.common.errors import (
            ResourceAlreadyExistsError, ResourceNotFoundError)

        svc = self.snapshots
        lifecycle = self.cluster.snapshot_lifecycle
        orig_put_repo = svc.put_repository
        orig_del_repo = svc.delete_repository
        orig_get = svc.get_snapshots

        # ---- data-plane hooks -------------------------------------------
        lifecycle.repo_factory = svc.get_repository
        # generic pool, NOT the snapshot pool: the REST create handler
        # blocks a snapshot-pool thread polling for completion, and
        # upload jobs queued behind it would deadlock the lifecycle
        lifecycle.executor = functools.partial(
            self.thread_pool.submit, "generic")

        def shard_uploader(repo_name, index, shard_id):
            from elasticsearch_tpu.recovery.snapshot import snapshot_shard
            repo = svc.get_repository(repo_name)
            shard = self.cluster.local_shards.get((index, shard_id))
            if shard is None:
                raise ResourceNotFoundError(
                    f"shard [{index}][{shard_id}] is not allocated here")
            # block-level snapshot (recovery/snapshot.py): sealed
            # segments, cached columnar blocks, the ledger and trained
            # IVF layouts as content-addressed blobs — only blocks the
            # repository has never seen upload
            # active_vector_store(): a text-only shard must not
            # materialize its lazy device store just to snapshot nothing
            return snapshot_shard(
                repo, shard.engine, shard.active_vector_store(),
                settings=self.cluster.cluster_state.settings)

        lifecycle.shard_uploader = shard_uploader

        def shard_restore_hook(restore, index, shard_id, path):
            from elasticsearch_tpu.recovery.snapshot import restore_shard
            repo = svc.get_repository(restore["repo"])
            entry = restore["shards"].get(str(shard_id)) or {}
            if "blocks" in entry:
                # digest-verified reassembly; fetched blobs also land in
                # the node block cache, so a later peer recovery of the
                # same data re-ships nothing
                restore_shard(repo, entry, path,
                              cache=self.cluster.block_cache)
                return
            for fname, digest in (entry.get("files") or {}).items():
                repo.get_blob(digest, os.path.join(path, fname))

        self.cluster.shard_restore_hook = shard_restore_hook

        # ---- repositories replicate through cluster state ---------------
        def put_repository(name, body, verify=True):
            had = name in svc.repositories
            orig_put_repo(name, body, verify=verify)  # validate locally first
            try:
                self._call(self.cluster.client_put_registry,
                           "repositories", name, body)
            except Exception:
                # failed publish must not leave this node diverged: undo the
                # local registration before surfacing the error
                if not had:
                    svc.repositories.pop(name, None)
                raise
            self._record_registry("repositories", name, body)

        def delete_repository(name):
            svc.get_repository(name)  # 404 before cluster traffic
            self._call(self.cluster.client_put_registry,
                       "repositories", name, None)
            try:
                orig_del_repo(name)
            except Exception:
                pass
            self._record_registry("repositories", name, None)

        svc.put_repository = put_repository
        svc.delete_repository = delete_repository
        self._registry_originals["repository"] =             lambda key, value: orig_put_repo(key, value, verify=False)
        self._registry_originals["del_repository"] = orig_del_repo
        self._registry_sections = getattr(self, "_registry_sections", ()) + (
            ("repositories", self._registry_originals["repository"],
             self._registry_originals["del_repository"]),)

        # ---- snapshot create / get / restore through the lifecycle ------
        def create_snapshot(repo_name, snapshot, body=None):
            repo = svc.get_repository(repo_name)
            if snapshot in repo.list_snapshots():
                raise ResourceAlreadyExistsError(
                    f"snapshot with the same name [{snapshot}] "
                    "already exists")
            body = body or {}
            expr = body.get("indices", "_all")
            if isinstance(expr, list):
                expr = ",".join(expr)
            self._call(lifecycle.client_create, repo_name, snapshot, expr)
            deadline = _time.monotonic() + 60
            while _time.monotonic() < deadline:
                try:
                    m = repo.get_manifest(snapshot)
                    return {"snapshot": {
                        "snapshot": snapshot, "state": m["state"],
                        "indices": sorted(m.get("indices", {})),
                        "shards": m.get("shards", {})}}
                except ResourceNotFoundError:
                    _time.sleep(0.1)
            raise ClusterCallError(
                f"snapshot [{snapshot}] did not complete in time")

        def get_snapshots(repo_name, expr="_all"):
            out = orig_get(repo_name, expr)
            from elasticsearch_tpu.common.patterns import (
                matches_csv_patterns)
            sips = self.cluster.cluster_state.metadata.get(
                SNAPSHOTS_IN_PROGRESS) or {}
            listed = {s["snapshot"] for s in out["snapshots"]}
            for entry in sips.values():
                name = entry["snapshot"]
                if entry["repo"] != repo_name or name in listed:
                    continue
                if not matches_csv_patterns(name, expr):
                    continue
                out["snapshots"].append({
                    "snapshot": name, "state": "IN_PROGRESS",
                    "indices": sorted(entry.get("indices", {})),
                    "start_time_in_millis": entry["start_ms"],
                    "end_time_in_millis": None})
            return out

        def restore_snapshot(repo_name, snapshot, body=None):
            import re as _re
            repo = svc.get_repository(repo_name)
            manifest = repo.get_manifest(snapshot)
            body = body or {}
            indices_expr = body.get("indices", "_all")
            rename_pattern = body.get("rename_pattern")
            rename_replacement = body.get("rename_replacement", "")
            targets = {}
            from elasticsearch_tpu.common.patterns import (
                matches_csv_patterns)
            for index_name, entry in manifest["indices"].items():
                if not matches_csv_patterns(index_name, indices_expr):
                    continue
                target = index_name
                if rename_pattern:
                    target = _re.sub(rename_pattern, rename_replacement,
                                     index_name)
                # existence is validated by the MASTER against its current
                # state — this node's applied state may lag a just-committed
                # delete, and a stale local check would reject a valid
                # restore
                targets[target] = entry
            if not targets:
                raise ResourceNotFoundError(
                    f"no indices in snapshot [{snapshot}] match the restore "
                    f"expression [{indices_expr}]")
            self._call(lifecycle.client_restore, repo_name, snapshot,
                       targets)
            # wait for every restored primary to come up (the shaped
            # response reports shard counts, like the single-node path)
            deadline = _time.monotonic() + 60
            done = False
            prim = []
            while _time.monotonic() < deadline:
                state = self.cluster.cluster_state
                prim = [r for r in state.routing
                        if r.index in targets and r.primary]
                if prim and all(r.state == "STARTED" for r in prim) \
                        and not (state.metadata.get(RESTORE_IN_PROGRESS)
                                 or {}).keys() & targets.keys():
                    done = True
                    break
                _time.sleep(0.1)
            if not done:
                started = sum(1 for r in prim if r.state == "STARTED")
                raise ClusterCallError(
                    f"restore of [{snapshot}] did not complete in time "
                    f"({started}/{len(prim)} primaries started)")
            return {"snapshot": {"snapshot": snapshot,
                                 "indices": sorted(targets),
                                 "shards": {"total": len(prim), "failed": 0,
                                            "successful": len(prim)}}}

        svc.create_snapshot = create_snapshot
        svc.get_snapshots = get_snapshots
        svc.restore_snapshot = restore_snapshot

    def _cat_fanout(self, op: str, params: Optional[dict] = None) -> list:
        out = self._fanout(op, params)
        rows: List[Any] = []
        for nid in sorted(out["results"]):
            rows.extend(out["results"][nid] or [])
        return rows

    def cat_threadpool_rows_api(self, pool_filter=None) -> list:
        return self._cat_fanout("cat_thread_pool",
                                {"pool_filter": pool_filter})

    def cat_nodeattrs_rows_api(self) -> list:
        return self._cat_fanout("cat_nodeattrs")

    def cat_fielddata_rows_api(self, field_filter=None) -> list:
        return self._cat_fanout("cat_fielddata",
                                {"field_filter": field_filter})

    def cat_tasks_rows_api(self) -> list:
        return self._cat_fanout("cat_tasks")

    def _wire_replicated_registries(self) -> None:
        """Ingest pipelines, index templates, and stored scripts live in the
        cluster state (IngestMetadata / IndexTemplateMetaData / ScriptMetaData
        analogs): every mutation publishes through the master, every applied
        state syncs the local registries — a pipeline PUT on one node is
        immediately usable on every node."""
        from elasticsearch_tpu.ingest.service import IngestService
        from elasticsearch_tpu.node_admin import TemplateService
        from elasticsearch_tpu.script.service import ScriptService

        node = self

        def replicate(section, key, value):
            node._call(node.cluster.client_put_registry, section, key, value)

        ingest, templates, scripts = self.ingest, self.templates, self.scripts
        # originals come from the CLASS, never from the instance: the script
        # registry is a process-wide singleton, so instance attributes may
        # hold a previous node's wrappers — rebinding from the class keeps
        # wiring idempotent (latest node wins) with no wrapper chains
        orig_put_pipeline = IngestService.put_pipeline.__get__(ingest)
        orig_del_pipeline = IngestService.delete_pipeline.__get__(ingest)
        orig_put_template = TemplateService.put.__get__(templates)
        orig_del_template = TemplateService.delete.__get__(templates)
        orig_put_script = ScriptService.put_stored.__get__(scripts)
        orig_del_script = ScriptService.delete_stored.__get__(scripts)

        record = self._record_registry

        # order: VALIDATE locally, REPLICATE (raises on failure — nothing
        # applied anywhere), then apply locally and record ownership; a
        # failed publish can therefore never leave this node diverged
        def put_pipeline(pid, definition):
            from elasticsearch_tpu.ingest.service import Pipeline
            Pipeline(pid, definition)  # validation only
            replicate("pipelines", pid, definition)
            orig_put_pipeline(pid, definition)
            record("pipelines", pid, definition)

        def delete_pipeline(pid):
            self.ingest.get_pipeline(pid)  # 404 before any cluster traffic
            replicate("pipelines", pid, None)
            orig_del_pipeline(pid)
            record("pipelines", pid, None)

        def put_template(name, body, composable=False):
            if not body.get("index_patterns"):
                raise IllegalArgumentError(
                    "index template must define index_patterns")
            key = f"{'c' if composable else 'l'}:{name}"
            replicate("templates", key, body)
            orig_put_template(name, body, composable=composable)
            record("templates", key, body)

        def delete_template(name, composable=False):
            self.templates.get(name, composable=composable)
            key = f"{'c' if composable else 'l'}:{name}"
            replicate("templates", key, None)
            orig_del_template(name, composable=composable)
            record("templates", key, None)

        def put_stored(sid, body):
            from elasticsearch_tpu.common.errors import ParsingError
            spec = body.get("script")
            if not isinstance(spec, dict) or "source" not in spec:
                raise ParsingError("stored script must define [script.source]")
            replicate("scripts", sid, body)
            orig_put_script(sid, body)
            record("scripts", sid, body)

        def delete_stored(sid):
            self.scripts.get_stored(sid)
            replicate("scripts", sid, None)
            orig_del_script(sid)
            record("scripts", sid, None)

        ingest.put_pipeline = put_pipeline
        ingest.delete_pipeline = delete_pipeline
        templates.put = put_template
        templates.delete = delete_template
        scripts.put_stored = put_stored
        scripts.delete_stored = delete_stored
        self._applied_registries = {}
        self._registry_originals = {
            "pipeline": orig_put_pipeline, "template": orig_put_template,
            "script": orig_put_script, "del_pipeline": orig_del_pipeline,
            "del_template": orig_del_template, "del_script": orig_del_script}
        self.cluster.state_listeners.append(self._sync_registries)

    def _record_registry(self, section, key, value) -> None:
        """Track what this node applied locally (the sync's diff base)."""
        regs = self._applied_registries.setdefault(section, {})
        if value is None:
            regs.pop(key, None)
        else:
            regs[key] = value

    def _sync_registries(self, state) -> None:
        """Reconcile local registries to the cluster-state truth: apply
        adds AND updates (compared against what this node last applied),
        remove entries gone from the state."""
        from elasticsearch_tpu.cluster.cluster_node import REGISTRIES_KEY
        regs = state.metadata.get(REGISTRIES_KEY) or {}
        applied = getattr(self, "_applied_registries", None)
        if applied is None:
            applied = self._applied_registries = {}

        def put_template(key, body):
            self._registry_originals["template"](
                key[2:], body, composable=key.startswith("c:"))

        def del_template(key):
            self._registry_originals["del_template"](
                key[2:], composable=key.startswith("c:"))

        sections = (
            ("pipelines", self._registry_originals["pipeline"],
             self._registry_originals["del_pipeline"]),
            ("templates", put_template, del_template),
            ("scripts", self._registry_originals["script"],
             self._registry_originals["del_script"]),
        ) + tuple(getattr(self, "_registry_sections", ()))
        for section, put_fn, del_fn in sections:
            want = regs.get(section) or {}
            have = applied.setdefault(section, {})
            for key, value in want.items():
                if have.get(key) != value:  # new OR changed definition
                    try:
                        put_fn(key, value)
                        have[key] = value
                    except Exception:
                        pass  # a bad remote definition must not kill apply
            for key in list(have):
                if key not in want:
                    try:
                        del_fn(key)
                    except Exception:
                        pass
                    have.pop(key, None)

    # ------------------------------------------------------------- plumbing
    def _call(self, fn, *args, timeout: float = 30.0, **kwargs) -> Any:
        """Run a callback-style cluster client method from a worker thread:
        schedule it on the node's event loop, block for the result."""
        done = threading.Event()
        box: Dict[str, Any] = {}

        def on_done(result):
            box["r"] = result
            done.set()

        def on_failure(err):
            box["e"] = err
            done.set()

        def invoke():
            try:
                kw = dict(kwargs)
                if "on_failure" in fn.__code__.co_varnames:
                    kw["on_failure"] = on_failure
                fn(*args, on_done=on_done, **kw)
            except Exception as e:  # defensive: surface instead of hanging
                on_failure(e)

        self.loop.call_soon_threadsafe(invoke)
        if not done.wait(timeout):
            raise ClusterCallError("timed out waiting for the cluster")
        if "e" in box:
            err = box["e"]
            raise err if isinstance(err, SearchEngineError) \
                else ClusterCallError(str(err))
        result = box["r"]
        if isinstance(result, dict) and result.get("error") is not None:
            err = result["error"]
            reason = err.get("reason", str(err)) if isinstance(err, dict) else str(err)
            if isinstance(err, dict) and err.get("type") == "index_not_found_exception":
                raise IndexNotFoundError(reason)
            if isinstance(err, dict) \
                    and err.get("type") == "search_context_missing_exception":
                from elasticsearch_tpu.common.errors import (
                    SearchContextMissingError)
                raise SearchContextMissingError(reason)
            raise SearchEngineError(reason)
        return result

    def _write_with_retry(self, index: str, op: dict,
                          timeout_s: float = 30.0,
                          retry_not_found: bool = False) -> dict:
        """Writes wait for an active primary (TransportReplicationAction's
        wait_for_active_shards / cluster-state observer retry): right after
        auto-create or failover the routing may not show a started primary
        yet. IndexNotFound retries ONLY when the caller just auto-created
        (this node's applier may lag the master's commit); a genuinely
        missing index stays a fast 404."""
        import time as _time
        deadline = _time.monotonic() + timeout_s
        nf_deadline = _time.monotonic() + min(timeout_s, 10.0)
        while True:
            try:
                return self._call(self.cluster.client_write, index, op)
            except IndexNotFoundError:
                if retry_not_found and _time.monotonic() < nf_deadline:
                    _time.sleep(0.2)
                    continue
                raise
            except SearchEngineError as e:
                if "no active primary" in str(e) \
                        and _time.monotonic() < deadline:
                    _time.sleep(0.2)
                    continue
                raise

    def _meta(self, index: str) -> dict:
        meta = self.cluster.cluster_state.metadata.get(index)
        if meta is None:
            raise IndexNotFoundError(index)
        return meta

    # ------------------------------------------------------------ documents
    def index_doc(self, index: str, doc_id: Optional[str], body: dict,
                  op_type: str = "index", refresh: Optional[str] = None,
                  routing: Optional[str] = None,
                  if_seq_no: Optional[int] = None,
                  if_primary_term: Optional[int] = None,
                  version: Optional[int] = None,
                  version_type: str = "internal",
                  pipeline: Optional[str] = None) -> dict:
        import uuid as _uuid
        auto_created = False
        state = self.cluster.cluster_state  # ONE snapshot for this request
        if index not in state.metadata:
            # auto-create FIRST (with matching templates), so a template-
            # provided index.default_pipeline applies to the first doc too
            resolved = self.templates.resolve(index)
            self._call(self.cluster.client_create_index, index,
                       resolved["settings"] or None,
                       resolved["mappings"]
                       if resolved["mappings"].get("properties") else None)
            auto_created = True
            if pipeline is None:
                pipeline = (resolved["settings"] or {}).get(
                    "index.default_pipeline")
        elif pipeline is None:
            # index.default_pipeline lives in the cluster metadata here
            meta = state.metadata.get(index)
            if meta is not None:
                pipeline = (meta.get("settings") or {}).get(
                    "index.default_pipeline")
        if pipeline and pipeline != "_none":
            body = self.ingest.execute(pipeline, index, doc_id, body)
            if body is None:
                return {"_index": index, "_id": doc_id, "result": "noop",
                        "_version": -1, "_seq_no": -1, "_primary_term": 0,
                        "_shards": {"total": 0, "successful": 0, "failed": 0}}
        if doc_id is None:
            doc_id = _uuid.uuid4().hex[:20]
            op_type = "create"
        op = {"type": "index", "id": str(doc_id), "source": body,
              "op_type": op_type, "routing": routing,
              "if_seq_no": if_seq_no, "if_primary_term": if_primary_term,
              "version": version, "version_type": version_type}
        resp = self._write_with_retry(index, op,
                                      retry_not_found=auto_created)
        out = {"_index": index, "_id": resp.get("_id", doc_id),
               "_version": resp.get("_version"),
               "result": resp.get("result", "created"),
               "_seq_no": resp.get("_seq_no"),
               "_primary_term": resp.get("_primary_term"),
               "_shards": {"total": 1, "successful": 1, "failed": 0}}
        self._maybe_cluster_refresh(index, refresh)
        if refresh in ("true", "", True):
            out["forced_refresh"] = True
        return out

    def delete_doc(self, index: str, doc_id: str, refresh: Optional[str] = None,
                   routing: Optional[str] = None,
                   if_seq_no: Optional[int] = None,
                   if_primary_term: Optional[int] = None,
                   version: Optional[int] = None,
                   version_type: str = "internal") -> dict:
        self._meta(index)
        op = {"type": "delete", "id": str(doc_id), "routing": routing,
              "if_seq_no": if_seq_no, "if_primary_term": if_primary_term,
              "version": version, "version_type": version_type}
        resp = self._write_with_retry(index, op)
        self._maybe_cluster_refresh(index, refresh)
        out = {"_index": index, "_id": doc_id,
               "_version": resp.get("_version"), "result": "deleted",
               "_seq_no": resp.get("_seq_no"),
               "_primary_term": resp.get("_primary_term"),
               "_shards": {"total": 1, "successful": 1, "failed": 0}}
        if refresh in ("true", "", True):
            out["forced_refresh"] = True
        return out

    def get_doc(self, index: str, doc_id: str, routing: Optional[str] = None,
                source_includes=None, realtime: bool = True) -> dict:
        self._meta(index)
        return self._call(self.cluster.client_get, index, str(doc_id),
                          routing=routing)

    def update_doc(self, index: str, doc_id: str, body: dict,
                   refresh: Optional[str] = None,
                   routing: Optional[str] = None,
                   if_seq_no: Optional[int] = None,
                   if_primary_term: Optional[int] = None,
                   source_filter=None) -> dict:
        import copy as _copy

        from elasticsearch_tpu.common.errors import DocumentMissingError
        from elasticsearch_tpu.node import _apply_update_script, _deep_merge
        self._validate_update_body(body)
        if source_filter is None and body and "_source" in body:
            source_filter = body["_source"]

        def _with_get(out, src):
            if source_filter is not None and source_filter is not False:
                doc = {"_source": _copy.deepcopy(src)}
                self._apply_mget_projection(doc, {}, None, index,
                                            source_filter)
                out["get"] = {"_source": doc.get("_source", {}),
                              "found": True}
            return out

        existing = self.get_doc(index, doc_id, routing=routing)
        if not existing.get("found"):
            if "upsert" in body:
                return _with_get(
                    self.index_doc(index, doc_id, body["upsert"],
                                   refresh=refresh, routing=routing),
                    body["upsert"])
            if body.get("doc_as_upsert") and "doc" in body:
                return _with_get(
                    self.index_doc(index, doc_id, body["doc"],
                                   refresh=refresh, routing=routing),
                    body["doc"])
            raise DocumentMissingError(f"[{doc_id}]: document missing")
        if if_seq_no is not None and existing["_seq_no"] != if_seq_no or \
                if_primary_term is not None \
                and existing.get("_primary_term") != if_primary_term:
            from elasticsearch_tpu.common.errors import VersionConflictError
            raise VersionConflictError(
                f"[{doc_id}]: version conflict, required seqNo "
                f"[{if_seq_no}], primary term [{if_primary_term}]")
        source = _copy.deepcopy(existing["_source"])
        if "doc" in body:
            _deep_merge(source, body["doc"])
            if body.get("detect_noop", True) \
                    and source == existing["_source"]:
                return _with_get({
                    "_index": index, "_id": doc_id,
                    "_version": existing["_version"], "result": "noop",
                    "_seq_no": existing["_seq_no"],
                    "_primary_term": existing.get("_primary_term", 1),
                    "_shards": {"total": 0, "successful": 0,
                                "failed": 0}}, source)
        elif "script" in body:
            verdict: Dict[str, Any] = {}
            source = _apply_update_script(source, body["script"],
                                          ctx_extra=verdict)
            op = verdict.get("op", "index")
            if op == "none":
                return _with_get({
                    "_index": index, "_id": doc_id,
                    "_version": existing["_version"], "result": "noop",
                    "_seq_no": existing["_seq_no"],
                    "_primary_term": existing.get("_primary_term", 1),
                    "_shards": {"total": 0, "successful": 0,
                                "failed": 0}}, source)
            if op == "delete":
                out = self.delete_doc(index, doc_id, refresh=refresh,
                                      routing=routing)
                out["result"] = "deleted"
                return out
        else:
            raise IllegalArgumentError("update requires [doc] or [script]")
        out = self.index_doc(index, doc_id, source, refresh=refresh,
                             routing=routing,
                             if_seq_no=existing["_seq_no"],
                             if_primary_term=existing.get("_primary_term"))
        out["result"] = "updated"
        return _with_get(out, source)

    # --------------------------------------------------------------- search
    def search(self, index_expr: Optional[str], body: Optional[dict],
               ignore_throttled: bool = True,
               ignore_unavailable: bool = False,
               allow_no_indices: bool = True,
               expand_wildcards: Optional[str] = None) -> dict:
        if index_expr and ":" in index_expr:
            # cross-cluster search from a clustered coordinator: split
            # `alias:index` parts, one wire request per remote cluster,
            # local part through the distributed scatter below
            # (TransportSearchAction + SearchResponseMerger)
            from elasticsearch_tpu.xpack.ccr import merge_ccs_responses
            local_expr, remote_exprs = self.remotes.split_indices(index_expr)
            remote_resps, clusters = self.remotes.search_remotes(
                remote_exprs, dict(body or {}))
            local_resp = self.search(
                local_expr, body, ignore_throttled=ignore_throttled,
                ignore_unavailable=ignore_unavailable,
                allow_no_indices=allow_no_indices,
                expand_wildcards=expand_wildcards) if local_expr else None
            return merge_ccs_responses(local_resp, remote_resps, body,
                                       clusters)
        if not allow_no_indices and index_expr and "*" in index_expr:
            # IndicesOptions.allowNoIndices=false: an unmatched wildcard is
            # an error at the coordinator, before the scatter
            if not self.cluster.resolve_indices(index_expr):
                raise IndexNotFoundError(index_expr)
        if expand_wildcards and {"closed", "all"} & set(
                str(expand_wildcards).split(",")):
            # closed indices surface through the LOCAL view (cluster
            # metadata doesn't carry index state; closing is node-local)
            for svc in self.indices.resolve(index_expr, expand_closed=True):
                self.indices.check_open(svc)
        if ignore_unavailable and index_expr:
            # lenientExpandOpen: drop concrete names absent from cluster
            # metadata before the scatter
            meta = self.cluster.cluster_state.metadata
            kept = [p.strip() for p in index_expr.split(",")
                    if "*" in p or p.strip() in ("_all", "")
                    or p.strip() in meta]
            if not kept:
                return _empty_search_response()
            index_expr = ",".join(kept)
        t0 = _time.perf_counter()
        # hand the REST thread's telemetry context (trace + task) to the
        # coordinator explicitly: client_search runs on the event loop,
        # where thread-locals cannot follow the request
        resp = self._call(self.cluster.client_search, index_expr,
                          dict(body or {}),
                          telemetry_ctx=_teletrace.capture())
        self.counters["search"] += 1
        took_s = _time.perf_counter() - t0
        _telemetry.stage_done("search.took", t0 * 1e9,
                              (t0 + took_s) * 1e9)
        # the coordinator ships the phase summary on a private key so
        # the slow log gets it on UNPROFILED requests too; pop it before
        # the response reaches the client
        phases = resp.pop("_took_phases", None) \
            if isinstance(resp, dict) else None
        # coordinator slow log: the fan-out path must breach per-index
        # thresholds exactly like the single-node query path; entries
        # carry the fan-out phase summary instead of shard-local nanos
        if isinstance(resp, dict) and "error" not in resp:
            meta = self.cluster.cluster_state.metadata
            # cheap gate: the common case configures no slow-log
            # thresholds anywhere — skip the second index resolution
            # entirely then (the coordinator already resolved once)
            if any(isinstance(m, dict) and any(
                    ".slowlog.threshold." in key
                    for key in (m.get("settings") or {}))
                   for m in meta.values()):
                _task = _teletrace.current_task()
                try:
                    names = self.cluster.resolve_indices(index_expr)
                except Exception:
                    names = []
                for name in names:
                    settings = (meta.get(name) or {}).get("settings") or {}
                    self.search_slow_log.maybe_log(
                        settings, name, took_s,
                        source=(body or {}).get("query"),
                        opaque_id=getattr(_task, "opaque_id", None),
                        trace=_teletrace.current_trace(),
                        phases=phases)
        return resp

    def count(self, index_expr: Optional[str], body: Optional[dict]) -> dict:
        body = dict(body or {})
        body["size"] = 0
        body.pop("sort", None)
        body["track_total_hits"] = True
        resp = self.search(index_expr, body)
        return {"count": resp["hits"]["total"]["value"],
                "_shards": resp.get("_shards",
                                    {"total": 1, "successful": 1,
                                     "skipped": 0, "failed": 0})}

    # ----------------------------------------------------------------- scroll

    def search_scroll_start(self, index_expr: Optional[str],
                            body: Optional[dict], keep_alive: str = "1m",
                            ignore_throttled: bool = True) -> dict:
        """Cluster scroll with REAL per-shard pinned reader contexts
        (reference: SearchService scroll contexts +
        SearchScrollAsyncAction): each shard holds its own sorted
        snapshot under a keepalive; the coordinator keeps per-shard
        cursors and merge-sorts windows per page, so a scroll over
        millions of docs never materializes more than a page per shard."""
        body = dict(body or {})
        if body.get("collapse") is not None:
            raise IllegalArgumentError(
                "cannot use `collapse` in a scroll context")
        return self._call(self.cluster.client_scroll_start, index_expr,
                          body, _parse_keepalive_s(keep_alive))

    def search_scroll_next(self, scroll_id: str,
                           keep_alive: Optional[str] = None) -> dict:
        return self._call(self.cluster.client_scroll_next, scroll_id,
                          _parse_keepalive_s(keep_alive)
                          if keep_alive else None)

    def clear_scroll(self, scroll_id: str) -> dict:
        return self._call(self.cluster.client_scroll_clear, scroll_id)

    def clear_all_scrolls(self) -> dict:
        return self._call(self.cluster.client_scroll_clear_all)

    def pending_cluster_tasks(self) -> list:
        return self.cluster.coordinator.pending_tasks()

    # ------------------------------------------------------- index admin
    def _maybe_cluster_refresh(self, index: str, refresh) -> None:
        if refresh in ("true", "wait_for", True, ""):
            self._call(self.cluster.client_refresh, index)

    def _refresh_indices(self, names) -> None:
        """Bulk epilogue refresh: broadcast through the cluster (the local
        IndicesService holds no cluster shards)."""
        for name in names:
            self._call(self.cluster.client_refresh, name)

    def create_index_api(self, name: str, settings: Optional[dict] = None,
                         mappings: Optional[dict] = None) -> dict:
        return self._call(self.cluster.client_create_index, name,
                          settings, mappings)

    def delete_index_api(self, name: str) -> dict:
        self._meta(name)
        return self._call(self.cluster.client_delete_index, name)

    def cluster_index_names(self) -> List[str]:
        return sorted(self.cluster.cluster_state.metadata)
