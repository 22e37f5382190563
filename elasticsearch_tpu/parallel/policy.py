"""Host-side mesh routing policy: single-device vs SPMD per dispatch.

The reference routes every search through a coordinator that fans out to
however many shards the index was created with — shard count is a static
index property. Here the analogous decision is DYNAMIC and per dispatch:
a corpus small enough that one chip's matmul beats the all-gather merge
should stay on one device, a corpus at HBM scale must spread. This module
owns that decision for every serving leg (exact kNN, IVF, BM25), plus the
process-wide serving mesh the sharded kernels execute on, and the
counters `_nodes/stats indices.mesh` / `profile.mesh` report.

Settings (read once at node boot, `node.py` calls `configure`):

  search.mesh.enabled      true | false | unset (auto: mesh when >1
                           device is visible)
  search.mesh.num_shards   mesh shard-axis size (default: all visible
                           devices / dp)
  search.mesh.dp           data-parallel axis size (default 1; floored
                           to a power of two). dp > 1 replicates the
                           sharded corpus across dp device groups so
                           independent query batches execute
                           CONCURRENTLY on disjoint groups — the
                           throughput axis, where more shards is the
                           latency axis. Replication costs dp× HBM.
  search.mesh.min_rows     corpora below this many rows stay
                           single-device (the all-gather merge + per-leg
                           SPMD overhead only pays for itself once the
                           local matmul dominates; default 32768)
  search.mesh.hbm_budget_bytes
                           device-memory budget for mesh-resident corpus
                           copies. Replication costs dp× device bytes,
                           so with dp > 1 a corpus is mesh-eligible only
                           while dp × its estimated device footprint
                           (the columnar store's per-field accounting,
                           `vectors/store.device_corpus_nbytes`) fits
                           the budget — before this gate only
                           `min_rows` guarded eligibility, and a large
                           corpus under dp=4 quadrupled HBM silently.
                           Default unset: no budget (real budgets come
                           from deployment sizing; CPU-sim hosts have
                           no HBM to guard).

With dp > 1 the router additionally chooses a dp-vs-shard SPLIT per
dispatch: a batch under queue pressure lands on one dp group (round-
robin — queued batches overlap on the other groups), an idle batch on a
large corpus spreads over the full mesh (all devices cooperate, queries
split along dp). The load signal is the continuous batcher's live
scheduler state (queued + in-flight dispatches) × corpus size; every
split decision is counted with its reason in `stats()["router"]["dp"]`.

The policy is process-wide like `ops/dispatch.DISPATCH` — one physical
mesh serves every index on the node, so per-index state would only
duplicate the counters.
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

logger = logging.getLogger(__name__)

# below this many corpus rows the single-device program wins: the sharded
# program's fixed costs (S-way dispatch, [S, Q, k] all-gather, merge) are
# corpus-size independent, while the local matmul saving scales with rows
DEFAULT_MIN_ROWS = 32_768

_lock = threading.Lock()
_cfg = {"enabled": None, "num_shards": None, "min_rows": DEFAULT_MIN_ROWS,
        "dp": None, "hbm_budget_bytes": None}
_mesh = None          # cached jax Mesh (built lazily)
_mesh_built = False   # latch: None is a valid cache value (no mesh)
# dp-group submeshes per FULL mesh, keyed by mesh equality: the dispatch
# cache keys executables on mesh identity, so the router and the warmup
# grid must hand out ONE set of group objects per serving mesh
_groups: dict = {}
# secondary meshes for consumers whose shard count is fixed by the index
# (the node.py multi-shard adapter), built through the same path so the
# dp setting applies everywhere or nowhere — keyed by shard count
_shard_meshes: dict = {}
_rr = 0               # round-robin dp-group cursor

_counters = {
    "decisions_mesh": 0,
    "decisions_single_device": 0,
    "searches": {"knn": 0, "ivf": 0, "bm25": 0},
    "reasons": {},            # reason -> count (single-device routes)
    # dp-vs-shard split of mesh-accepted dispatches (dp > 1 only):
    # "shard" = full-mesh program, "dp" = one dp-group submesh
    "dp_routes": {"shard": 0, "dp": 0},
    "dp_reasons": {},         # split reason -> count
    "dp_group_dispatches": {},  # group index -> dispatches routed to it
    # dp-aware HBM budget gate (eligible()): corpora whose dp-replicated
    # device footprint exceeded search.mesh.hbm_budget_bytes
    "hbm_rejections": 0,
    "hbm_last_rejected_bytes": 0,
    "hbm_accepted_bytes": 0,    # high-water accepted dp× footprint
    # per-leg counts of sharded dispatches and their analytic all-gather
    # payload (a profile reads one batch's share as a difference). Their
    # TIMES are telemetry's: the stages `dispatch.*` and `mesh.guard_wait`
    "legs": {},               # leg -> {collective_bytes, dispatches}
}


_UNSET = object()

# bumped on every configure()/full reset(): the request-cache "live
# settings epoch" component (search/caches.request_cache_key) — a
# serving-policy change must MISS the read-path caches, not serve a
# result (and its route diagnostics) computed under the old config
_cfg_epoch = 0


def config_epoch() -> int:
    return _cfg_epoch


def configure(enabled=_UNSET, num_shards=_UNSET, min_rows=_UNSET,
              dp=_UNSET, hbm_budget_bytes=_UNSET) -> None:
    """Install `search.mesh.*` settings. PARTIAL update: only the
    keyword arguments the caller passes change — a node that sets one
    key must not clobber the others an earlier in-process node
    configured (same rule as the dispatcher's warmup policy). Passing
    None explicitly resets that key to auto/default. Drops the cached
    mesh (and its dp groups / secondary shard meshes) so the next
    dispatch rebuilds against the new config."""
    global _mesh, _mesh_built, _cfg_epoch
    with _lock:
        _cfg_epoch += 1
        if enabled is not _UNSET:
            _cfg["enabled"] = enabled
        if num_shards is not _UNSET:
            _cfg["num_shards"] = (int(num_shards)
                                  if num_shards is not None else None)
        if min_rows is not _UNSET:
            _cfg["min_rows"] = (int(min_rows) if min_rows is not None
                                else DEFAULT_MIN_ROWS)
        if dp is not _UNSET:
            _cfg["dp"] = int(dp) if dp is not None else None
        if hbm_budget_bytes is not _UNSET:
            _cfg["hbm_budget_bytes"] = (int(hbm_budget_bytes)
                                        if hbm_budget_bytes is not None
                                        else None)
        _mesh, _mesh_built = None, False
        _groups.clear()
        _shard_meshes.clear()


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def _effective_dp(n_devices: int) -> int:
    """Configured dp clamped to the device budget and floored to a power
    of two (query buckets are pow-2, so only a pow-2 dp divides every
    full-mesh batch)."""
    dp = _cfg["dp"] or 1
    dp = max(1, min(int(dp), max(n_devices, 1)))
    floored = _pow2_floor(dp)
    if floored != dp:
        logger.warning("search.mesh.dp=%d floored to %d (power of two "
                       "required for bucket divisibility)", dp, floored)
    return floored


def min_rows() -> int:
    return _cfg["min_rows"]


def explicitly_enabled() -> bool:
    """`search.mesh.enabled: true`, as against unset (auto): the operator
    sized the deployment for the mesh, so a field it answers keeps no
    whole copy on one device (`vectors/store.py` `sync`)."""
    return _cfg["enabled"] is True


def _mesh_build_failed(what: str, exc: Exception) -> None:
    """A mesh that cannot be built: with `search.mesh.enabled: true` the
    operator asked for it, so serving on one device instead would hide
    the fault — raise. In auto mode one chip is a normal deployment: log
    why and stay single-device (the caller latches that until restart or
    reconfigure; stats alone show `available: false`, not why)."""
    if _cfg["enabled"] is True:
        raise RuntimeError(
            f"search.mesh.enabled is set but {what} could not be "
            f"built: {exc}") from exc
    logger.warning("mesh serving off: %s could not be built", what,
                   exc_info=exc)


def serving_mesh():
    """The process-wide (dp=R, shard=S) serving mesh, or None when mesh
    execution is off (disabled, or fewer than 2 usable devices). R comes
    from `search.mesh.dp` (default 1); S from `search.mesh.num_shards`
    (default: remaining devices per dp group)."""
    global _mesh, _mesh_built
    with _lock:
        if _mesh_built:
            return _mesh
    mesh = None
    if _cfg["enabled"] is not False:
        try:
            import jax

            from elasticsearch_tpu.parallel import mesh as mesh_lib
            n_dev = len(jax.devices())
            dp = _effective_dp(n_dev)
            n = _cfg["num_shards"] if _cfg["num_shards"] else n_dev // dp
            n = max(1, min(n, n_dev // dp))
            # dp groups of a single shard are still a mesh (pure
            # replication — the throughput-only shape); a 1x1 "mesh" is
            # just the single device and stays off
            if dp * n >= 2:
                mesh = mesh_lib.make_mesh(num_shards=n, dp=dp)
            elif _cfg["enabled"] is True:
                raise RuntimeError(
                    f"{n_dev} device(s) cannot form a mesh of 2 or more")
        except Exception as exc:
            _mesh_build_failed("the serving mesh", exc)
    with _lock:
        if _mesh_built:
            # another thread won the build race: keep ITS object — the
            # identity-compared caches (store append path, lexical
            # mesh-CSR, sharded IVF pytree) all key on the cached mesh,
            # and caching a second equal-but-distinct Mesh would force
            # each of them through one redundant corpus re-upload
            return _mesh
        _mesh, _mesh_built = mesh, True
        return _mesh


def num_shards() -> int:
    mesh = serving_mesh()
    if mesh is None:
        return 0
    from elasticsearch_tpu.parallel import mesh as mesh_lib
    return mesh.shape[mesh_lib.SHARD_AXIS]


def dp_size() -> int:
    """dp-axis size of the serving mesh (0 = no mesh)."""
    mesh = serving_mesh()
    if mesh is None:
        return 0
    from elasticsearch_tpu.parallel import mesh as mesh_lib
    return mesh_lib.dp_size(mesh)


def dp_groups(mesh=None):
    """The dp-group submeshes of `mesh` (default: the serving mesh) —
    ONE canonical tuple per mesh, because the dispatch cache keys
    executables on mesh identity: the router's group pick and the warmup
    grid must name the same objects or warmed programs would never be
    hit. Keyed by mesh equality, so an equal-but-distinct mesh resolves
    to the same group set."""
    if mesh is None:
        mesh = serving_mesh()
    if mesh is None:
        return ()
    from elasticsearch_tpu.parallel import mesh as mesh_lib
    with _lock:
        groups = _groups.get(mesh)
        if groups is None:
            groups = (mesh_lib.dp_submeshes(mesh)
                      if mesh_lib.dp_size(mesh) > 1 else (mesh,))
            _groups[mesh] = groups
        return groups


def mesh_for_shards(n_shards: int):
    """One mesh build path for EVERY consumer whose shard count is fixed
    externally (the node multi-shard adapter maps one engine shard per
    mesh column) — previously a second hand-rolled `make_mesh(dp=1)`
    beside the serving mesh, which is exactly how a dp setting
    half-applies. Returns the serving mesh when its shard axis already
    matches, else builds (and caches per shard count) a mesh with the
    configured dp clamped to the device budget; None when `n_shards`
    devices aren't available."""
    n_shards = int(n_shards)
    mesh = serving_mesh()
    from elasticsearch_tpu.parallel import mesh as mesh_lib
    if mesh is not None and mesh_lib.shard_size(mesh) == n_shards:
        return mesh
    with _lock:
        if n_shards in _shard_meshes:
            return _shard_meshes[n_shards]
    built = None
    try:
        import jax
        n_dev = len(jax.devices())
        if n_shards >= 1 and n_shards <= n_dev:
            dp = min(_effective_dp(n_dev), _pow2_floor(n_dev // n_shards))
            built = mesh_lib.make_mesh(num_shards=n_shards, dp=max(dp, 1))
    except Exception as exc:
        _mesh_build_failed(f"a {n_shards}-shard mesh", exc)
    with _lock:
        return _shard_meshes.setdefault(n_shards, built)


def eligible(n_rows: int, device_bytes: Optional[int] = None) -> bool:
    """Build-time check (no routing decision counted): is this corpus
    one the router could ever send to the mesh? Gates the sharded
    upload at refresh so small indexes never pay the second resident
    copy.

    `device_bytes` is the field's estimated single-copy device
    footprint (the columnar store's per-field accounting). Replication
    multiplies it by the dp-axis size — each dp group holds the whole
    sharded corpus — so with a `search.mesh.hbm_budget_bytes` budget
    configured, a corpus whose dp× footprint exceeds the budget stays
    single-device (counted under `stats()["hbm"]`)."""
    if (n_rows < _cfg["min_rows"] or _cfg["enabled"] is False):
        return False
    mesh = serving_mesh()
    if mesh is None:
        return False
    return hbm_allows(device_bytes, mesh)


def hbm_allows(device_bytes: Optional[int], mesh=None) -> bool:
    """The budget-only half of `eligible()`, for consumers whose mesh
    participation is fixed externally (the node.py multi-shard adapter
    maps one engine shard per mesh column regardless of `min_rows`):
    with `search.mesh.hbm_budget_bytes` configured, a dp-replicated
    footprint past the budget is rejected and counted."""
    budget = _cfg["hbm_budget_bytes"]
    if budget is None or device_bytes is None:
        return True
    if mesh is None:
        mesh = serving_mesh()
    if mesh is None:
        return True
    from elasticsearch_tpu.parallel import mesh as mesh_lib
    dp = mesh_lib.dp_size(mesh)
    need = int(device_bytes) * max(dp, 1)
    if need > budget:
        with _lock:
            _counters["hbm_rejections"] += 1
            _counters["hbm_last_rejected_bytes"] = need
        return False
    with _lock:
        _counters["hbm_accepted_bytes"] = max(
            _counters["hbm_accepted_bytes"], need)
    return True


def _choose_split(batch, n_rows: int, queue_depth: int, dp: int,
                  n_shards: int):
    """dp-vs-shard split for one mesh-accepted dispatch.

    "dp" sends the batch to ONE dp group (S shards, 1/dp of the
    devices), leaving the other groups free — concurrent batches overlap
    on disjoint device groups, the throughput shape. "shard" runs the
    full-mesh program (queries split along dp, corpus along shard) — all
    devices cooperate on this one batch, the latency shape. The decision
    is the unified dispatch cost model's (serving/router.py): queue wait
    vs device-leg estimate per route, calibrated so the historical
    min_rows*dp break-even (and the five pinned reason strings) hold."""
    from elasticsearch_tpu.serving import router as dispatch_router
    return dispatch_router.choose_split(
        batch, n_rows, int(queue_depth), dp, n_shards, _cfg["min_rows"])


def decide(leg: str, n_rows: int, has_mesh_state: bool = True,
           batch=None, queue_depth: int = 0):
    """Route one serving dispatch: returns the mesh to execute on —
    the full serving mesh, or (dp > 1) one dp-group submesh — or None
    for single-device. Counts the decision (the router half of
    `_nodes/stats indices.mesh`).

    `batch` is the dispatch's PADDED query bucket (full-mesh programs
    split it along dp, so it must divide); `queue_depth` the caller's
    live load signal — queued + in-flight dispatches beyond this one
    (the continuous batcher's scheduler state)."""
    global _rr
    from elasticsearch_tpu.parallel import mesh as mesh_lib
    mesh = serving_mesh()
    reason = None
    if mesh is None:
        reason = "no_mesh"
    elif not has_mesh_state:
        reason = "no_sharded_corpus"
    elif n_rows < _cfg["min_rows"]:
        reason = "corpus_below_min_rows"
    split = group_idx = None
    if reason is None:
        dp = mesh_lib.dp_size(mesh)
        if dp > 1:
            split, split_reason = _choose_split(
                batch, n_rows, int(queue_depth), dp,
                mesh_lib.shard_size(mesh))
    with _lock:
        _counters["searches"][leg] = _counters["searches"].get(leg, 0) + 1
        if reason is not None:
            _counters["decisions_single_device"] += 1
            _counters["reasons"][reason] = \
                _counters["reasons"].get(reason, 0) + 1
            return None
        _counters["decisions_mesh"] += 1
        if split is not None:
            _counters["dp_routes"][split] += 1
            _counters["dp_reasons"][split_reason] = \
                _counters["dp_reasons"].get(split_reason, 0) + 1
            if split == "dp":
                group_idx = _rr
                _rr = (_rr + 1) % mesh_lib.dp_size(mesh)
                gd = _counters["dp_group_dispatches"]
                gd[group_idx] = gd.get(group_idx, 0) + 1
    if group_idx is not None:
        return dp_groups(mesh)[group_idx]
    return mesh


def reclassify_single(reason: str) -> None:
    """A leg accepted a mesh route but discovered mid-leg that the
    sharded program can't hold its result contract (e.g. a BM25 ranked
    window deeper than one shard's slot range): move the already-counted
    mesh decision over to single-device so the router stats reflect
    where the dispatch actually ran."""
    with _lock:
        if _counters["decisions_mesh"] > 0:
            _counters["decisions_mesh"] -= 1
        _counters["decisions_single_device"] += 1
        _counters["reasons"][reason] = \
            _counters["reasons"].get(reason, 0) + 1


def record_leg(leg: str, collective_bytes: int) -> None:
    """Count one sharded dispatch and its analytic all-gather payload
    (S * Q * k * (score + id bytes)): per leg here, where a profile
    takes one batch's share, and in all under the telemetry counters
    `mesh.dispatches` and `mesh.collective_bytes`."""
    from elasticsearch_tpu.telemetry import metrics
    metrics.counter("mesh.dispatches").inc()
    metrics.counter("mesh.collective_bytes").inc(int(collective_bytes))
    with _lock:
        entry = _counters["legs"].setdefault(
            leg, {"collective_bytes": 0, "dispatches": 0})
        entry["collective_bytes"] += int(collective_bytes)
        entry["dispatches"] += 1


def gather_bytes(n_shards: int, n_queries: int, k: int,
                 bytes_per_slot: int = 8) -> int:
    """Analytic all-gather payload of one [S, Q, k] candidate merge
    (f32 score + int32 id = 8 bytes/slot by default)."""
    return int(n_shards) * int(n_queries) * int(k) * int(bytes_per_slot)


def stats() -> dict:
    """`_nodes/stats indices.mesh` section."""
    from elasticsearch_tpu.parallel import mesh as mesh_lib
    from elasticsearch_tpu.serving import router as dispatch_router
    mesh = serving_mesh()
    # shard-axis size, not devices.size: the two differ once dp > 1
    n_shards = 0 if mesh is None else mesh_lib.shard_size(mesh)
    dp = 0 if mesh is None else mesh_lib.dp_size(mesh)
    with _lock:
        return {
            "available": mesh is not None,
            # where a mesh-served field keeps its whole single-device
            # copy: built on first use under `enabled: true`, resident
            # beside the shards otherwise (`vectors/store.py` `sync`)
            "single_device_copy": ("on_first_use" if explicitly_enabled()
                                   else "resident"),
            "num_shards": n_shards,
            "dp": dp,
            "devices": {"total": n_shards * dp, "shard_axis": n_shards,
                        "dp_axis": dp},
            "min_rows": _cfg["min_rows"],
            "hbm": {
                "budget_bytes": _cfg["hbm_budget_bytes"],
                "rejections": _counters["hbm_rejections"],
                "last_rejected_bytes":
                    _counters["hbm_last_rejected_bytes"],
                "accepted_bytes_high_water":
                    _counters["hbm_accepted_bytes"],
            },
            "router": {
                "mesh": _counters["decisions_mesh"],
                "single_device": _counters["decisions_single_device"],
                "reasons": dict(_counters["reasons"]),
                "searches": dict(_counters["searches"]),
                # dp-vs-shard split of mesh-accepted dispatches, with
                # reasons and the per-group round-robin spread (dp > 1)
                "dp": {
                    "routes": dict(_counters["dp_routes"]),
                    "reasons": dict(_counters["dp_reasons"]),
                    "group_dispatches": {
                        str(g): n for g, n in sorted(
                            _counters["dp_group_dispatches"].items())},
                },
                # unified per-dispatch cost router (serving/router.py):
                # copy-selection / split / placement decisions with
                # reasons, plus the live per-node cost estimates
                "dispatch": dispatch_router.stats(),
            },
            "legs": {leg: dict(v)
                     for leg, v in sorted(_counters["legs"].items())},
        }


def reset(full: bool = False) -> None:
    """Zero the counters (tests). full=True also drops the config and the
    cached mesh back to auto defaults."""
    global _mesh, _mesh_built, _rr, _cfg_epoch
    from elasticsearch_tpu.serving import router as dispatch_router
    dispatch_router.reset()
    with _lock:
        _cfg_epoch += 1
        _counters["decisions_mesh"] = 0
        _counters["decisions_single_device"] = 0
        _counters["reasons"].clear()
        _counters["legs"].clear()
        _counters["dp_routes"] = {"shard": 0, "dp": 0}
        _counters["dp_reasons"].clear()
        _counters["dp_group_dispatches"].clear()
        _counters["hbm_rejections"] = 0
        _counters["hbm_last_rejected_bytes"] = 0
        _counters["hbm_accepted_bytes"] = 0
        _rr = 0
        for leg in _counters["searches"]:
            _counters["searches"][leg] = 0
        if full:
            _cfg.update({"enabled": None, "num_shards": None,
                         "min_rows": DEFAULT_MIN_ROWS, "dp": None,
                         "hbm_budget_bytes": None})
            _mesh, _mesh_built = None, False
            _groups.clear()
            _shard_meshes.clear()
