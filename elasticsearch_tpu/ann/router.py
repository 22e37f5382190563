"""Query-time IVF engine: routing, nprobe selection, fallback, timings.

The serving-facing half of `tpu_ivf`. `IVFRouter.search` runs the two
device stages of `ops/knn_ivf.py` — centroid routing and pruned scoring —
as separate dispatches so the per-phase wall times the profiler and
`_nodes/stats` report (route / score / merge) are measured, not modeled.

nprobe selection:
  * an integer setting is clamped to nlist and snapped up to the
    dispatch grid's pow-2 ladder (nprobe is a compiled-shape parameter;
    see ops/dispatch.py — snapping up never probes fewer partitions
    than configured);
  * `"auto"` tunes once per layout generation: a held-out sample of the
    indexed vectors becomes the query set, the engine's own full-probe
    (nprobe = nlist) result the ground truth, and nprobe doubles until
    recall@k meets `recall_target` — the recall-gate escape hatch.
    Full-probe truth isolates routing loss (what nprobe controls) from
    storage-quantization loss (what dtype controls); at the limit the
    tuner returns nlist and the engine is exactly as good as
    exhaustive-over-buckets.

Fallback (exhaustive `ops/knn.py`) triggers whenever pruning can't hold
its contract: filtered searches (the mask may eliminate every probed
partition), layouts flagged `needs_retrain`, k beyond the probed-row
budget, or f32-precision requests (IVF is a throughput path; exactness
asks go to the exact kernel).
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from elasticsearch_tpu.ann.ivf_index import IVFIndex


def _pad_back_k(scores, rows, k: int, k_dev: int):
    """Widen device results [Q, k_dev] back to the requested [Q, k]
    with the empty-slot sentinels (-inf, -1) — the probed-row budget
    caps what the kernels can return. Shared by the single-device and
    mesh paths so the result contract can never diverge."""
    scores_np = np.asarray(scores)
    rows_np = np.asarray(rows)
    if k_dev < k:
        pad = k - k_dev
        scores_np = np.pad(scores_np, ((0, 0), (0, pad)),
                           constant_values=-np.inf)
        rows_np = np.pad(rows_np, ((0, 0), (0, pad)),
                         constant_values=-1)
    return scores_np, rows_np


class IVFRouter:
    """One field's IVF engine instance (wraps the layout + tuning state)."""

    def __init__(self, index: IVFIndex, nprobe="auto",
                 recall_target: float = 0.95, tune_sample: int = 128,
                 tune_seed: int = 0, tune_margin: float = 0.01):
        self.index = index
        self.nprobe_setting = nprobe
        self.recall_target = float(recall_target)
        self.tune_sample = int(tune_sample)
        self.tune_seed = int(tune_seed)
        # tune slightly past the target: the gate is measured on a finite
        # held-out sample, and serving queries are noisier than corpus rows
        self.tune_margin = float(tune_margin)
        self._tuned_nprobe: Optional[int] = None
        self.last_phases: dict = {}

    def with_index(self, index: IVFIndex) -> "IVFRouter":
        """A new router serving `index` with this router's settings AND
        its tuned nprobe carried over — the segments merge scheduler
        swaps extended layouts in without re-running the recall-gate
        tuner (the layout geometry is unchanged by an append)."""
        new = IVFRouter(index, nprobe=self.nprobe_setting,
                        recall_target=self.recall_target,
                        tune_sample=self.tune_sample,
                        tune_seed=self.tune_seed,
                        tune_margin=self.tune_margin)
        new._tuned_nprobe = self._tuned_nprobe
        return new

    # ---------------------------------------------------------- nprobe

    def effective_nprobe(self, k: int) -> int:
        if self.nprobe_setting != "auto":
            n = max(1, min(int(self.nprobe_setting), self.index.nlist))
            if n != self.index.nlist and n & (n - 1):
                # nprobe is a static arg of the dispatched kernels and
                # the closed grid only admits pow-2 rungs (or full
                # nlist): snap an off-ladder setting UP — never fewer
                # probes than configured, recall only improves
                n = min(1 << (n - 1).bit_length(), self.index.nlist)
            return n
        if self._tuned_nprobe is None:
            self._tuned_nprobe = self.tune_nprobe(k=max(k, 10))
        return self._tuned_nprobe

    def tune_nprobe(self, k: int = 10) -> int:
        """Recall-gate auto-tune: double nprobe until recall@k on a
        held-out sample of the indexed vectors meets the target.

        Ground truth is the engine's own full-probe (nprobe = nlist)
        result over the same partitions and storage dtype — that isolates
        the loss nprobe actually controls (routing) from quantization
        loss, which no amount of extra probing can recover and would
        otherwise drive the tuner all the way to exhaustive."""
        idx = self.index
        valid_mask = idx.part_rows >= 0
        flat_vecs = idx.part_vecs[valid_mask]
        n = int(valid_mask.sum())
        if n == 0:
            return 1
        rng = np.random.default_rng(self.tune_seed)
        sample = min(self.tune_sample, n)
        pick = rng.choice(n, size=sample, replace=False)
        queries = flat_vecs[pick]
        k_eff = min(k, n)

        _, truth, _ = self._device_search(queries, k_eff, idx.nlist)
        truth_rows = [set(t[t >= 0]) for t in truth]

        gate = min(1.0, self.recall_target + self.tune_margin)
        nprobe = 1
        while True:
            _, got_rows, _ = self._device_search(queries, k_eff, nprobe)
            hits = sum(len(truth_rows[i] & set(got_rows[i]))
                       for i in range(sample))
            recall = hits / max(sum(len(t) for t in truth_rows), 1)
            if recall >= gate or nprobe >= idx.nlist:
                return nprobe
            nprobe = min(idx.nlist, nprobe * 2)

    # ---------------------------------------------------------- search

    def should_fallback(self, k: int, has_filter: bool,
                        precision: str) -> Optional[str]:
        """Reason string when this search must take the exhaustive path."""
        idx = self.index
        if has_filter:
            return "filtered"
        if precision == "f32":
            return "f32_precision"
        if idx.needs_retrain:
            return "needs_retrain"
        if idx.total == 0:
            return "empty"
        if k > idx.cap:  # one probe can't even fill the result list
            return "k_exceeds_partition"
        return None

    def _device_search(self, queries: np.ndarray, k: int, nprobe: int):
        """(scores [Q,k], rows [Q,k], phases dict) — rows are
        device-corpus row ids, -1 for empty slots."""
        import jax.numpy as jnp

        from elasticsearch_tpu.ops import knn_ivf

        from elasticsearch_tpu.ops import pallas_ivf_fused as fused

        idx = self.index
        nprobe = max(1, min(nprobe, idx.nlist))
        t0 = time.perf_counter_ns()
        parts = idx.device_partitions()
        q = knn_ivf._prep_queries(jnp.asarray(queries, dtype=jnp.float32),
                                  idx.metric)
        probe_ids, cent_scores = knn_ivf.route(q, parts, nprobe,
                                               metric=idx.metric)
        probe_ids.block_until_ready()
        t1 = time.perf_counter_ns()
        k_dev = min(k, nprobe * idx.cap)
        # fused Pallas gather+score when the layout/metric allow and the
        # backend prefers it (accelerators; ES_TPU_IVF_FUSED forces in
        # interpret mode) — no [Q, nprobe, cap, D] staged tile gather
        use_fused = (fused.fused_eligible(parts.parts.dtype, idx.metric)
                     and fused.fused_preferred())
        if use_fused:
            scores, rows = fused.fused_probe_scores(
                q, parts, probe_ids, k_dev, metric=idx.metric)
        else:
            scores, rows = knn_ivf.score_probes(q, parts, probe_ids, k_dev,
                                                metric=idx.metric)
        rows.block_until_ready()
        t2 = time.perf_counter_ns()
        scores_np, rows_np = _pad_back_k(scores, rows, k, k_dev)
        t3 = time.perf_counter_ns()
        phases = {"engine": "tpu_ivf", "nprobe": nprobe,
                  "nlist": idx.nlist,
                  "scored_rows": nprobe * idx.cap,
                  "fused_probe": use_fused,
                  "route_nanos": t1 - t0, "score_nanos": t2 - t1,
                  "merge_nanos": t3 - t2}
        return scores_np, rows_np, phases

    def _mesh_search(self, queries: np.ndarray, k: int, nprobe: int,
                     mesh):
        """SPMD execution: one compiled program routes on replicated
        centroids, scores each shard's owned partitions, and merges the
        [S, Q, k] candidates over ICI (`parallel/sharded_ivf.py`). Same
        result contract as `_device_search` — row ids are flat
        device-corpus rows either way."""
        import jax
        import jax.numpy as jnp

        from elasticsearch_tpu.ops import knn_ivf
        from elasticsearch_tpu.parallel import mesh as mesh_lib
        from elasticsearch_tpu.parallel import policy
        from elasticsearch_tpu.parallel.sharded_ivf import (
            sharded_ivf_search)

        idx = self.index
        nprobe = max(1, min(nprobe, idx.nlist))
        t0 = time.perf_counter_ns()
        sivf = idx.device_partitions_sharded(mesh)
        # prep on device with the single-device recipe (bitwise-identical
        # routing scores), then re-lay out across the mesh WITHOUT a
        # host round-trip — np.asarray here would sync and re-upload the
        # whole query batch per dispatch
        q = knn_ivf._prep_queries(
            jnp.asarray(np.asarray(queries, dtype=np.float32)),
            idx.metric)
        q = jax.device_put(q, mesh_lib.query_sharding(mesh))
        k_dev = min(k, nprobe * idx.cap)
        scores, rows = sharded_ivf_search(q, sivf, k_dev, nprobe, mesh,
                                          metric=idx.metric)
        rows.block_until_ready()
        t1 = time.perf_counter_ns()
        scores_np, rows_np = _pad_back_k(scores, rows, k, k_dev)
        t2 = time.perf_counter_ns()
        n_shards = int(mesh.shape[mesh_lib.SHARD_AXIS])
        gather = policy.gather_bytes(n_shards, len(queries), k_dev)
        policy.record_leg("ivf", gather)
        phases = {"engine": "tpu_ivf_mesh", "nprobe": nprobe,
                  "nlist": idx.nlist, "mesh_shards": n_shards,
                  "scored_rows": nprobe * idx.cap,
                  "collective_bytes": gather,
                  # route + score + merge run inside ONE SPMD program;
                  # the in-program split is not observable from the host
                  "route_nanos": 0, "score_nanos": t1 - t0,
                  "merge_nanos": t2 - t1}
        return scores_np, rows_np, phases

    def search(self, queries: np.ndarray, k: int,
               nprobe: Optional[int] = None,
               num_candidates: Optional[int] = None,
               mesh=None):
        """Pruned top-k over the partition layout.

        num_candidates (the `_search` knn API knob) widens probing the way
        ef does for HNSW: enough partitions are probed that at least that
        many rows get scored.

        mesh: a (dp, shard) serving mesh to execute on as one SPMD
        program (the store's mesh router passes it); None = the
        single-device two-dispatch path.

        Returns (scores [Q, k], rows [Q, k], phases). Callers decide
        fallback beforehand via `should_fallback` — this always prunes.
        """
        if nprobe is None:
            nprobe = self.effective_nprobe(k)
        if num_candidates is not None and num_candidates > 0:
            want = -(-int(num_candidates) // max(self.index.cap, 1))
            if want > nprobe:
                # num_candidates is a PER-REQUEST knob and nprobe is a
                # static arg of the routed kernels (a distinct value is a
                # fresh compiled shape): snap the widening to the next
                # pow-2 rung, clamped to nlist, so a client sweeping
                # num_candidates stays inside the closed dispatch grid.
                # Probing more partitions than asked only helps recall —
                # "at least num_candidates rows" still holds.
                nprobe = min(1 << (want - 1).bit_length(),
                             self.index.nlist)
        if mesh is not None:
            scores, rows, phases = self._mesh_search(
                np.asarray(queries, dtype=np.float32), k, nprobe, mesh)
        else:
            scores, rows, phases = self._device_search(
                np.asarray(queries, dtype=np.float32), k, nprobe)
        self.last_phases = phases
        return scores, rows, phases
