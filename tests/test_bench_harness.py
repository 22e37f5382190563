"""The bench harness: `bench.py` measures on the chip or fails, and the
`bench_matrix.py` rows read their stats from the live node that served the
queries."""

import importlib.util
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _load_bench_matrix():
    spec = importlib.util.spec_from_file_location(
        "bench_matrix", REPO / "bench_matrix.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_without_a_chip_prints_no_number():
    """One process, no child, no CPU floor, no last-known-good: with no
    TPU `bench.py` exits non-zero and its stdout stays empty — a CPU
    timing is never written under the device metric's name."""
    r = subprocess.run([sys.executable, str(REPO / "bench.py")],
                       capture_output=True, text=True, timeout=120,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                            "PYTHONPATH": str(REPO)})
    assert r.returncode != 0
    assert r.stdout == ""
    assert "No number is printed" in r.stderr


def test_hybrid_bench_row_counts_plan_cache_hits_from_live_node(tmp_path):
    """A round-6 capture's `plan_cache_hits: 0` over 108 identical
    bodies: root-caused to the rows having been captured by a PRE-PR4
    bench/engine snapshot (they lacked the per-row `dispatch` delta PR 4
    added) — the capture predated the plan-cache key fix. It was never
    a wrong-process/wrong-engine stats read: this test pins that the
    bench row's stats fields come from the SAME live node that served
    the queries, and that structurally-identical bodies actually hit."""
    import numpy as np

    from elasticsearch_tpu.node import Node

    bench_matrix = _load_bench_matrix()
    rng = np.random.default_rng(0)
    node = Node(str(tmp_path))
    node.create_index_with_templates("hy", mappings={"properties": {
        "body": {"type": "text"},
        "v": {"type": "dense_vector", "dims": 8}}})
    ops = []
    for i in range(40):
        ops.append({"index": {"_index": "hy", "_id": str(i)}})
        ops.append({"body": f"tok{i % 5} tok{i % 7}",
                    "v": rng.standard_normal(8).astype(float).tolist()})
    node.bulk(ops)
    node.indices.get("hy").force_merge()

    def body():
        return {"rank": {"rrf": {"rank_constant": 60,
                                 "rank_window_size": 100}},
                "query": {"match": {"body": "tok1 tok2"}},
                "knn": {"field": "v",
                        "query_vector":
                            rng.standard_normal(8).astype(float).tolist(),
                        "k": 10, "num_candidates": 10},
                "size": 10, "_source": False}

    n_queries = 8
    for _ in range(n_queries):
        assert node.search("hy", body())["hits"]["hits"]
    row = bench_matrix.hybrid_serving_stats(node)
    # identical SHAPES (different vectors/text) must share one plan:
    # exactly one miss, everything after it a hit — counted by the same
    # executor instance the searches went through
    assert row["plan_cache_misses"] == 1
    assert row["plan_cache_hits"] == n_queries - 1
    assert row["hybrid_batches"] >= 1
    assert row["rejected_429"] == 0
    # the tail-attribution split is present and self-consistent
    assert set(row["tail_ms"]) == {"queue_wait", "device", "hydrate"}
    assert row["tail_ms"]["device"] > 0
    assert set(row["sched"]) >= {"topups", "deadline_sheds",
                                 "overlap_hits"}
    node.close()


def test_closed_loop_row_scheduler_fields(tmp_path):
    """The 1cl/4cl rows' scheduler fields read from the live node's kNN
    batchers (`_nodes/stats indices.knn.scheduler`)."""
    import numpy as np

    from elasticsearch_tpu.node import Node

    bench_matrix = _load_bench_matrix()
    rng = np.random.default_rng(1)
    node = Node(str(tmp_path))
    node.create_index_with_templates("cl", mappings={"properties": {
        "v": {"type": "dense_vector", "dims": 8}}})
    ops = []
    for i in range(32):
        ops.append({"index": {"_index": "cl", "_id": str(i)}})
        ops.append({"v": rng.standard_normal(8).astype(float).tolist()})
    node.bulk(ops)
    node.indices.get("cl").refresh()
    for _ in range(4):
        node.search("cl", {
            "knn": {"field": "v",
                    "query_vector":
                        rng.standard_normal(8).astype(float).tolist(),
                    "k": 5, "num_candidates": 5},
            "size": 5, "_source": False})
    row = bench_matrix.knn_scheduler_stats(node)
    assert row["sched"]["batches"] >= 1
    assert set(row["tail_ms"]) == {"queue_wait", "dispatch", "finalize"}
    node.close()


class _capture_stdout:
    def __enter__(self):
        import io
        self._old = sys.stdout
        sys.stdout = buf = io.StringIO()
        return buf

    def __exit__(self, *exc):
        sys.stdout = self._old
        return False
