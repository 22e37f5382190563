"""A served batch crosses to the device once each way.

Down: the program the store launches returns ONE int32 board [Q, 2k],
the float32 scores' bit patterns beside the int32 ids
(`ops/topk.pack_board`), packed inside the program that computed the
pair; the finalizer makes one read and splits it on the host. Up: the
padded queries ride the launch as host numpy (one chip) or go to the mesh
in one placement.

1. PARITY — for every serving kernel, at the query rungs 1, 8 and 16, the
   board split on the host IS the pair the unpacked entry point returns,
   byte for byte, `-inf` / `-1` padding included.
2. CROSSINGS — one served batch through `VectorStoreShard` records each of
   the six dispatch stages once and ONE device→host read, on the
   single-device route and on the mesh route; after the store's warm-up a
   served batch compiles nothing (a missed dispatcher key for a numpy or a
   sharded argument fails here, not in a chip's window).
"""

import tempfile

import numpy as np
import pytest

import jax

from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops import knn as knn_ops
from elasticsearch_tpu.ops import pallas_knn_binned as binned
from elasticsearch_tpu.ops import similarity as sim
from elasticsearch_tpu.ops import topk as topk_ops
from elasticsearch_tpu.parallel.sharded_knn import (
    ShardedFieldState, distributed_knn_search)
from elasticsearch_tpu.telemetry import metrics as telemetry_metrics
from elasticsearch_tpu.vectors.store import SERVING_PRECISION

pytestmark = pytest.mark.multidevice

K = 16                  # deeper than the valid rows below: padding shows
DIMS = 32
STAGES = ("dispatch.prepare", "dispatch.h2d", "dispatch.launch",
          "dispatch.sync_wait", "dispatch.d2h", "dispatch.land")


def _binned_corpus(rng, dtype):
    # one kernel tile, mostly padding: 300 real rows of 8,192
    return knn_ops.build_corpus(
        rng.standard_normal((300, DIMS)).astype(np.float32),
        metric=sim.COSINE, dtype=dtype, pad_to=binned.BLOCK_N)


def _binned(rng, queries, board):
    return binned.binned_knn_search(
        queries, _binned_corpus(rng, "bf16"), K, interpret=True,
        board=board)


def _rescored_packed(rng, queries, board):
    return binned.binned_knn_search_rescored_packed(
        queries, _binned_corpus(rng, "int8"), K, rescore_candidates=64,
        interpret=True, board=board)


def _exact(rng, queries, board, filtered=False):
    # 12 real rows under k = 16: four NEG_INF slots a query at the least
    corpus = knn_ops.build_corpus(
        rng.standard_normal((12, DIMS)).astype(np.float32),
        metric=sim.COSINE, dtype="bf16")
    mask = None
    if filtered:
        mask = rng.random((len(queries), corpus.matrix.shape[0])) < 0.5
    return knn_ops.knn_search(queries, corpus, K, filter_mask=mask,
                              precision=SERVING_PRECISION, board=board)


def _exact_filtered(rng, queries, board):
    return _exact(rng, queries, board, filtered=True)


def _mesh(rng, queries, board):
    from elasticsearch_tpu.parallel import policy
    # 12 rows over 8 shards, two of them empty, under k = 16: (-inf, -1)
    # slots enter the merge and four a query leave it
    state = ShardedFieldState(
        rng.standard_normal((12, DIMS)).astype(np.float32),
        policy.serving_mesh(), "cosine", "f32")
    q = jax.device_put(queries, state.query_sharding())
    return distributed_knn_search(q, state.corpus, K, state.mesh,
                                  precision=SERVING_PRECISION, board=board)


@pytest.mark.parametrize("rung", [1, 8, 16])
@pytest.mark.parametrize("kernel", [_binned, _exact, _exact_filtered,
                                    _rescored_packed, _mesh],
                         ids=["knn.binned", "knn.exact",
                              "knn.exact-filtered",
                              "knn.binned_rescored_packed", "mesh.knn"])
def test_board_is_the_pair_byte_for_byte(mesh_serving, kernel, rung):
    queries = np.random.default_rng(rung).standard_normal(
        (rung, DIMS)).astype(np.float32)
    # the same seed twice: the same corpus (and mask) on both sides
    scores, ids = kernel(np.random.default_rng(7), queries, False)
    board = np.asarray(kernel(np.random.default_rng(7), queries, True))
    assert board.dtype == np.int32 and board.shape == (rung, 2 * K)
    got_scores, got_ids = topk_ops.split_board(board)
    scores, ids = np.asarray(scores), np.asarray(ids)
    assert scores.dtype == got_scores.dtype == np.float32
    assert ids.dtype == got_ids.dtype == np.int32
    # scores as their bit patterns: -inf and NEG_INF compare like numbers
    np.testing.assert_array_equal(got_scores.view(np.int32),
                                  scores.view(np.int32))
    np.testing.assert_array_equal(got_ids, ids)
    if kernel in (_exact, _exact_filtered, _mesh):
        assert (scores < -1e37).any(), "no padding slot in this case"
    if kernel is _mesh:
        assert (ids == -1).any()


def test_split_board_on_the_device_is_the_same_pair():
    """The generational fan-out's legs split a board without reading it:
    the device-side split is the host-side one."""
    rng = np.random.default_rng(3)
    scores = rng.standard_normal((8, K)).astype(np.float32)
    scores[:, -2:] = -np.inf
    ids = rng.integers(-1, 1000, (8, K)).astype(np.int32)
    board = topk_ops.pack_board(jax.numpy.asarray(scores),
                                jax.numpy.asarray(ids))
    on_device = topk_ops.split_board(board)
    on_host = topk_ops.split_board(np.asarray(board))
    for got in (on_device, on_host):
        np.testing.assert_array_equal(
            np.asarray(got[0]).view(np.int32), scores.view(np.int32))
        np.testing.assert_array_equal(np.asarray(got[1]), ids)


# ------------------------------------------------------------ the store


def _node(dims, n=200, seed=5):
    from elasticsearch_tpu.node import Node

    rng = np.random.default_rng(seed)
    node = Node(tempfile.mkdtemp())
    node.create_index_with_templates("m", mappings={"properties": {
        "v": {"type": "dense_vector", "dims": dims,
              "similarity": "cosine"}}})
    ops = []
    for i in range(n):
        ops.append({"index": {"_index": "m", "_id": str(i)}})
        ops.append({"v": rng.standard_normal(dims).tolist()})
    node.bulk(ops)
    node.indices.get("m").refresh()
    return node, rng


def _counts():
    snap = {n: telemetry_metrics.histogram(n).count for n in STAGES}
    snap["dispatch.host_reads"] = telemetry_metrics.counter(
        "dispatch.host_reads").value
    return snap


def _serve(store, fc, rng, n_queries, dims):
    reqs = [(rng.standard_normal(dims).astype(np.float32), None)
            for _ in range(n_queries)]
    handle = store._dispatch_many(fc, 10, SERVING_PRECISION, reqs,
                                  field="v")
    return handle[0], store.finalize_many(handle)


@pytest.fixture
def single_device():
    from elasticsearch_tpu.parallel import policy
    policy.reset(full=True)
    policy.configure(enabled=False)
    yield "pending"
    policy.reset(full=True)


@pytest.fixture
def mesh_route(mesh_serving):
    return "mesh"


@pytest.mark.parametrize("route", ["single_device", "mesh_route"])
def test_a_served_batch_crosses_once_each_way(request, monkeypatch, route):
    """The six stages once each and one read of the result, whichever
    exhaustive route serves; and, the store's grid warmed, the served
    rungs 1 and 8 find their programs: a numpy argument keys like a
    device array, a sharded one carries its `NamedSharding`."""
    kind = request.getfixturevalue(route)
    # a row width no other test of this process serves: the dispatcher's
    # cache is the process's, and this test has to see its own compiles
    dims = 24 if kind == "pending" else 40
    node, rng = _node(dims)
    real_warmup = dispatch.DISPATCH.warmup
    monkeypatch.setattr(
        dispatch.DISPATCH, "warmup",
        lambda entries, background=True: real_warmup(entries,
                                                     background=False))
    monkeypatch.setattr(dispatch.DISPATCH, "strict", True)
    try:
        store = node.indices.get("m").shards[0].vector_store
        fc = store.field("v")
        before = dispatch.stats(per_bucket=False)
        monkeypatch.setattr(store, "warmup", True)
        store._schedule_warmup(fc)
        warmed = dispatch.stats(per_bucket=False)
        assert warmed["warmup_compiles"] > before["warmup_compiles"]
        for n_queries in (1, 5):            # the rungs 1 and 8
            counts = _counts()
            got_kind, results = _serve(store, fc, rng, n_queries, dims)
            assert got_kind == kind
            assert len(results) == n_queries
            assert all(len(rows) == 10 for rows, _scores in results)
            after = _counts()
            assert {n: after[n] - counts[n] for n in after} \
                == {n: 1 for n in after}
        served = dispatch.stats(per_bucket=False)
        assert served["compiles"] == warmed["compiles"]
        assert served["out_of_grid_compiles"] == \
            warmed["out_of_grid_compiles"]
    finally:
        node.close()
