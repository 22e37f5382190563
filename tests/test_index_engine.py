"""Engine tests: CRUD, versioning, refresh/NRT, translog recovery, merge."""

import os

import numpy as np
import pytest

from elasticsearch_tpu.common.errors import (
    DocumentMissingError, MapperParsingError, VersionConflictError,
)
from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.index.mapping import MapperService
from elasticsearch_tpu.index.translog import Translog, TranslogCorruptedError
from elasticsearch_tpu.telemetry import metrics
from tests import wire_reference

MAPPING = {
    "properties": {
        "title": {"type": "text", "analyzer": "standard"},
        "tag": {"type": "keyword"},
        "views": {"type": "long"},
        "price": {"type": "float"},
        "published": {"type": "date"},
        "active": {"type": "boolean"},
        "embedding": {"type": "dense_vector", "dims": 4, "similarity": "cosine"},
    }
}


@pytest.fixture
def engine(tmp_path):
    e = Engine(str(tmp_path / "shard0"), MapperService(MAPPING))
    yield e
    e.close()


def test_index_and_get(engine):
    r = engine.index("1", {"title": "hello world", "views": 10})
    assert r.result == "created" and r.version == 1 and r.seq_no == 0
    doc = engine.get("1")
    assert doc["_source"]["title"] == "hello world"
    assert doc["_version"] == 1
    # realtime: visible before refresh
    assert engine.get("1", realtime=True) is not None


def test_update_and_versioning(engine):
    engine.index("1", {"title": "v1"})
    r2 = engine.index("1", {"title": "v2"})
    assert r2.result == "updated" and r2.version == 2
    assert engine.get("1")["_source"]["title"] == "v2"
    assert engine.doc_count() == 1


def test_op_type_create_conflict(engine):
    engine.index("1", {"title": "x"})
    with pytest.raises(VersionConflictError):
        engine.index("1", {"title": "y"}, op_type="create")


def test_if_seq_no_conflict(engine):
    r = engine.index("1", {"title": "x"})
    engine.index("1", {"title": "y"})  # bumps seq_no
    with pytest.raises(VersionConflictError):
        engine.index("1", {"title": "z"}, if_seq_no=r.seq_no, if_primary_term=r.primary_term)


def test_external_versioning(engine):
    engine.index("1", {"title": "x"}, version=5, version_type="external")
    with pytest.raises(VersionConflictError):
        engine.index("1", {"title": "y"}, version=4, version_type="external")
    r = engine.index("1", {"title": "z"}, version=9, version_type="external")
    assert r.version == 9


def test_delete(engine):
    engine.index("1", {"title": "x"})
    r = engine.delete("1")
    assert r.result == "deleted"
    assert engine.get("1") is None
    assert engine.doc_count() == 0
    with pytest.raises(DocumentMissingError):
        engine.delete("1")


def test_refresh_visibility(engine):
    engine.index("1", {"title": "the quick brown fox"})
    reader = engine.acquire_searcher()
    # was refreshed at engine init; new doc is in the builder, not the reader
    assert reader.num_docs == 0
    reader = engine.refresh()
    assert reader.num_docs == 1
    p = reader.views[0].segment.get_postings("title", "quick")
    assert p is not None and p.doc_freq == 1


def test_deletes_visible_in_reader(engine):
    engine.index("1", {"tag": "a"})
    engine.index("2", {"tag": "b"})
    engine.refresh()
    engine.delete("1")
    reader = engine.refresh()
    assert reader.num_docs == 1
    rows = reader.live_global_rows()
    assert all(reader.get_id(r) == "2" for r in rows)


def test_translog_recovery(tmp_path):
    path = str(tmp_path / "shard")
    e = Engine(path, MapperService(MAPPING))
    e.index("1", {"title": "persisted"})
    e.index("2", {"title": "also persisted"})
    e.delete("1")
    e.close()
    # reopen WITHOUT flush: everything must come back from the translog
    e2 = Engine(path, MapperService(MAPPING))
    assert e2.doc_count() == 1
    assert e2.get("2")["_source"]["title"] == "also persisted"
    assert e2.get("1") is None
    assert e2.local_checkpoint == 2
    e2.close()


def test_flush_and_recovery(tmp_path):
    path = str(tmp_path / "shard")
    e = Engine(path, MapperService(MAPPING))
    for i in range(5):
        e.index(str(i), {"title": f"doc {i}", "views": i})
    e.flush()
    e.index("9", {"title": "after flush"})
    e.close()
    e2 = Engine(path, MapperService(MAPPING))
    assert e2.doc_count() == 6
    assert e2.get("9") is not None
    assert e2.get("3")["_source"]["views"] == 3
    e2.close()


def test_merge_compacts(engine):
    for i in range(10):
        engine.index(str(i), {"tag": f"t{i}"})
    engine.refresh()
    for i in range(5):
        engine.delete(str(i))
    engine.index("3", {"tag": "resurrected"})
    engine.refresh()
    assert len(engine.segments) == 2
    engine.merge()
    assert len(engine.segments) == 1
    reader = engine.acquire_searcher()
    assert reader.num_docs == 6  # 5 survivors + resurrected "3"
    assert engine.get("3")["_source"]["tag"] == "resurrected"
    assert engine.get("4") is None


def test_replica_out_of_order(engine):
    engine.index("1", {"title": "new"}, seq_no=5, primary_term=1, version=2, origin="replica")
    r = engine.index("1", {"title": "old"}, seq_no=3, primary_term=1, version=1, origin="replica")
    assert r.result == "noop"
    assert engine.get("1")["_source"]["title"] == "new"


def test_vector_field(engine):
    engine.index("1", {"embedding": [1.0, 0.0, 0.0, 0.0], "title": "v"})
    reader = engine.refresh()
    seg = reader.views[0].segment
    mat, present = seg.vectors["embedding"]
    assert mat.shape == (1, 4) and present[0]
    np.testing.assert_allclose(mat[0], [1, 0, 0, 0])
    with pytest.raises(MapperParsingError):
        engine.index("2", {"embedding": [1.0, 2.0]})  # wrong dims


def test_translog_corruption_detected(tmp_path):
    t = Translog(str(tmp_path / "tl"))
    t.add({"op": "index", "id": "1", "seq_no": 0, "source": {"a": 1}})
    t.close()
    # flip a byte in the payload
    path = str(tmp_path / "tl" / "translog-1.tlog")
    data = bytearray(open(path, "rb").read())
    data[3] ^= 0xFF
    open(path, "wb").write(bytes(data))
    t2 = Translog(str(tmp_path / "tl"))
    with pytest.raises(TranslogCorruptedError):
        t2.read_ops(0)
    t2.close()


def _vector_ops(dims, n=5):
    rng = np.random.default_rng(dims)
    ops = [{"op": "index", "id": f"d{i}", "seq_no": i, "primary_term": 1, "version": 1,
            "source": {"emb": rng.standard_normal(dims).tolist(), "title": f"t{i}"}}
           for i in range(n)]
    ops[1]["routing"] = "r1"
    ops.append({"op": "delete", "id": "d0", "seq_no": n, "primary_term": 1, "version": 2})
    ops.append({"op": "noop", "seq_no": n + 1, "reason": "gap", "primary_term": 1})
    return ops


@pytest.mark.parametrize("dims", [4, 256, 768])
def test_translog_records_are_the_element_walks(tmp_path, dims):
    """A generation file written one element at a time (the format before
    vectors were packed) replays to the same operations, and `add` writes
    that very file."""
    ops = _vector_ops(dims)
    old = tmp_path / "old"
    old.mkdir()
    log = b"".join(wire_reference.translog_record(op) for op in ops)
    (old / "translog-1.tlog").write_bytes(log)
    t = Translog(str(old), sync_policy="async")
    assert t.read_ops(0) == ops
    t.close()
    t = Translog(str(tmp_path / "new"), sync_policy="async")
    for op in ops:
        t.add(op)
    t.close()
    assert (tmp_path / "new" / "translog-1.tlog").read_bytes() == log


def test_vector_bulk_survives_a_dropped_engine(tmp_path):
    """2,048 vector documents under `async`, synced, the engine dropped with
    no flush: every `_source` comes back as JSON gave it (doubles, not the
    float32 the mapper coerces to), and the counters say each vector took the
    array path."""
    dims, n = 256, 2048
    mapping = {"properties": {"emb": {"type": "dense_vector", "dims": dims},
                              "title": {"type": "keyword"}}}
    rng = np.random.default_rng(29)
    sources = [{"emb": v.tolist(), "title": f"t{i}"}
               for i, v in enumerate(rng.standard_normal((n, dims)))]
    path = str(tmp_path / "shard")
    ops0 = metrics.counter("translog.ops").value
    packed0 = metrics.counter("translog.packed_lists").value
    e = Engine(path, MapperService(mapping), translog_sync="async")
    for i, src in enumerate(sources):
        e.index(str(i), src)
    assert metrics.counter("translog.ops").value - ops0 == n
    assert metrics.counter("translog.packed_lists").value - packed0 == n
    e.translog.sync()
    del e    # no flush, no close: what a killed process leaves behind
    e2 = Engine(path, MapperService(mapping), translog_sync="async")
    assert e2.doc_count() == n
    for i, src in enumerate(sources):
        assert e2.get(str(i))["_source"] == src
    x = e2.get("0")["_source"]["emb"][0]
    assert type(x) is float and float(np.float32(x)) != x
    e2.close()


@pytest.mark.parametrize("policy,fsyncs", [("request", 2), ("async", 0)])
def test_translog_add_fsyncs_as_before(tmp_path, monkeypatch, policy, fsyncs):
    """`request` durability: the generation file and the checkpoint, each
    once an operation; `async`: none until `sync()`."""
    t = Translog(str(tmp_path / "tl"), sync_policy=policy)
    calls = []
    real = os.fsync

    def counted(fd):
        calls.append(fd)
        return real(fd)

    monkeypatch.setattr(os, "fsync", counted)
    for op in _vector_ops(256):
        before = len(calls)
        t.add(op)
        assert len(calls) - before == fsyncs
    before = len(calls)
    t.sync()
    assert len(calls) - before == 2
    t.close()


def test_mapping_dynamic_and_multifield(tmp_path):
    ms = MapperService({"properties": {}})
    e = Engine(str(tmp_path / "s"), ms)
    e.index("1", {"title": "Some Text Here", "count": 7, "score": 1.5, "flag": True})
    assert ms.get("title").type_name == "text"
    assert ms.get("title.keyword").type_name == "keyword"
    assert ms.get("count").type_name == "long"
    assert ms.get("score").type_name == "float"
    assert ms.get("flag").type_name == "boolean"
    reader = e.refresh()
    # keyword multi-field indexed the raw string
    p = reader.views[0].segment.get_postings("title.keyword", "Some Text Here")
    assert p is not None
    e.close()


def test_mapping_render_roundtrip():
    ms = MapperService(MAPPING)
    rendered = ms.to_dict()
    assert rendered["properties"]["embedding"]["type"] == "dense_vector"
    assert rendered["properties"]["embedding"]["dims"] == 4
    ms2 = MapperService(rendered)
    assert ms2.get("embedding").dims == 4


# ---------------------------------------------------------------------------
# a commit of a keyword ARRAY a document beside a text field (ISSUE 33's
# mapping): every posting comes back from the disk
# ---------------------------------------------------------------------------

def _postings_of(engine):
    return {(seg.seg_id, field, term): (p.doc_ids.tolist(), p.freqs.tolist(),
                                        p.positions)
            for seg in engine.segments
            for field, terms in seg.postings.items()
            for term, p in terms.items()}


def test_a_commit_keeps_every_posting_of_a_keyword_array_and_of_text(tmp_path):
    path = str(tmp_path / "shard")
    e = Engine(path, MapperService(MAPPING))
    for i in range(40):
        e.index(str(i), {"title": f"doc number {i} of forty doc",
                         "tag": [f"t{i % 7}", f"u{i % 3}", "all"]})
    e.refresh()
    e.index("last", {"tag": ["t0"]})      # a second, small segment
    e.flush()
    before = _postings_of(e)
    assert any(pos is not None for _d, _f, pos in before.values())
    tags = {term for _s, field, term in before if field == "tag"}
    assert len(tags) == 7 + 3 + 1
    assert len(before[(e.segments[0].seg_id, "tag", "all")][0]) == 40
    e.close()
    e2 = Engine(path, MapperService(MAPPING))
    assert _postings_of(e2) == before
    assert e2.doc_count() == 41
    e2.close()
