"""What `mesh-knn-steady` added to the yardstick as new files: the reader
`readers/mesh_trace_reduction.py` on hand-made inputs, the kind
`kinds/knn_int8.py` with planted faults, its int4 control, and one whole
run of the cell on 4 virtual CPU devices."""

import argparse
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import loadgen, roofline, verify  # noqa: E402
from benchmark.data import Corpus  # noqa: E402
from benchmark.kinds import knn, knn_int8  # noqa: E402
from benchmark.kinds import knn_int8_reference as reference  # noqa: E402
from benchmark.readers import mesh_trace_reduction as reader  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
with open(os.path.join(REPO, "benchmark", "configs",
                       "cohere-768-int8-mesh4.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(REPO, "benchmark", "traffic",
                       "mesh-knn-steady.json")) as _f:
    TRAFFIC = json.load(_f)
ROWS, QUERIES, K = 4096, 96, 10


# ---------------------------------------------------------------------------
# the reader
# ---------------------------------------------------------------------------

def _ctx(searches, busy_s, platform="tpu", rows=CONFIG["rows"]):
    path = "indices/knn/scheduler/requests"
    def stats(n):
        return {"indices": {"knn": {"scheduler": {"requests": n}}}}
    return {"trace": {"busy_s": busy_s, "window_s": 5.0},
            "trace_before": stats(100), "trace_after": stats(100 + searches),
            "config": CONFIG, "rows": rows, "device_kind": "TPU v5 lite",
            "platform": platform}, path


def test_the_mesh_roofline_counts_one_shard_in_int8():
    ctx, path = _ctx(1000, 0.010)
    spec = {"quantity": "roofline", "searches": [path]}
    peaks = roofline.peaks_for("TPU v5 lite")
    shard = CONFIG["rows"] // CONFIG["chips"]
    compute = 2.0 * 1000 * shard * 768 / peaks["ops_per_s"]["int8"]
    memory = shard * 768 * 1 / peaks["bytes_per_s"]
    got = reader.read(spec, ctx)
    assert got == pytest.approx(100 * max(compute, memory) / 0.010)
    # a quarter of what the one-device reader would say of all the rows
    whole = roofline.share_percent(1000, CONFIG["rows"], 768, "int8", peaks,
                                   0.010)
    assert got == pytest.approx(whole / 4)
    # nothing to take a share of: a CPU rehearsal, an empty trace, no trace
    assert reader.read(spec, _ctx(1000, 0.010, platform="cpu")[0]) is None
    assert reader.read(spec, _ctx(1000, 0.0)[0]) is None
    assert reader.read(spec, _ctx(0, 0.010)[0]) is None
    assert reader.read(spec, dict(ctx, trace=None)) is None
    with pytest.raises(ValueError):
        reader.read({"quantity": "other"}, ctx)


@pytest.mark.parametrize("queries", [1, 8, 64, 1000, 10 ** 6])
def test_the_mesh_roofline_cannot_pass_100_percent_at_the_floor(queries):
    """A device that took exactly the least time reads 100, whatever Q."""
    peaks = roofline.peaks_for("TPU v5 lite")
    shard = CONFIG["rows"] // CONFIG["chips"]
    least = roofline.least_seconds(queries, shard, 768, "int8",
                                   peaks)["seconds"]
    ctx, path = _ctx(queries, least)
    got = reader.read({"quantity": "roofline", "searches": [path]}, ctx)
    assert got == pytest.approx(100.0)
    slower = reader.read({"quantity": "roofline", "searches": [path]},
                         _ctx(queries, least * 3)[0])
    assert slower == pytest.approx(100.0 / 3)


def test_the_gather_share_is_the_all_gather_and_what_follows_it():
    def run(start):
        # one execution of the program: score 100, top-k 50, two
        # all-gathers 4 each, merge 12
        ops = [("%fusion = f32[8,32768] fusion(...)", start + 1, 100),
               ("%custom-call = (f32[8,10], s32[8,10]) custom-call", start + 102, 50),
               ("%all-gather-start = f32[4,8,10] all-gather-start(", start + 153, 4),
               ("%all-gather.1 = s32[4,8,10] all-gather(", start + 158, 4),
               ("%sort = (f32[8,40], s32[8,40]) sort(", start + 163, 12)]
        return ("jit__distributed_knn_impl(1)", start, 180), ops
    modules, ops = [], []
    for start in (1000, 5000, 9000):
        m, o = run(start)
        modules.append(m)
        ops.extend(o)
    # another program on the same device, with no all-gather in it
    modules.append(("jit_other(2)", 20000, 40))
    ops.append(("%fusion.9 = f32[8] fusion(", 20001, 30))
    devices = {"/device:TPU:0": (modules, ops),
               "/device:TPU:1": (list(modules), list(ops))}
    want = 100.0 * 3 * (4 + 4 + 12) / (3 * 170 + 30)
    assert reader.gather_share(devices) == pytest.approx(want)
    # no all-gather anywhere, or no operation at all: nothing to report
    assert reader.gather_share({"/device:TPU:0": (modules[-1:],
                                                   ops[-1:])}) is None
    assert reader.gather_share({}) is None


def test_the_reader_finds_the_run_s_trace_directory_as_run_py_does():
    bench = os.path.join(REPO, "benchmark")
    assert reader.trace_dir(["--workload", "mesh-knn-steady", "--seed", "1"]) \
        == os.path.join(bench, "out", "mesh-knn-steady", "trace_main")
    assert reader.trace_dir(["--workload", "w", "--out", "/tmp/x"]) == \
        "/tmp/x/trace_main"
    # no trace there: the share is left out and nothing is raised
    ctx, _ = _ctx(10, 0.01)
    assert reader.read({"quantity": "gather_share"}, ctx) is None


# ---------------------------------------------------------------------------
# the kind's comparison, with planted faults
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """Rows, queries and the answers a correct program gives: the int8
    scan's own top k and scores, as `_search` bodies."""
    corpus = Corpus(2 ** 31 + 28, CONFIG)
    docs = corpus.block_docs
    rows = corpus.rows([(b, docs) for b in range(ROWS // docs)])
    q, _ = rows.queries(0, QUERIES)
    ids, cos = reference.Int8Rows(rows).scan(q, K)
    answers = [(i.tolist(), reference.to_scores(c)) for i, c in zip(ids, cos)]
    return rows, q, answers


def _body(ids, scores):
    return json.dumps({"_shards": {"failed": 0}, "hits": {"hits": [
        {"_id": str(i), "_score": s} for i, s in zip(ids, scores)]}}).encode()


def _stats(single=0, host=0, used=(30, 30, 30, 30)):
    return {"indices": {"knn": {"host_mirror_searches": host},
                        "mesh": {"router": {"single_device": single}}},
            "device": {"memory": [{"bytes_in_use": int(u * 1e6)}
                                  for u in used]}}


def _judge(served, answers=None, after=None, control=False):
    rows, _q, good = served
    answers = good if answers is None else answers
    sample = loadgen.Sample(t0=0.0, seconds=1.0)
    for i, a in enumerate(answers):
        sample.add(loadgen.Item(i, "POST", "/bench/_search", b""), 0.01 * i,
                   0.01 * i, 0.01 * i + 0.005, 200, _body(*a))
    run = types.SimpleNamespace(
        child=types.SimpleNamespace(stop=lambda: None),
        args=argparse.Namespace(seed=5, control=control),
        cell=types.SimpleNamespace(config=CONFIG))
    state = knn.State(rows, dict(TRAFFIC, verify_sample=len(answers)))
    got = {"sample": sample, "before": _stats(),
           "after": _stats() if after is None else after}
    out = knn_int8.judge(run, state, got)
    return verify.judge(out["numbers"], CONFIG["limits"]), out


def test_a_correct_mesh_run_is_correct(served):
    compared, out = _judge(served)
    assert all(c["ok"] for c in compared.values()), compared
    assert set(compared) == set(CONFIG["limits"])
    assert compared["score_rms_err"]["value"] < 1e-6
    assert compared["recall_at_k"]["value"] > 0.97
    assert compared["device0_excess_shards"]["value"] == 0.0
    assert out["ok"] == [True] * QUERIES and out["rows"] == ROWS


def _not_ok(compared):
    return {name for name, c in compared.items() if not c["ok"]}


def test_planted_faults_fail_the_number_they_are_for(served):
    rows, _q, good = served
    nudged = [(ids, [s + 0.004 for s in scores]) for ids, scores in good]
    assert _not_ok(_judge(served, answers=nudged)[0]) == {"score_rms_err"}
    # scores of rows held in bf16, not in int8: another deployment
    assert _not_ok(_judge(served, answers=[
        (ids, reference.to_scores(c)) for (ids, _s), c in zip(
            good, rows.cosines(served[1], [a[0] for a in good]))])[0]) == \
        {"score_rms_err"}
    # one search of the window answered by one device
    assert _not_ok(_judge(served, after=_stats(single=1))[0]) == \
        {"single_device_searches"}
    assert _not_ok(_judge(served, after=_stats(host=2))[0]) == \
        {"host_mirror_searches"}
    # device 0 holds a whole copy beside its shard: four shards' worth
    shard_mb = ROWS / 4 * CONFIG["dims"] / 1e6
    whole = _stats(used=(30 + 4 * shard_mb, 30, 30, 30))
    compared, _ = _judge(served, after=whole)
    assert _not_ok(compared) == {"device0_excess_shards"}
    assert compared["device0_excess_shards"]["value"] == pytest.approx(
        4.0, abs=0.01)
    # where the allocator counts nothing, the number is left out
    compared, _ = _judge(served, after=dict(_stats(),
                                            device={"memory": [{}] * 4}))
    assert "device0_excess_shards" not in compared
    assert all(c["ok"] for c in compared.values())


def test_the_int4_control_fails_score_rms_err_and_nothing_else(served):
    _compared, out = _judge(served, control=True)
    ctl = dict(out["control"])
    scan_recall = ctl.pop("int4_scan_recall_at_k")
    judged = verify.judge(ctl, CONFIG["limits"])
    assert _not_ok(judged) == {"score_rms_err"}, judged
    limit = CONFIG["limits"]["score_rms_err"]["limit"]
    assert judged["score_rms_err"]["value"] > 20 * limit
    assert judged["recall_at_k"]["value"] >= 0.97
    # an int4 scan that also chose the rows loses more of the top 10
    # than the limit allows: why the control keeps the stated scan's rows
    assert 0.5 < scan_recall < 0.95


def test_the_reference_quantises_as_the_configuration_states():
    x = np.array([[0.6, -1.0, 0.25, 0.0], [0.0, 0.0, 0.0, 0.0]], np.float32)
    levels, scale = reference.quantise(x, 127)
    assert scale[0] == pytest.approx(1.0 / 127) and scale[1] > 0
    assert levels[0].tolist() == [76.0, -127.0, 32.0, 0.0]
    assert levels[1].tolist() == [0.0] * 4
    levels4, scale4 = reference.quantise(x, 7)
    assert levels4[0].tolist() == [4.0, -7.0, 2.0, 0.0]
    assert scale4[0] == pytest.approx(1.0 / 7)
    # nothing of the program, nothing of JAX
    with open(os.path.join(REPO, "benchmark", "kinds",
                           "knn_int8_reference.py")) as f:
        imports = [ln for ln in f.read().splitlines()
                   if ln.startswith(("import ", "from "))]
    assert not [ln for ln in imports
                if "jax" in ln or "elasticsearch_tpu" in ln]


# ---------------------------------------------------------------------------
# one whole run of the cell
# ---------------------------------------------------------------------------

def _run(tmp_path, devices, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "mesh-knn-steady", "--seed", str(2 ** 31 + 2801),
         "--seconds", "2", "--trace", str(trace), "--rehearse",
         "--rows", str(ROWS), "--out", str(tmp_path / f"out{devices}")],
        cwd=REPO, env=env, text=True, capture_output=True, timeout=240)


def test_a_rehearsal_of_the_cell_ends_with_the_contract_line(tmp_path):
    done = _run(tmp_path, 4, 1)
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["correct"] is True, done.stderr[-3000:]
    assert last["rehearsal"] is True and last["failed"] == 0
    assert last["device"]["count"] == 4
    assert last["device"]["platform"] == "cpu"
    # 2 s of Poisson arrivals at the cell's rate
    rate = TRAFFIC["rate_per_s"]
    assert 0.7 * 2 * rate <= last["attempted"] <= 1.3 * 2 * rate
    want = {m["name"] for m in BENCH["per_layer"]
            if "mesh-knn-steady" in m.get("workloads", [])}
    got = set(last["metrics"])
    # the two shares of a device trace have no chip to take a share of
    assert want - got == {"knn_roofline.mesh", "mesh_gather_share"}
    assert got <= want
    assert last["metrics"]["mesh_route_share"]["value"] == 100.0
    assert last["metrics"]["mesh_fallbacks"]["value"] == 0.0
    assert last["metrics"]["window_compiles.mesh"]["value"] == 0.0
    assert last["metrics"]["mesh_collective_bytes_per_batch"]["value"] >= 320
    assert last["metrics"]["mesh_guard_wait_mean_ms"]["value"] >= 0
    compared = last["compared"]
    assert compared["single_device_searches"] == {"value": 0, "limit": 0}
    assert compared["host_mirror_searches"] == {"value": 0, "limit": 0}
    assert compared["recall_at_k"]["value"] >= 0.95
    assert compared["score_rms_err"]["value"] < \
        CONFIG["limits"]["score_rms_err"]["limit"]
    # the CPU's allocator counts nothing: the number is left out there
    assert "device0_excess_shards" not in compared


def test_fewer_devices_than_the_cell_asks_for_give_no_line(tmp_path):
    short = _run(tmp_path, 2, 0)
    assert short.returncode != 0
    assert not [ln for ln in short.stdout.splitlines() if ln.startswith("{")]
