"""The per-layer metrics that read the program's stages (ISSUE 26): a
`--rehearse --trace 1` run of each cell at a few thousand rows prints every
one of them, each from the span or counter its file names, and the sums the
stages were cut for hold as inequalities even on the CPU (a stage lies
inside what it splits). Numbers from these runs are counts and orderings,
never speeds. Both runs start together, each under its own limit."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LIMIT_S = 240

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

FRONT = ["http_read_mean_ms", "pool_wait_mean_ms", "rest_handle_mean_ms",
         "loop_wake_mean_ms", "http_respond_mean_ms"]
DISPATCH = ["dispatch_prepare_mean_ms", "dispatch_h2d_mean_ms",
            "dispatch_launch_mean_ms", "sync_wait_mean_ms", "d2h_mean_ms",
            "land_mean_ms"]
NEW = {
    "knn-steady": FRONT + ["batch_form_mean_ms"] + DISPATCH
    + ["idle_no_request_share", "idle_pickup_share"],
    "knn-saturated": [n + ".sat" for n in FRONT]
    + ["keepalive_gap_mean_ms.sat", "idle_no_request_share.sat",
       "idle_pickup_share.sat", "gc_gen2_collections.sat"],
}


def test_the_new_metrics_are_data_files_over_the_readers_that_were_there():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for cell, names in NEW.items():
        for name in names:
            assert by_name[name]["workloads"] == [cell]
            assert by_name[name]["source"] in ("program_span",
                                               "program_counter")
            with open(os.path.join(REPO, "benchmark", "layer_metrics",
                                   name + ".json")) as f:
                spec = json.load(f)
            assert spec["reader"] in ("histogram_mean", "stats_ratio",
                                      "stats_delta")


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    root = tmp_path_factory.mktemp("stages")
    procs = {}
    for i, cell in enumerate(NEW):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(root / f"{cell}_cache"))
        env.pop("XLA_FLAGS", None)
        procs[cell] = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
             "--workload", cell, "--seed", str(2 ** 31 + 26 + i),
             "--seconds", "3", "--trace", "1", "--rehearse",
             "--rows", "3072", "--out", str(root / cell)],
            cwd=REPO, env=env, text=True, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE)
    out = {}
    for cell, proc in procs.items():
        try:
            stdout, stderr = proc.communicate(timeout=LIMIT_S)
        except subprocess.TimeoutExpired:
            proc.kill()
            stdout, stderr = proc.communicate()
            pytest.fail(f"{cell} passed its {LIMIT_S}s limit:\n"
                        + stderr[-2000:])
        assert proc.returncode == 0, stderr[-2000:]
        with open(root / cell / "sample.json") as f:
            sample = json.load(f)
        out[cell] = (json.loads(stdout.splitlines()[-1]), sample)
    return out


@pytest.mark.parametrize("cell", sorted(NEW))
def test_a_traced_rehearsal_prints_every_new_metric_of_its_cell(lines, cell):
    last, _sample = lines[cell]
    assert last["correct"] is True and last["rehearsal"] is True
    metrics = {n: m["value"] for n, m in last["metrics"].items()}
    for name in NEW[cell]:
        assert name in metrics, f"{name} missing from {sorted(metrics)}"
    for name in NEW[cell]:
        if name.endswith("_ms") or name.endswith("_ms.sat"):
            assert metrics[name] > 0, name
    # what the benchmark read before still reads
    for name in ("server_took_mean_ms", "queue_wait_mean_ms",
                 "dispatch_sync_mean_ms", "window_compiles") \
            if cell == "knn-steady" else ("batch_fill.sat",):
        assert name in metrics


@pytest.mark.parametrize("cell", sorted(NEW))
def test_the_stages_lie_inside_what_they_split(lines, cell):
    last, sample = lines[cell]
    m = {n: v["value"] for n, v in last["metrics"].items()}
    sfx = ".sat" if cell == "knn-saturated" else ""
    # socket to socket lies inside what the client saw, sent to answered
    seen = [d - s for d, s in zip(sample["done"], sample["sent"])
            if d is not None]
    client_ms = sum(seen) / len(seen) * 1000.0
    front = sum(m[n + sfx] for n in FRONT)
    assert 0 < front <= client_ms * 1.05, (front, client_ms)
    # the device-starved shares are shares of one window
    idle = m["idle_no_request_share" + sfx] + m["idle_pickup_share" + sfx]
    assert 0 <= m["idle_pickup_share" + sfx] <= idle <= 100.5
    if cell == "knn-steady":
        # six parts of the two stages the old metric sums
        parts = sum(m[n] for n in DISPATCH)
        assert 0.5 * m["dispatch_sync_mean_ms"] < parts \
            <= m["dispatch_sync_mean_ms"] * 1.0001
        assert m["rest_handle_mean_ms"] <= client_ms
    else:
        assert m["gc_gen2_collections.sat"] >= 0
        # a closed loop's cycle: the five stages and the gap between a
        # response and the next request are all of a client's 1/rate
        cycle_ms = 64 / (len(seen) / sample["seconds"]) * 1000.0
        whole = front + m["keepalive_gap_mean_ms.sat"]
        assert 0.8 * cycle_ms <= whole <= 1.1 * cycle_ms, (whole, cycle_ms)
