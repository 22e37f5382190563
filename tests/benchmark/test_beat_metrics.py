"""The per-layer metrics that read the server's heartbeat:
`lock_wait_mean_ms`, `server_stalled_share`, `server_stalls`,
`rest_handle_cpu_share` and their `.sat` twins. All are data over readers
that were there: each file loads and names such a reader, reads what a
hand-made pair of `_nodes/stats` holds, reads NOTHING (and does not raise)
over the stats of a program without the heartbeat, and is printed by a CPU
rehearsal of each of its cells. What is checked of `BENCHMARK.json` is read
from it: no count and no position, so that entries appended after these
pass as they do.
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
BENCH_DIR = os.path.join(REPO, "benchmark")
LIMIT_S = 240

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

STEADY = ["knn-steady", "mesh-knn-steady", "dash-aggs-steady",
          "filtered-steady"]
SAT = ["knn-saturated"]
INTERPRETER, REST = "server interpreter", "REST and query phase"
# metric -> (cells, layer, the end-to-end metric it should move, source)
NEW = {
    "lock_wait_mean_ms": (STEADY, INTERPRETER, "search_p50_ms",
                          "program_span"),
    "lock_wait_mean_ms.sat": (SAT, INTERPRETER, "search_qps",
                              "program_span"),
    "server_stalled_share": (STEADY, INTERPRETER, "search_p95_ms",
                             "program_counter"),
    "server_stalled_share.sat": (SAT, INTERPRETER, "search_qps",
                                 "program_counter"),
    "server_stalls": (STEADY, INTERPRETER, "search_p95_ms",
                      "program_counter"),
    "rest_handle_cpu_share": (STEADY, REST, "search_p50_ms",
                              "program_counter"),
    "rest_handle_cpu_share.sat": (SAT, REST, "search_qps",
                                  "program_counter"),
}
# cell -> (rows of its rehearsal, virtual devices)
CELLS = {"knn-steady": (2048, 1), "knn-saturated": (2048, 1),
         "mesh-knn-steady": (4096, 4), "dash-aggs-steady": (32768, 1),
         "filtered-steady": (4096, 1)}


def _spec(name):
    with open(os.path.join(BENCH_DIR, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def _read(name, ctx):
    spec = _spec(name)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(spec, ctx)


def _stats(lock=(0, 0), stalls=0, stall_nanos=0, cpu=0, handle=(0, 0)):
    return {"telemetry": {
        "counters": {"runtime.stalls": stalls,
                     "runtime.stall_nanos": stall_nanos,
                     "rest.handle.cpu_nanos": cpu},
        "histograms": {
            "runtime.lock_wait": {"count": lock[0], "sum_nanos": lock[1]},
            "rest.handle": {"count": handle[0], "sum_nanos": handle[1]}}}}


BEFORE = _stats(lock=(1_000, 5_000_000), stalls=3,
                stall_nanos=400_000_000, cpu=2_000_000_000,
                handle=(10_000, 9_000_000_000))
AFTER = _stats(lock=(6_000, 30_000_000), stalls=5,
               stall_nanos=1_650_000_000, cpu=5_000_000_000,
               handle=(26_500, 29_000_000_000))
# 5,000 beats late by 25 ms in all; 2 stalls, 1.25 s of a 50 s window;
# 3 s of processor in 20 s of handlers
UNITS = {"lock_wait_mean_ms": "ms", "server_stalled_share": "%",
         "server_stalls": "count", "rest_handle_cpu_share": "%"}
WANT = {"lock_wait_mean_ms": 0.005, "server_stalled_share": 2.5,
        "server_stalls": 2.0, "rest_handle_cpu_share": 15.0}


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_is_data_over_a_reader_that_was_there(name):
    cells, layer, moves, source = NEW[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["workloads"] == cells
    assert (entry["layer"], entry["moves"], entry["source"]) == \
        (layer, moves, source)
    assert entry["unit"] == UNITS[name.replace(".sat", "")]
    spec = _spec(name)
    assert spec["reader"] in ("histogram_mean", "stats_ratio", "stats_delta")
    assert os.path.exists(os.path.join(BENCH_DIR, "readers",
                                       spec["reader"] + ".py"))
    # a twin reads what its steady form reads
    base = _spec(name.replace(".sat", ""))
    assert {k: v for k, v in spec.items() if k != "what"} == \
        {k: v for k, v in base.items() if k != "what"}


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_reader_reads_a_window_and_nothing_of_a_parent(name):
    ctx = {"before": BEFORE, "after": AFTER, "seconds": 50.0}
    assert _read(name, ctx) == pytest.approx(WANT[name.replace(".sat", "")])
    # a window in which nothing stalled reads 0, not nothing
    if name.startswith("server_stall"):
        assert _read(name, {"before": AFTER, "after": AFTER,
                            "seconds": 50.0}) == 0.0
    # the parent commit has no heartbeat: no counter, no histogram
    bare = {"telemetry": {"counters": {}, "histograms": {
        "rest.handle": {"count": 9, "sum_nanos": 9_000}}}}
    later = {"telemetry": {"counters": {}, "histograms": {
        "rest.handle": {"count": 19, "sum_nanos": 19_000}}}}
    assert _read(name, {"before": bare, "after": later,
                        "seconds": 50.0}) is None


def test_the_benchmark_gained_seven_entries_and_no_cell_of_filtered():
    """The seven entries are there once each, wherever they stand in
    `per_layer`; every cell they list, `filtered-steady` among them, is
    rehearsed below."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert set(NEW) <= set(names)
    assert len(names) == len(set(names)) <= 128
    assert {c for cells, *_rest in NEW.values() for c in cells} == set(CELLS)
    # each cell reports the end-to-end metric its new metrics should move
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for cells, _layer, moves, _source in NEW.values():
        assert all(c in e2e[moves]["workloads"] for c in cells)


# ---------------------------------------------------------------------------
# a rehearsal of each cell prints them
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsals(tmp_path_factory):
    """The contract line of a traced CPU rehearsal of each cell, all
    started together."""
    root = tmp_path_factory.mktemp("beat_cells")
    procs = {}
    for cell, (rows, devices) in CELLS.items():
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(root / f"{cell}_cache"))
        env.pop("XLA_FLAGS", None)
        if devices > 1:
            env["XLA_FLAGS"] = \
                f"--xla_force_host_platform_device_count={devices}"
        procs[cell] = subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "run.py"),
             "--workload", cell, "--seed", str(2 ** 31 + 3700 + len(procs)),
             "--seconds", "2", "--trace", "1", "--rehearse",
             "--rows", str(rows), "--out", str(root / cell)],
            cwd=REPO, env=env, text=True, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE)
    lines = {}
    try:
        for cell, proc in procs.items():
            try:
                stdout, stderr = proc.communicate(timeout=LIMIT_S)
            except subprocess.TimeoutExpired:
                pytest.fail(f"{cell}: the rehearsal passed {LIMIT_S}s")
            assert proc.returncode == 0, (cell, stderr[-3000:])
            lines[cell] = json.loads(stdout.splitlines()[-1])
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
                proc.communicate()
    return lines


@pytest.mark.parametrize("cell, name", [
    (cell, name) for name, (cells, *_rest) in NEW.items() for cell in cells])
def test_a_rehearsal_of_the_cell_prints_the_metric(rehearsals, cell, name):
    last = rehearsals[cell]
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    got = last["metrics"][name]
    assert got["unit"] == entry["unit"]
    value = got["value"]
    assert isinstance(value, float)
    base = name.replace(".sat", "")
    if base == "lock_wait_mean_ms":
        assert 0 <= value < 1000
    elif base == "server_stalls":
        assert value >= 0 and value == int(value)
    elif base == "server_stalled_share":
        assert 0 <= value <= 100
    else:
        # a handler's thread cannot run for longer than the handler took
        assert 0 < value <= 100
