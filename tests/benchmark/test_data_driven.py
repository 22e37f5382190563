"""The harness is driven by data: a configuration, a traffic mix, a traffic
kind (another request shape with its own comparison), an end-to-end metric,
a per-layer metric and two cells written as NEW files in a copy of the
benchmark run with no edit to a file that was there; a configuration of
another kind and entries appended at the end pass the benchmark's own
static tests; and `BENCHMARK.json` and the data files keep to the
characters and lengths the driver accepts."""

import json
import os
import re
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def _line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def _vector_field(config):
    """The vector field a configuration's data names, or None."""
    return config.get("data", {}).get("vector_field")


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(_line(w) for w in BENCH["command"])
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in BENCH["paths"])
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert len(configs) == len(BENCH["configs"]) <= 24
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(REPO, c["file"])) as f:
            held = json.load(f)
        # the file holds the configuration as it is run, and says what it
        # cut: for each key, the value run here and the source's
        assert held["name"] == c["name"] and held["source"] == c["source"]
        assert set(held["reduced"]) == set(c["reduced"])
        for key in c["reduced"]:
            assert key in held and "source_" + key in held, key
        # a vector field is held to its mapping; no other kind needs one
        if _vector_field(held):
            assert held["index"]["mappings"]["properties"][
                _vector_field(held)]["dims"] == held["dims"]
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert len(cells) == len(BENCH["workloads"]) <= 24
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.exists(os.path.join(BENCH_DIR, "traffic",
                                           w["traffic"] + ".json"))
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(cells) // 2)

    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    layer = {m["name"]: m for m in BENCH["per_layer"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert len(e2e) == len(BENCH["end_to_end"]) <= 16
    assert len(layer) == len(BENCH["per_layer"]) <= 128
    assert not set(e2e) & set(layer)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e
        # every cell the metric lists reports the metric it should move
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert "workloads" not in moved or cell in moved["workloads"]
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert all(c in cells for c in m.get("workloads", []))
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    for cell in cells:
        mine = [m for m in BENCH["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]
        assert len(mine) >= 2
        assert any("workloads" not in m or cell in m["workloads"]
                   for m in BENCH["per_layer"])


def test_every_data_file_is_there_and_named_as_the_driver_accepts():
    for root in BENCH["paths"]:
        for here, _dirs, names in os.walk(os.path.join(REPO, root)):
            if "__pycache__" in here or os.sep + "out" in here:
                continue
            for n in names:
                rel = os.path.relpath(os.path.join(here, n), REPO)
                assert PATH.match(rel), rel
    def modules(sub):
        return {n[:-3] for n in os.listdir(os.path.join(BENCH_DIR, sub))
                if n.endswith(".py") and n != "__init__.py"}

    readers, kinds = modules("readers"), modules("kinds")
    for w in BENCH["workloads"]:
        with open(os.path.join(BENCH_DIR, "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert traffic["kind"] in kinds and _line(traffic["why"])
        assert traffic["loop"] in ("closed", "open")
    for m in BENCH["end_to_end"]:
        with open(os.path.join(BENCH_DIR, "end_to_end",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["statistic"] in ("setup_seconds", "latency_percentile",
                                     "rate_in_window") and _line(spec["what"])
    for m in BENCH["per_layer"]:
        with open(os.path.join(BENCH_DIR, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] in readers and _line(spec["what"])
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["source"] and "TPU v5 lite" in peaks["devices"]
    for c in BENCH["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            held = json.load(f)
        limits = held["limits"]
        assert limits
        assert all(v["must"] in (">=", "<=", "==") for v in limits.values())
        # a search over vectors is held to the recall the source states
        if _vector_field(held):
            assert limits["recall_at_k"] == {"limit": 0.95, "must": ">="}
            assert limits["score_rms_err"]["limit"] > 0


# ---------------------------------------------------------------------------
# a new cell as files only
# ---------------------------------------------------------------------------

NEW_CONFIG = {
    "name": "tiny-mesh4",
    "source": "a test's own: 64-d cosine rows sharded over a 4-device mesh",
    "rows": 4096, "source_rows": 4096, "dims": 64, "similarity": "cosine",
    "device_dtype": "bf16", "k": 10, "chips": 4, "reduced": {},
    "assumed": [],
    "server_settings": ["search.mesh.enabled=true",
                        "search.mesh.num_shards=4",
                        "search.mesh.min_rows=1"],
    "index": {"settings": {"number_of_shards": 1, "number_of_replicas": 0},
              "mappings": {"properties": {
                  "vec": {"type": "dense_vector", "dims": 64,
                          "similarity": "cosine"},
                  "colour": {"type": "keyword"}}}},
    "load": {"settings": {"index.translog.durability": "async",
                          "refresh_interval": "-1"},
             "then": ["_flush", "_refresh"]},
    "data": {"block_docs": 1024, "centres": 32, "row_noise": 0.6,
             "query_noise": 0.3, "vector_field": "vec",
             "fields": [{"name": "colour", "type": "keyword", "values": 4,
                         "zipf_s": 1.0, "prefix": "c"}]},
    "guarantees": [],
    "limits": {"recall_at_k": {"limit": 0.95, "must": ">="},
               "score_rms_err": {"limit": 0.01, "must": "<="},
               "filter_violations": {"limit": 0, "must": "=="},
               "unanswered": {"limit": 0, "must": "=="},
               "host_mirror_searches": {"limit": 0, "must": "=="},
               "count_diff": {"limit": 0, "must": "=="}},
}
NEW_TRAFFIC = {
    "kind": "knn", "loop": "open", "clients": 16, "rate_per_s": 40,
    "burst": {"every_s": 1.0, "size": [3, 9]},
    "request": {"k": 10, "num_candidates": 100, "filter_field": "colour"},
    "verify_sample": 64, "why": "a test's own",
}
# another request shape with its own comparison: a file under kinds/
NEW_KIND = '''"""A test's own traffic kind: `_count` over the loaded rows."""
import json

from benchmark import loadgen
from benchmark.setup import INDEX, create_index, load_rows


def prepare(run):
    create_index(run.child, run.cell.config, "load")
    blocks = load_rows(run.child, run.corpus, run.n_rows)
    for step in run.cell.config["load"]["then"]:
        run.child.ok("POST", f"/{INDEX}/{step}")
    return sum(n for _b, n in blocks)


def make_items(rows, first, count):
    return [loadgen.Item(first + j, "GET", f"/{INDEX}/_count", None)
            for j in range(count)]


def judge(run, rows, got):
    run.child.stop()
    sample = got["sample"]
    counts = [json.loads(raw)["count"] if st == 200 else None
              for raw, st in zip(sample.raw, sample.status)]
    ok = [c is not None for c in counts]
    off = max(abs(c - rows) for c in counts if c is not None)
    return {"ok": ok, "control": None, "rows": rows,
            "numbers": {"count_diff": off, "unanswered": ok.count(False)}}
'''
COUNT_TRAFFIC = {"kind": "count", "loop": "closed", "clients": 4,
                 "pool_per_s": 100, "why": "a test's own"}
NEW_END_TO_END = {"statistic": "latency_percentile", "q": 99,
                  "what": "a test's own: the 99th percentile"}
NEW_LATENESS = {"reader": "client_sample", "statistic": "stall_max_ms",
                "what": "a test's own"}
NEW_METRIC = {"reader": "stats_delta", "paths": ["indices/knn/mesh_searches"],
              "what": "batches the mesh program answered in the window"}


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A mesh configuration on 4 (virtual CPU) devices, a bursty open-loop
    mix, a cell and a per-layer metric: added, never edited."""
    work = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, work / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(REPO, "elasticsearch_tpu"),
               work / "elasticsearch_tpu")
    os.symlink(os.path.join(REPO, "native"), work / "native")
    before = {p: p.read_bytes() for p in (work / "benchmark").rglob("*")
              if p.is_file()}
    (work / "benchmark/configs/tiny-mesh4.json").write_text(
        json.dumps(NEW_CONFIG))
    (work / "benchmark/traffic/bursty-open.json").write_text(
        json.dumps(NEW_TRAFFIC))
    (work / "benchmark/layer_metrics/mesh_searches.tiny.json").write_text(
        json.dumps(NEW_METRIC))
    (work / "benchmark/kinds/count.py").write_text(NEW_KIND)
    (work / "benchmark/traffic/count-closed.json").write_text(
        json.dumps(COUNT_TRAFFIC))
    (work / "benchmark/end_to_end/count_p99_ms.json").write_text(
        json.dumps(NEW_END_TO_END))
    (work / "benchmark/layer_metrics/count_stall_ms.json").write_text(
        json.dumps(NEW_LATENESS))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "tiny-mesh4", "source": NEW_CONFIG["source"],
        "file": "benchmark/configs/tiny-mesh4.json", "reduced": [],
        "why": "a test's own"})
    bench["workloads"].append({
        "name": "mesh-bursty", "config": "tiny-mesh4",
        "traffic": "bursty-open", "chips": 4, "why": "a test's own"})
    bench["workloads"].append({
        "name": "mesh-count", "config": "tiny-mesh4",
        "traffic": "count-closed", "chips": 4, "why": "a test's own"})
    for m in bench["end_to_end"]:
        if m["name"] in ("search_p50_ms", "search_p95_ms"):
            m["workloads"].append("mesh-bursty")
    bench["end_to_end"].append({
        "name": "count_p99_ms", "unit": "ms", "better": "lower",
        "bound": 0.25, "source": "host_clock", "workloads": ["mesh-count"]})
    bench["per_layer"].append({
        "name": "count_stall_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "whole request",
        "moves": "count_p99_ms", "workloads": ["mesh-count"]})
    bench["per_layer"].append({
        "name": "mesh_searches.tiny", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "device kernels",
        "moves": "search_p50_ms", "workloads": ["mesh-bursty"]})
    (work / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))

    def run(trace, workload="mesh-bursty"):
        return subprocess.run(
            [sys.executable, str(work / "benchmark/run.py"),
             "--workload", workload, "--seed", "11", "--seconds", "2",
             "--trace", str(trace), "--rehearse"],
            cwd=work, env=env, text=True, capture_output=True, timeout=240)

    done = run(1)
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["correct"] is True, done.stderr[-3000:]
    assert last["device"]["count"] == 4 and last["device"]["platform"] == "cpu"
    # 2 s at 40/s plus one burst of 3..9 at 1 s
    assert 83 <= last["attempted"] <= 89
    # they rode the mesh program: the counter counts its dispatched batches,
    # one request or more each (how many depends on the machine's load)
    assert 0 < last["metrics"]["mesh_searches.tiny"]["value"] <= \
        last["attempted"]
    assert "window_compiles" not in last["metrics"]   # not this cell's
    # every request carried a filter, and every hit satisfied it
    assert last["compared"]["filter_violations"] == {"value": 0, "limit": 0}

    # the other request shape, its comparison and its end-to-end metric
    done = run(0, "mesh-count")
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["correct"] is True, done.stderr[-3000:]
    assert set(last["metrics"]) == {"setup_s", "count_p99_ms"}
    assert last["metrics"]["count_p99_ms"]["value"] > 0
    assert last["compared"]["count_diff"] == {"value": 0, "limit": 0}
    assert set(last["compared"]) == {"count_diff", "unanswered"}
    # no file that was there was touched
    for p, content in before.items():
        assert p.read_bytes() == content, p

    # the same cell on two devices: fewer chips than it asks for, no result
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    short = run(0)
    assert short.returncode != 0
    assert not [ln for ln in short.stdout.splitlines() if ln.startswith("{")]


# the benchmark's own tests that read BENCHMARK.json and start no run
STATIC = [
    "test_data_driven.py::test_benchmark_json_keeps_to_the_contract",
    "test_data_driven.py::"
    "test_every_data_file_is_there_and_named_as_the_driver_accepts",
    "test_beat_metrics.py::test_the_metric_is_data_over_a_reader_that_was_there",
    "test_beat_metrics.py::"
    "test_the_reader_reads_a_window_and_nothing_of_a_parent",
    "test_beat_metrics.py::"
    "test_the_benchmark_gained_seven_entries_and_no_cell_of_filtered",
    "test_stage_metrics.py::"
    "test_the_new_metrics_are_data_files_over_the_readers_that_were_there",
]
# metric -> (its file, the cells it lists)
APPENDED = {
    "aggs_plan_mean_ms.weekly": (
        {"reader": "histogram_mean", "sum_of": ["aggs.plan"],
         "count_of": "aggs.plan", "scale": 1e-06, "what": "a test's own"},
        ["logs-weekly-steady"]),
    "aggs_mask_scatter_share.weekly": (
        {"reader": "stats_ratio",
         "paths": ["telemetry/counters/aggs.mask_scattered"],
         "over": ["telemetry/counters/aggs.mask_scattered",
                  "telemetry/counters/aggs.mask_searched"],
         "scale": 100, "what": "a test's own"},
        ["logs-weekly-steady"]),
    "server_took_mean_ms.both": (
        {"reader": "histogram_mean", "sum_of": ["search.took"],
         "count_of": "search.took", "scale": 1e-06, "what": "a test's own"},
        ["logs-weekly-steady", "filtered-steady"]),
}


def _without_vectors(config):
    """A deployment of log lines with no vector at all, cut in rows and in
    indices (seven weekly indices held as one): no vector field, mapping,
    width or limit of a search over vectors."""
    config = json.loads(json.dumps(config))
    field = config["data"].pop("vector_field")
    del config["index"]["mappings"]["properties"][field], config["dims"]
    for name in ("recall_at_k", "score_rms_err", "filter_violations"):
        del config["limits"][name]
    config.update(name="logs-weekly", indices=1, source_indices=7)
    config["reduced"] = {"rows": "a test's own", "indices": "a test's own"}
    return config


def test_a_configuration_of_another_kind_and_appended_entries_pass(tmp_path):
    """A configuration with no vector field, cut in a key besides `rows`,
    a one-chip cell, and three per-layer entries appended at the END of
    `per_layer` (one of them also in `filtered-steady`): the benchmark's
    own static tests pass over them in a copy, and no file that was there
    is edited; only `BENCHMARK.json` gains entries."""
    work = tmp_path / "checkout"
    ignore = shutil.ignore_patterns("out", "__pycache__")
    shutil.copytree(BENCH_DIR, work / "benchmark", ignore=ignore)
    shutil.copytree(os.path.join(REPO, "tests", "benchmark"),
                    work / "tests" / "benchmark", ignore=ignore)
    os.symlink(os.path.join(REPO, "elasticsearch_tpu"),
               work / "elasticsearch_tpu")
    before = {p: p.read_bytes() for d in ("benchmark", "tests")
              for p in (work / d).rglob("*") if p.is_file()}

    with open(os.path.join(BENCH_DIR, "configs", "http-logs-dash.json")) as f:
        config = _without_vectors(json.load(f))
    (work / "benchmark/configs/logs-weekly.json").write_text(
        json.dumps(config))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "logs-weekly", "source": config["source"],
        "file": "benchmark/configs/logs-weekly.json",
        "reduced": ["rows", "indices"], "why": "a test's own"})
    bench["workloads"].append({
        "name": "logs-weekly-steady", "config": "logs-weekly",
        "traffic": "dash-aggs-steady", "chips": 1, "why": "a test's own"})
    for m in bench["end_to_end"]:
        if m["name"] in ("search_p50_ms", "search_p95_ms"):
            m["workloads"].append("logs-weekly-steady")
    for name, (spec, cells) in APPENDED.items():
        (work / "benchmark/layer_metrics" / f"{name}.json").write_text(
            json.dumps(spec))
        bench["per_layer"].append({
            "name": name, "unit": "%" if "share" in name else "ms",
            "better": "higher" if "share" in name else "lower",
            "source": "program_counter" if "share" in name
            else "program_span",
            "layer": "aggregation engine", "moves": "search_p50_ms",
            "workloads": cells})
    (work / "BENCHMARK.json").write_text(json.dumps(bench))

    def pytest(*args):
        return subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             *args], cwd=work, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            text=True, capture_output=True, timeout=240)

    done = pytest(*[os.path.join("tests", "benchmark", t) for t in STATIC])
    assert done.returncode == 0, done.stdout[-3000:]
    assert " passed" in done.stdout and "failed" not in done.stdout
    # the int8 control of a search over vectors is not asked of it
    done = pytest("--collect-only", "tests/benchmark/test_control.py")
    assert done.returncode == 0, done.stdout[-3000:]
    assert "nytimes-256-angular" in done.stdout
    assert "logs-weekly" not in done.stdout
    for p, content in before.items():
        assert p.read_bytes() == content, p


def test_no_result_where_only_the_benchmark_is(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths`: no program to run, so no result and a code other than 0."""
    work = tmp_path / "bare"
    shutil.copytree(BENCH_DIR, work / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), work / "BENCHMARK.json")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "knn-saturated",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=work, env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True,
        capture_output=True, timeout=120)
    assert done.returncode != 0
    assert not [ln for ln in done.stdout.splitlines() if ln.startswith("{")]
