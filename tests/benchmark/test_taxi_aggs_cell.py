"""The cell `taxi-aggs-steady`: the comparison of its kind
(`benchmark/kinds/taxi_aggs.py`) on the reference's own answers with
planted faults, the control, the roofline's reader on hand-made inputs,
and one whole rehearsal on the CPU.

The faults are planted in the ANSWERS, before `compare_answers` sees
them: each has to be caught by the number that is there for it, and by
no other."""

import copy
import datetime
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import verify  # noqa: E402
from benchmark.kinds import taxi_aggs  # noqa: E402
from benchmark.kinds import taxi_aggs_reference as reference  # noqa: E402
from benchmark.readers import taxi_aggs_trace_reduction  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
with open(os.path.join(REPO, "benchmark", "configs",
                       "nyc-taxis-dash.json")) as _f:
    CONFIG = json.load(_f)
CELL = "taxi-aggs-steady"
with open(os.path.join(REPO, "benchmark", "traffic", CELL + ".json")) as _f:
    MIX = json.load(_f)
ROWS, REQUESTS = 32768, 90
LIMIT_S = 240
OWN = ("answer_errors", "unanswered", "host_agg_nodes", "cached_answers")
NEW = ("aggs_narrow_share.taxi", "aggs_device_share.taxi",
       "aggs_device_mean_ms.taxi", "aggs_mask_mean_ms.taxi",
       "aggs_assemble_mean_ms.taxi", "server_took_mean_ms.taxi",
       "aggs_auto_round_mean_ms", "aggs_roofline.taxi")


def _make(n_rows):
    corpus = reference.RideCorpus(2 ** 31 + 4343, CONFIG, n_rows, MIX)
    docs = min(corpus.block_docs, n_rows)
    corpus.block_docs = docs
    rows = corpus.rows([(b, docs) for b in range(n_rows // docs)])
    requests = rows.requests(0, REQUESTS)
    return rows, requests, [rows.answer(*req) for req in requests]


@pytest.fixture(scope="module")
def made():
    return _make(ROWS)


@pytest.fixture(scope="module")
def sparse():
    """So few rides that some days hold none."""
    return _make(512)


def _judge(made, answers, host_agg_nodes=0, cached_answers=0):
    rows, requests, _good = made
    numbers = taxi_aggs.compare_answers(rows, requests, answers)
    numbers.pop("wrong_by_panel")
    numbers.update(unanswered=sum(a is None for a in answers),
                   host_agg_nodes=host_agg_nodes,
                   cached_answers=cached_answers)
    return verify.judge(numbers, CONFIG["limits"])


def test_the_references_own_answers_are_correct(made):
    got = _judge(made, made[2])
    assert set(got) == set(OWN)
    assert all(c["ok"] and c["value"] == 0 for c in got.values()), got
    # through JSON and back, as a server prints them
    assert _judge(made, json.loads(json.dumps(made[2])))[
        "answer_errors"]["value"] == 0


def test_the_windows_choose_the_roundings_elasticsearch_does(made):
    rows, requests, good = made
    seen = {}
    for (panel, _t, width), answer in zip(requests, good):
        if panel == "autohisto_agg":
            seen.setdefault(width, set()).add(
                answer["aggregations"]["dropoffs_over_time"]["interval"])
    # the track's 21 days: three 7d buckets
    assert seen == {1: {"3h"}, 7: {"12h"}, 21: {"7d"}}


def _at(made, panel, width=None):
    return next(i for i, (name, _t, w) in enumerate(made[1])
                if name == panel and (width is None or w == width))


def _min_off_by_a_cent(made):
    out = copy.deepcopy(made[2])
    stats = out[_at(made, "distance_amount_agg")]["aggregations"][
        "distance_histo"]["buckets"][3]["total_amount_stats"]
    stats["min"] = round(stats["min"] - 0.01, 2)
    return out


def _interval_1d_for_7d(made):
    out = copy.deepcopy(made[2])
    node = out[_at(made, "autohisto_agg", 21)]["aggregations"][
        "dropoffs_over_time"]
    assert node["interval"] == "7d"
    node["interval"] = "1d"
    return out


def _a_missing_empty_day(made):
    """A day with no ride left out of a daily histogram (the sparse
    corpus's)."""
    out = copy.deepcopy(made[2])
    for answer in out:
        node = answer["aggregations"]["dropoffs_over_time"] \
            if "dropoffs_over_time" in answer["aggregations"] else {}
        buckets = node.get("buckets", [])
        empty = [j for j, b in enumerate(buckets) if not b["doc_count"]]
        if "interval" not in node and empty:
            del buckets[empty[0]]
            return out
    raise AssertionError("no daily answer with an empty day")


def _key_as_string_in_iso(made):
    out = copy.deepcopy(made[2])
    b = out[_at(made, "date_histogram_agg")]["aggregations"][
        "dropoffs_over_time"]["buckets"][0]
    b["key_as_string"] = datetime.datetime.fromtimestamp(
        b["key"] / 1000, datetime.timezone.utc).strftime(
            "%Y-%m-%dT%H:%M:%S.000Z")
    return out


def _a_sum_off_past_rounding(made):
    out = copy.deepcopy(made[2])
    stats = out[_at(made, "distance_amount_agg")]["aggregations"][
        "distance_histo"]["buckets"][1]["total_amount_stats"]
    stats["sum"] *= 1 + 1e-9
    return out


@pytest.mark.parametrize("fault", [
    _min_off_by_a_cent, _interval_1d_for_7d, _a_missing_empty_day,
    _key_as_string_in_iso, _a_sum_off_past_rounding],
    ids=["stats-min-off-by-a-cent", "interval-1d-for-7d",
         "a-missing-empty-day", "key_as_string-in-iso",
         "a-sum-off-by-1e-9"])
def test_a_planted_fault_fails_answer_errors_and_no_other(made, sparse,
                                                          fault):
    where = sparse if fault is _a_missing_empty_day else made
    got = _judge(where, fault(where))
    assert got["answer_errors"]["value"] == 1
    assert not got["answer_errors"]["ok"]
    assert all(c["ok"] for n, c in got.items() if n != "answer_errors")


def test_a_sum_within_its_rounding_is_correct(made):
    out = copy.deepcopy(made[2])
    stats = out[_at(made, "distance_amount_agg")]["aggregations"][
        "distance_histo"]["buckets"][1]["total_amount_stats"]
    stats["sum"] *= 1 + 4e-16           # the next double or two
    assert _judge(made, out)["answer_errors"]["value"] == 0


def test_a_fallback_a_cached_and_a_missing_answer_fail_their_numbers(made):
    good = made[2]
    got = _judge(made, good, host_agg_nodes=1)
    assert [n for n, c in got.items() if not c["ok"]] == ["host_agg_nodes"]
    got = _judge(made, good, cached_answers=1)
    assert [n for n, c in got.items() if not c["ok"]] == ["cached_answers"]
    missing = list(good)
    missing[4] = None
    got = _judge(made, missing)
    assert got["unanswered"]["value"] == 1 and not got["unanswered"]["ok"]


def test_the_control_fails_answer_errors_through_the_sums_alone(made):
    """Every `sum` accumulated in float32 row after row, the nearest
    precision below the stated exact sum: every distance answer is
    wrong, no date answer."""
    rows, requests, _good = made
    ctl = taxi_aggs.compare_answers(
        rows, requests, reference.control_answers(rows, requests))
    wrong = ctl["wrong_by_panel"]
    asked = sum(name == "distance_amount_agg" for name, *_ in requests)
    assert wrong["distance_amount_agg"] == asked == REQUESTS // 3
    assert wrong["autohisto_agg"] == wrong["date_histogram_agg"] == 0
    judged = verify.judge({"answer_errors": ctl["answer_errors"],
                           "unanswered": 0, "host_agg_nodes": 0,
                           "cached_answers": 0}, CONFIG["limits"])
    assert [n for n, c in judged.items() if not c["ok"]] == ["answer_errors"]


def test_the_configuration_states_the_deployment():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["rows"] and set(CONFIG["reduced"]) == {"rows"}
    assert CONFIG["source_rows"] == 165346692 and CONFIG["architecture"] is None
    assert CONFIG["rows"] >= 131072 and CONFIG["rows"] % 131072 == 0
    assert CONFIG["server_settings"] == ["search.aggs.cost_router=false"]
    assert CONFIG["data"]["vector_field"] is None
    props = CONFIG["index"]["mappings"]["properties"]
    assert len(props) == 22
    for name in ("dropoff_datetime", "pickup_datetime"):
        assert props[name] == {"type": "date",
                               "format": "yyyy-MM-dd HH:mm:ss"}
    for name in ("trip_distance", "total_amount", "fare_amount", "tip_amount"):
        assert props[name] == {"type": "scaled_float", "scaling_factor": 100}
    for name in OWN:
        assert CONFIG["limits"][name] == {"limit": 0, "must": "=="}
    assert set(CONFIG["limits"]) == set(OWN)
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG["name"]
    with open(os.path.join(REPO, "benchmark", "traffic",
                           CELL + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "taxi_aggs" and traffic["loop"] == "open"
    assert traffic["clients"] == 64 and traffic["verify_sample"] == 1024
    assert f"{CONFIG['rows']:,}" in cell["why"]
    # the eight entries this cell brought; others may list it later
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        assert CELL in per_layer[name]["workloads"], name
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["search_p50_ms"]["workloads"]
    assert CELL in e2e["search_p95_ms"]["workloads"]


# ---------------------------------------------------------------------------
# the roofline's reader, on hand-made inputs
# ---------------------------------------------------------------------------

def _ctx(made, platform="tpu", busy_s=0.02):
    """A window of 10 s whose last 5 were traced, six requests: two sent
    before the traced seconds, one unanswered."""
    rows, requests, good = made
    s = types.SimpleNamespace(t0=100.0, seconds=10.0, index=[], sent=[],
                              done=[], status=[], raw=[])
    for i in range(6):
        s.index.append(i)
        s.sent.append(100.0 + (3.0, 4.9, 5.1, 6, 7, 8)[i])
        s.done.append(None if i == 5 else s.sent[-1] + 0.3)
        s.status.append(200)
        s.raw.append(json.dumps({"aggregations": good[i]["aggregations"]})
                     .encode())
    return {"trace": {"busy_s": busy_s, "window_s": 5.2}, "sample": s,
            "platform": platform, "device_kind": "TPU v5 lite",
            "config": CONFIG}, rows, requests


def test_the_reader_counts_four_bytes_a_column_a_matched_row(made):
    ctx, rows, requests = _ctx(made)
    spec = {"reader": "taxi_aggs_trace_reduction", "traced_seconds": 5.0}
    width = {"distance_amount_agg": 8, "autohisto_agg": 4,
             "date_histogram_agg": 4}
    counted = [2, 3, 4]                 # 0, 1 too early; 5 never
    least = sum(rows.matched_rows(*requests[i]) * width[requests[i][0]]
                for i in counted) / 819e9
    assert taxi_aggs_trace_reduction.read(spec, ctx) == pytest.approx(
        100 * least / 0.02)
    assert 0 < taxi_aggs_trace_reduction.read(spec, ctx) < 100
    assert taxi_aggs_trace_reduction.read(spec, _ctx(made, "cpu")[0]) \
        is None
    assert taxi_aggs_trace_reduction.read(spec, _ctx(made,
                                                     busy_s=0)[0]) is None


# ---------------------------------------------------------------------------
# the whole cell, once, on the CPU
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    root = tmp_path_factory.mktemp("taxi")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / "cache"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 4344),
         "--seconds", "3", "--trace", "1", "--rehearse", "--rows",
         str(ROWS), "--control", "--out", str(root / "out")],
        cwd=REPO, env=env, text=True, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE)
    try:
        stdout, stderr = proc.communicate(timeout=LIMIT_S)
    except subprocess.TimeoutExpired:
        proc.kill()
        stdout, stderr = proc.communicate()
        pytest.fail(f"the rehearsal passed its {LIMIT_S}s limit:\n"
                    + stderr[-2000:])
    assert proc.returncode == 0, stderr[-3000:]
    return json.loads(stdout.splitlines()[-1]), stderr


def test_a_traced_rehearsal_of_the_cell_is_correct(rehearsal):
    last, stderr = rehearsal
    print("correct", last["correct"], "compared", last["compared"],
          "control", last.get("control"))
    assert last["correct"] is True and last["rehearsal"] is True, \
        stderr[-3000:]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert set(last["compared"]) == set(OWN)
    assert all(c == {"value": 0, "limit": 0}
               for c in last["compared"].values()), last["compared"]
    # the control: wrong in every distance answer, a third of the
    # sample, and in nothing else
    assert set(last["control"]) == {"answer_errors"}
    assert 0.25 * last["attempted"] <= last["control"]["answer_errors"] \
        <= 0.34 * last["attempted"] + 1
    assert "router_host_routed=0" in stderr and "fallback_reasons={}" \
        in stderr


def test_the_rehearsal_prints_the_cells_eight_new_metrics(rehearsal):
    last, _stderr = rehearsal
    want = {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [])}
    assert set(NEW) <= want             # counted from BENCHMARK.json
    got = {n: m["value"] for n, m in last["metrics"].items()}
    print({n: got.get(n) for n in sorted(want)})
    # a CPU has no chip to take a share of
    assert want - set(got) == {"aggs_roofline.taxi"}
    assert set(got) <= want
    assert got["aggs_narrow_share.taxi"] == 100.0
    assert got["aggs_device_share.taxi"] == 100.0
    assert 0 < got["aggs_auto_round_mean_ms"] < got["aggs_device_mean_ms.taxi"]
    for name in ("aggs_mask_mean_ms.taxi", "aggs_device_mean_ms.taxi",
                 "aggs_assemble_mean_ms.taxi"):
        assert 0 < got[name] <= got["server_took_mean_ms.taxi"], name
