"""The cell `dash-aggs-steady` (ISSUE 35): the comparison of its kind
(`benchmark/kinds/aggs.py`) on the reference's own answers with planted
faults, the control, the roofline's count and its reader on hand-made
inputs, and one whole rehearsal on the CPU.

The faults are planted in the ANSWERS, before `compare_answers` sees them:
each has to be caught by the number that is there for it, and by no
other."""

import copy
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

from benchmark import roofline_aggs, verify  # noqa: E402
from benchmark.kinds import aggs  # noqa: E402
from benchmark.kinds import aggs_reference as reference  # noqa: E402
from benchmark.readers import aggs_trace_reduction  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
with open(os.path.join(REPO, "benchmark", "configs",
                       "http-logs-dash.json")) as _f:
    CONFIG = json.load(_f)
CELL = "dash-aggs-steady"
# a week's bytes pass 2^24 from about 20,000 rows on: below that a
# float32 sum is exact and the control is correct, as it should be
ROWS, REQUESTS = 32768, 256
LIMIT_S = 240
OWN = ("answer_errors", "unanswered", "host_agg_nodes", "cached_answers")


def _make(n_rows):
    corpus = reference.LogCorpus(2 ** 31 + 3535, CONFIG, n_rows)
    docs = corpus.block_docs
    rows = corpus.rows([(b, docs) for b in range(n_rows // docs)])
    requests = rows.requests(0, REQUESTS)
    good = [rows.answer(*req) for req in requests]
    return rows, requests, good


@pytest.fixture(scope="module")
def made():
    return _make(ROWS)


@pytest.fixture(scope="module")
def sparse():
    """So few rows that some hours hold none: the faults' corpus."""
    return _make(4096)


def _judge(made, answers, host_agg_nodes=0, cached_answers=0):
    rows, requests, _good = made
    numbers = aggs.compare_answers(rows, requests, answers)
    numbers.pop("wrong_by_panel")
    numbers.update(unanswered=sum(a is None for a in answers),
                   host_agg_nodes=host_agg_nodes,
                   cached_answers=cached_answers)
    return verify.judge(numbers, CONFIG["limits"])


def test_the_references_own_answers_are_correct(made):
    got = _judge(made, made[2])
    assert set(got) == set(OWN)
    assert all(c["ok"] and c["value"] == 0 for c in got.values()), got
    # through JSON and back, a sum as the float a server prints
    as_served = json.loads(json.dumps(made[2]))
    for a in as_served:
        tile = a["aggregations"].get("total_bytes")
        if tile:
            tile["value"] = float(tile["value"])
    assert _judge(made, as_served)["answer_errors"]["value"] == 0


def _at(made, panel):
    return next(i for i, (name, _t) in enumerate(made[1]) if name == panel)


def _count_off_by_one(made):
    out = copy.deepcopy(made[2])
    out[_at(made, "hourly")]["aggregations"]["by_hour"]["buckets"][7][
        "doc_count"] += 1
    return out


def _a_missing_empty_bucket(made):
    out = copy.deepcopy(made[2])
    buckets = out[_at(made, "hourly")]["aggregations"]["by_hour"]["buckets"]
    del buckets[next(j for j, b in enumerate(buckets)
                     if b["doc_count"] == 0)]
    return out


def _two_terms_swapped(made):
    out = copy.deepcopy(made[2])
    buckets = out[_at(made, "status-in-range")]["aggregations"][
        "by_status"]["buckets"]
    buckets[0], buckets[1] = buckets[1], buckets[0]
    return out


def _a_sum_plus_one(made):
    out = copy.deepcopy(made[2])
    out[_at(made, "bytes-by-hour")]["aggregations"]["total_bytes"][
        "value"] += 1
    return out


def _a_term_under_an_hour_off(made):
    out = copy.deepcopy(made[2])
    buckets = out[_at(made, "status-by-hour")]["aggregations"]["by_hour"][
        "buckets"]
    inner = next(b for b in buckets if b["doc_count"])["by_status"]
    inner["sum_other_doc_count"] += 1
    return out


def _total_hits_off(made):
    out = copy.deepcopy(made[2])
    out[_at(made, "status-in-range")]["total"]["value"] += 1
    return out


def _a_key_as_string_off(made):
    out = copy.deepcopy(made[2])
    b = out[_at(made, "bytes-by-hour")]["aggregations"]["by_hour"][
        "buckets"][0]
    b["key_as_string"] = b["key_as_string"].replace(".000Z", "Z")
    return out


@pytest.mark.parametrize("fault", [
    _count_off_by_one, _a_missing_empty_bucket, _two_terms_swapped,
    _a_sum_plus_one, _a_term_under_an_hour_off, _total_hits_off,
    _a_key_as_string_off],
    ids=["a-count-off-by-one", "a-missing-empty-bucket",
         "two-terms-swapped", "a-sum-plus-one",
         "sum_other_doc_count-under-an-hour", "hits-total-off",
         "key_as_string-off"])
def test_a_planted_fault_fails_answer_errors_and_no_other(sparse, fault):
    got = _judge(sparse, fault(sparse))
    assert got["answer_errors"]["value"] == 1
    assert not got["answer_errors"]["ok"]
    assert all(c["ok"] for n, c in got.items() if n != "answer_errors")


def test_a_fallback_a_cached_and_a_missing_answer_fail_their_numbers(made):
    good = made[2]
    got = _judge(made, good, host_agg_nodes=1)
    assert [n for n, c in got.items() if not c["ok"]] == ["host_agg_nodes"]
    got = _judge(made, good, cached_answers=1)
    assert [n for n, c in got.items() if not c["ok"]] == ["cached_answers"]
    missing = list(good)
    missing[5] = None
    got = _judge(made, missing)
    assert got["unanswered"]["value"] == 1 and not got["unanswered"]["ok"]
    # what is no sound answer: an HTTP error, a failed shard, timed out,
    # not JSON, no aggregations
    ok = {"_shards": {"failed": 0}, "timed_out": False,
          "hits": {"total": {"value": 0, "relation": "eq"}},
          "aggregations": {}}
    assert aggs.parse_answer(json.dumps(ok).encode(), 200) is not None
    for bad in (dict(ok, _shards={"failed": 1}), dict(ok, timed_out=True),
                {k: v for k, v in ok.items() if k != "aggregations"}):
        assert aggs.parse_answer(json.dumps(bad).encode(), 200) is None
    assert aggs.parse_answer(json.dumps(ok).encode(), 503) is None
    assert aggs.parse_answer(b"<html>", 200) is None


def test_the_control_fails_answer_errors_through_the_sums_alone(made):
    """Every `sum` accumulated in float32, the nearest precision below
    the stated f64 / int64: a week's bytes pass 2^24, an hour's may
    not."""
    rows, requests, _good = made
    ctl = aggs.compare_answers(rows, requests,
                               reference.control_answers(rows, requests))
    wrong = ctl["wrong_by_panel"]
    asked = sum(name == "bytes-by-hour" for name, _t in requests)
    assert asked == REQUESTS // 4
    assert wrong["bytes-by-hour"] >= 0.9 * asked
    assert wrong["hourly"] == wrong["status-in-range"] \
        == wrong["status-by-hour"] == 0
    assert ctl["answer_errors"] == wrong["bytes-by-hour"]
    judged = verify.judge({"answer_errors": ctl["answer_errors"]},
                          CONFIG["limits"])
    assert not judged["answer_errors"]["ok"]
    # a week's total is where it goes wrong; its counts stay right
    req = next(r for r in requests if r[0] == "bytes-by-hour")
    exact = rows.answer(*req)["aggregations"]
    low = rows.answer(*req, sum_dtype=np.float32)["aggregations"]
    assert exact["total_bytes"]["value"] > 2 ** 24
    assert low["total_bytes"]["value"] != exact["total_bytes"]["value"]
    assert [b["doc_count"] for b in low["by_hour"]["buckets"]] == \
        [b["doc_count"] for b in exact["by_hour"]["buckets"]]


def test_the_configuration_states_the_deployment():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert CONFIG["source_rows"] == 247249096 and CONFIG["architecture"] is None
    assert CONFIG["rows"] >= 524288 and CONFIG["rows"] % 131072 == 0
    assert CONFIG["server_settings"] == ["search.aggs.cost_router=false"]
    props = CONFIG["index"]["mappings"]["properties"]
    assert props["@timestamp"] == {
        "type": "date", "format": "strict_date_optional_time||epoch_second"}
    assert props["clientip"] == {"type": "ip"}
    assert props["request"] == {"type": "text", "fields": {
        "raw": {"type": "keyword", "ignore_above": 256}}}
    assert props["status"] == props["size"] == {"type": "integer"}
    for name in OWN:
        assert CONFIG["limits"][name] == {"limit": 0, "must": "=="}
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG["name"]
    with open(os.path.join(REPO, "benchmark", "traffic",
                           CELL + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "aggs" and traffic["loop"] == "open"
    assert traffic["clients"] == 64 and traffic["verify_sample"] == 1024
    # so few a second that the issue's "down to a five" became a half
    assert traffic["rate_per_s"] % 0.5 == 0 and traffic["rate_per_s"] > 0
    assert f"{traffic['rate_per_s']}/s" in cell["why"]
    assert f"{CONFIG['rows']:,}" in cell["why"]


# ---------------------------------------------------------------------------
# the roofline's count and its reader, on hand-made inputs
# ---------------------------------------------------------------------------

PEAKS = {"bytes_per_s": 819e9}


def test_the_floor_counts_each_field_once_at_the_mappings_widths():
    props = CONFIG["index"]["mappings"]["properties"]
    width = {name: roofline_aggs.row_bytes(
        reference.body(name, 0 if span else None)["aggs"], props)
        for name, span in reference.PANELS}
    # date 8, integer 4; `size` read by two nodes of one request: once
    assert width == {"hourly": 8, "bytes-by-hour": 12, "status-in-range": 4,
                     "status-by-hour": 12}
    assert roofline_aggs.fields_read(
        reference.body("status-by-hour", 0)["aggs"]) == {"@timestamp",
                                                         "status"}
    with pytest.raises(KeyError):       # a type with no width: an error
        roofline_aggs.row_bytes({"a": {"terms": {"field": "request"}}},
                                props)


def test_matched_rows_are_read_off_the_answer(made):
    rows, requests, good = made
    for (panel, t), answer in zip(requests[:64], good[:64]):
        assert roofline_aggs.matched_rows(answer["aggregations"]) == \
            rows.matched_rows(panel, t)
    assert roofline_aggs.matched_rows({"total_bytes": {"value": 5}}) == 0
    # terms cut to their size: the rest is in sum_other_doc_count
    assert roofline_aggs.matched_rows({"t": {
        "sum_other_doc_count": 7,
        "buckets": [{"key": 200, "doc_count": 5}]}}) == 12


def test_the_share_is_least_time_over_busy_time():
    work = [(1_000_000, 8), (100_000, 12)]
    least = (8e6 + 1.2e6) / 819e9
    assert roofline_aggs.least_seconds(work, PEAKS) == pytest.approx(least)
    assert roofline_aggs.share_percent(work, PEAKS, 0.01) == pytest.approx(
        100 * least / 0.01)
    assert roofline_aggs.share_percent(work, PEAKS, least) == \
        pytest.approx(100.0)            # a device that did nothing else
    assert roofline_aggs.share_percent([], PEAKS, 0.01) is None
    assert roofline_aggs.share_percent(work, PEAKS, 0.0) is None


def _ctx(made, platform="tpu", busy_s=0.02):
    """A window of 10 s whose last 5 were traced, eight requests: two
    sent before the traced seconds, one unanswered, one an error."""
    rows, requests, good = made
    s = types.SimpleNamespace(t0=100.0, seconds=10.0, index=[], sent=[],
                              done=[], status=[], raw=[])
    for i in range(8):
        s.index.append(i)
        s.sent.append(100.0 + (3.0, 4.9, 5.1, 6, 7, 8, 9, 9.5)[i])
        s.done.append(None if i == 5 else s.sent[-1] + 0.3)
        s.status.append(503 if i == 6 else 200)
        s.raw.append(json.dumps({"aggregations": good[i]["aggregations"]})
                     .encode())
    return {"trace": {"busy_s": busy_s, "window_s": 5.2}, "sample": s,
            "platform": platform, "device_kind": "TPU v5 lite",
            "config": CONFIG}, rows, requests


def test_the_reader_counts_only_requests_sent_and_answered_in_the_trace(
        made):
    ctx, rows, requests = _ctx(made)
    spec = {"reader": "aggs_trace_reduction", "traced_seconds": 5.0}
    width = {"hourly": 8, "bytes-by-hour": 12, "status-in-range": 4,
             "status-by-hour": 12}
    counted = [2, 3, 4, 7]              # 0, 1 too early; 5 never; 6 a 503
    least = sum(rows.matched_rows(*requests[i]) * width[requests[i][0]]
                for i in counted) / 819e9
    assert aggs_trace_reduction.read(spec, ctx) == pytest.approx(
        100 * least / 0.02)
    assert 0 < aggs_trace_reduction.read(spec, ctx) < 100
    # nothing to take a share of: a CPU, an empty trace, no trace
    assert aggs_trace_reduction.read(spec, _ctx(made, "cpu")[0]) is None
    assert aggs_trace_reduction.read(spec, _ctx(made, busy_s=0)[0]) is None
    assert aggs_trace_reduction.read(spec, dict(ctx, trace=None)) is None


# ---------------------------------------------------------------------------
# the whole cell, once, on the CPU
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    root = tmp_path_factory.mktemp("dash")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / "cache"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 3505),
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
    assert last["correct"] is True and last["rehearsal"] is True, \
        stderr[-3000:]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert set(last["compared"]) == set(OWN)
    assert all(c == {"value": 0, "limit": 0}
               for c in last["compared"].values()), last["compared"]
    # the control: wrong in (nearly) every bytes-by-hour answer, a
    # quarter of the sample, and in nothing else
    ctl = last["control"]
    assert set(ctl) == {"answer_errors"}
    assert 0.2 * last["attempted"] <= ctl["answer_errors"] \
        <= 0.26 * last["attempted"] + 1
    assert "control wrong_by_panel=" in stderr
    assert "warm rounds=" in stderr and "router_host_routed=0" in stderr


def test_the_rehearsal_prints_every_per_layer_metric_of_the_cell(rehearsal):
    last, _stderr = rehearsal
    want = {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [])}
    assert len(want) >= 23              # counted from BENCHMARK.json
    got = {n: m["value"] for n, m in last["metrics"].items()}
    # a CPU has no chip to take a share of
    assert want - set(got) == {"aggs_roofline"}
    assert set(got) <= want
    assert got["window_compiles.aggs"] == 0
    assert got["aggs_device_share"] == 100.0
    assert got["agg_plan_cache_hit_share"] == 100.0
    # a stage lies inside what it splits
    assert 0 < got["aggs_launch_mean_ms"] + got["aggs_sync_wait_mean_ms"] \
        <= got["aggs_device_mean_ms"]
    for name in ("aggs_plan_mean_ms", "aggs_mask_mean_ms",
                 "aggs_device_mean_ms", "aggs_assemble_mean_ms"):
        assert 0 < got[name] <= got["server_took_mean_ms.aggs"], name
    # the mask rides every launch: 1, 3, 1 and 2 programs a panel
    r_pad = 1 << (ROWS - 1).bit_length()
    assert got["aggs_mask_bytes_per_request"] == pytest.approx(
        1.75 * r_pad, rel=0.1)
    assert got["aggs_board_lanes_per_request"] > 2048
