"""The cell `filtered-steady` (ISSUE 33): the comparison of its kind
(`benchmark/kinds/knn_tags.py`) on hand-made answers with planted faults,
the control, the query stream, and one whole rehearsal on the CPU.

The faults are planted in the ANSWERS, before `compare_answers` sees them
(`serve.py` is not edited): each has to be caught by the number that is
there for it, and by no other."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import verify  # noqa: E402
from benchmark.kinds import knn_tags  # noqa: E402
from benchmark.kinds import knn_tags_reference as reference  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
with open(os.path.join(REPO, "benchmark", "configs",
                       "yfcc-192-uint8-tags.json")) as _f:
    CONFIG = json.load(_f)
ROWS, QUERIES, K = 4096, 192, 10
LIMIT_S = 240
EXACT = ("recall_at_k", "filter_violations", "hit_count_errors")


@pytest.fixture(scope="module")
def made():
    corpus = reference.TagCorpus(2 ** 31 + 333, CONFIG)
    docs = corpus.block_docs
    rows = corpus.rows([(b, docs) for b in range(ROWS // docs)])
    q, tags = rows.queries(0, QUERIES)
    exact = [rows.topk(qi, t, K) for qi, t in zip(q, tags)]
    # a correct program's answers: the exact rows, `_score` one float32
    # division of the exact integer
    good = [(ids.tolist(),
             (np.float32(1.0) / (np.float32(1.0) + d2.astype(np.float32)))
             .astype(np.float64).tolist()) for ids, d2, _n in exact]
    return rows, q, tags, exact, good


def _judge(made, answers):
    rows, q, tags, exact, _good = made
    numbers = knn_tags.compare_answers(rows, q, tags, answers, K, exact)
    return verify.judge(numbers, CONFIG["limits"])


def test_a_correct_programs_answers_are_correct(made):
    got = _judge(made, made[4])
    assert all(c["ok"] for c in got.values()), got
    assert got["recall_at_k"]["value"] == 1.0
    assert 0 < got["dist_rel_err"]["value"] < 1e-7     # one f32 division
    # without the reference handed in, the comparison computes it itself
    rows, q, tags, _exact, good = made
    assert knn_tags.compare_answers(rows, q[:8], tags[:8], good[:8], K) == \
        knn_tags.compare_answers(rows, q[:8], tags[:8], good[:8], K,
                                 made[3][:8])


def _true_score(rows, query, row):
    return float(reference.score(rows.distances(query, np.asarray([row])))[0])


def _without_its_tag(made):
    """The first hit of the first answer swapped for a row outside the
    filter, under that row's own true score."""
    rows, q, tags, _exact, good = made
    out = [(list(i), list(s)) for i, s in good]
    stranger = next(r for r in range(len(rows))
                    if not rows.holds(r, tags[0]))
    out[0][0][0] = stranger
    out[0][1][0] = _true_score(rows, q[0], stranger)
    return out


def _short(made):
    out = [(list(i), list(s)) for i, s in made[4]]
    at = next(j for j, (i, _s) in enumerate(out) if len(i) >= 2)
    out[at] = (out[at][0][:-1], out[at][1][:-1])
    return out


def _long(made):
    """A hit more than the rows that hold the tags: a row from outside
    the filter where fewer than k match."""
    rows, q, tags, exact, good = made
    out = [(list(i), list(s)) for i, s in good]
    at = next(j for j, (_i, _d, n) in enumerate(exact) if n < K)
    stranger = next(r for r in range(len(rows))
                    if not rows.holds(r, tags[at]))
    out[at] = (out[at][0] + [stranger],
               out[at][1] + [_true_score(rows, q[at], stranger)])
    return out


def _scores_altered(made):
    return [(i, [v * (1.0 + 1e-4) for v in s]) for i, s in made[4]]


def _stray_id(made):
    out = [(list(i), list(s)) for i, s in made[4]]
    out[3][0][0] = 10 ** 9
    return out


@pytest.mark.parametrize("fault, fails", [
    (_without_its_tag, {"filter_violations", "recall_at_k"}),
    (_short, {"hit_count_errors", "recall_at_k"}),
    (_long, {"hit_count_errors", "filter_violations"}),
    (_scores_altered, {"dist_rel_err"}),
    (_stray_id, {"filter_violations", "recall_at_k"}),
], ids=["a-hit-without-its-tag", "a-short-answer", "a-hit-too-many",
        "scores-altered", "an-id-no-row-has"])
def test_a_planted_fault_is_caught_by_its_number(made, fault, fails):
    got = _judge(made, fault(made))
    primary = {"filter_violations", "hit_count_errors", "dist_rel_err"} \
        & fails
    for name in primary:
        assert not got[name]["ok"], (name, got[name])
    # nothing else fails; the recall may lose the one altered hit and
    # stays above its limit
    for name, c in got.items():
        if name not in fails:
            assert c["ok"], (name, c)
    assert got["recall_at_k"]["ok"]
    missing = list(made[4])
    missing[5] = None
    assert _judge(made, missing)["recall_at_k"]["value"] < 1.0


def test_the_control_fails_dist_rel_err_and_nothing_else(made):
    rows, q, _tags, exact, _good = made
    control = _judge(made, reference.control_answers(rows, q, exact))
    assert not control["dist_rel_err"]["ok"], control
    for name in EXACT:
        assert control[name]["ok"], (name, control[name])
    assert control["recall_at_k"]["value"] == 1.0
    # the limit lies between the two readings with room on both sides
    limit = CONFIG["limits"]["dist_rel_err"]["limit"]
    program = _judge(made, made[4])["dist_rel_err"]["value"]
    assert 30 * program < limit < control["dist_rel_err"]["value"] / 30


def test_query_i_is_a_function_of_seed_and_i(made):
    rows = made[0]
    q_all, t_all = rows.queries(1000, 60)       # crosses a chunk at 1024
    q_part, t_part = rows.queries(1020, 10)
    assert np.array_equal(q_all[20:30], q_part) and t_all[20:30] == t_part
    assert q_all.min() >= 0 and q_all.max() <= 255
    one = sum(len(t) == 1 for t in made[2])
    assert 0.3 * QUERIES < one < 0.7 * QUERIES
    for (qi, t) in zip(made[1][:32], made[2][:32]):
        assert len(set(t)) == len(t) and len(rows.matching(t)) >= 1
    # the deciles the kind prints: the matched share spans orders of size
    share = knn_tags.deciles([n / ROWS for _i, _d, n in made[3]])
    assert share[0] >= 1.0 / ROWS and share[-1] > 0.3 and share[5] < 0.01


# ---------------------------------------------------------------------------
# the whole cell, once, on the CPU
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    root = tmp_path_factory.mktemp("filtered")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / "cache"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "filtered-steady", "--seed", str(2 ** 31 + 3303),
         "--seconds", "3", "--trace", "1", "--rehearse", "--rows", "4096",
         "--control", "--out", str(root / "out")],
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
    assert last["correct"] is True and last["rehearsal"] is True, stderr[-3000:]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert set(last["compared"]) == {
        "recall_at_k", "filter_violations", "hit_count_errors",
        "dist_rel_err", "unanswered", "host_mirror_searches"}
    assert last["compared"]["recall_at_k"]["value"] >= 0.95
    ctl = last["control"]
    limits = CONFIG["limits"]
    assert ctl["dist_rel_err"] > limits["dist_rel_err"]["limit"] \
        > last["compared"]["dist_rel_err"]["value"]
    assert ctl["recall_at_k"] >= 0.95 and ctl["filter_violations"] == 0 \
        and ctl["hit_count_errors"] == 0
    assert "matched_share_deciles=" in stderr


def test_the_rehearsal_prints_every_per_layer_metric_of_the_cell(rehearsal):
    last, _stderr = rehearsal
    want = {m["name"] for m in BENCH["per_layer"]
            if "filtered-steady" in m.get("workloads", [])}
    assert len(want) == 29
    got = {n: m["value"] for n, m in last["metrics"].items()}
    # a CPU has no chip to take a share of
    assert want - set(got) == {"knn_roofline.filtered"}
    assert set(got) <= want
    assert got["window_compiles.filtered"] == 0
    assert got["filtered_search_share"] == 100.0
    assert 0 <= got["filter_cache_hit_share"] <= 100
    # a stage lies inside what it splits; a mask is a byte a padded cell
    assert 0 < got["mask_build_mean_ms"] <= \
        got["dispatch_prepare_mean_ms.filtered"]
    assert 0 < got["filter_resolve_mean_ms"] <= \
        got["server_took_mean_ms.filtered"]
    assert got["mask_bytes_per_batch"] >= 4096
