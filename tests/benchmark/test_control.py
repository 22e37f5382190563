"""The control of `correct`, kept as a test at a size a test run can hold.

The control is the plain reference put in the program's place and computed
in int8, the nearest precision below the configuration's bf16. Judged by the
comparison and the limits the benchmark's own runs use, it has to come out
NOT correct, while the reference itself, and the reference with its rows and
queries rounded to bf16 as the configuration states, come out correct. (On
the chip, at the cells' own sizes: PERF.md section 2.)
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import verify  # noqa: E402
from benchmark.data import Corpus, bf16_round  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
ROWS, QUERIES, K = 8192, 256, 10


def _names_a_vector_field(entry):
    with open(os.path.join(REPO, entry["file"])) as f:
        return bool(json.load(f).get("data", {}).get("vector_field"))


# the configurations this control is for: those that search vectors
VECTOR_CONFIGS = [c["file"] for c in BENCH["configs"]
                  if _names_a_vector_field(c)]
# the one the checks below were written for: bf16 cosine, unreduced
NYTIMES = next(c["file"] for c in BENCH["configs"]
               if c["name"] == "nytimes-256-angular")


TAG = {"name": "tag", "type": "keyword", "values": 16, "zipf_s": 1.0,
       "prefix": "t"}


def _setup(config_file, seed, filtered=False):
    """Rows and queries of a committed configuration at a test's size;
    `filtered` adds a keyword field of 16 Zipf tags and filters on it."""
    with open(os.path.join(REPO, config_file)) as f:
        config = json.load(f)
    if filtered:
        config["data"]["fields"] = config["data"]["fields"] + [TAG]
    corpus = Corpus(seed, config)
    docs = corpus.block_docs
    rows = corpus.rows([(b, docs) for b in range(ROWS // docs)])
    field = TAG["name"] if filtered else None
    q, tags = rows.queries(0, QUERIES, field)
    return config, rows, q, tags, field


def _judge(config, rows, q, tags, field, answers):
    numbers = verify.compare_answers(rows, q, answers, K, tags, field)
    return verify.judge(numbers, config["limits"])


def _answers(rows, q, tags, field, unit):
    ids, cos = rows.topk(q, K, tags, field, unit=unit)
    return [(i.tolist(), ((1.0 + c.astype(np.float64)) / 2).tolist())
            for i, c in zip(ids, cos)]


def _stated(rows, q, tags, field):
    """Answers computed as the configuration states: rows and unit query
    in bfloat16, products summed in float32 (here in another order than the
    reference's own sum, as a kernel's would be)."""
    ids, _ = rows.topk(q, K, tags, field)
    qn = q.astype(np.float32)
    qn = bf16_round(qn / np.linalg.norm(qn, axis=1, keepdims=True))
    out = []
    for i, row in enumerate(ids):
        prod = bf16_round(rows.unit[row]) * qn[i]
        cos = prod[:, ::-1].sum(axis=1, dtype=np.float32)
        out.append((row.tolist(), ((1.0 + cos.astype(np.float64)) / 2)
                    .tolist()))
    return out


@pytest.mark.parametrize("config_file", VECTOR_CONFIGS)
@pytest.mark.parametrize("filtered", [False, True])
@pytest.mark.parametrize("seed", [5, 2 ** 31 + 17, 123456789])
def test_the_int8_control_is_not_correct_and_bf16_is(config_file, seed,
                                                     filtered):
    config, rows, q, tags, field = _setup(config_file, seed, filtered)
    stated = _judge(config, rows, q, tags, field,
                    _stated(rows, q, tags, field))
    assert all(c["ok"] for c in stated.values()), stated
    assert stated["recall_at_k"]["value"] == 1.0
    assert stated["score_rms_err"]["value"] < 1e-6

    # a program that computed in full float32 is correct too
    exact = _judge(config, rows, q, tags, field,
                   _answers(rows, q, tags, field, rows.unit))
    assert all(c["ok"] for c in exact.values()), exact

    control = _judge(config, rows, q, tags, field,
                     verify.control_answers(rows, q, K, tags, field))
    assert not control["score_rms_err"]["ok"], control
    assert control["score_rms_err"]["value"] >= \
        1.4 * config["limits"]["score_rms_err"]["limit"]
    assert control["filter_violations"]["ok"]


def test_an_answer_altered_where_it_is_produced_is_not_correct():
    config, rows, q, tags, field = _setup(NYTIMES, 3)
    good = _answers(rows, q, tags, field, rows.unit)
    shifted = [([i + 1 for i in ids], scores) for ids, scores in good]
    assert not _judge(config, rows, q, tags, field,
                      shifted)["recall_at_k"]["ok"]
    nudged = [(ids, [s + 0.004 for s in scores]) for ids, scores in good]
    assert not _judge(config, rows, q, tags, field,
                      nudged)["score_rms_err"]["ok"]
    missing = good[:-3] + [None] * 3
    got = _judge(config, rows, q, tags, field, missing)
    assert got["unanswered"]["value"] == 3 and not got["unanswered"]["ok"]
    # an id that no row has is a violation, not a crash
    stray = [([10 ** 9] + ids[1:], scores) for ids, scores in good]
    assert not _judge(config, rows, q, tags, field,
                      stray)["filter_violations"]["ok"]


def test_a_hit_outside_the_filter_is_a_violation():
    config, rows, q, tags, field = _setup(NYTIMES, 4, filtered=True)
    unfiltered = _answers(rows, q, None, None, rows.unit)
    got = _judge(config, rows, q, tags, field, unfiltered)
    assert got["filter_violations"]["value"] > 0
    assert not got["filter_violations"]["ok"]


def test_the_sample_is_drawn_from_the_seed_and_keeps_what_it_must():
    a = verify.pick_sample(5000, 100, seed=2 ** 31 + 1, always=[4999])
    b = verify.pick_sample(5000, 100, seed=2 ** 31 + 1, always=[4999])
    c = verify.pick_sample(5000, 100, seed=7, always=[4999])
    assert a == b != c and len(a) == 100 and 4999 in a and 4999 in c
    assert verify.pick_sample(10, 100, seed=1) == list(range(10))
