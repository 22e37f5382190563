"""Traffic kind `aggs`: REST `_search` with `size: 0` and one dashboard
panel of aggregations under a time range, over log lines that are loaded
before the window and read-only in it (the deployment `http-logs-dash`).

From `kinds/knn.py` by import: the state, the warm-up's base and its limit
of rounds. Its own, because `knn.prepare`, `knn.warm` and `knn.make_items`
load `data.Corpus` rows and build `knn` requests:

    the rows          `aggs_reference.LogCorpus`, made from the run's seed
                      in `prepare` (`run.corpus` is `data.Corpus`'s and is
                      not used); the index is created WITHOUT the
                      configuration's vector property, which only the
                      unedited tests read
    the requests      request i is panel i % 4 of `aggs_reference.PANELS`
                      at a whole hour drawn from the seed
    the warm-up       every panel alone, then bursts up to the cell's
                      connections, until two rounds in a row add no
                      dispatch miss AND build no column
                      (`indices/aggs/column_rebuilds`, `columns`)
    the comparison    sampled answers against the exact reference:

    answer_errors     sampled answers whose aggregations tree differs from
                      the reference's in any bucket's key, `key_as_string`,
                      `doc_count`, order, presence, `sum` or
                      `sum_other_doc_count`, or whose `hits.total` differs.
                      The control (`--control`: every `sum` accumulated in
                      float32) has to fail it, and nothing else
    unanswered        HTTP error, failed shard, `timed_out`, not JSON, no
                      `aggregations`
    host_agg_nodes    aggregation nodes the host walker answered in the
                      window (`indices/aggs/host_nodes`): the configuration
                      states that the device answers
    cached_answers    hits of the request cache in the window
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmark import arithmetic, loadgen, verify
from benchmark.child import RunFailure
from benchmark.kinds import aggs_reference as reference
from benchmark.kinds import knn
from benchmark.setup import (INDEX, burst, create_index, load_rows, misses,
                             note, settle_compiles)

PATH = f"/{INDEX}/_search?request_cache=false"
WARM_BURST = 8              # a later round's burst: two of every panel


def make_items(state, first: int, count: int) -> list:
    return [loadgen.Item(first + j, "POST", PATH,
                         json.dumps(reference.body(panel, t),
                                    separators=(",", ":")).encode())
            for j, (panel, t) in enumerate(state.rows.requests(first,
                                                               count))]


def _standing(node: dict) -> tuple:
    aggs = node["indices"]["aggs"]
    return misses(node), aggs["columns"], aggs["column_rebuilds"]


def warm(child, state) -> None:
    """Every panel once, one at a time (the first request of a panel
    builds its columns and compiles its programs); then rounds of one
    burst each, the first as wide as the cell's connections, until two
    rounds in a row add no dispatch miss and build no column. A panel's
    bucket rungs follow from the COLUMN's span and cardinality, not from
    the request's range, and no batcher stands between a request and its
    programs: every panel at any range reaches every program the window
    can form, and a burst adds only the launches at once of the twenty
    `search` workers. (`knn.warm`'s six bursts and a closed loop a round
    are some 440 requests: minutes, at the few requests a second this
    engine answers.)"""
    nxt, quiet = knn.WARM_BASE, 0
    for item in make_items(state, nxt, len(reference.PANELS)):
        burst(child.port, [item])
    nxt += len(reference.PANELS)
    before = _standing(settle_compiles(child, quiet_s=0.5))
    for rnd in range(knn.WARM_MAX_ROUNDS):
        n = state.traffic["clients"] if rnd == 0 else WARM_BURST
        burst(child.port, make_items(state, nxt, n))
        nxt += n
        now = _standing(settle_compiles(child, quiet_s=0.5))
        quiet = quiet + 1 if now == before else 0
        before = now
        if quiet >= 2:
            break
    note(f"warm rounds={rnd + 1} quiet_rounds={quiet} misses={before[0]} "
         f"columns={before[1]} column_rebuilds={before[2]}")


def prepare(run):
    config, child = run.cell.config, run.child
    corpus = reference.LogCorpus(run.args.seed, config, run.n_rows)
    # the track's five fields and nothing else: the vector property is
    # in the file for the unedited tests alone
    props = {name: spec for name, spec
             in config["index"]["mappings"]["properties"].items()
             if name != config["data"]["vector_field"]}
    create_index(child, dict(config, index=dict(
        config["index"], mappings={"properties": props})), "load")
    blocks = load_rows(child, corpus, run.n_rows)
    state = knn.State(corpus.rows(blocks), run.cell.traffic)
    t = time.monotonic()
    for step in config["load"].get("then", []):
        child.ok("POST", f"/{INDEX}/{step}")
    note(f"flush_refresh_s={time.monotonic() - t:.1f}")
    settle_compiles(child)
    t = time.monotonic()
    warm(child, state)
    note(f"warm_s={time.monotonic() - t:.1f}")
    return state


def parse_answer(raw: bytes, status: int):
    """`{"total", "aggregations"}` of one `_search` answer; None where it
    is no sound answer."""
    if status != 200:
        return None
    try:
        resp = json.loads(raw)
        if resp["_shards"].get("failed") or resp.get("timed_out"):
            return None
        return {"total": resp["hits"]["total"],
                "aggregations": resp["aggregations"]}
    except (ValueError, KeyError, TypeError):
        return None


def compare_answers(rows, requests: list, answers: list) -> dict:
    """`answers[i]` is `parse_answer`'s of `requests[i]` = (panel, t), or
    None."""
    want = {}
    wrong = {name: 0 for name, _width in reference.PANELS}
    for req, got in zip(requests, answers):
        if req not in want:
            want[req] = rows.answer(*req)
        if got is None or reference.differs(got, want[req]):
            wrong[req[0]] += 1
    return {"answer_errors": sum(wrong.values()), "wrong_by_panel": wrong}


def compare(state, sample, answers: list, seed: int, control: bool):
    """The sampled answers of the window (`kinds/knn.py`'s sample: drawn
    from the seed, the slowest among them) against the reference."""
    rows = state.rows
    lat = [(d - s) if d is not None else float("inf")
           for s, d in zip(sample.due, sample.done)]
    slowest = max(range(len(lat)), key=lat.__getitem__) if lat else 0
    picked = verify.pick_sample(len(answers), state.traffic["verify_sample"],
                                seed, always=[slowest] if lat else [])
    if not picked:
        raise RunFailure("the window completed no request")
    requests = [rows.requests(sample.index[i], 1)[0] for i in picked]
    got = compare_answers(rows, requests, [answers[i] for i in picked])
    note("wrong_by_panel=" + json.dumps(got.pop("wrong_by_panel")))
    matched = {name: [] for name, _width in reference.PANELS}
    for req in set(requests):
        matched[req[0]].append(rows.matched_rows(*req))
    note("matched_rows_mean=" + json.dumps(
        {name: round(float(np.mean(v)), 1) for name, v in matched.items()
         if v}))
    ctl = None
    if control:
        ctl = compare_answers(rows, requests,
                              reference.control_answers(rows, requests))
        note("control wrong_by_panel=" + json.dumps(
            ctl.pop("wrong_by_panel")) + f" of {len(requests)} sampled")
    return got, ctl, len(picked)


def judge(run, state, got: dict) -> dict:
    sample = got["sample"]
    t = time.monotonic()
    run.child.stop()                   # the program's state is freed first
    note(f"child_stop_s={time.monotonic() - t:.1f}")
    answers = [parse_answer(raw, st)
               for raw, st in zip(sample.raw, sample.status)]
    ok = [a is not None for a in answers]
    t = time.monotonic()
    numbers, ctl, n_checked = compare(state, sample, answers, run.args.seed,
                                      run.args.control)
    numbers["unanswered"] = ok.count(False)
    numbers["host_agg_nodes"] = int(arithmetic.delta(
        got["before"], got["after"], ["indices/aggs/host_nodes"]))
    numbers["cached_answers"] = int(arithmetic.delta(
        got["before"], got["after"], ["indices/request_cache/hit_count"]))
    aggs = got["after"]["indices"]["aggs"]
    note(f"router_host_routed={aggs['router_host_routed']} "
         f"fallback_reasons={json.dumps(aggs['fallback_reasons'])}")
    note(f"checked {n_checked} of {len(answers)} answers "
         f"reference_s={time.monotonic() - t:.1f}")
    return {"ok": ok, "numbers": numbers, "control": ctl,
            "rows": len(state.rows)}
