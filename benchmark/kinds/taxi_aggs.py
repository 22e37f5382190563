"""Traffic kind `taxi_aggs`: REST `_search` with `size: 0` and one of the
three aggregation operations of Rally's `nyc_taxis`, over taxi rides that
are loaded before the window and read-only in it (the deployment
`nyc-taxis-dash`).

From `kinds/knn.py` by import: the state, the warm-up's base and its limit
of rounds; from `kinds/aggs.py`: the parse of an answer and the warm-up's
test for a quiet round. Its own:

    the rows          `taxi_aggs_reference.RideCorpus`, made from the
                      run's seed in `prepare` (`run.corpus` is
                      `data.Corpus`'s and is not used)
    the requests      request i is panel i % 3 of
                      `taxi_aggs_reference.PANELS`; a date panel's window
                      starts on a whole day drawn from the seed and is
                      as wide as the traffic file's `window_days` gives
    the warm-up       every panel at every width alone, then bursts up to
                      the cell's connections, until two rounds in a row
                      add no dispatch miss AND build no column. A node the
                      host walker answered there ends the run at once: the
                      configuration states that the device answers every
                      node, and a window would only confirm it
    the comparison    sampled answers against the exact reference:

    answer_errors     sampled answers that differ from the reference
                      (`taxi_aggs_reference.differs`: exact in every key,
                      `key_as_string`, `doc_count`, bucket, `interval`,
                      `hits.total` and a `stats`' count, min and max; its
                      `sum` and `avg` within 1e-12). The control
                      (`--control`: every `sum` in float32) has to fail
                      it, and nothing else
    unanswered        HTTP error, failed shard, `timed_out`, not JSON, no
                      `aggregations`
    host_agg_nodes    aggregation nodes the host walker answered in the
                      window (`indices/aggs/host_nodes`)
    cached_answers    hits of the request cache in the window
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmark import arithmetic, loadgen, verify
from benchmark.child import RunFailure
from benchmark.kinds import aggs, knn
from benchmark.kinds import taxi_aggs_reference as reference
from benchmark.setup import (INDEX, burst, create_index, load_rows, note,
                             settle_compiles)

PATH = f"/{INDEX}/_search?request_cache=false"


def make_items(state, first: int, count: int) -> list:
    return [loadgen.Item(first + j, "POST", PATH,
                         json.dumps(state.rows.body(*req),
                                    separators=(",", ":")).encode())
            for j, req in enumerate(state.rows.requests(first, count))]


def _host_nodes(node: dict) -> int:
    return int(node["indices"]["aggs"]["host_nodes"])


def warm(child, state) -> None:
    """Every panel at every width once, one at a time (the first request
    of a shape builds its columns and compiles its programs); then rounds
    of one burst each, the first as wide as the cell's connections,
    until two rounds in a row add no dispatch miss and build no column
    (`aggs.warm`'s rule). An auto rounding's rung follows from the
    matched span, which the widths cover."""
    nxt, quiet = knn.WARM_BASE, 0
    # a later round's burst too: every panel and width
    first = len(reference.PANELS) * len(state.rows.corpus.widths)
    walked0 = _host_nodes(child.node_stats())
    for item in make_items(state, nxt, first):
        burst(child.port, [item])
    nxt += first
    node = settle_compiles(child, quiet_s=0.5)
    walked = _host_nodes(node) - walked0
    if walked:
        raise RunFailure(
            f"the host walker answered {walked} aggregation nodes of the "
            f"warm-up (fallback_reasons "
            f"{json.dumps(node['indices']['aggs']['fallback_reasons'])}): "
            f"the configuration states that the device answers every node")
    before = aggs._standing(node)
    for rnd in range(knn.WARM_MAX_ROUNDS):
        n = state.traffic["clients"] if rnd == 0 else first
        burst(child.port, make_items(state, nxt, n))
        nxt += n
        now = aggs._standing(settle_compiles(child, quiet_s=0.5))
        quiet = quiet + 1 if now == before else 0
        before = now
        if quiet >= 2:
            break
    note(f"warm rounds={rnd + 1} quiet_rounds={quiet} misses={before[0]} "
         f"columns={before[1]} column_rebuilds={before[2]}")


def prepare(run):
    config, child = run.cell.config, run.child
    corpus = reference.RideCorpus(run.args.seed, config, run.n_rows,
                                  run.cell.traffic)
    create_index(child, config, "load")
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


def compare_answers(rows, requests: list, answers: list) -> dict:
    """`answers[i]` is `aggs.parse_answer`'s of `requests[i]`, or None."""
    want = {}
    wrong = {name: 0 for name in reference.PANELS}
    for req, got in zip(requests, answers):
        if req not in want:
            want[req] = rows.answer(*req)
        if got is None or reference.differs(got, want[req]):
            wrong[req[0]] += 1
    return {"answer_errors": sum(wrong.values()), "wrong_by_panel": wrong}


def compare(state, sample, answers: list, seed: int, control: bool):
    """The sampled answers of the window (the seed's draw, the slowest
    among them) against the reference."""
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
    matched = {name: [] for name in reference.PANELS}
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
    answers = [aggs.parse_answer(raw, st)
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
    stats = got["after"]["indices"]["aggs"]
    note(f"router_host_routed={stats['router_host_routed']} "
         f"fallback_reasons={json.dumps(stats['fallback_reasons'])}")
    note(f"checked {n_checked} of {len(answers)} answers "
         f"reference_s={time.monotonic() - t:.1f}")
    return {"ok": ok, "numbers": numbers, "control": ctl,
            "rows": len(state.rows)}
