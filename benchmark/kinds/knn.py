"""Traffic kind `knn`: REST `_search` with a `knn` clause over a corpus that
is loaded before the window and read-only in it.

A traffic kind is what a traffic file's `kind` names: the set-up its
requests need, the builder of its requests, and the comparison that decides
`correct` for its answers. `run.py` finds it by that name and calls:

    prepare(run) -> state           index, rows, warm-up: all of it set-up
    make_items(state, first, count) requests first..first+count of the
                                    run's one stream, serialised
    judge(run, state, got) -> dict  after the window: `ok` (one flag a
                                    request), `numbers` (each held to the
                                    configuration's `limits`), `control`
                                    (the same numbers of the control, or
                                    None), `rows`

`run` is `run.py`'s `Run`: cell, args, child, corpus, n_rows, directories.
Another request shape or comparison is another file here, never an edit.
"""

from __future__ import annotations

import json
import time

from benchmark import arithmetic, loadgen, verify
from benchmark.setup import (INDEX, burst, create_index, load_rows, misses,
                             note, settle_compiles)
from benchmark.child import RunFailure

WARM_BASE = 1 << 24         # warm-up queries come from far down the stream
WARM_BURSTS = (1, 5, 12, 24, 48)
WARM_MAX_ROUNDS = 8


class State:
    def __init__(self, rows, traffic: dict):
        self.rows = rows
        self.traffic = traffic


def make_items(state: State, first: int, count: int) -> list:
    rows, req = state.rows, state.traffic["request"]
    queries, tags = rows.queries(first, count, req["filter_field"])
    field_spec = {f["name"]: f for f in rows.corpus.fields}
    items = []
    for j, vec in enumerate(queries.tolist()):
        knn = {"field": rows.corpus.vector_field, "query_vector": vec,
               "k": req["k"], "num_candidates": req["num_candidates"]}
        if req["filter_field"]:
            f = field_spec[req["filter_field"]]
            knn["filter"] = {"term": {f["name"]: f"{f['prefix']}{tags[j]}"}}
        body = json.dumps({"size": req["k"], "_source": False, "knn": knn},
                          separators=(",", ":")).encode()
        items.append(loadgen.Item(first + j, "POST",
                                  f"/{INDEX}/_search?request_cache=false",
                                  body))
    return items


def warm(child, state: State) -> None:
    """Form every batch size the cell's concurrency can, until two rounds
    in a row add no dispatch miss; each round ends with a short stint of a
    closed loop of the cell's clients, which has to add none either."""
    traffic = state.traffic
    sizes = [s for s in WARM_BURSTS if s < traffic["clients"]]
    sizes.append(traffic["clients"])
    nxt, quiet = WARM_BASE, 0
    before = misses(child.node_stats())
    for rnd in range(WARM_MAX_ROUNDS):
        for n in sizes:
            burst(child.port, make_items(state, nxt, n))
            nxt += n
        source = loadgen.ItemSource(
            lambda first, count: make_items(state, nxt + first, count), 512)
        stint = loadgen.closed_loop(child.port, traffic["clients"], 0.5,
                                    source)
        nxt += len(stint.index) + 512
        now = misses(settle_compiles(child, quiet_s=0.5))
        quiet = quiet + 1 if now == before else 0
        before = now
        if quiet >= 2:
            break
    note(f"warm rounds={rnd + 1} quiet_rounds={quiet}")


def prepare(run) -> State:
    config, child = run.cell.config, run.child
    create_index(child, config, "load")
    blocks = load_rows(child, run.corpus, run.n_rows)
    state = State(run.corpus.rows(blocks), run.cell.traffic)
    t = time.monotonic()
    for step in config["load"].get("then", []):
        child.ok("POST", f"/{INDEX}/{step}")
    note(f"flush_refresh_s={time.monotonic() - t:.1f}")
    settle_compiles(child)
    warm(child, state)
    return state


def compare(state: State, sample, answers: list, seed: int, control: bool):
    """The sampled answers of the window against the reference."""
    rows, req = state.rows, state.traffic["request"]
    lat = [(d - s) if d is not None else float("inf")
           for s, d in zip(sample.due, sample.done)]
    slowest = max(range(len(lat)), key=lat.__getitem__) if lat else 0
    picked = verify.pick_sample(len(answers), state.traffic["verify_sample"],
                                seed, always=[slowest] if lat else [])
    if not picked:
        raise RunFailure("the window completed no request")
    # the run's one query stream, over the span the sample touches
    lo = min(sample.index[i] for i in picked)
    hi = max(sample.index[i] for i in picked)
    span_q, span_t = rows.queries(lo, hi - lo + 1, req["filter_field"])
    at = [sample.index[i] - lo for i in picked]
    q = span_q[at]
    tags = span_t[at] if req["filter_field"] else None
    numbers = verify.compare_answers(rows, q, [answers[i] for i in picked],
                                     req["k"], tags, req["filter_field"])
    ctl = None
    if control:
        ctl = verify.compare_answers(
            rows, q, verify.control_answers(rows, q, req["k"], tags,
                                            req["filter_field"]),
            req["k"], tags, req["filter_field"])
    return numbers, ctl, len(picked)


def judge(run, state: State, got: dict) -> dict:
    sample = got["sample"]
    t = time.monotonic()
    run.child.stop()                   # the program's state is freed first
    note(f"child_stop_s={time.monotonic() - t:.1f}")
    answers = [verify.parse_hits(raw, st)
               for raw, st in zip(sample.raw, sample.status)]
    ok = [a is not None for a in answers]
    t = time.monotonic()
    numbers, ctl, n_checked = compare(state, sample, answers, run.args.seed,
                                      run.args.control)
    numbers["unanswered"] = ok.count(False)
    numbers["host_mirror_searches"] = int(arithmetic.delta(
        got["before"], got["after"], ["indices/knn/host_mirror_searches"]))
    note(f"checked {n_checked} of {len(answers)} answers "
         f"reference_s={time.monotonic() - t:.1f}")
    return {"ok": ok, "numbers": numbers, "control": ctl,
            "rows": len(state.rows)}
