"""Traffic kind `knn_tags`: REST `_search` with a `knn` clause whose
`filter` is a `bool` of one or two `term`s over a keyword ARRAY, on an
`l2_norm` field of uint8 values (the deployment `yfcc-192-uint8-tags`).

From `kinds/knn.py` by import: the state, the sample's choice, the load's
steps. Its own, because `knn.warm` and `knn.make_items` build one-term
requests over `data.Corpus` rows:

    the rows          `knn_tags_reference.TagCorpus`, made from the run's
                      seed in `prepare` (`run.corpus` is `data.Corpus`'s
                      and is not used)
    the requests      k, num_candidates from the traffic file; a filter of
                      the query's one or two tags, ANDed
    the warm-up       every batch rung the cell's connections can form,
                      with the two-term shape, until two rounds add no
                      dispatch miss
    the comparison    against the exact filtered reference:

    recall_at_k        share of the reference's top k (of min(k, matching
                       rows)) that were served
    filter_violations  served hits that lack a requested tag, or whose id
                       no row has
    hit_count_errors   answers whose number of hits is not min(k, rows
                       that hold every tag)
    dist_rel_err       rms over served hits of (1 / `_score` - 1 - d2) /
                       max(d2, 1), d2 the reference's int64 distance of
                       THAT row: what is stated is exact, so what is left
                       is one float32 division (and its text form). The
                       control (`--control`: the rows held as int8 with one
                       scale a row) has to fail it, and nothing else
    unanswered, host_mirror_searches   as `kinds/knn.py`
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmark import arithmetic, loadgen, verify
from benchmark.child import RunFailure
from benchmark.kinds import knn
from benchmark.kinds import knn_tags_reference as reference
from benchmark.setup import (INDEX, burst, create_index, load_rows, misses,
                             note, settle_compiles)


def make_items(state, first: int, count: int) -> list:
    rows, req = state.rows, state.traffic["request"]
    queries, tags = rows.queries(first, count)
    field = rows.corpus.tag_field
    items = []
    for j, vec in enumerate(queries.tolist()):
        terms = [{"term": {field: rows.name(t)}} for t in tags[j]]
        body = json.dumps(
            {"size": req["k"], "_source": False,
             "knn": {"field": rows.corpus.vector_field, "query_vector": vec,
                     "k": req["k"], "num_candidates": req["num_candidates"],
                     "filter": {"bool": {"filter": terms}}}},
            separators=(",", ":")).encode()
        items.append(loadgen.Item(first + j, "POST",
                                  f"/{INDEX}/_search?request_cache=false",
                                  body))
    return items


def warm(child, state) -> None:
    """`knn.warm`'s rounds with this kind's requests: bursts that form
    every batch rung up to the cell's connections, then a short closed
    loop, until two rounds in a row add no dispatch miss."""
    traffic = state.traffic
    sizes = [s for s in knn.WARM_BURSTS if s < traffic["clients"]]
    sizes.append(traffic["clients"])
    nxt, quiet = knn.WARM_BASE, 0
    before = misses(child.node_stats())
    for rnd in range(knn.WARM_MAX_ROUNDS):
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


def prepare(run):
    config, child = run.cell.config, run.child
    corpus = reference.TagCorpus(run.args.seed, config)
    create_index(child, config, "load")
    blocks = load_rows(child, corpus, run.n_rows)
    state = knn.State(corpus.rows(blocks), run.cell.traffic)
    t = time.monotonic()
    for step in config["load"].get("then", []):
        child.ok("POST", f"/{INDEX}/{step}")
    note(f"flush_refresh_s={time.monotonic() - t:.1f}")
    settle_compiles(child)
    warm(child, state)
    return state


def compare_answers(rows, queries, tags: list, answers: list, k: int,
                    exact: list = None) -> dict:
    """`answers[i]` is (ids, scores) for (`queries[i]`, `tags[i]`), or
    None; `exact[i]` the reference's `rows.topk` of it, where the caller
    has it already."""
    if exact is None:
        exact = [rows.topk(q, t, k) for q, t in zip(queries, tags)]
    want = held = violations = count_errors = n_hits = 0
    sq = 0.0
    for q, t, a, (want_ids, _d2, matching) in zip(queries, tags, answers,
                                                  exact):
        want += len(want_ids)
        if not a:
            continue
        ids, scores = a
        count_errors += len(ids) != min(k, matching)
        known = [j for j, i in enumerate(ids) if 0 <= i < len(rows)]
        violations += len(ids) - len(known)
        at = np.asarray([ids[j] for j in known], dtype=np.int64)
        violations += sum(not rows.holds(i, t) for i in at)
        held += len(set(at.tolist()) & set(want_ids.tolist()))
        d2 = rows.distances(q, at)
        served = 1.0 / np.asarray(scores, dtype=np.float64)[known] - 1.0
        err = (served - d2) / np.maximum(d2, 1)
        sq += float((err * err).sum())
        n_hits += len(known)
    return {"recall_at_k": held / float(want) if want else 1.0,
            "filter_violations": int(violations),
            "hit_count_errors": int(count_errors),
            "dist_rel_err": (sq / n_hits) ** 0.5 if n_hits else float("inf")}


def deciles(values) -> list:
    """The 0, 10, .., 100 % points of `values`."""
    return [float(v) for v in np.quantile(values, np.linspace(0, 1, 11))]


def compare(state, sample, answers: list, seed: int, control: bool):
    """The sampled answers of the window (`kinds/knn.py`'s sample: drawn
    from the seed, the slowest among them) against the reference; with
    `control`, the control's answers to the same queries against the
    same."""
    rows, k = state.rows, state.traffic["request"]["k"]
    lat = [(d - s) if d is not None else float("inf")
           for s, d in zip(sample.due, sample.done)]
    slowest = max(range(len(lat)), key=lat.__getitem__) if lat else 0
    picked = verify.pick_sample(len(answers), state.traffic["verify_sample"],
                                seed, always=[slowest] if lat else [])
    if not picked:
        raise RunFailure("the window completed no request")
    lo = min(sample.index[i] for i in picked)
    hi = max(sample.index[i] for i in picked)
    span_q, span_t = rows.queries(lo, hi - lo + 1)
    at = [sample.index[i] - lo for i in picked]
    q, tags = span_q[at], [span_t[i] for i in at]
    exact = [rows.topk(qi, t, k) for qi, t in zip(q, tags)]
    numbers = compare_answers(rows, q, tags, [answers[i] for i in picked], k,
                              exact)
    share = deciles([n / float(len(rows)) for _ids, _d2, n in exact])
    note("matched_share_deciles=" + json.dumps([round(v, 6) for v in share]))
    note(f"tags_per_request one={sum(len(t) == 1 for t in tags)} "
         f"two={sum(len(t) == 2 for t in tags)}")
    ctl = None
    if control:
        ctl = compare_answers(
            rows, q, tags, reference.control_answers(rows, q, exact), k,
            exact)
    return numbers, ctl, len(picked)


def judge(run, state, got: dict) -> dict:
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
