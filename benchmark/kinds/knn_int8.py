"""Traffic kind `knn_int8`: `kinds/knn.py`'s load, warm-up and `_search`
requests, judged as an int8 deployment on a mesh states them.

What it adds to `kinds/knn.py` (whose `prepare` and `make_items` it takes
by import), all in `judge`:

    score_rms_err            against the cosine as an int8 configuration
                             states it (`knn_int8_reference.py`: int8 rows
                             with one scale a row, bf16 query, float32 sum),
                             not against `data.Rows.cosines`' bf16 rows
    single_device_searches   searches the mesh router sent to one device
                             inside the window (`indices/mesh/router/
                             single_device`): the configuration states that
                             every search is answered by the mesh
    device0_excess_shards    after the window, the fullest device's
                             `bytes_in_use` less the emptiest's
                             (`_nodes/stats device`, the allocator's own
                             count), over one shard's int8 bytes: each chip
                             holds its shard and no whole copy. Left out
                             where the platform's allocator counts nothing
                             (a CPU rehearsal)
    the control (`--control`) rows in int4 in the program's place

and one check in `prepare`, before a row is loaded: a program that keeps a
whole copy of a mesh-served field on one device cannot hold the deployment
(at the source's size that device would need 25 GB), so the run fails at
once, as it does for a missing chip.
"""

from __future__ import annotations

import time

from benchmark import arithmetic, verify
from benchmark.child import RunFailure
from benchmark.kinds import knn
from benchmark.kinds import knn_int8_reference as reference
from benchmark.setup import note

make_items = knn.make_items


def prepare(run):
    mesh = run.child.node_stats()["indices"].get("mesh", {})
    if mesh.get("single_device_copy") != "on_first_use":
        raise RunFailure(
            "the program keeps a whole single-device copy beside the "
            "sharded one (indices.mesh.single_device_copy is "
            f"{mesh.get('single_device_copy')!r}): it cannot hold a "
            "deployment in which each chip holds its shard only")
    return knn.prepare(run)


def bytes_in_use(node: dict) -> list:
    """Each device's `bytes_in_use` (`_nodes/stats device`); None where
    the platform's allocator keeps no count."""
    return [m.get("bytes_in_use") for m in node["device"].get("memory", [])]


def excess_shards(node: dict, rows: int, dims: int, shards: int):
    """(fullest - emptiest device's bytes in use) / one shard's int8
    bytes; None where the allocator reports no count."""
    used = bytes_in_use(node)
    if len(used) < 2 or any(u is None for u in used):
        return None
    return (max(used) - min(used)) / (rows / float(shards) * dims)


def compare(state, sample, answers: list, seed: int, control: bool):
    """The sampled answers of the window (`kinds/knn.py`'s sample: drawn
    from the seed, the slowest among them) against this configuration's
    reference; with `control`, the control's answers to the same queries
    against the same."""
    rows, req = state.rows, state.traffic["request"]
    field, k = req["filter_field"], req["k"]
    lat = [(d - s) if d is not None else float("inf")
           for s, d in zip(sample.due, sample.done)]
    slowest = max(range(len(lat)), key=lat.__getitem__) if lat else 0
    picked = verify.pick_sample(len(answers), state.traffic["verify_sample"],
                                seed, always=[slowest] if lat else [])
    if not picked:
        raise RunFailure("the window completed no request")
    lo = min(sample.index[i] for i in picked)
    hi = max(sample.index[i] for i in picked)
    span_q, span_t = rows.queries(lo, hi - lo + 1, field)
    at = [sample.index[i] - lo for i in picked]
    q, tags = span_q[at], (span_t[at] if field else None)
    stated = reference.Int8Rows(rows)
    numbers = verify.compare_answers(stated, q, [answers[i] for i in picked],
                                     k, tags, field)
    ctl = None
    if control:
        if field:
            raise RunFailure("the int4 control is for unfiltered requests")
        ctl_answers, scan_recall = reference.control_answers(rows, q, k)
        ctl = verify.compare_answers(stated, q, ctl_answers, k)
        ctl["int4_scan_recall_at_k"] = scan_recall
    return numbers, ctl, len(picked)


def judge(run, state, got: dict) -> dict:
    sample, config = got["sample"], run.cell.config
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
    for name, path in (
            ("host_mirror_searches", "indices/knn/host_mirror_searches"),
            ("single_device_searches", "indices/mesh/router/single_device")):
        numbers[name] = int(arithmetic.delta(got["before"], got["after"],
                                             [path]))
    excess = excess_shards(got["after"], len(state.rows), config["dims"],
                           config["chips"])
    if excess is not None:
        numbers["device0_excess_shards"] = excess
    note(f"device_bytes_in_use={bytes_in_use(got['after'])}")
    note(f"checked {n_checked} of {len(answers)} answers "
         f"reference_s={time.monotonic() - t:.1f}")
    return {"ok": ok, "numbers": numbers, "control": ctl,
            "rows": len(state.rows)}
