"""The benchmark's arithmetic on hand-made inputs: latency from due time,
lateness, percentiles and rates over the whole window, histogram deltas, the
arrival schedule, the roofline floors, the peaks table, and the reduction of
a recorded device trace to busy time, operations and idle gaps."""

import json
import math
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import arithmetic, loadgen, roofline, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
V5E = {"ops_per_s": {"bf16": 197e12, "int8": 393e12}, "bytes_per_s": 819e9}


def test_latency_counts_from_due_so_a_stalled_generator_raises_the_tail():
    # ten requests due 10 ms apart; the generator stalls 100 ms before the
    # sixth, and every request takes 2 ms once sent
    due = [0.01 * i for i in range(10)]
    sent = [d if i < 5 else max(d, 0.15 + 0.002 * (i - 5))
            for i, d in enumerate(due)]
    done = [s + 0.002 for s in sent]
    lat = arithmetic.latencies_ms(due, done, [True] * 10)
    assert lat[0] == pytest.approx(2.0)
    assert lat[5] == pytest.approx(102.0)          # 150 + 2 - 50
    assert arithmetic.percentile(lat, 95) > 100.0  # the stall is in the tail
    from_send = [(d - s) * 1000 for s, d in zip(sent, done)]
    assert max(from_send) == pytest.approx(2.0)    # and hidden from send time
    late = arithmetic.lateness_ms(due, sent)
    assert late[:5] == [0.0] * 5 and late[5] == pytest.approx(100.0)


@pytest.mark.parametrize("q, want", [(50, 5.0), (95, 10.0), (100, 10.0),
                                     (10, 1.0), (1, 1.0)])
def test_percentile_is_nearest_rank_over_all_values(q, want):
    assert arithmetic.percentile([float(i) for i in range(10, 0, -1)],
                                 q) == want


def test_a_failed_request_is_missing_from_no_percentile():
    lat = arithmetic.latencies_ms([0.0] * 20, [0.001] * 19 + [None],
                                  [True] * 19 + [False])
    assert arithmetic.percentile(lat, 50) == pytest.approx(1.0)
    assert arithmetic.percentile(lat, 99) == math.inf
    lat = arithmetic.latencies_ms([0.0, 0.0], [0.001, 0.001], [True, False])
    assert lat[1] == math.inf        # answered, but not a sound answer


def test_rate_is_all_the_work_done_inside_the_window_over_its_seconds():
    done = [9.9, 10.0, 15.0, 20.0, 20.1, None, 12.0]
    weight = [100.0] * 7
    ok = [True, True, True, True, True, True, False]
    # 10.0, 15.0 and 20.0 lie inside [10, 20]; 12.0 failed
    assert arithmetic.rate_in_window(done, weight, ok, 10.0, 10.0) == 30.0


def test_histogram_mean_reads_count_and_sum_only():
    from benchmark.readers import histogram_mean

    def stats(**hists):
        return {"telemetry": {"histograms": hists}}

    before = stats(a={"count": 10, "sum_nanos": 10_000_000, "p50_nanos": 1},
                   b={"count": 10, "sum_nanos": 5_000_000})
    after = stats(a={"count": 30, "sum_nanos": 70_000_000,
                     "p50_nanos": 2 ** 40},
                  b={"count": 30, "sum_nanos": 25_000_000})
    one = {"sum_of": ["a"], "count_of": "a", "scale": 1e-6}
    assert histogram_mean.read(one, {"before": before, "after": after}) == 3.0
    # two spans of one event: both sums over the one count
    two = {"sum_of": ["a", "b"], "count_of": "a", "scale": 1e-6}
    assert histogram_mean.read(two, {"before": before, "after": after}) == 4.0
    assert histogram_mean.read(one, {"before": {}, "after": after}) == \
        pytest.approx(70 / 30)
    assert histogram_mean.read(one, {"before": after, "after": after}) is None


def test_spread_is_the_interquartile_distance_over_the_median():
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    # statistics.quantiles (exclusive): q1 = 100.75, q3 = 104.25
    assert arithmetic.spread(values) == pytest.approx(3.5 / 102.5)


def test_stats_lookup_and_delta():
    before = {"a": {"b": 3, "c": 1}}
    after = {"a": {"b": 10, "c": 2}}
    assert arithmetic.lookup(after, "a/b") == 10
    assert arithmetic.lookup(after, "a/x/y") is None
    assert arithmetic.delta(before, after, ["a/b", "a/c"]) == 8
    assert arithmetic.delta(before, after, ["a/missing"]) is None


def test_every_seed_gets_the_same_gaps_in_another_order():
    a = loadgen.arrival_offsets(200.0, 10.0, seed=1)
    b = loadgen.arrival_offsets(200.0, 10.0, seed=2 ** 31 + 5)
    assert len(a) == len(b) == 2000
    assert 0.0 < a[0] and a[-1] < 10.0
    gaps_a, gaps_b = np.diff(a, prepend=0.0), np.diff(b, prepend=0.0)
    assert not np.allclose(gaps_a, gaps_b)
    assert np.allclose(np.sort(gaps_a), np.sort(gaps_b))
    # exponential gaps: the mean is 1/rate and the deviation about the same
    assert gaps_a.mean() == pytest.approx(1 / 200.0, rel=0.01)
    assert gaps_a.std() == pytest.approx(1 / 200.0, rel=0.05)


def test_bursts_ride_on_top_of_the_poisson_arrivals():
    plain = loadgen.arrival_offsets(50.0, 4.0, seed=3)
    burst = loadgen.arrival_offsets(50.0, 4.0, seed=3,
                                    burst={"every_s": 1.0, "size": [17, 32]})
    sizes = sorted(int((burst == t).sum()) for t in (1.0, 2.0, 3.0))
    assert sizes == [17, 24, 32]
    assert len(burst) == len(plain) + sum(sizes)


def test_roofline_floors_on_hand_made_shapes():
    # 1,000 queries over 131,072 x 128 bf16 rows on a v5e:
    # 2 * 1000 * 131072 * 128 = 3.355e10 ops -> 170.3 us of the MXU;
    # 131072 * 128 * 2 B = 33.5 MB -> 40.97 us of HBM: compute binds
    least = roofline.least_seconds(1000, 131072, 128, "bf16", V5E)
    assert least["compute_s"] == pytest.approx(2e3 * 131072 * 128 / 197e12)
    assert least["memory_s"] == pytest.approx(131072 * 128 * 2 / 819e9)
    assert least["bound_by"] == "compute"
    assert least["seconds"] == least["compute_s"]
    # one query: the corpus read binds
    one = roofline.least_seconds(1, 131072, 128, "bf16", V5E)
    assert one["bound_by"] == "memory"
    # int8: half the bytes, twice the peak
    i8 = roofline.least_seconds(1000, 131072, 128, "int8", V5E)
    assert i8["memory_s"] == pytest.approx(one["memory_s"] / 2)
    assert i8["compute_s"] == pytest.approx(2e3 * 131072 * 128 / 393e12)
    # the share: least over busy; nothing to read gives nothing, never 0
    assert roofline.share_percent(1000, 131072, 128, "bf16", V5E,
                                  busy_s=0.01) == \
        pytest.approx(100 * least["seconds"] / 0.01)
    assert roofline.share_percent(0, 131072, 128, "bf16", V5E, 0.01) is None
    assert roofline.share_percent(10, 131072, 128, "bf16", V5E, 0.0) is None


def test_the_peaks_table_refuses_a_device_it_does_not_know():
    assert roofline.peaks_for("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind"):
        roofline.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")


def test_trace_reduction_on_a_recorded_trace():
    """`data/trace_events.json`: device operations cut from a profiler
    trace of the chip (see the file's `origin`), with what a reading by hand
    gives."""
    with open(os.path.join(DATA, "trace_events.json")) as f:
        rec = json.load(f)
    devices = {d: [tuple(e) for e in evs]
               for d, evs in rec["devices"].items()}
    got = trace.reduce_events(devices, rec["window_s"])
    assert got["devices"] == len(devices)
    assert got["window_s"] == rec["window_s"]
    assert got["busy_s"] == pytest.approx(rec["by_hand"]["busy_s"])
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["device_ops"][0][0] == rec["by_hand"]["top_op"]
    assert got["device_ops"][0][1] == pytest.approx(
        rec["by_hand"]["top_op_s"])
    assert got["idle_gaps"][0][1] == pytest.approx(
        rec["by_hand"]["longest_gap_s"])
    assert all(name == "unattributed" for name, _ in got["idle_gaps"])
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_trace_reduction_takes_the_union_of_overlapping_operations():
    events = {"/device:TPU:0": [("a", 0.0, 100.0), ("b", 50.0, 100.0),
                                ("a", 400.0, 100.0)],
              "/device:TPU:1": [("a", 0.0, 100.0)]}
    got = trace.reduce_events(events, window_s=1e-6)
    # device 0: [0,150] + [400,500] = 250 ns; device 1: 100 ns; mean 175
    assert got["busy_s"] == pytest.approx(175e-9)
    assert got["device_ops"] == [["a", pytest.approx(150e-9)],
                                 ["b", pytest.approx(50e-9)]]
    assert got["idle_gaps"] == [["unattributed", pytest.approx(250e-9)]]
    assert trace.reduce_events({}, 1.0)["busy_s"] == 0.0


def test_the_client_sample_reader_sees_the_tail_and_the_stall():
    from benchmark.readers import client_sample
    # 100 requests due 10 ms apart, each answered 5 ms after it was due;
    # but nothing comes back between 0.40 s and 0.90 s: those due in the
    # stall are answered when it ends
    due = [10.0 + 0.01 * i for i in range(100)]
    done = [d + 0.005 if not 10.40 <= d + 0.005 < 10.90 else 10.90 + 0.0001 * i
            for i, d in enumerate(due)]
    sample = loadgen.Sample(t0=10.0, seconds=1.0, index=list(range(100)),
                            weight=[1.0] * 100, due=due, sent=due, done=done,
                            status=[200] * 100, raw=[b""] * 100)
    ctx = {"sample": sample}
    stall = client_sample.read({"statistic": "stall_max_ms"}, ctx)
    assert 495.0 < stall < 515.0
    assert client_sample.read({"statistic": "lateness_p95_ms"}, ctx) == 0.0
    # half the window rode the stall: the end-to-end tail says so
    ok = [True] * 100
    p95 = arithmetic.end_to_end({"statistic": "latency_percentile", "q": 95},
                                sample, ok, 0.0)
    assert 400.0 < p95 < 500.0
    ok[0], sample.done[0] = False, None  # one failure: still a percentile
    assert arithmetic.end_to_end({"statistic": "latency_percentile",
                                  "q": 95}, sample, ok, 0.0) == \
        pytest.approx(p95, rel=0.05)
    with pytest.raises(ValueError, match="unknown statistic"):
        client_sample.read({"statistic": "no_such"}, ctx)
    empty = loadgen.Sample(t0=0.0, seconds=1.0)
    assert client_sample.read({"statistic": "stall_max_ms"},
                              {"sample": empty}) is None


@pytest.mark.parametrize("spec, want", [
    # a cumulative count: its change over the window
    ({"counter": "gc_pauses"}, 30.0),
    # nanoseconds of pause over a 10 s window, as a percentage of it
    ({"counter": "gc_pause_nanos", "per": "second", "scale": 1e-7}, 2.5),
    # a maximum the child starts anew at each read: the value read last
    ({"counter": "gc_pause_max_nanos", "since_last_read": True,
      "scale": 1e-6}, 120.0),
    ({"counter": "not_kept"}, None),
])
def test_the_child_counter_reader(spec, want):
    from benchmark.readers import child_counter
    ctx = {"seconds": 10.0,
           "counters_before": {"gc_pauses": 70, "gc_pause_nanos": 4e8,
                               "gc_pause_max_nanos": 9e8},
           "counters_after": {"gc_pauses": 100, "gc_pause_nanos": 6.5e8,
                              "gc_pause_max_nanos": 1.2e8}}
    got = child_counter.read(spec, ctx)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("spec, want", [
    ({"statistic": "setup_seconds"}, 77.5),
    # five answers due at 0..4 s, taking 10, 20, 30, 40 ms and never
    ({"statistic": "latency_percentile", "q": 50}, 30.0),
    ({"statistic": "latency_percentile", "q": 80}, 40.0),
    ({"statistic": "latency_percentile", "q": 95}, math.inf),
    # four sound answers inside the 5 s window
    ({"statistic": "rate_in_window", "of": "requests"}, 0.8),
    # ... weighing 100 documents each
    ({"statistic": "rate_in_window", "of": "weight"}, 80.0),
])
def test_an_end_to_end_metric_is_the_statistic_its_file_names(spec, want):
    due = [100.0 + i for i in range(5)]
    done = [100.01, 101.02, 102.03, 103.04, None]
    sample = loadgen.Sample(t0=100.0, seconds=5.0, index=list(range(5)),
                            weight=[100.0] * 5, due=due, sent=due, done=done,
                            status=[200] * 4 + [0], raw=[b""] * 5)
    ok = [True] * 4 + [False]
    got = arithmetic.end_to_end(spec, sample, ok, 77.5)
    assert got == pytest.approx(want)


def test_an_unknown_end_to_end_statistic_is_an_error():
    with pytest.raises(ValueError, match="unknown end-to-end statistic"):
        arithmetic.end_to_end({"statistic": "median_of_chunks"},
                              loadgen.Sample(), [], 0.0)


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_a_generator_that_cannot_connect_fails_and_does_not_hang(loop):
    from benchmark.child import RunFailure, free_port
    port = free_port()                  # nothing listens there
    with pytest.raises(RunFailure, match="could not connect"):
        if loop == "closed":
            loadgen.closed_loop(port, 4, 0.2, lambda: None)
        else:
            loadgen.open_loop(port, 4, 0.2, np.zeros(0), [])
