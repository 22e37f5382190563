"""The server's heartbeat (ISSUE 37, `elasticsearch_tpu/telemetry/beat.py`):

* `runtime.lock_wait`: what a beat overslept. A quiet process reads a
  mean well under 2 ms; a thread that spins in Python under a switch
  interval of 50 ms makes the beats late by about that;
* a stall: work in flight and no response out for 50 ms. Open and close
  are counted exactly on a hand-made clock; the record taken while a
  planted lock-holder spins names that thread and its frame;
* `rest.handle.cpu_nanos`: a handler's own processor time, far under its
  wall time where it sleeps and never over it;
* one clock: under a profiler session (on the CPU, in this process) the
  host plane holds `es.runtime.beat` events that carry `mono_ns`, and the
  offset they give places a sampled request's `rest.handle` span (a
  `time.monotonic_ns()` reading, from `GET _nodes/traces`) on its
  `es.rest.handle` event.
"""

import asyncio
import glob
import http.client
import json
import logging
import os
import sys
import threading
import time

import pytest

from elasticsearch_tpu import telemetry
from elasticsearch_tpu.monitor.hot_threads import subsystem_of
from elasticsearch_tpu.telemetry import beat as beat_mod
from elasticsearch_tpu.telemetry import metrics
from elasticsearch_tpu.telemetry.beat import BEAT, PERIOD_NS, STALL_NS, Beat
from tools.beat_anchor import read_beats, summary

MS = 1_000_000
# a stall opens at the first beat that finds 50 ms of silence
OPENS_NS = -(-STALL_NS // PERIOD_NS) * PERIOD_NS


class StubExecutor:
    def __init__(self, name, active=0, queued=0):
        self.name, self.active, self.queued = name, active, queued


class StubPools:
    """What the beat reads of a node's `ThreadPool`: the executors it
    has spun up."""

    def __init__(self, **executors):
        self._pools = {name: StubExecutor(name, *counts)
                       for name, counts in executors.items()}


def _counter(name):
    return metrics.counter(name).value


def _lock_wait():
    h = metrics.histogram(beat_mod.LOCK_WAIT)
    return h.count, h.sum_ns


# ---------------------------------------------------------------------------
# the wait for the lock
# ---------------------------------------------------------------------------

def test_a_quiet_process_reads_a_small_lock_wait():
    idle = StubPools()
    BEAT.watch(idle)
    assert BEAT._thread.is_alive() and BEAT._thread.daemon
    assert BEAT._thread.name == "telemetry-beat"
    assert subsystem_of(BEAT._thread.name) == "telemetry heartbeat"
    means = []
    for _attempt in range(4):       # the sandbox has neighbours
        t0 = time.monotonic_ns()
        n0, s0 = _lock_wait()
        time.sleep(0.4)
        n1, s1 = _lock_wait()
        # one thread, one period: about 100 beats a second, never more
        assert 5 <= n1 - n0 <= (time.monotonic_ns() - t0) / PERIOD_NS + 1
        means.append((s1 - s0) / (n1 - n0))
        if means[-1] < 2 * MS:
            break
    assert min(means) < 2 * MS, means
    # watching a second node's pools starts no second thread
    BEAT.watch(StubPools())
    assert sum(t.name == "telemetry-beat"
               for t in threading.enumerate()) == 1


def test_a_spinning_thread_makes_beats_late_and_the_record_names_it():
    busy = StubPools(search=(1, 0))     # one request, never answered
    BEAT.watch(busy)
    stop = threading.Event()

    def planted_lock_holder():
        n = 0
        while not stop.is_set():
            n += 1                      # Python, and nothing else
        return n

    spinner = threading.Thread(target=planted_lock_holder,
                               name="es[search][planted]", daemon=True)
    stalls, stalled = _counter("runtime.stalls"), \
        _counter("runtime.stall_nanos")
    newest = BEAT.snapshot()["records"][:1]
    prior = sys.getswitchinterval()
    sys.setswitchinterval(0.05)
    try:
        spinner.start()
        time.sleep(0.8)
        stop.set()
    finally:
        sys.setswitchinterval(prior)
    spinner.join(15)
    assert not spinner.is_alive()
    records = []
    for r in BEAT.snapshot()["records"]:
        if newest and r["at_ns"] == newest[0]["at_ns"]:
            break
        records.append(r)
    assert records, "0.8 s with a request in flight and no response"
    # a beat woke, asked for the lock and got it a switch interval later
    late = max(w for r in records for w in r["lock_wait_nanos"])
    assert 25 * MS <= late <= 400 * MS, late
    named = [t for r in records for t in r["threads"]
             if t["name"] == "es[search][planted]"]
    assert named, records[0]["threads"]
    assert named[0]["subsystem"] == "search pool"
    assert 1 <= len(named[0]["frames"]) <= 3
    assert any("planted_lock_holder" in f and "test_telemetry_beat.py" in f
               for t in named for f in t["frames"])
    for r in records:
        assert r["cause"] in ("no_response", "late_beat")
        assert r["in_flight"] == 1
        assert r["pools"] == {"search": {"active": 1, "queued": 0}}
        assert len(r["threads"]) <= 32 and len(r["lock_wait_nanos"]) <= 8
        assert r["gc"]["running"] is False
        assert all(t["name"] != "telemetry-beat" for t in r["threads"])
    # the request is answered (its worker is done): the stall closes
    busy._pools["search"].active = 0
    deadline = time.monotonic() + 5
    while _counter("runtime.stalls") == stalls \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert _counter("runtime.stalls") == stalls + 1
    assert _counter("runtime.stall_nanos") - stalled >= 700 * MS
    opened = [r for r in BEAT.snapshot()["records"] if "stall_nanos" in r]
    assert opened and opened[0]["stall_nanos"] >= 700 * MS


# ---------------------------------------------------------------------------
# open and close, on a hand-made clock
# ---------------------------------------------------------------------------

class Clocked:
    """A `Beat` of the test's own, never started, ticked by hand every
    period of a clock that is a number."""

    def __init__(self, **executors):
        self.pools = StubPools(**executors)
        self.beat = Beat()
        self.beat._pools.add(self.pools)
        self.now = 1_000 * MS
        self.tick()                     # the first beat sees the counts

    def tick(self, late_ns=0):
        start = self.now
        self.now += PERIOD_NS + late_ns
        self.beat.tick(start, self.now)

    def run(self, nanos):
        for _ in range(nanos // PERIOD_NS):
            self.tick()


def _respond():
    metrics.counter(beat_mod.RESPONSES[0]).inc()


def test_a_stall_of_120_ms_adds_one_and_its_length(caplog):
    c = Clocked(search=(2, 1))
    stalls, nanos = _counter("runtime.stalls"), \
        _counter("runtime.stall_nanos")
    with caplog.at_level(logging.WARNING, "elasticsearch_tpu.telemetry"):
        c.run(40 * MS)
        assert c.beat.snapshot()["records"] == []      # not yet 50 ms
        c.run(60 * MS)
        # open: the record is taken once, while the stall goes on
        (record,) = c.beat.snapshot()["records"]
        assert record["cause"] == "no_response"
        assert record["at_ns"] - record["start_ns"] == OPENS_NS
        assert record["in_flight"] == 3
        assert record["pools"] == {"search": {"active": 2, "queued": 1}}
        assert "stall_nanos" not in record
        assert _counter("runtime.stalls") == stalls
        _respond()                      # 100 ms after the last one seen,
        c.tick()                        # which the next beat sees
    assert _counter("runtime.stalls") == stalls + 1
    length = _counter("runtime.stall_nanos") - nanos
    assert abs(length - 120 * MS) <= PERIOD_NS and length > 0
    (record,) = c.beat.snapshot()["records"]
    assert record["stall_nanos"] == length
    # one WARN line a record
    lines = [r.getMessage() for r in caplog.records
             if r.name == "elasticsearch_tpu.telemetry"]
    assert len(lines) == 1 and "\n" not in lines[0]
    assert lines[0].startswith(
        "[beat][no_response] beat late by [0ms], last response seen "
        f"[{OPENS_NS // MS}ms] ago, [3] in flight (active+queued: "
        "search=2+1)")
    # answers that keep leaving keep it shut, however many are in flight
    for _ in range(30):
        _respond()
        c.tick()
    assert _counter("runtime.stalls") == stalls + 1
    assert len(c.beat.snapshot()["records"]) == 1


def test_no_stall_with_nothing_in_flight_whatever_the_silence():
    c = Clocked(search=(0, 0), write=(0, 0))
    stalls, nanos = _counter("runtime.stalls"), \
        _counter("runtime.stall_nanos")
    c.run(3_000 * MS)
    assert _counter("runtime.stalls") == stalls
    assert _counter("runtime.stall_nanos") == nanos
    assert c.beat.snapshot()["records"] == []
    # a request that arrives after the silence starts its own 50 ms
    c.pools._pools["search"].queued = 1
    c.run(40 * MS)
    assert c.beat.snapshot()["records"] == []
    c.pools._pools["search"].queued = 0     # taken and answered
    _respond()
    c.run(100 * MS)
    assert _counter("runtime.stalls") == stalls


def test_a_stall_ends_where_nothing_is_in_flight_any_more():
    c = Clocked(write=(1, 0))
    stalls, nanos = _counter("runtime.stalls"), \
        _counter("runtime.stall_nanos")
    c.run(80 * MS)
    c.pools._pools["write"].active = 0      # a client that went away
    c.tick()
    assert _counter("runtime.stalls") == stalls + 1
    assert abs(_counter("runtime.stall_nanos") - nanos - 90 * MS) \
        <= PERIOD_NS


def test_a_late_beat_takes_a_record_at_once_and_keeps_the_newest_eight(
        caplog):
    c = Clocked(search=(0, 0))
    stalls = _counter("runtime.stalls")
    with caplog.at_level(logging.INFO, "elasticsearch_tpu.telemetry"):
        for i in range(11):
            c.tick(late_ns=STALL_NS + i * MS)
            c.tick(late_ns=STALL_NS)    # late beats in a row: the first
            c.run(1_000 * MS)
    # nothing was in flight, nobody waited: a line each, below WARN
    assert [r.levelno for r in caplog.records] == [logging.INFO] * 11
    snap = c.beat.snapshot()
    assert len(snap["records"]) == 8
    assert [r["cause"] for r in snap["records"]] == ["late_beat"] * 8
    # newest first; a record carries the last beats' waits, its own last
    assert snap["records"][0]["lock_wait_nanos"][-1] == STALL_NS + 10 * MS
    assert snap["records"][7]["lock_wait_nanos"][-1] == STALL_NS + 3 * MS
    assert all(len(r["lock_wait_nanos"]) <= 8 for r in snap["records"])
    # nothing was in flight: late beats alone are no stall
    assert _counter("runtime.stalls") == stalls
    assert snap["count"] == _counter("runtime.stalls")
    assert snap["nanos"] == _counter("runtime.stall_nanos")


def test_a_stall_the_beat_slept_through_counts_by_its_lateness():
    """A full collection holds the lock: the beat due inside it runs only
    after it, and by then the first answers are out again."""
    c = Clocked(search=(20, 35))
    stalls, nanos = _counter("runtime.stalls"), \
        _counter("runtime.stall_nanos")
    for _ in range(5):
        _respond()
        c.tick()
    _respond()                          # one left before the beat looked
    c.tick(late_ns=716 * MS)
    assert _counter("runtime.stalls") == stalls + 1
    assert _counter("runtime.stall_nanos") - nanos == 716 * MS
    (record,) = c.beat.snapshot()["records"]
    assert record["cause"] == "late_beat"
    assert record["stall_nanos"] == 716 * MS
    # the next late beat falls inside the records' one-second gap: it
    # counts all the same
    _respond()
    c.tick(late_ns=60 * MS)
    assert _counter("runtime.stalls") == stalls + 2
    assert _counter("runtime.stall_nanos") - nanos == 776 * MS
    assert len(c.beat.snapshot()["records"]) == 1
    # where no response left either, the stall opens as any other and is
    # counted once, when it closes, from the last response seen
    for _ in range(100):
        _respond()
        c.tick()
    c.tick(late_ns=300 * MS)
    assert _counter("runtime.stalls") == stalls + 2
    _respond()
    c.tick()
    assert _counter("runtime.stalls") == stalls + 3
    assert _counter("runtime.stall_nanos") - nanos == \
        (776 + 300) * MS + 2 * PERIOD_NS
    assert [r["cause"] for r in c.beat.snapshot()["records"]] == \
        ["late_beat", "late_beat"]


def test_a_record_says_whether_the_collector_runs():
    import gc

    telemetry.time_gc()
    seen = []

    def look(phase, _info):
        if phase == "stop":             # after the hook's own `start`
            c = Clocked(search=(1, 0))
            c.tick(late_ns=STALL_NS)
            seen.append(c.beat.snapshot()["records"][0]["gc"])

    gc.callbacks.insert(0, look)
    try:
        gc.collect()
    finally:
        gc.callbacks.remove(look)
    assert seen and seen[0]["running"] is True
    c = Clocked(search=(1, 0))
    c.tick(late_ns=STALL_NS)
    after = c.beat.snapshot()["records"][0]["gc"]
    assert after["running"] is False and after["last_nanos"] > 0
    assert after["last_start_ns"] == seen[0]["last_start_ns"]


# ---------------------------------------------------------------------------
# the handler's own processor time
# ---------------------------------------------------------------------------

def _handle(fn):
    """(processor nanos, wall nanos) that one `Front` block filed."""
    cpu0 = _counter("rest.handle.cpu_nanos")
    hist = metrics.histogram("rest.handle")
    wall0 = hist.sum_ns
    now = time.monotonic_ns()
    with telemetry.Front(now, now):
        fn()
    return _counter("rest.handle.cpu_nanos") - cpu0, hist.sum_ns - wall0


def test_a_handler_that_sleeps_reads_little_processor_time():
    cpu, wall = _handle(lambda: time.sleep(0.1))
    assert wall >= 100 * MS
    assert 0 <= cpu < wall / 10


def test_a_handler_that_computes_never_reads_more_than_its_wall_time():
    def work():
        deadline = time.monotonic() + 0.05
        while time.monotonic() < deadline:
            sum(range(1000))
    for _ in range(3):
        cpu, wall = _handle(work)
        assert 0 < cpu <= wall
    # and most of it where nothing else wants the processor
    assert max(_handle(work)[0] / 50e6 for _ in range(3)) > 0.5


# ---------------------------------------------------------------------------
# one clock
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """A node behind its HTTP server, five sampled searches under a
    profiler session, and what the session wrote: (the `es.runtime.beat`
    events as (start, duration, `mono_ns`), the `es.rest.handle` events
    as (start, duration), the `rest.handle` spans of `GET _nodes/traces`)."""
    import jax

    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.rest.actions import register_all
    from elasticsearch_tpu.rest.controller import RestController
    from elasticsearch_tpu.rest.http_server import HttpServer

    node = Node(str(tmp_path_factory.mktemp("beat_node")),
                settings={"telemetry.tracing.sample_rate": 0.0})
    rest = RestController()
    register_all(rest, node)
    node.index_doc("idx", "1", {"a": "hello"}, refresh="true")
    server = HttpServer(rest, host="127.0.0.1", port=0,
                        thread_pool=node.thread_pool)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def serve():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(server.start())
        started.set()
        loop.run_forever()

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    assert started.wait(15)
    trace_dir = str(tmp_path_factory.mktemp("beat_trace"))
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)

    def call(method, path, body=None):
        conn.request(method, path, body and json.dumps(body),
                     {"content-type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        return json.loads(resp.read())

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # as benchmark/serve.py starts it
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for _ in range(5):
            call("POST", "/idx/_search?trace=true",
                 {"query": {"match": {"a": "hello"}}})
            time.sleep(0.03)
        time.sleep(0.1)
    finally:
        jax.profiler.stop_trace()
    try:
        traces = call("GET", "/_nodes/traces?size=50")
    finally:
        conn.close()
        loop.call_soon_threadsafe(loop.stop)
        thread.join(15)
        node.close()
    spans = [sp for section in traces["nodes"].values()
             for tr in section["traces"] for sp in tr["spans"]
             if sp["name"] == "rest.handle"]
    beats = read_beats(trace_dir)       # the operator's reader, tools/
    paths = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(sorted(paths)[-1])
    handles = [(e.start_ns, e.duration_ns)
               for plane in data.planes if plane.name == "/host:CPU"
               for line in plane.lines for e in line.events
               if e.name == "es.rest.handle"]
    return beats, handles, spans


def test_the_host_plane_holds_beats_that_carry_the_monotonic_clock(session):
    beats, _handles, _spans = session
    assert len(beats) >= 5          # some 0.3 s of session
    monos = [mono for _s, _d, mono in beats]
    assert all(isinstance(m, int) and m > 0 for m in monos)
    assert monos == sorted(monos)
    now = time.monotonic_ns()
    assert all(now - 600e9 < m < now for m in monos)
    # the event is the beat's sleep: its length less the period is the
    # beat's lock wait
    for _start, dur, _mono in beats:
        assert PERIOD_NS <= dur < PERIOD_NS + 200 * MS
    # every beat gives nearly the same offset: two clocks, one rate
    diffs = sorted(start - mono for start, _d, mono in beats)
    assert diffs[len(diffs) // 2] - diffs[0] < 1 * MS


def test_the_anchor_places_a_sampled_span_on_its_profiler_event(session):
    beats, handles, spans = session
    assert len(spans) == 5 and len(handles) >= 5
    offset = beat_mod.anchor_offset_ns(
        (start, mono) for start, _d, mono in beats)
    errors = []
    for sp in spans:
        placed = sp["start_ns"] + offset
        start, dur = min(handles, key=lambda h: abs(h[0] - placed))
        errors.append(abs(start - placed))
        assert abs(dur - sp["dur_ns"]) < 1 * MS
    errors.sort()
    # a thread may lose the processor between an event's start and the
    # span's clock reading: the median of five does not
    assert errors[2] < 1 * MS, errors
    # what `tools/beat_anchor.py` prints of the same trace
    printed = summary(beats)
    assert printed["offset_ns"] == offset and printed["beats"] == len(beats)
    assert abs(printed["drift_ns"]) < 1 * MS
    assert 0 <= printed["lock_wait_mean_ms"] <= printed["lock_wait_max_ms"]
    with pytest.raises(ValueError):
        beat_mod.anchor_offset_ns([])


def test_nodes_stats_shows_the_stalls_and_the_names_from_the_start(
        tmp_path):
    from elasticsearch_tpu.node import Node

    node = Node(str(tmp_path / "n"))
    try:
        stats = node._telemetry_stats_section()
    finally:
        node.close()
    for name in ("runtime.stalls", "runtime.stall_nanos",
                 "rest.handle.cpu_nanos"):
        assert stats["counters"][name] >= 0, name
    assert "runtime.lock_wait" in stats["histograms"]
    assert set(stats["stalls"]) == {"count", "nanos", "records"}
    assert stats["stalls"]["count"] == stats["counters"]["runtime.stalls"]
    json.dumps(stats["stalls"])         # the REST layer can render it
