"""The program's stages on the profiler's clock (ISSUE 26): a
`jax.profiler` session, in this process, on the CPU, around a few
`telemetry.stage` blocks; the `.xplane.pb` it wrote is read back and the
`es.<name>` events are found on the host plane, each on the line of the
thread it ran on, nested as the blocks were. This is the small recorded
trace the stages are checked against. One file, because it starts a
profiler session on the backend of its worker; nothing of `benchmark/` is
used (the reduction that names idle gaps from these events is the next
`benchmark` issue).
"""

import gc
import glob
import os
import threading
import time

import pytest

from elasticsearch_tpu import telemetry


@pytest.fixture(scope="module")
def host_lines(tmp_path_factory):
    """{line index: [(name, start_ns, duration_ns)]} of the host plane's
    `es.*` events, from one session."""
    import jax
    import jax.numpy as jnp

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    telemetry.time_gc()
    with telemetry.stage("t26p.before_session"):
        pass                        # no session: no event, no error
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # as benchmark/serve.py starts it
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with telemetry.stage("t26p.outer"):
            with telemetry.stage("t26p.inner"):
                jnp.dot(jnp.ones((64, 64)),
                        jnp.ones((64, 64))).block_until_ready()
            telemetry.stage_done("t26p.wait", time.monotonic_ns() - 10,
                                 time.monotonic_ns())

        # the HTTP front's stretches: the event alone (their stages are
        # filed from clock marks when the response is written)
        with telemetry.annotation("t26p.mark"):
            time.sleep(0.001)

        def worker():
            with telemetry.stage("t26p.worker", section="batcher-drain"):
                time.sleep(0.002)
            now = time.monotonic_ns()
            with telemetry.Front(now, now):     # es.rest.handle
                time.sleep(0.001)

        th = threading.Thread(target=worker)
        th.start()
        th.join(10)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert paths, "the session wrote no .xplane.pb"
    data = jax.profiler.ProfileData.from_file(paths[-1])
    lines = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            events = [(e.name, e.start_ns, e.duration_ns)
                      for e in line.events if e.name.startswith("es.")]
            if events:
                lines[i] = events
    return lines


def _find(lines, name):
    return [(i, ev) for i, evs in lines.items() for ev in evs
            if ev[0] == name]


def test_same_thread_stages_are_host_events_named_es(host_lines):
    for name in ("es.t26p.outer", "es.t26p.inner", "es.t26p.worker"):
        assert len(_find(host_lines, name)) == 1, (name, host_lines)


def test_nested_stages_nest_on_their_threads_line(host_lines):
    (lo, outer), = _find(host_lines, "es.t26p.outer")
    (li, inner), = _find(host_lines, "es.t26p.inner")
    assert lo == li, "one thread, one line"
    assert outer[1] <= inner[1]
    assert inner[1] + inner[2] <= outer[1] + outer[2]
    assert inner[2] > 0


def test_a_stage_on_another_thread_is_on_that_threads_line(host_lines):
    (lo, _outer), = _find(host_lines, "es.t26p.outer")
    (lw, worker), = _find(host_lines, "es.t26p.worker")
    assert lw != lo
    assert worker[2] >= 2_000_000      # it slept 2 ms inside


def test_the_fronts_stretches_are_events_without_a_stage_object(host_lines):
    (_lm, mark), = _find(host_lines, "es.t26p.mark")
    assert mark[2] >= 1_000_000
    (lw, _worker), = _find(host_lines, "es.t26p.worker")
    (lh, handle), = _find(host_lines, "es.rest.handle")
    assert lh == lw and handle[2] >= 1_000_000
    # with no session on, the same call hands out one shared no-op
    assert telemetry.annotation("t26p.off") is telemetry.annotation("x")


def test_cross_thread_form_and_sessionless_stage_leave_no_event(host_lines):
    # stage_done is a wait that ended elsewhere: no annotation to enter
    assert _find(host_lines, "es.t26p.wait") == []
    assert _find(host_lines, "es.t26p.before_session") == []


def test_collector_pause_is_a_host_event(host_lines):
    pauses = _find(host_lines, "es.runtime.gc_pause")
    assert pauses, "gc.collect() ran inside the session"
    assert all(ev[2] > 0 for _i, ev in pauses)
