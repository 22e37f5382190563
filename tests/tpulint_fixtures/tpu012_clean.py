"""TPU012 clean: monotonic duration clocks; spans closed structurally."""
# tpulint: hot-path
import time


def monotonic_duration(fn):
    t0 = time.perf_counter_ns()
    fn()
    return time.perf_counter_ns() - t0


def deadline_math(budget_s):
    return time.monotonic() + budget_s


def context_manager_span(telemetry, work):
    with telemetry.stage("score"):
        return work()


def try_finally_span(trace, work):
    sp = trace.begin_span("drain")
    try:
        return work()
    finally:
        trace.end_span(sp)


def cross_closure_close(trace, launch):
    leg = trace.begin_span("leg")

    def resolve(outcome):
        trace.end_span(leg, status=outcome)

    launch(resolve)


def closed_span(telemetry, start_ns, end_ns):
    # born closed — stage_done cannot leak
    telemetry.stage_done("device.sync", start_ns, end_ns)
