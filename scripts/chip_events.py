#!/usr/bin/env python3
"""One traced run of a cell (`benchmarks/tests/chip_spans.py`, same arguments,
same output) that also reads, at the instants the window opens and closes,
how many Events the apiserver has created and what
`scheduler_failed_scheduling_events_total` says of the server loop's queue,
and times how long after the close the sink thread wrote the last of them.
ISSUE 25's check on the chip: the FailedScheduling Events written inside the
window against the pods wave 1 failed, and `dropped` 0. A program without
the queue (the parent) reads zeros for its outcomes and still counts the
creates.

    python3 scripts/chip_events.py --workload flagship-5k.backlog \
        --seed 3000000019 --seconds 40 [--rehearse]
"""

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks", "tests")]


def main() -> int:
    import chip_spans                      # before jax: it stamps the start
    import jax

    from benchmarks.harness import trace as trace_mod
    from kubernetes_tpu.apiserver.server import REQUEST_DURATION
    from kubernetes_tpu.sched.metrics import FAILED_EVENTS

    def reading() -> dict:
        return {"event_creates_at_the_apiserver": REQUEST_DURATION.count(
                    verb="create", resource="events", subresource=""),
                **{o: int(FAILED_EVENTS.value(outcome=o)) for o in
                   ("queued", "coalesced", "dropped", "emitted", "error")}}

    at = {}
    annotation = jax.profiler.TraceAnnotation

    def time_the_sink(t_close: float) -> None:
        # after the window, so its wake-ups cost the measurement nothing
        for _ in range(1200):
            now = reading()
            if now["emitted"] + now["error"] + now["dropped"] >= \
                    now["queued"]:
                at["sink_done_s_after_close"] = round(
                    time.perf_counter() - t_close, 3)
                return
            time.sleep(0.05)

    def marking(name, *a, **kw):
        # the harness enters one annotation as the window opens and one as
        # it closes: take the reading there, on its own thread
        if name in (trace_mod.MARK_OPEN, trace_mod.MARK_CLOSE):
            at[name] = reading()
        if name == trace_mod.MARK_CLOSE:
            threading.Thread(target=time_the_sink, daemon=True,
                             args=(time.perf_counter(),)).start()
        return annotation(name, *a, **kw)

    jax.profiler.TraceAnnotation = marking
    code = chip_spans.main()
    opened, closed = at.get(trace_mod.MARK_OPEN), at.get(trace_mod.MARK_CLOSE)
    if opened and closed:
        print("events " + json.dumps({
            "in_window": {k: closed[k] - opened[k] for k in closed},
            "sink_done_s_after_close": at.get("sink_done_s_after_close"),
            "after_the_settle": {k: v - opened[k]
                                 for k, v in reading().items()}}),
              flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
