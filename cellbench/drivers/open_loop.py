"""Open loop: requests are sent when they are due, at the rate fixed in the
mix's file, whether or not earlier ones have finished. Times count from when
a request was due, so a stall shows in the requests behind it."""

import queue
import time

from cellbench import harness, tracing, traffic
from cellbench.drivers import serving


def run(run):
    mix = run.cell.traffic
    served = serving.Served(run)
    try:
        before = served.scheduler.stats()
        due = traffic.arrival_times(mix, run.seed, run.seconds)
        records = [serving.Record(i, *served.requests[i], due=float(t))
                   for i, t in enumerate(due)]
        tracer = tracing.Slice(run, mix)
        t0 = harness.now()
        setup_s = run.setup_s(t0)
        compiles = served.watch.mark()
        tracer.arm(t0)
        for r in records:
            wait = r.due - (harness.now() - t0)
            if wait > 0:
                time.sleep(wait)
            r.submitted = harness.now() - t0
            try:
                r.future = served.submit(r, timeout=0.0)
            except queue.Full:
                r.error = "queue.Full"
        serving.collect(records, t0)
        tracer.close()
        ends = [r.submitted + r.result.latency_s for r in records
                if r.result is not None]
        t_end = t0 + (max(ends) if ends else run.seconds)
        observed = serving.finish(run, served, records, t0, t_end, before, tracer,
                                  compiles)
    finally:
        if served.scheduler is not None:
            served.close()
    observed["end_to_end"]["setup_s"] = setup_s
    return observed
