"""Closed loop: `clients` callers, each sends its next request when its last
completes. Submission stops at `--seconds`; requests in flight are drained
and the rate divides by the time to the last completion."""

import queue

from cellbench import harness, tracing
from cellbench.drivers import serving


def run(run):
    mix = run.cell.traffic
    served = serving.Served(run)
    try:
        before = served.scheduler.stats()
        finished = queue.Queue()
        records = []

        def send(t0):
            i = len(records)
            prompt, new = served.requests[i]
            r = serving.Record(i, prompt, new, due=harness.now() - t0)
            r.submitted = r.due
            records.append(r)
            r.future = served.submit(r)
            r.future.add_done_callback(
                lambda f, r=r: finished.put((r, harness.now())))

        tracer = tracing.Slice(run, mix)
        t0 = harness.now()
        setup_s = run.setup_s(t0)
        compiles = served.watch.mark()
        tracer.arm(t0)
        for _ in range(int(mix["clients"])):
            send(t0)
        outstanding, t_end = int(mix["clients"]), t0
        while outstanding:
            r, t_done = finished.get(timeout=120)
            r.done, t_end = t_done - t0, t_done
            if harness.now() - t0 < run.seconds:
                send(t0)
            else:
                outstanding -= 1
        tracer.close()
        serving.collect(records, t0)
        observed = serving.finish(run, served, records, t0, t_end, before, tracer,
                                  compiles)
    finally:
        if served.scheduler is not None:
            served.close()
    observed["end_to_end"]["setup_s"] = setup_s
    observed["end_to_end"].pop("ttft_p50_ms", None)   # a closed loop has no due time
    return observed
