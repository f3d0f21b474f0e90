"""The rate sweep that finds an open-loop cell's knee, once, on the chip.

    python3 cellbench/tools/sweep.py --workload <open-loop cell> --seed 1 \
        --rates 2,3,4,5,6,7,8 --seconds 15

One Scheduler, one set of weights; each rate gets its own window and drain.
The knee is the highest rate at which completions keep up with arrivals (the
drain after the window stays short and TTFT does not climb through the
window). One JSON line a rate; appended to chiprun_out/sweep_<workload>.jsonl.
"""

import argparse
import copy
import json
import os
import queue
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=15.0)
    args = parser.parse_args(argv)

    from cloud_tpu.parallel import compile_cache

    from cellbench import harness, traffic
    from cellbench.drivers import serving

    cell = harness.load_cell(args.workload)
    stamp = harness.device_stamp(cell.chips)
    compile_cache.enable()
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds, trace=False,
                      t_process=time.perf_counter(), device=stamp)
    served = serving.Served(run)
    os.makedirs(os.path.join(harness.ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(harness.ROOT, "chiprun_out",
                        "sweep_{}.jsonl".format(args.workload))
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = copy.deepcopy(cell.traffic)
            mix["rate_per_s"] = rate
            due = traffic.arrival_times(mix, args.seed + k, args.seconds)
            records = [serving.Record(i, *served.requests[i + 1000 * k], due=float(t))
                       for i, t in enumerate(due)]
            before = served.scheduler.stats()
            t0 = harness.now()
            for r in records:
                wait = r.due - (harness.now() - t0)
                if wait > 0:
                    time.sleep(wait)
                r.submitted = harness.now() - t0
                try:
                    r.future = served.submit(r, timeout=0.0)
                except queue.Full:
                    r.error = "queue.Full"
            t_sent = harness.now() - t0
            serving.collect(records, t0, grace_s=120.0)
            done = [r for r in records if r.result is not None]
            ttft = [(r.submitted - r.due) + r.result.ttft_s for r in done]
            half = len(ttft) // 2
            ends = [r.submitted + r.result.latency_s for r in done]
            tpot = [(r.result.latency_s - r.result.ttft_s) / (r.new_tokens - 1)
                    for r in done]
            after = served.scheduler.stats()

            def hist(key):
                n = after[key].get("count", 0) - before[key].get("count", 0)
                total = after[key].get("sum", 0.0) - before[key].get("sum", 0.0)
                return [n, 1e3 * total / n if n else None]
            pct = lambda xs, q: 1e3 * harness.percentile(xs, q) if xs else None
            out = {"rate_per_s": rate, "attempted": len(records),
                   "completed": len(done), "sent_s": t_sent,
                   "drain_s": (max(ends) if ends else 0.0) - args.seconds,
                   "tokens_per_s": sum(r.new_tokens for r in done) / max(ends),
                   "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95),
                   "ttft_p50_first_half_ms": pct(ttft[:half], 50),
                   "ttft_p50_second_half_ms": pct(ttft[half:], 50),
                   "tpot_p50_ms": pct(tpot, 50), "tpot_p95_ms": pct(tpot, 95),
                   "ticks": after["ticks"] - before["ticks"],
                   "queue_wait_n_meanms": hist("queue_wait"),
                   "reserve_wait_n_meanms": hist("reserve_wait"),
                   "prefill_n_meanms": hist("prefill"),
                   "decode_gap_n_meanms": hist("decode_gap"),
                   "prefix_hits": after["prefix_hits"] - before["prefix_hits"],
                   "occupancy": {k: [g["ticks"], g["occupancy_mean"]] for k, g in
                                 after["geometry"]["per_geometry"].items()}}
            line = json.dumps(out)
            print(line, flush=True)
            with open(path, "a", encoding="utf-8") as f:
                f.write(line + "\n")
    finally:
        served.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
