"""The readings the block-diffusion cell's limits are set from, on the chip at
the cell's own size and traffic, one seed after another in one process:

    python3 cellbench/tools/readings_sdar.py --workload sdar_blockdiff_gen \
        --seeds 1,2 [--control int8] [--fault causal_block,no_clean_pass,least_confident] \
        [--seconds 45] [--trace 1]

The program's numbers against the reference (the lower reading); the upper
readings, each put in the program's place and held to the cell's limits
through `harness.Compared` as a run is (`correct`): with `--control` the
replay in that lower precision of its products; with `--fault` the replay
with a fault of block diffusion in it (`cellbench/reference/sdar.py`
`FAULTS`: "causal_block", attention inside a block made causal;
"no_clean_pass", every block closed without its clean pass, so the cache
keeps the rows of its last step, mask tokens where that step committed),
or "least_confident": every step's choice replaced by its least confident
masked rows, a remasking that ranks backwards (the driver's `rank_excess`).
Beside them the line gives what `near_tie_eps` is chosen from (for a ladder of
eps, the share of positions left out and the gaps kept) and what `rank_eps`
is chosen from (for a ladder of eps, the share of steps ranked otherwise),
the run's end-to-end metrics and its memory peak. One JSON line a seed, on standard output and appended to
chiprun_out/readings_<workload>.jsonl; with `--trace 1` the traced slice's ops
go to chiprun_out/trace_<workload>_ops.json. The benchmark's own runs never
call this.
"""

import argparse
import json
import os
import sys
import time

LEAST_CONFIDENT = "least_confident"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def judged(driver, limits, gaps, margins, excess):
    """An upper reading held to the cell's limits as a run is."""
    from cellbench import harness
    from cellbench.drivers import closed_loop_routed

    compared = harness.Compared()
    driver.add_compared(compared, limits, gaps, margins, excess)
    return {"correct": compared.ok, "compared": compared.as_dict(),
            "by_eps": closed_loop_routed.ladder(gaps, margins),
            "rank_flip_by_eps": driver.rank_ladder(excess),
            "rank_excess_percentiles": percentiles(excess)}


def percentiles(excess):
    """The 1st, 10th, 50th, 90th and 99th percentiles and the largest of
    the steps' rank excess (`rank_excess`)."""
    from cellbench import harness

    if not excess:
        return None
    return [harness.percentile(excess, q) for q in (1, 10, 50, 90, 99)] + [
        max(excess)]


def readings(run, args):
    from cellbench import harness

    driver = harness.find("drivers", run.cell.traffic["driver"])
    kept = {}
    original = driver.served_gaps

    def keeping(cfg, shapes, seed, sequences, **kw):
        kept.update(cfg=cfg, shapes=shapes, sequences=sequences)
        kept["program"] = original(cfg, shapes, seed, sequences, **kw)
        return kept["program"]

    driver.served_gaps = keeping
    try:
        observed = driver.run(run)
    finally:
        driver.served_gaps = original
    out = {"correct": observed["compared"].ok,
           "program": {r["name"]: r["value"] for r in observed["compared"].rows},
           "end_to_end": observed["end_to_end"],
           "memory_peak_bytes": observed["memory_peak_bytes"],
           "window_s": observed["window_s"],
           "reference_s": observed["reference_s"],
           "counters": observed["counters"]}
    gaps, margins, steps = kept["program"]
    excess = driver.rank_excess(steps)
    out["rank_excess_percentiles"] = percentiles(excess)
    again = lambda **kw: original(kept["cfg"], kept["shapes"], run.seed,
                                  kept["sequences"], **kw)[:2]
    limits = run.cell.limits
    if args.control:
        out["control_" + args.control] = judged(
            driver, limits, *again(chooser=args.control), excess)
    for fault in filter(None, (args.fault or "").split(",")):
        if fault == LEAST_CONFIDENT:
            out["fault_" + fault] = judged(driver, limits, gaps, margins,
                                           driver.rank_excess(steps, True))
        else:
            out["fault_" + fault] = judged(driver, limits,
                                           *again(fault=fault), excess)
    trace = observed.get("trace")
    if trace is not None:
        out["line_metrics"] = harness.metric_values(run.cell, run, observed)
        out["busy_s"], out["window_s_traced"] = trace.busy_s, trace.window_s
        out["breakdown"] = trace.breakdown()
        path = os.path.join(harness.ROOT, "chiprun_out",
                            "trace_{}_ops.json".format(run.cell.name))
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"window_s": trace.window_s, "busy_s": trace.busy_s,
                       "programs": sorted(set(trace.modules.names)),
                       "ops": [[n, trace.op_seconds[n], trace.op_counts[n],
                                trace.op_text[n][:400]] for n in sorted(
                                    trace.op_seconds,
                                    key=trace.op_seconds.get, reverse=True)]},
                      f)
    return out


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control", default=None)
    parser.add_argument("--fault", default=None)
    parser.add_argument("--seconds", type=float, default=45.0)
    parser.add_argument("--trace", type=int, default=0)
    args = parser.parse_args(argv)

    from cloud_tpu.parallel import compile_cache

    from cellbench import harness

    cell = harness.load_cell(args.workload)
    stamp = harness.device_stamp(cell.chips)
    compile_cache.enable()
    os.makedirs(os.path.join(harness.ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(harness.ROOT, "chiprun_out",
                        "readings_{}.jsonl".format(args.workload))
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run = harness.Run(cell=cell, seed=seed, seconds=args.seconds,
                          trace=bool(args.trace), t_process=t0,
                          peaks=harness.peaks_for(stamp["kind"]), device=stamp)
        out = readings(run, args)
        out.update(workload=args.workload, seed=seed,
                   seconds_taken=time.perf_counter() - t0)
        line = json.dumps(out)
        print(line, flush=True)
        with open(path, "a", encoding="utf-8") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
