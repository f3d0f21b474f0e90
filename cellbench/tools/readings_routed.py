"""The readings a routed cell's limits are set from, on the chip at the cell's
own size and traffic, one seed after another in one process:

    python3 cellbench/tools/readings_routed.py --workload <name> --seeds 1,2 \
        [--control int8] [--fault swap_experts] [--seconds 45] [--trace 1]

The program's numbers against the reference (the lower reading); with
`--control` the reference in that lower precision put in the program's place,
and with `--fault swap_experts` the reference with two held experts' weights
swapped in one layer put there (the upper readings). Beside the numbers
compared, the line gives what `near_tie_eps` is chosen from: for a ladder of
eps, the share of positions left out and the widest gap among those kept. One
JSON line a seed, on standard output and appended to
chiprun_out/readings_<workload>.jsonl, and every position's gap and margin
(program, control, fault) in chiprun_out/readings_<workload>_<seed>_gaps.json;
with `--trace 1` the traced slice's
ops (name, seconds, events, text) go to chiprun_out/trace_<workload>_ops.json.
The benchmark's own runs never call this.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def swap_experts(params, layer="block_2", a=3, b=5):
    """`params` with experts a and b of one layer's held experts swapped."""
    import jax

    def swapped(w):
        return w.at[a].set(w[b]).at[b].set(w[a])
    moe = dict(params[layer]["moe"])
    for name in ("expert_gate", "expert_up", "expert_down"):
        moe[name] = jax.jit(swapped)(moe[name])
    out = dict(params)
    out[layer] = dict(params[layer], moe=moe)
    return out


def readings(run, args):
    from cellbench import harness

    driver = harness.find("drivers", run.cell.traffic["driver"])
    ladder = driver.ladder
    kept = {}
    original = driver.served_gaps

    def keeping(cfg, shapes, seed, sequences, max_seq, max_new, **kw):
        kept.update(cfg=cfg, shapes=shapes, sequences=sequences, max_seq=max_seq,
                    max_new=max_new)
        out = original(cfg, shapes, seed, sequences, max_seq, max_new, **kw)
        kept["program"] = out
        return out

    driver.served_gaps = keeping
    try:
        observed = driver.run(run)
    finally:
        driver.served_gaps = original
    gaps, margins = kept["program"]
    out = {"program": {r["name"]: r["value"] for r in observed["compared"].rows},
           "program_by_eps": ladder(gaps, margins),
           "end_to_end": observed["end_to_end"],
           "memory_peak_bytes": observed["memory_peak_bytes"],
           "reference_s": observed["reference_s"],
           "counters": observed["counters"]}
    again = lambda **kw: original(kept["cfg"], kept["shapes"], run.seed,
                                  kept["sequences"], kept["max_seq"],
                                  kept["max_new"], **kw)
    arrays = {"program": kept["program"]}
    if args.control:
        arrays["control_" + args.control] = again(chooser=args.control)
    if args.fault == "swap_experts":
        arrays["fault_swap_experts"] = again(plant=swap_experts)
    for name, (gaps, margins) in arrays.items():
        if name != "program":
            out[name] = ladder(gaps, margins)
    # Every position's gap and margin, for a statistic the ladder lacks.
    with open(os.path.join(harness.ROOT, "chiprun_out", "readings_{}_{}_gaps.json"
                           .format(run.cell.name, run.seed)), "w",
              encoding="utf-8") as f:
        json.dump({name: {"gaps": gaps, "margins": margins}
                   for name, (gaps, margins) in arrays.items()}, f)
    trace = observed.get("trace")
    if trace is not None:
        out["line_metrics"] = harness.metric_values(run.cell, run, observed)
        path = os.path.join(harness.ROOT, "chiprun_out",
                            "trace_{}_ops.json".format(run.cell.name))
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"window_s": trace.window_s, "busy_s": trace.busy_s,
                       "programs": sorted(set(trace.modules.names)),
                       "program_runs": len(trace.modules.names),
                       "ops": [[n, trace.op_seconds[n], trace.op_counts[n],
                                trace.op_text[n]] for n in sorted(
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
