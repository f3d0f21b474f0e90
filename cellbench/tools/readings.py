"""The readings a limit is set from, on the chip at the cell's own size.

    python3 cellbench/tools/readings.py --workload <name> --seeds 1,2,3 \
        [--control fp8|int8|bfloat16] [--fault half_batch] [--seconds 6]

For each seed, in one process: the program's numbers against the reference
(the lower reading), and with `--control` the reference computed in the
nearest precision below the configuration's, put in the program's place (the
upper reading). `--fault half_batch` (training) plants that fault in the
reference put in the program's place. One JSON line a seed, on standard
output and appended to chiprun_out/readings_<workload>.jsonl. The benchmark's
own runs never call this.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def train_readings(run, args):
    from cellbench import harness
    from cellbench.drivers import fit_window as fw

    built = fw.Built(run)
    got = built.first_steps()
    batches, shapes, cfg, opt = (built.check_batches(), built.shapes, built.cfg,
                                 built.opt)
    built.free()
    want = fw.reference_readings(cfg, batches, shapes, run.seed, opt)
    lax = {k: float("inf") for k in ("grad_norm_gap_median_leaf",
                                     "update_norm_gap")}
    out = {}

    def held(name, readings):
        compared = harness.Compared()
        leaves = fw.compare_readings(compared, readings, want, lax)
        out[name] = {r["name"]: r["value"] for r in compared.rows}
        out[name].update(leaves)

    held("program", got)
    if args.control:
        held("control_" + args.control, fw.reference_readings(
            cfg, batches, shapes, run.seed, opt, precision=args.control))
    if args.fault == "half_batch":
        half = [(x[:len(x) // 2], y[:len(y) // 2]) for x, y in batches]
        held("fault_half_batch", fw.reference_readings(
            cfg, half, shapes, run.seed, opt))
    out["ref_losses"] = want["losses"]
    grads = sorted(want["grad_norms"].values())
    out["ref_grad_norm_min_median_max"] = [grads[0], grads[len(grads) // 2], grads[-1]]
    return out


def serve_readings(run, args):
    from cellbench import harness
    from cellbench.drivers import serving

    driver = harness.find("drivers", run.cell.traffic["driver"])
    kept = {}
    original = serving.served_gaps

    def keeping(cfg, shapes, seed, sequences, max_seq, max_new, **kw):
        kept.update(cfg=cfg, shapes=shapes, sequences=sequences, max_seq=max_seq,
                    max_new=max_new)
        return original(cfg, shapes, seed, sequences, max_seq, max_new, **kw)

    serving.served_gaps = keeping
    try:
        observed = driver.run(run)
    finally:
        serving.served_gaps = original
    out = {"program": {r["name"]: r["value"] for r in observed["compared"].rows},
           "end_to_end": observed["end_to_end"],
           "counters": {k: observed["counters"][k] for k in (
               "requests", "completed", "compared_tokens", "ticks")}}
    if args.control:
        gaps = original(kept["cfg"], kept["shapes"], run.seed, kept["sequences"],
                        kept["max_seq"], kept["max_new"], chooser=args.control)
        gaps = sorted(gaps)
        out["control_" + args.control] = {
            "served_logit_gap_max": gaps[-1],
            "gap_p50": gaps[len(gaps) // 2], "gap_p90": gaps[int(len(gaps) * 0.9)]}
    return out


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control", default=None)
    parser.add_argument("--fault", default=None)
    parser.add_argument("--seconds", type=float, default=6.0)
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
        run = harness.Run(cell=cell, seed=seed, seconds=args.seconds, trace=False,
                          t_process=t0, peaks=harness.peaks_for(stamp["kind"]),
                          device=stamp)
        entry = cell.config["entry"]
        out = (train_readings if entry == "train_fit" else serve_readings)(run, args)
        out.update(workload=args.workload, seed=seed,
                   seconds_taken=time.perf_counter() - t0)
        line = json.dumps(out)
        print(line, flush=True)
        with open(path, "a", encoding="utf-8") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
