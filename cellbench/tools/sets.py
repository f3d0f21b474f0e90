"""The runs a bound is set from: for one cell, two sets of runs with the same
seeds in both, each run a process of its own, as the driver's check makes
them.

    python3 cellbench/tools/sets.py --workload <name> --seconds 45 \
        --seeds 2147480001,2147480002,... [--sets 2] [--trace 0]

Every result line is appended to chiprun_out/sets_<workload>.jsonl with its
set's number and the run's wall seconds; the summary gives, per metric and
set, the median and the spread (distance between the first and third quartile
as `statistics.quantiles(values, n=4)` gives them, over the median). This
process never touches JAX: a chip belongs to one process at a time.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one_run(workload, seed, seconds, trace):
    t0 = time.perf_counter()
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "cellbench", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, check=False)
    lines = done.stdout.strip().splitlines()
    try:
        line = json.loads(lines[-1]) if lines else {}
    except ValueError:
        line = {}
    line.update(rc=done.returncode, wall_s=time.perf_counter() - t0,
                stderr_tail=done.stderr[-1500:])
    return line


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--trace", type=int, default=0)
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", "sets_{}.jsonl".format(args.workload))
    values = {}
    for k in range(args.sets):
        for seed in seeds:
            line = one_run(args.workload, seed, args.seconds, args.trace)
            line["set"] = k
            with open(path, "a", encoding="utf-8") as f:
                f.write(json.dumps(line) + "\n")
            metrics = {n: m["value"] for n, m in line.get("metrics", {}).items()}
            print(json.dumps({
                "set": k, "seed": seed, "rc": line["rc"],
                "correct": line.get("correct"), "wall_s": round(line["wall_s"], 1),
                "metrics": metrics,
                "compared": {n: c["value"] for n, c in
                             line.get("compared", {}).items()},
                "memory_peak_bytes": line.get("device", {}).get("memory_peak_bytes"),
            }), flush=True)
            if line["rc"] != 0 or not line.get("correct"):
                print(line["stderr_tail"], flush=True)
            for name, value in metrics.items():
                values.setdefault(name, {}).setdefault(k, []).append(value)
    for name, by_set in sorted(values.items()):
        for k, xs in sorted(by_set.items()):
            # The first run of the first set may compile: set-up apart.
            kept = xs[1:] if name == "setup_s" and k == 0 else xs
            print("summary {} set {}: n={} median={:.6g} spread={}".format(
                name, k, len(kept), statistics.median(kept),
                "{:.4%}".format(spread(kept)) if spread(kept) is not None else "-"),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
