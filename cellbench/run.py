"""One process, one cell, once.

    python3 cellbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Stamps the device first and exits non-zero when JAX's default backend is not
a TPU with the chips the cell asks for: there is no CPU fallback and no switch
for one (tier-1 calls the drivers directly at toy widths). The last line of
standard output is the result; the numbers compared for `correct`, each
beside its limit, are the last lines of standard error and the last key of
the result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    # First, so that a directory without the program fails before any output.
    from cloud_tpu.parallel import compile_cache

    from cellbench import harness

    cell = harness.load_cell(args.workload)
    stamp = harness.device_stamp(cell.chips)
    peaks = harness.peaks_for(stamp["kind"])
    print("device: platform={platform} kind={kind!r} count={count}".format(**stamp),
          file=sys.stderr, flush=True)
    compile_cache.enable()
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_process=T_PROCESS, peaks=peaks,
                      device=stamp)
    observed = harness.find("drivers", cell.traffic["driver"]).run(run)
    line = harness.result_line(cell, run, observed)
    cache = compile_cache.stats()
    print("compile_cache: hits={persistent_hits} misses={persistent_misses}".format(
        **cache), file=sys.stderr, flush=True)
    print("counters: {}".format(json.dumps(observed.get("counters", {}))),
          file=sys.stderr, flush=True)
    observed["compared"].print_stderr()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
