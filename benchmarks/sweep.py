"""ResNet50 throughput sweep: batch size x stem variant on one chip.

Finds the best operating point for the flagship metric (bench.py,
BASELINE.md config 2) by running `python bench.py` across a grid. Each
point is its own bounded child process (bench.py reads its BENCH_* env
at import, and an infeasible point must not take the sweep down),
emits one JSON line, and the sweep ends with a summary line naming the
best config as the BENCH_BATCH / BENCH_S2D / BENCH_SPE env to give
bench.py.

One process for each chip: THIS parent never imports JAX, so it never
holds the chip its children need; points run one after another.

Axis VALUE ORDER is execution order: the defaults run the
highest-expected-value points first (spe=5 at the flagship batch).

Usage: python benchmarks/sweep.py [--batches 256,512,128] [--s2d 0,1]
       [--spe 5,10,1] [--bf16-input 0,1] [--resident 0,1]
       [--async-log 0,1] [--warm 0,1] [--configs bf16_input,...]
"""

import argparse
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(_REPO_ROOT, "bench.py")

from _subproc import run_json_point


def run_point(batch, s2d, spe, timeout, bf16_input=0, resident=0,
              async_log=0, warm=0):
    env = dict(
        os.environ,
        BENCH_BATCH=str(batch),
        BENCH_S2D=str(s2d),
        BENCH_SPE=str(spe),
        BENCH_BF16_INPUT=str(bf16_input),
        BENCH_RESIDENT=str(resident),
        BENCH_ASYNC_LOG=str(async_log),
        BENCH_WARM=str(warm),
    )
    point = {"batch": batch, "s2d": s2d, "spe": spe,
             "resident": resident, "async_log": async_log, "warm": warm}
    record, err = run_json_point([sys.executable, BENCH], timeout,
                                 _REPO_ROOT, env=env, error_extra=point)
    if record is None:
        return err
    record.update(point)
    return record


def run_named_point(name, timeout):
    """One bench.py NAMED_CONFIGS point (BENCH_CONFIG=<name>).

    The name is passed through and expanded by bench.py itself — the
    sweep never duplicates the knob table, so the two can't drift; an
    unknown name comes back as an error record, not a crash. Named
    points ride at the caller's operating point (BENCH_BATCH/BENCH_SPE
    from the environment) — they measure the variant's delta at the
    flagship shape, not a new grid.
    """
    env = dict(os.environ, BENCH_CONFIG=name)
    point = {"config": name}
    record, err = run_json_point([sys.executable, BENCH], timeout,
                                 _REPO_ROOT, env=env, error_extra=point)
    if record is None:
        return err
    record.update(point)
    return record


def main(argv=None):
    parser = argparse.ArgumentParser()
    # Axis VALUE ORDER is execution order (see the loop below): the
    # highest-expected-value points run first — spe=5 (the
    # dispatch-amortization lever), batch 256 (the flagship shape) —
    # and the spe=1 baseline points last.
    parser.add_argument("--batches", default="256,512,128")
    parser.add_argument("--s2d", default="0,1")
    # In-graph multi-step (steps_per_execution): spe>1 separates chip
    # throughput from per-dispatch host cost; the spe=1 points record
    # the contrast.
    parser.add_argument("--spe", default="5,10,1")
    # bf16 input feeding: shrinks the stem's input HBM reads here
    # (the resident batch is never re-uploaded; real pipelines also
    # halve per-step H2D). Default sweeps both to record the delta.
    parser.add_argument("--bf16-input", default="0,1")
    # Device-resident input pipeline (bench.py _res series): draws
    # every batch in-graph from a one-time HBM upload instead of
    # re-feeding one host batch. Default 0,1 records the contrast; it
    # measures a different feeding regime, not a fair-game knob of the
    # flagship series.
    parser.add_argument("--resident", default="0,1")
    # Async host loop (bench.py _async series): the timed loop hands
    # per-chunk losses to the background metric reader instead of
    # sync-fetching them. Default OFF in the sweep grid (it measures
    # the host-loop regime, not a chip knob; the flagship bench.py run
    # records the contrast) — pass --async-log 0,1 to sweep it.
    parser.add_argument("--async-log", default="0")
    # Warm-start contrast (bench.py _warm series): same measurement,
    # separate metric name, compile-census fields tracked against
    # other warm runs (the second warm point in a sweep proves the
    # persistent cache: compile_seconds collapses). Default OFF in the
    # grid — pass --warm 0,1 to sweep it: it names a cold-start
    # regime, not a chip knob.
    parser.add_argument("--warm", default="0")
    # Named bench configs (bench.py NAMED_CONFIGS: bf16_input,
    # space_to_depth, bf16_s2d): extra contrast points run AFTER the
    # grid. Contrast series only — never eligible for `best` (a named
    # point can enable s2d, which changes the model being measured).
    parser.add_argument("--configs", default="",
                        help="comma list of bench.py NAMED_CONFIGS "
                             "names to run as extra contrast points")
    parser.add_argument("--timeout", type=float, default=480.0)
    args = parser.parse_args(argv)


    best = None
    records = []
    # Nesting puts the spe axis outermost (its first value is the
    # highest-value lever) and bf16 innermost, so the first four
    # points are the spe-first, flagship-batch contrasts.
    for spe in [int(v) for v in args.spe.split(",")]:
        for batch in [int(v) for v in args.batches.split(",")]:
            for s2d in [int(v) for v in args.s2d.split(",")]:
                for bf16 in [int(v) for v in args.bf16_input.split(",")]:
                    for res in [int(v)
                                for v in args.resident.split(",")]:
                        for al in [int(v)
                                   for v in args.async_log.split(",")]:
                            for wm in [int(v)
                                       for v in args.warm.split(",")]:
                                record = run_point(batch, s2d, spe,
                                                   args.timeout,
                                                   bf16_input=bf16,
                                                   resident=res,
                                                   async_log=al,
                                                   warm=wm)
                                record.setdefault("bf16_input", bf16)
                                print(json.dumps(record), flush=True)
                                records.append(record)
                                if "error" not in record and (
                                        best is None
                                        or record["value"]
                                        > best["value"]):
                                    best = record
    # Named contrast points: printed like grid points but kept OUT of
    # `best`/`records` — a named config may flip s2d (a different
    # model).
    for name in [c for c in args.configs.split(",") if c]:
        print(json.dumps(run_named_point(name, args.timeout)),
              flush=True)
    if best is None:
        print(json.dumps({"sweep": "failed",
                          "hint": "every point failed"}))
        return 1
    pin = {"BENCH_BATCH": best["batch"], "BENCH_S2D": best["s2d"],
           "BENCH_SPE": best["spe"],
           "BENCH_BF16_INPUT": best.get("bf16_input", 0)}
    print(json.dumps({
        "sweep": "best",
        "value": best["value"],
        "unit": best.get("unit", "images/sec"),
        "pin": pin,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
