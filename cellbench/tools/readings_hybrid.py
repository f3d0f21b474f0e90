"""The readings a hybrid (state-space + expert) cell's limits are set from, on
the chip at the cell's own size and traffic, one seed after another in one
process:

    python3 cellbench/tools/readings_hybrid.py --workload <name> --seeds 1,2 \
        [--control int8] [--state-dtype bfloat16] \
        [--fault swap_experts,roll_experts] \
        [--plant state_dropped|state_bfloat16] [--seconds 45] [--trace 1]

The program's numbers against the reference (the lower reading). The upper
readings, each in the program's place and each held to the cell's limits
through `harness.Compared` as a run is (`correct`): with `--control` the
reference in that lower precision of its products; with `--state-dtype` the
reference with its recurrent state rounded to that dtype after every token;
with `--fault` the reference with a fault in its weights
(`swap_experts`: two held experts' weights swapped in one layer;
`roll_experts`: every held expert of one layer in its neighbour's place); with `--plant` the PROGRAM ITSELF
served with a fault planted under it (`harness.Run.plant`):
`state_dropped`, a slot's recurrent state zeroed right after its insertion, so
its first tick starts from an empty state and an empty convolution window; or
`state_bfloat16`, the recurrent state rounded to bfloat16 after every tick,
which is what keeping it in bfloat16 would store. One JSON line a seed, on
standard output and appended to chiprun_out/readings_<workload>.jsonl; with
`--trace 1` the traced slice's ops go to chiprun_out/trace_<workload>_ops.json.
The benchmark's own runs never call this.
"""

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def swap_experts(params, layer="block_3", a=3, b=5):
    """`params` with experts a and b of one layer's held experts swapped."""
    import jax

    def swapped(w):
        return w.at[a].set(w[b]).at[b].set(w[a])
    moe = dict(params[layer]["moe"])
    for name in ("expert_up", "expert_down"):
        moe[name] = jax.jit(swapped)(moe[name])
    out = dict(params)
    out[layer] = dict(params[layer], moe=moe)
    return out


def roll_experts(params, layer="block_3"):
    """`params` with one layer's held experts each in its neighbour's place:
    every routed pair of that layer meets the wrong expert."""
    import jax
    import jax.numpy as jnp

    moe = dict(params[layer]["moe"])
    for name in ("expert_up", "expert_down"):
        moe[name] = jax.jit(lambda w: jnp.roll(w, 1, axis=0))(moe[name])
    out = dict(params)
    out[layer] = dict(params[layer], moe=moe)
    return out


FAULTS = {"swap_experts": swap_experts, "roll_experts": roll_experts}


def _map_state(cache, fn):
    """`fn` over the recurrent leaves of the engine's cache (by the names the
    state-space layer gives them)."""
    if isinstance(cache, dict):
        return {k: (fn(k, v) if k in ("ssm_state", "conv_state")
                    else _map_state(v, fn)) for k, v in cache.items()}
    return cache


def plant_state_dropped(served):
    """After every insertion the slot's recurrent state is zeroed."""
    import jax
    import jax.numpy as jnp

    from cloud_tpu.models.decoding import best_effort_donation

    engine = served.scheduler.engine
    insert = engine.insert

    @best_effort_donation
    @functools.partial(jax.jit, donate_argnums=0)
    def drop(cache, slot):
        return _map_state(cache, lambda _, leaf: leaf.at[slot].set(
            jnp.zeros((), leaf.dtype)))

    def dropped(slot, *args, **kwargs):
        insert(slot, *args, **kwargs)
        engine.cache = drop(engine.cache, np.int32(slot))
    # Warm, so that the window compiles nothing (no slot is occupied).
    engine.cache = drop(engine.cache, np.int32(0))
    engine.insert = dropped


def plant_state_bfloat16(served):
    """After every tick the recurrent state is rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    from cloud_tpu.models.decoding import best_effort_donation

    engine = served.scheduler.engine
    tick = engine.tick

    @best_effort_donation
    @functools.partial(jax.jit, donate_argnums=0)
    def rounded(cache):
        # Not `astype` there and back: XLA takes such a pair out (it allows
        # itself excess precision), and the plant then plants nothing.
        return _map_state(cache, lambda name, leaf: (
            jax.lax.reduce_precision(leaf, exponent_bits=8, mantissa_bits=7)
            if name == "ssm_state" else leaf))

    def coarse():
        out = tick()
        engine.cache = rounded(engine.cache)
        return out
    engine.cache = rounded(engine.cache)
    engine.tick = coarse


PLANTS = {"state_dropped": plant_state_dropped,
          "state_bfloat16": plant_state_bfloat16}


def judged(driver, limits, gaps, margins, errors=None):
    """An upper reading held to the cell's limits as a run is: the numbers
    compared through `harness.Compared`, `correct`, and the ladders they are
    chosen from."""
    from cellbench import harness

    compared = harness.Compared()
    p99, mean, share = driver.numbers_compared(gaps, margins,
                                               limits["near_tie_eps"])
    compared.add("served_logit_gap_p99", p99, limits["served_logit_gap_p99"])
    compared.add("served_logit_gap_mean", mean, limits["served_logit_gap_mean"])
    compared.add("near_tie_share", share, limits["near_tie_share_max"])
    if errors is not None:
        for name, value in driver.state_numbers(*errors).items():
            compared.add(name, value, limits[name])
    return {"correct": compared.ok, "compared": compared.as_dict(),
            "by_eps": driver.ladder(gaps, margins),
            "state_err": state_ladder(errors)}


def state_ladder(errors):
    """A layer's percentiles over its heads and the probes."""
    from cellbench import harness

    if errors is None:
        return None
    return [{"p{}".format(q): harness.percentile(layer.ravel(), q)
             for q in (0, 10, 50, 90, 100)}
            for layer in np.moveaxis(errors[0], 1, 0)]


def readings(run, args):
    from cellbench import harness

    driver = harness.find("drivers", run.cell.traffic["driver"])
    kept = {}
    gaps_fn, errors_fn = driver.served_gaps, driver.state_errors

    def keeping_gaps(cfg, shapes, seed, sequences, max_seq, max_new, **kw):
        kept.update(cfg=cfg, shapes=shapes, sequences=sequences, max_seq=max_seq,
                    max_new=max_new)
        kept["program"] = gaps_fn(cfg, shapes, seed, sequences, max_seq, max_new,
                                  **kw)
        return kept["program"]

    def keeping_errors(cfg, shapes, seed, probes, max_seq, **kw):
        kept["probes"] = probes
        kept["errors"] = errors_fn(cfg, shapes, seed, probes, max_seq, **kw)
        return kept["errors"]

    driver.served_gaps, driver.state_errors = keeping_gaps, keeping_errors
    try:
        observed = driver.run(run)
    finally:
        driver.served_gaps, driver.state_errors = gaps_fn, errors_fn
    limits = run.cell.limits
    gaps, margins = kept["program"]
    out = {"correct": observed["compared"].ok,
           "program": {r["name"]: r["value"] for r in observed["compared"].rows},
           "program_by_eps": driver.ladder(gaps, margins),
           "program_state_err": state_ladder(kept["errors"]),
           # [probes, layers, heads] and the heads' rates [layers, heads].
           "program_state_errors": [a.tolist() for a in kept["errors"]],
           "planted": args.plant,
           "end_to_end": observed["end_to_end"],
           "memory_peak_bytes": observed["memory_peak_bytes"],
           "reference_s": observed["reference_s"],
           "counters": observed["counters"]}
    again = lambda **kw: gaps_fn(kept["cfg"], kept["shapes"], run.seed,
                                 kept["sequences"], kept["max_seq"],
                                 kept["max_new"], **kw)
    states = lambda **kw: errors_fn(kept["cfg"], kept["shapes"], run.seed,
                                    kept["probes"], kept["max_seq"], **kw)
    if args.control:
        out["control_" + args.control] = judged(
            driver, limits, *again(chooser=args.control),
            states(chooser=args.control))
    if args.state_dtype:
        out["control_state_" + args.state_dtype] = judged(
            driver, limits, *again(state_dtype=args.state_dtype),
            states(state_dtype=args.state_dtype))
    for fault in filter(None, (args.fault or "").split(",")):
        out["fault_" + fault] = judged(driver, limits,
                                       *again(plant=FAULTS[fault]))
    trace = observed.get("trace")
    if trace is not None:
        out["line_metrics"] = harness.metric_values(run.cell, run, observed)
        out["busy_s"], out["window_s"] = trace.busy_s, trace.window_s
        out["breakdown"] = trace.breakdown()
        path = os.path.join(harness.ROOT, "chiprun_out",
                            "trace_{}_ops.json".format(run.cell.name))
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"window_s": trace.window_s, "busy_s": trace.busy_s,
                       "programs": sorted(set(trace.modules.names)),
                       "program_runs": len(trace.modules.names),
                       "gaps": trace.gaps[:40],
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
    parser.add_argument("--state-dtype", default=None)
    parser.add_argument("--plant", default=None, choices=sorted(PLANTS))
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
                          peaks=harness.peaks_for(stamp["kind"]), device=stamp,
                          plant=PLANTS.get(args.plant))
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
