"""The readings an EVA cell's limit is set from, on the chip at the cell's own
size and traffic, one seed after another in one process:

    python3 cellbench/tools/readings_eva.py --workload <name> --seeds 1,2 \
        [--control int8] [--fault no_summaries,stale_ring,phi_zero] \
        [--seconds 45] [--trace 1] [--trace-after 4]

The program's served bytes against the reference (the lower reading). The
upper readings, each in the program's place and each held to the cell's limit
through `harness.Compared` as a run is (`correct`): with `--control` the
reference in that lower precision of its products; with `--fault` the reference
with a fault a cache of two kinds of row can have:

    no_summaries  (a) the summaries never visible: every query sees the exact
                  keys of its own window only
    stale_ring    (b) the ring's rows above `t mod W` left visible after a
                  window ends: a query also sees the LAST window's keys at the
                  ring rows its own window has not yet overwritten
    phi_zero      (c) `phi` zeroed on the judged side only: every in-chunk
                  softmax uniform

One JSON line a seed, on standard output and appended to
chiprun_out/readings_<workload>.jsonl; with `--trace 1` the traced slice's ops
go to chiprun_out/trace_<workload>_ops.json, and `--trace-after` moves the slice
(to the prefills, which the cell's own slice leaves out by design). The
benchmark's own runs never call this.
"""

import argparse
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def faulty_attention(fault):
    """`reference.evabyte.attention` with `fault` in its masks."""
    import jax
    import jax.numpy as jnp

    def attend(q, k, v, k_sum, v_sum, cfg, mm):
        window, chunk = cfg["window_size"], cfg["chunk_size"]
        batch, seq, heads, depth = q.shape
        windows = seq // window
        by_window = lambda x: jnp.moveaxis(
            x.reshape(batch, windows, window, heads, depth), 1, 0)
        last = lambda x: jnp.roll(by_window(x), 1, axis=0)
        row = jnp.arange(window)
        causal = row[None, :] <= row[:, None]
        chunk_index = jnp.arange(seq // chunk)

        def one(args):
            n, qn, kn, vn, kl, vl = args
            seen = jnp.broadcast_to(
                (chunk_index < n * (window // chunk))
                & (fault != "no_summaries"), (window, seq // chunk))
            # The last window's key at ring row j is still there for a query
            # at ring row i < j.
            stale = (row[None, :] > row[:, None]) & (n > 0) & (
                fault == "stale_ring")
            mask = jnp.concatenate([seen, causal, stale], axis=1)
            keys = jnp.concatenate([k_sum, kn, kl], axis=1)
            values = jnp.concatenate([v_sum, vn, vl], axis=1)
            scores = mm("bqhd,bkhd->bhqk", qn, keys) / math.sqrt(depth)
            probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
            return mm("bhqk,bkhd->bqhd", probs, values)

        out = jax.lax.map(one, (jnp.arange(windows), by_window(q), by_window(k),
                                by_window(v), last(k), last(v)))
        return jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads, depth)
    return attend


class Variant:
    """The reference's family with a faulty attention in its layer."""

    def __init__(self, fault):
        from cellbench.reference import evabyte as fam

        self.layer_names, self.head_params = fam.layer_names, fam.head_params
        self.embed, self.head = fam.embed, fam.head
        self.layer = functools.partial(fam.layer,
                                       attend=faulty_attention(fault))


@functools.lru_cache(maxsize=None)
def variant(fault):
    return Variant(fault)


def phi_zero(params):
    """`params` with every attention layer's `phi` at zero."""
    import jax
    import jax.numpy as jnp

    out = dict(params)
    for name, block in params.items():
        if isinstance(block, dict) and "phi" in block.get("attention", {}):
            att = dict(block["attention"])
            att["phi"] = jax.jit(jnp.zeros_like)(att["phi"])
            out[name] = dict(block, attention=att)
    return out


# What `served_gaps` is given for each upper reading.
UPPER = {"no_summaries": lambda: {"fam": variant("no_summaries")},
         "stale_ring": lambda: {"fam": variant("stale_ring")},
         "phi_zero": lambda: {"plant": phi_zero}}


def judged(limits, gaps):
    """Gaps put in the program's place: the line's `correct` and the number
    compared, as a run's."""
    from cellbench import harness

    compared = harness.Compared()
    compared.add("served_logit_gap_max", max(gaps) if gaps else float("inf"),
                 limits["served_logit_gap_max"])
    return {"correct": compared.ok, "compared": compared.as_dict(),
            "gap_p99": harness.percentile(gaps, 99),
            "gap_mean": sum(gaps) / max(len(gaps), 1),
            "beyond_share": sum(g > limits["served_logit_gap_max"]
                                for g in gaps) / max(len(gaps), 1)}


def upper_readings(driver, limits, args_kept, seed, control=None, faults=()):
    """Each control and fault through the driver's `served_gaps` on the
    sequences a run sampled."""
    again = lambda **kw: driver.served_gaps(
        args_kept["cfg"], args_kept["shapes"], seed, args_kept["sequences"],
        args_kept["max_seq"], args_kept["max_new"], **kw)[0]
    out = {}
    if control:
        out["control_" + control] = judged(limits, again(chooser=control))
    for fault in faults:
        out["fault_" + fault] = judged(limits, again(**UPPER[fault]()))
    return out


def readings(run, args):
    from cellbench import harness

    driver = harness.find("drivers", run.cell.traffic["driver"])
    kept = {}
    original = driver.served_gaps

    def keeping(cfg, shapes, seed, sequences, max_seq, max_new, **kw):
        kept.update(cfg=cfg, shapes=shapes, sequences=sequences, max_seq=max_seq,
                    max_new=max_new)
        kept["program"] = original(cfg, shapes, seed, sequences, max_seq,
                                   max_new, **kw)
        return kept["program"]

    driver.served_gaps = keeping
    try:
        observed = driver.run(run)
    finally:
        driver.served_gaps = original
    gaps = kept["program"][0]
    out = {"correct": observed["compared"].ok,
           "program": dict({r["name"]: r["value"]
                            for r in observed["compared"].rows},
                           **{k: v for k, v in judged(run.cell.limits,
                                                      gaps).items()
                              if k.startswith(("gap_", "beyond"))}),
           "end_to_end": observed["end_to_end"],
           "memory_peak_bytes": observed["memory_peak_bytes"],
           "window_s": observed["window_s"],
           "reference_s": observed["reference_s"],
           "counters": observed["counters"]}
    out.update(upper_readings(
        driver, run.cell.limits, kept, run.seed, control=args.control,
        faults=list(filter(None, (args.fault or "").split(",")))))
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
    parser.add_argument("--seconds", type=float, default=45.0)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--trace-after", type=float, default=None)
    args = parser.parse_args(argv)

    from cloud_tpu.parallel import compile_cache

    from cellbench import harness

    cell = harness.load_cell(args.workload)
    if args.trace_after is not None:
        cell.traffic["trace_after_s"] = args.trace_after
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
