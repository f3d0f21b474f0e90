"""Closed loop over a block-diffusion model: the clients of
`closed_loop_routed.py` (as many as the mix says, each sends its next request
when its last completes, `requests_per_client` each, the window closing at the
last completion), on `serving.Served`, `Record` and `collect`. It differs from
that driver in three things.

(a) Each request carries its own `denoising_steps`, from the mix's grid over a
cycle (`denoising_steps`, the quantile grid of `traffic.size_grid` in the
mix's fixed order): fewer steps commit more tokens a step.

(b) `observed["counters"]` also holds the window's deltas of the block tick's
counters (`diffusion_*`, the expert counters, `kv_live_tokens`), and in a
traced run the ticks, live keys, pairs computed and experts touched between
the traced slice's start and end (`TRACED`, each `<name>_traced`, read on
timers of the slice's own times), for the readers of the cell's per-layer
metrics.

(c) The schedule is checked exactly, for every finished request and from
SDAR's rule computed here (`schedule`), not from the program's: step s of a
block opened with m masked rows commits m // steps rows, one more in the
first m % steps steps, so the block takes min(steps, m) steps; and the
window's `diffusion_slot_steps` is the sum of those.

(d) The comparison replays the program's steps (`cellbench/reference/sdar.py`
`replay`): for each sampled request, each new token's logit at the step the
program committed it, against the reference's best at that row and step. The
rows at which the reference's routing is a near-tie in some layer (the 8th
and 9th router logits closer than `near_tie_eps`: there a bfloat16 hidden
state may choose the other expert, a rounding and no error) are left out and
counted (`near_tie_share_max`); the others are held to the 99th percentile
and the mean of their gaps, as `closed_loop_routed` holds a routed cell.
The rows each step committed are held to the reference's ranking: a step
whose most confident row left masked lies more than `rank_eps` above its
least confident committed row (in the log of the reference's confidence)
ranked otherwise, and the share of such steps is compared
(`rank_flip_share_max`); within `rank_eps` a bfloat16 rounding may swap two
rows.
"""

import gc
import queue
import threading

import numpy as np

from cellbench import harness, tracing, traffic, weights
from cellbench.drivers import closed_loop_routed, serving
from cellbench.reference import sdar as ref

COUNTERS = ("ticks", "tokens_emitted", "kv_live_tokens", "kv_walked_tokens",
            "moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "diffusion_slot_steps", "diffusion_tokens_committed",
            "diffusion_blocks_closed", "diffusion_rows_computed")
#: The counters read again at the traced slice's start and end.
TRACED = ("ticks", "kv_live_tokens", "moe_pairs_held", "moe_experts_touched")


def steps_grid(mix):
    """The denoising steps of a cycle's requests, in the mix's order."""
    return traffic.spread(traffic.size_grid(mix["denoising_steps"],
                                            int(mix["cycle"])))


def run(run):
    mix = run.cell.traffic
    served = serving.Served(run)
    try:
        # Prompt ids below the tokenizer's special range (the mask token
        # lies in it), the same sizes as the mix's.
        served.requests = traffic.Requests(
            mix, int(mix["token_ids_below"]), served.model.max_seq_len,
            run.seed)
        grid = steps_grid(mix)
        total = int(mix["clients"]) * closed_loop_routed.requests_per_client(
            mix, run.seconds)
        before = served.scheduler.stats()
        finished = queue.Queue()
        records = []

        def send(t0):
            from cloud_tpu.serving import ServeRequest

            i = len(records)
            prompt, new = served.requests[i]
            r = serving.Record(i, prompt, new, due=harness.now() - t0)
            r.submitted = r.due
            r.steps = int(grid[i % len(grid)])
            records.append(r)
            r.future = served.scheduler.submit(ServeRequest(
                prompt=prompt.tolist(), max_new_tokens=new, temperature=0.0,
                denoising_steps=r.steps))
            r.future.add_done_callback(
                lambda f, r=r: finished.put((r, harness.now())))

        tracer = tracing.Slice(run, mix)
        marks, timers = [], []

        def mark():
            # Through `served`: a name of its own here would keep the
            # engine's pool alive under the reference.
            stats = served.scheduler.stats()
            marks.append({key: stats[key] for key in TRACED})
        t0 = harness.now()
        setup_s = run.setup_s(t0)
        compiles = served.watch.mark()
        tracer.arm(t0)
        if tracer.enabled:
            for at in (tracer.after, tracer.after + tracer.length):
                timers.append(threading.Timer(at, mark))
                timers[-1].daemon = True
                timers[-1].start()
        for _ in range(int(mix["clients"])):
            send(t0)
        outstanding, t_end = int(mix["clients"]), t0
        while outstanding:
            r, t_done = finished.get(timeout=600)
            r.done, t_end = t_done - t0, t_done
            if len(records) < total:
                send(t0)
            else:
                outstanding -= 1
        tracer.close()
        for timer in timers:
            timer.cancel()
        if len(marks) == 1:
            mark()      # the window's end overtook the slice's
        serving.collect(records, t0)
        observed = finish(run, served, records, t0, t_end, before, tracer,
                          compiles)
        if len(marks) == 2:
            observed["counters"].update({
                key + "_traced": marks[1][key] - marks[0][key]
                for key in TRACED})
    finally:
        if served.scheduler is not None:
            served.close()
    observed["end_to_end"]["setup_s"] = setup_s
    return observed


def finish(run, served, records, t0, t_end, before, tracer, compiles):
    """The window's metrics and exact checks (with (c)), the counters, then
    the comparison of (d)."""
    cfg, mix = run.cell.config, run.cell.traffic
    compiled, compile_s = served.watch.since(compiles)
    after = served.scheduler.stats()
    done = [r for r in records if r.result is not None]
    failed = len(records) - len(done)
    window_s = t_end - t0
    out_tokens = sum(r.new_tokens for r in done)
    tpot = [(r.result.latency_s - r.result.ttft_s) / (r.new_tokens - 1)
            for r in done if r.new_tokens > 1]
    ttft = [r.result.ttft_s for r in done]
    e2e = {"serve_tokens_per_s": out_tokens / window_s,
           "tpot_p95_ms": 1e3 * harness.percentile(tpot, 95) if tpot else None}
    peak = harness.memory_peak_bytes([served.device])
    delta = lambda key: after[key] - before[key]

    compared = harness.Compared()
    echoed = all(
        len(r.result.tokens) == len(r.prompt) + r.new_tokens
        and np.array_equal(np.asarray(r.result.tokens)[:len(r.prompt)], r.prompt)
        and r.result.commit_steps is not None
        and len(r.result.commit_steps) == r.new_tokens
        for r in done)
    compared.require("prompt_echoed_and_length", echoed and bool(done))
    compared.require("every_request_answered", failed == 0)
    blk = int(cfg["assumed"]["block_length"])
    blocks = sum(blocks_of(len(r.prompt), r.new_tokens, blk) for r in done)
    compared.require("every_block_closed",
                     delta("diffusion_blocks_closed") == blocks
                     and delta("diffusion_tokens_committed") == out_tokens)
    schedules = [schedule_kept(len(r.prompt), r.new_tokens,
                               r.result.commit_steps, r.steps, blk)
                 for r in done] if echoed else []
    compared.require("commits_by_schedule",
                     bool(schedules) and all(ok for ok, _ in schedules))
    compared.require("slot_steps_by_schedule",
                     bool(schedules) and delta("diffusion_slot_steps")
                     == sum(taken for _, taken in schedules))
    try:
        served.scheduler.engine.check_no_retrace()
        retraces = 0
    except Exception as e:  # noqa: BLE001 - reported, and fails `correct`
        retraces = str(e)
    compared.require("no_compile_in_window", retraces == 0 and compiled == 0)
    sample = serving.pick_sample(done, int(mix["check_requests"]), run.seed)
    sequences = [(np.asarray(r.result.tokens), len(r.prompt),
                  np.asarray(r.result.commit_steps)) for r in sample]

    # Free the program's state before the reference touches the chip.
    served.close()
    shapes = served.shapes
    served.scheduler = served.model = None
    gc.collect()
    t_ref = harness.now()
    limits = run.cell.limits
    gaps, margins, steps = served_gaps(cfg, shapes, run.seed, sequences)
    excess = rank_excess(steps)
    add_compared(compared, limits, gaps, margins, excess)
    reference_s = harness.now() - t_ref

    hist = lambda key, field, zero: (after[key].get(field, zero)
                                     - before[key].get(field, zero))
    load = np.asarray(after["moe_expert_load"] or [0]) - np.asarray(
        before["moe_expert_load"] or [0])
    geometry = after["geometry"]["per_geometry"]
    kept = [g for g, m in zip(gaps, margins) if m >= limits["near_tie_eps"]]
    counters = {
        "requests": len(records), "completed": len(done), "out_tokens": out_tokens,
        "compared_tokens": len(kept),
        "near_tie_tokens": len(gaps) - len(kept),
        "gap_kept_max": max(kept, default=None),
        "gap_by_eps": closed_loop_routed.ladder(gaps, margins),
        "rank_steps": len(excess),
        "rank_excess_max": max(excess, default=None),
        "rank_flip_by_eps": rank_ladder(excess),
        "blocks": blocks,
        "denoising_steps": {str(s): sum(r.steps == s for r in done)
                            for s in sorted({r.steps for r in done})},
        "prefill_sum": hist("prefill", "sum", 0.0),
        "prefill_count": hist("prefill", "count", 0),
        "occupancy": {k: [g["ticks"], g["occupancy_mean"]]
                      for k, g in geometry.items()},
        "slots": int(cfg["assumed"]["slots"]),
        "moe_expert_load": [int(n) for n in load],
        "ttft_ms": serving.ladder(ttft), "tpot_ms": serving.ladder(tpot),
        "prefix_hits": delta("prefix_hits"), "shed": after["shed"],
        "faults": after["faults"], "retraces": retraces,
        "compiles_in_window": compiled, "compile_s_in_window": compile_s,
    }
    counters.update({key: delta(key) for key in COUNTERS})
    return {
        "attempted": len(records), "failed": failed, "compared": compared,
        "end_to_end": e2e, "memory_peak_bytes": peak, "window_s": window_s,
        "reference_s": reference_s, "trace": tracer.reduced(1),
        "config": cfg, "traffic": mix, "peaks": run.peaks, "chips": 1,
        "counters": counters,
    }


def blocks_of(prompt_len, new_tokens, blk):
    """The blocks a request opens and closes: from the one that holds its
    prompt's end to the one that holds its last new token."""
    return (prompt_len + new_tokens - 1) // blk - prompt_len // blk + 1


def schedule(steps, masks):
    """SDAR's `get_num_transfer_tokens`: of `masks` masked rows a block opens
    with, denoised in `steps` steps, the rows each step commits."""
    return [masks // steps + (s < masks % steps) for s in range(steps)]


def schedule_kept(prompt_len, new_tokens, commit_steps, steps, blk):
    """(whether every block of one request committed its rows by
    `schedule`, the steps its blocks took). A block's masked rows are its
    new tokens: the prompt's rows are clean and the rows past the new
    tokens' end are never committed."""
    at = np.asarray(commit_steps, np.int64)
    end = prompt_len + new_tokens
    kept, taken = True, 0
    for start in range(prompt_len // blk * blk, end, blk):
        lo, hi = max(start, prompt_len), min(start + blk, end)
        mine = at[lo - prompt_len:hi - prompt_len]
        want = schedule(steps, hi - lo)
        kept = kept and bool(mine.min() >= 0 and mine.max() < steps
                             and np.bincount(mine, minlength=steps).tolist()
                             == want)
        taken += sum(n > 0 for n in want)
    return kept, taken


def rank_excess(steps, least=False):
    """For each step that chose among its masked rows (some stayed masked):
    how far the most confident row it left masked lies above the least
    confident row it committed, in the log of the reference's confidence
    (above 0: the reference ranks the choice otherwise). `steps`: the
    (confidence, masked, committed) of `replay`, a sequence each. With
    `least`, each step's choice is replaced by its least confident masked
    rows: a remasking that ranks backwards, the fault the comparison has
    to see."""
    out = []
    for conf, masked, committed in steps:
        for c, m, k in zip(conf, masked, committed):
            n = int(k.sum())
            if not n or not (m & ~k).any():
                continue
            if least:
                k = np.zeros_like(m)
                k[np.argsort(np.where(m, c, np.inf), kind="stable")[:n]] = True
            out.append(float(c[m & ~k].max() - c[k].min()))
    return out


def rank_flip_share(excess, eps):
    """The share of choosing steps ranked otherwise by more than `eps`; inf
    where no step chose."""
    if not excess:
        return float("inf")
    return sum(e > eps for e in excess) / len(excess)


RANK_EPS_LADDER = (0.0, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5)


def rank_ladder(excess):
    return {str(eps): rank_flip_share(excess, eps) for eps in RANK_EPS_LADDER}


def add_compared(compared, limits, gaps, margins, excess):
    """The comparison with the reference: the gaps of (d) at the rows no
    near-tie leaves out, the share left out, and the steps ranked
    otherwise."""
    p99, mean, share = closed_loop_routed.numbers_compared(
        gaps, margins, limits["near_tie_eps"])
    compared.add("served_logit_gap_p99", p99, limits["served_logit_gap_p99"])
    compared.add("served_logit_gap_mean", mean, limits["served_logit_gap_mean"])
    compared.add("near_tie_share", share, limits["near_tie_share_max"])
    compared.add("rank_flip_share", rank_flip_share(excess, limits["rank_eps"]),
                 limits["rank_flip_share_max"])


def served_gaps(cfg, shapes, seed, sequences, chooser=None, fault=None):
    """For each new token of each sampled sequence, at the step the program
    committed it: how far its logit lies below the reference's best there,
    and the reference routing's least margin over the layers (in units of
    the router's logit); and each sequence's steps as `replay` gives them
    (`rank_excess`). With `chooser` (a lower precision) or `fault`
    (`reference.sdar.FAULTS`), the token judged is the one that replay puts
    first: the control and the faults."""
    params = weights.make_params(shapes, seed)
    gaps, margins, steps = [], [], []
    for tokens, prompt_len, at in sequences:
        picked, best, _, margin, step = ref.replay(params, cfg, tokens,
                                                   prompt_len, at)
        if chooser is not None or fault is not None:
            judged = ref.replay(params, cfg, tokens, prompt_len, at,
                                precision=chooser or "float32",
                                fault=fault)[2]
            picked = ref.replay(params, cfg, tokens, prompt_len, at,
                                judge=judged)[0]
        gaps.extend(float(g) for g in best - picked)
        margins.extend(float(m) for m in margin)
        steps.append(step)
    return gaps, margins, steps
