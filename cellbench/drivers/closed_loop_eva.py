"""Closed loop over a model with EVA attention: a slot keeps the exact keys of
its current window and one summary row per chunk of everything before it. The
loop is `closed_loop_routed.py`'s (one client a slot, a fixed count of requests
a client so that every seed does the same work, the window closes at the last
completion: a request of this mix lasts most of the window, and closing
submission by the clock made the count a draw, PERF.md section 6, PR 28), on
`serving.Served`, `Record` and `collect`. The comparison is `serving.finish`'s
own (prompt echoed, every request answered, nothing compiled in the window,
`served_logit_gap_max` of the served bytes against the plain reference run on
prompt + served bytes). It is a driver of its own for three reasons.

(a) The seeded weights get the family's initialisation of the two vectors a
head (`phi`, `mu`) on both sides (`seeded_vectors`, applied to the program's
tree once its programs are warm and to the reference's): `clip(n, -1, 1) x
D^-1/4`, n the standard normal `cellbench/weights.py` drew for the leaf. Under
that file's rule (std 0.02) every in-chunk softmax is uniform to a few per
cent, and a wrong `phi` path would read like a sound one. `serving.finish`
makes the reference's weights itself and has no place for that, so `finish`
here repeats it with the vectors set; the largest in-chunk weight's median
is printed with the counters (`chunk_weight_max_median`; uniform is 1 / C).

(b) The sampled requests are the longest and, among the others drawn from the
seed, at least one whose decode crossed a window's end (`pick_sample`): the
tick after a window's end is where a stale ring row or a missing summary would
show.

(c) `observed["counters"]` also holds the window's deltas of the scheduler's
EVA counters (`eva_rows_read`, `eva_summary_rows_read`, `eva_windows_closed`,
`kv_live_tokens`, `kv_walked_tokens`) and the gauge `eva_cache_bytes` at its
largest (read when every slot holds a request), for `tick_mfu.eva`,
`eva_read_roofline`, `eva_read_tick_share_pct.serve` and the family's counts.
In a traced run it also holds the rows read and the ticks counted between the
traced slice's start and its end (`eva_rows_read_traced`, `ticks_traced`, from
two readings of `stats()` on timers of the slice's own times): the rows a tick
attends grow through a request and differ threefold between a slice among the
prefills and one with every slot decoding, so a kernel's time in the slice is
held against the rows of the slice, not of the window's mean.
"""

import gc
import queue
import sys
import threading

import numpy as np

from cellbench import harness, tracing, weights
from cellbench.drivers import closed_loop_routed as routed
from cellbench.drivers import serving
from cellbench.reference import common as ref

EVA_COUNTERS = ("eva_rows_read", "eva_summary_rows_read", "kv_live_tokens",
                "kv_walked_tokens")
requests_per_client = routed.requests_per_client


def seeded_vectors(params):
    """`params` (the program's tree or the reference's: the names are the
    same) with every attention layer's `phi` and `mu` at the family's
    initialisation, from the normal `cellbench/weights.py` drew for them."""
    import jax
    import jax.numpy as jnp

    def init(leaf):
        depth = leaf.shape[-1]
        return (jnp.clip(leaf.astype(jnp.float32) / weights.STD, -1.0, 1.0)
                * depth ** -0.25).astype(leaf.dtype)
    out = dict(params)
    for name, block in params.items():
        if isinstance(block, dict) and "phi" in block.get("attention", {}):
            att = dict(block["attention"])
            # Made by a jitted call, as `weights.make_params` makes the rest.
            att["phi"], att["mu"] = jax.jit(init)(att["phi"]), jax.jit(init)(
                att["mu"])
            out[name] = dict(block, attention=att)
    return out


def run(run):
    mix = run.cell.traffic
    served = serving.Served(run)
    try:
        # The engine passes its parameters to every program as an argument, so
        # the tree can be replaced once the programs are warm. (No name is
        # kept for the engine here: `finish` frees it before the reference.)
        served.scheduler.engine._params = seeded_vectors(
            served.scheduler.engine._params)
        total = int(mix["clients"]) * requests_per_client(mix, run.seconds)
        before = served.scheduler.stats()
        finished = queue.Queue()
        records = []
        cache_bytes = 0

        def send(t0):
            i = len(records)
            prompt, new = served.requests[i]
            r = serving.Record(i, prompt, new, due=harness.now() - t0)
            r.submitted = r.due
            records.append(r)
            r.future = served.submit(r)
            r.future.add_done_callback(
                lambda f, r=r: finished.put((r, harness.now())))

        tracer = tracing.Slice(run, mix)
        marks, timers = [], []

        def mark():
            # Through `served`, not a name of its own: a name here would keep
            # the engine's pool alive under the reference.
            stats = served.scheduler.stats()
            marks.append((stats["eva_rows_read"], stats["ticks"]))
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
            # Before a completion frees its pages: the gauge at its largest.
            cache_bytes = max(cache_bytes, served.scheduler.stats()[
                "eva_cache_bytes"])
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
        observed["counters"]["eva_cache_bytes"] = cache_bytes
        if len(marks) == 2:
            (rows0, ticks0), (rows1, ticks1) = marks
            observed["counters"].update(eva_rows_read_traced=rows1 - rows0,
                                        ticks_traced=ticks1 - ticks0)
    finally:
        if served.scheduler is not None:
            served.close()
    observed["end_to_end"]["setup_s"] = setup_s
    return observed


def crossed(record, window):
    """Whether a tick of the request consumed a token of a later window than
    its prompt's last: its decode met a window's end."""
    last = len(record.prompt) + record.new_tokens - 2
    return last // window > (len(record.prompt) - 1) // window


def pick_sample(done, count, seed, window):
    """`serving.pick_sample` (the longest, then a draw from the seed), with one
    of the others, where none of them did, replaced by the first of the draw
    whose decode crossed a window's end."""
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + r.new_tokens)
    rest = [r for r in done if r is not longest]
    order = [rest[i] for i in harness.rng(seed, 7).permutation(len(rest))]
    others = order[:max(count - 1, 0)]
    late = [r for r in order[len(others):] if crossed(r, window)]
    if others and late and not any(crossed(r, window) for r in others):
        others[-1] = late[0]
    return [longest] + others


def finish(run, served, records, t0, t_end, before, tracer, compiles):
    """`serving.finish` with the vectors of (a) on the reference's side, the
    sample of (b) and the counters of (c)."""
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

    compared = harness.Compared()
    echoed = all(
        len(r.result.tokens) == len(r.prompt) + r.new_tokens
        and np.array_equal(np.asarray(r.result.tokens)[:len(r.prompt)], r.prompt)
        for r in done)
    compared.require("prompt_echoed_and_length", echoed and bool(done))
    compared.require("every_request_answered", failed == 0)
    try:
        served.scheduler.engine.check_no_retrace()
        retraces = 0
    except Exception as e:  # noqa: BLE001 - reported, and fails `correct`
        retraces = str(e)
    compared.require("no_compile_in_window", retraces == 0 and compiled == 0)
    sample = pick_sample(done, int(mix["check_requests"]), run.seed,
                         int(cfg["window_size"]))
    sequences = [(np.asarray(r.result.tokens), len(r.prompt)) for r in sample]

    # Free the program's state before the reference touches the chip.
    served.close()
    shapes, max_seq = served.shapes, served.model.max_seq_len
    served.scheduler = served.model = None
    gc.collect()
    t_ref = harness.now()
    gaps, weight_max = served_gaps(cfg, shapes, run.seed, sequences, max_seq,
                                   served.requests.max_new())
    compared.add("served_logit_gap_max", max(gaps) if gaps else float("inf"),
                 run.cell.limits["served_logit_gap_max"])
    reference_s = harness.now() - t_ref
    print("chunk_weight_max_median: {:.4f} (uniform {:.4f})".format(
        weight_max, 1.0 / int(cfg["chunk_size"])), file=sys.stderr, flush=True)

    delta = lambda key: after[key] - before[key]
    hist = lambda key, field, zero: (after[key].get(field, zero)
                                     - before[key].get(field, zero))
    geometry = after["geometry"]["per_geometry"]
    closed = {kind: after["eva_windows_closed"][kind]
              - before["eva_windows_closed"][kind]
              for kind in after["eva_windows_closed"]}
    counters = {
        "requests": len(records), "completed": len(done), "out_tokens": out_tokens,
        "compared_tokens": len(gaps),
        "sampled": [[len(r.prompt), r.new_tokens,
                     crossed(r, int(cfg["window_size"]))] for r in sample],
        "chunk_weight_max_median": weight_max,
        "ticks": delta("ticks"), "tokens_emitted": delta("tokens_emitted"),
        "prefill_sum": hist("prefill", "sum", 0.0),
        "prefill_count": hist("prefill", "count", 0),
        "prefill_chunk_sum": hist("prefill_chunk", "sum", 0.0),
        "prefill_chunk_count": hist("prefill_chunk", "count", 0),
        "occupancy": {k: [g["ticks"], g["occupancy_mean"]]
                      for k, g in geometry.items()},
        "slots": int(cfg["assumed"]["slots"]),
        "eva_windows_closed": closed,
        "ttft_ms": serving.ladder(ttft), "tpot_ms": serving.ladder(tpot),
        "prefix_hits": delta("prefix_hits"), "shed": after["shed"],
        "faults": after["faults"], "retraces": retraces,
        "compiles_in_window": compiled, "compile_s_in_window": compile_s,
    }
    counters.update({key: delta(key) for key in EVA_COUNTERS})
    return {
        "attempted": len(records), "failed": failed, "compared": compared,
        "end_to_end": e2e, "memory_peak_bytes": peak, "window_s": window_s,
        "reference_s": reference_s, "trace": tracer.reduced(1),
        "config": cfg, "traffic": mix, "peaks": run.peaks, "chips": 1,
        "counters": counters,
    }


def served_gaps(cfg, shapes, seed, sequences, max_seq, max_new, chooser=None,
                plant=None, fam=None):
    """(`serving.served_gaps` with the vectors of (a) set: for each served byte
    of each sampled sequence, how far its logit lies below the reference's best
    at that position; the median over the first sequence's first window of
    the largest in-chunk weight of the first layer). With `chooser` (a lower
    precision), `plant(params)` (a fault in the weights) or `fam` (a faulty
    variant of the reference's family), the byte judged is the one that
    variant puts first: the control, or a fault in the program's place."""
    import jax.numpy as jnp

    sound = ref.family(cfg["family"])
    params = seeded_vectors(weights.make_params(shapes, seed))
    gaps = []
    for tokens, prompt_len in sequences:
        new = len(tokens) - prompt_len
        padded = np.zeros(max_seq, np.int32)
        padded[:len(tokens)] = tokens
        rows = np.minimum(np.arange(max_new) + prompt_len - 1, len(tokens) - 2)
        logits = ref.logits_rows(sound, params, cfg, padded, rows)
        if chooser is None and plant is None and fam is None:
            judged = jnp.asarray(tokens[rows + 1])
        else:
            judged = jnp.argmax(ref.logits_rows(
                fam or sound, plant(params) if plant else params, cfg, padded,
                rows, precision=chooser or "float32"), axis=-1)
        gap = jnp.max(logits, axis=-1) - jnp.take_along_axis(
            logits, judged[:, None], axis=-1)[:, 0]
        gaps.extend(float(g) for g in np.asarray(gap)[:new])
    weight_max = float("nan")
    if sequences:
        first = sequences[0][0][:int(cfg["window_size"])]
        first = first[:len(first) - len(first) % int(cfg["chunk_size"])]
        a = sound.first_chunk_weights(params, cfg, first)
        weight_max = float(np.median(np.max(np.asarray(a), axis=1)))
    return gaps, weight_max
