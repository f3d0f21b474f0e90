"""Closed loop over a hybrid model: state-space (Mamba-2) layers, whose
per-request state is a fixed-size array beside the paged pool, with attention
and latent expert layers between them. The loop, the comparison and the
counters are `closed_loop_routed.py`'s (one client a slot, a fixed count of
requests a client so that every seed does the same work, the window closes at
the last completion; the routed comparison that leaves out and counts the
reference's own near-ties; the `moe_*` counters), on `serving.Served`. It is a
driver of its own for three reasons.

(a) The model has no window layer: `closed_loop_routed.finish` reads
`int(cfg["sliding_window"])`, which is `null` in this family's source. Every
attention layer here is a full one, so `window_token_ticks` is 0.

(b) The seeded weights get the state-space layers' PUBLISHED initialisation on
both sides (`seeded_init`, applied to the program's tree once its programs are
warm and to the reference's): `A_log = log U(1, 16)`, `dt_bias` the inverse
softplus of `exp U(log time_step_min, log time_step_max)` floored at
`time_step_floor`, `D = 1`, the depthwise convolution's kernel
`U(+-1/sqrt(conv_kernel))` and its bias 0, beside the router's selection bias
at zero (`closed_loop_routed`, (d)). Under the rule of `cellbench/weights.py`
(std 0.02 for all of these) `dt` is softplus(0) = 0.69 and `A` is -1, so the
state halves every token, and the convolution's output is 0.05, which makes the
state's share of `y` beside `D x` about a thousandth: a state dropped at
insertion, or kept in bfloat16, would then read like a sound run. The draws
come from the run's seed (`harness.rng(seed, stream)`), a stream a layer.

(c) `correct` also holds the state itself to the precision the configuration
states for it (`assumed.ssm_state_dtype`, float32), which the logits cannot:
the served tokens' gaps read the same with the state rounded to bfloat16 after
every tick (PERF.md section 2). Once the window has closed and before the
program is freed, `state_check.requests` of the sampled requests' prompts are
served again through the same scheduler, one at a time, for
`state_check.new_tokens` tokens (`probe_states`); when the scheduler evicts the
finished slot, the slot's `ssm_state` rows are read from the engine's cache
(the tick behind the last one leaves a finished slot's state as it is). The
plain reference then runs its sequential scan over the prompt and the tokens
the probe was given back, and each head's state is compared with the
program's: `|S - S_ref| / |S_ref|` (Frobenius, a head of a layer of a probe).
Two numbers of those errors are compared (`state_numbers`).

(d) `observed["counters"]` also holds the window's delta of `ssm_slot_steps`
(slots advanced x state layers, summed over ticks: each is one state read and
written) and the gauge `ssm_state_bytes`, for `ssm_decode_roofline`,
`ssm_update_tick_share_pct.serve` and the family's counts.
"""

import gc
import queue

import numpy as np

from cellbench import harness, tracing, weights
from cellbench.drivers import closed_loop_routed as routed
from cellbench.drivers import serving
from cellbench.reference import common as ref

COUNTERS = routed.MOE_COUNTERS + ("ssm_slot_steps",)
# Shared with `closed_loop_routed` (its readings tool and tests call them on
# the driver they are given).
ladder = routed.ladder
numbers_compared = routed.numbers_compared
requests_per_client = routed.requests_per_client
# `harness.rng(seed, stream)` streams of `seeded_init`: one a layer from here.
INIT_STREAM = 4000


def seeded_init(params, cfg, seed):
    """`params` (the program's tree or the reference's: the names are the
    same) with every expert layer's `router_bias` at zero and every
    state-space layer's own parameters as the family's published
    initialisation, drawn from `seed`."""
    import jax
    import jax.numpy as jnp

    lo, hi, floor = (float(cfg["time_step_min"]), float(cfg["time_step_max"]),
                     float(cfg["time_step_floor"]))
    out = routed.neutral_bias(params)
    # Made by a jitted call, as `weights.make_params` makes the rest.
    on_device = jax.jit(jnp.copy)
    for name, block in params.items():
        if not (isinstance(block, dict) and "mamba" in block):
            continue
        rng = harness.rng(seed, INIT_STREAM + int(name.split("_")[1]))
        old = block["mamba"]
        heads, taps = old["A_log"].shape[0], old["conv_kernel"].shape[0]
        dt = np.maximum(np.exp(rng.uniform(np.log(lo), np.log(hi), heads)),
                        floor)
        new = {"A_log": np.log(rng.uniform(1.0, 16.0, heads)),
               "dt_bias": dt + np.log(-np.expm1(-dt)),
               "D": np.ones(heads),
               "conv_kernel": rng.uniform(-taps ** -0.5, taps ** -0.5,
                                          old["conv_kernel"].shape),
               "conv_bias": np.zeros(old["conv_bias"].shape)}
        mamba = dict(old, **{key: on_device(jnp.asarray(value, old[key].dtype))
                             for key, value in new.items()})
        out[name] = dict(out[name], mamba=mamba)
    return out


def run(run):
    mix, cfg = run.cell.traffic, run.cell.config
    served = serving.Served(run)
    try:
        # The engine passes its parameters to every program as an argument, so
        # the tree can be replaced once the programs are warm. (No name is
        # kept for the engine here: `finish` frees it before the reference.)
        served.scheduler.engine._params = seeded_init(
            served.scheduler.engine._params, cfg, run.seed)
        total = int(mix["clients"]) * requests_per_client(mix, run.seconds)
        before = served.scheduler.stats()
        finished = queue.Queue()
        records = []

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
        t0 = harness.now()
        setup_s = run.setup_s(t0)
        compiles = served.watch.mark()
        tracer.arm(t0)
        for _ in range(int(mix["clients"])):
            send(t0)
        outstanding, t_end = int(mix["clients"]), t0
        while outstanding:
            r, t_done = finished.get(timeout=300)
            r.done, t_end = t_done - t0, t_done
            if len(records) < total:
                send(t0)
            else:
                outstanding -= 1
        tracer.close()
        serving.collect(records, t0)
        observed = finish(run, served, records, t0, t_end, before, tracer,
                          compiles)
    finally:
        if served.scheduler is not None:
            served.close()
    observed["end_to_end"]["setup_s"] = setup_s
    return observed


def finish(run, served, records, t0, t_end, before, tracer, compiles):
    """`closed_loop_routed.finish` for a model without a window layer and with
    state-space layers: the same metrics, exact checks and comparison, the
    state's counters beside the experts'."""
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
    sample = serving.pick_sample(done, int(mix["check_requests"]), run.seed)
    sequences = [(np.asarray(r.result.tokens), len(r.prompt)) for r in sample]
    t_probe = harness.now()
    probes = probe_states(served, [r.prompt for r in sample[:int(
        mix["state_check"]["requests"])]], int(mix["state_check"]["new_tokens"]))
    probe_s = harness.now() - t_probe

    # Free the program's state before the reference touches the chip.
    served.close()
    shapes, max_seq = served.shapes, served.model.max_seq_len
    served.scheduler = served.model = None
    gc.collect()
    t_ref = harness.now()
    limits = run.cell.limits
    gaps, margins = served_gaps(cfg, shapes, run.seed, sequences, max_seq,
                                served.requests.max_new())
    near = [m < limits["near_tie_eps"] for m in margins]
    held = [g for g, tie in zip(gaps, near) if not tie]
    p99, mean, share = numbers_compared(gaps, margins, limits["near_tie_eps"])
    compared.add("served_logit_gap_p99", p99, limits["served_logit_gap_p99"])
    compared.add("served_logit_gap_mean", mean, limits["served_logit_gap_mean"])
    compared.add("near_tie_share", share, limits["near_tie_share_max"])
    errors, rates = state_errors(cfg, shapes, run.seed, probes, max_seq)
    for name, value in state_numbers(errors, rates).items():
        compared.add(name, value, limits[name])
    reference_s = harness.now() - t_ref

    delta = lambda key: after[key] - before[key]
    hist = lambda key, field, zero: (after[key].get(field, zero)
                                     - before[key].get(field, zero))
    depths = lambda r: len(r.prompt) + np.arange(r.new_tokens)
    load = np.asarray(after["moe_expert_load"] or [0]) - np.asarray(
        before["moe_expert_load"] or [0])
    geometry = after["geometry"]["per_geometry"]
    counters = {
        "requests": len(records), "completed": len(done), "out_tokens": out_tokens,
        "compared_tokens": len(held), "near_tie_tokens": int(sum(near)),
        "gap_kept_max": max(held, default=None),
        "gap_near_tie_max": max([g for g, tie in zip(gaps, near) if tie],
                                default=None),
        "gap_by_eps": ladder(gaps, margins),
        "ticks": delta("ticks"), "tokens_emitted": delta("tokens_emitted"),
        "prefill_sum": hist("prefill", "sum", 0.0),
        "prefill_count": hist("prefill", "count", 0),
        "occupancy": {k: [g["ticks"], g["occupancy_mean"]]
                      for k, g in geometry.items()},
        "slots": int(cfg["assumed"]["slots"]),
        "live_token_ticks": int(sum(depths(r).sum() for r in done)),
        "window_token_ticks": 0,
        "moe_expert_load": [int(n) for n in load],
        "ssm_state_bytes": after.get("ssm_state_bytes"),
        # A layer's percentiles over its heads and the probes.
        "ssm_state_err": [{"p{}".format(q): harness.percentile(layer.ravel(), q)
                           for q in (0, 10, 50, 90, 100)}
                          for layer in np.moveaxis(errors, 1, 0)],
        "state_probe_s": probe_s,
        "ttft_ms": serving.ladder(ttft), "tpot_ms": serving.ladder(tpot),
        "prefix_hits": delta("prefix_hits"), "shed": after["shed"],
        "faults": after["faults"], "retraces": retraces,
        "compiles_in_window": compiled, "compile_s_in_window": compile_s,
    }
    # A program without a counter (the parent of the PR that brought it)
    # leaves it out; its readers then find nothing.
    counters.update({key: delta(key) for key in COUNTERS if key in after})
    return {
        "attempted": len(records), "failed": failed, "compared": compared,
        "end_to_end": e2e, "memory_peak_bytes": peak, "window_s": window_s,
        "reference_s": reference_s, "trace": tracer.reduced(1),
        "config": cfg, "traffic": mix, "peaks": run.peaks, "chips": 1,
        "counters": counters,
    }


def probe_states(served, prompts, new_tokens):
    """Serves each of `prompts` again, one at a time, for `new_tokens` greedy
    tokens, and reads the slot's state when the scheduler evicts it. A list of
    (prompt and served tokens, how many of them the state has seen, the
    state-space layers' states in the order of the layers, each [heads,
    head_dim, state])."""
    from cloud_tpu.ops import ssm
    from cloud_tpu.serving import ServeRequest, reqtrace

    engine, model = served.scheduler.engine, served.model
    evict, caught = engine.evict, queue.Queue()

    def rows(cache, slot, path=()):
        """(path, a slot's row) of every `ssm_state` leaf of the cache."""
        for name, sub in cache.items():
            if name == "ssm_state":
                yield path, sub[slot]
            elif isinstance(sub, dict):
                yield from rows(sub, slot, path + (name,))

    def catching(mask):
        for slot in np.flatnonzero(np.asarray(mask)):
            caught.put(dict(rows(engine.cache, int(slot))))
        evict(mask)

    engine.evict = catching
    out, probed = [], []
    try:
        for prompt in prompts:
            result = served.scheduler.submit(ServeRequest(
                prompt=np.asarray(prompt).tolist(), max_new_tokens=new_tokens,
                temperature=0.0)).result(timeout=300)
            probed.append(result.trace)
            tokens = np.asarray(result.tokens)
            found = caught.get(timeout=300)
            order = sorted(found, key=lambda k: int(k[0].split("_")[1]))
            out.append((tokens, len(tokens) - 1, [np.asarray(ssm.unpack_state(
                found[k], model.mamba_heads, model.ssm_groups,
                model.mamba_head_dim), np.float32) for k in order]))
    finally:
        engine.evict = evict
        # The probes are not the window's traffic: the program's records of
        # them go, so that `request_records.finished` still finds as many
        # records as requests completed (`decode_gap_p99_ms.serve`).
        kept = [r for r in reqtrace.recent(0)
                if not any(r is p for p in probed)]
        reqtrace.clear()
        for r in kept:
            reqtrace.publish(r)
    return out


def state_errors(cfg, shapes, seed, probes, max_seq, chooser=None,
                 state_dtype="float32"):
    """(errors [probes, layers, heads], rates [layers, heads]). An error is the
    distance of a head's judged state from the reference's, over the
    reference's norm; a head's rate is `softplus(dt_bias) exp(A_log)`, the
    share of its state it forgets a token where the input adds nothing to dt.
    The judged state is the program's (`probes`, as `probe_states` gives them);
    with `chooser` (a lower precision of the products) or `state_dtype` (of the
    state) it is the reference's own at that precision: the controls."""
    fam = ref.family(cfg["family"])
    params = seeded_init(weights.make_params(shapes, seed), cfg, seed)
    norm = lambda x: np.sqrt(np.sum(np.square(np.asarray(x, np.float64)),
                                    axis=(1, 2)))
    errors = []
    for tokens, count, states in probes:
        padded = np.zeros(max_seq, np.int32)
        padded[:len(tokens)] = tokens
        want = fam.states_after(params, cfg, padded, count)
        if chooser is not None or state_dtype != "float32":
            states = fam.states_after(params, cfg, padded, count,
                                      precision=chooser or "float32",
                                      state_dtype=state_dtype)
        errors.append([norm(np.asarray(got, np.float64) - np.asarray(exact))
                       / np.maximum(norm(exact), 1e-30)
                       for got, exact in zip(states, want, strict=True)])
    mixers = [params[name]["mamba"] for name in fam.layer_names(params)
              if "mamba" in params[name]]
    rates = [np.logaddexp(0.0, np.asarray(m["dt_bias"], np.float64))
             * np.exp(np.asarray(m["A_log"], np.float64)) for m in mixers]
    return np.asarray(errors), np.asarray(rates)


def state_numbers(errors, rates):
    """The two numbers of the state that are compared. `ssm_state_err_p50`:
    the median error over every probe, layer and head, which a state that is
    wrong in every head moves (a recurrence that differs; the products in int8
    read ten times a sound run). `ssm_state_slow_head_err_ratio`: in the FIRST
    state-space layer (its input is the embedding: nothing upstream of it is
    rounded), the mean error of the slowest sixteenth of the heads over the
    layer's median error. What the products' precision does to a state is the
    same for every head (it comes in with the last few tokens' x, B and dt:
    0.5 % at bfloat16, and the ratio reads 1), while a state KEPT below
    float32 is rounded every tick and loses the more the longer a head
    remembers, and a state dropped at insertion misses what only the slow
    heads still hold: the ratio holds the state to its dtype, and to its
    history, whatever the products' precision is."""
    if not errors.size:
        return {"ssm_state_err_p50": float("inf"),
                "ssm_state_slow_head_err_ratio": float("inf")}
    first = errors[:, 0].mean(axis=0)
    slow = np.argsort(rates[0])[:max(1, len(first) // 16)]
    return {"ssm_state_err_p50": float(np.median(errors)),
            "ssm_state_slow_head_err_ratio": float(
                first[slow].mean() / max(np.median(first), 1e-30))}


def served_gaps(cfg, shapes, seed, sequences, max_seq, max_new, chooser=None,
                plant=None, state_dtype="float32"):
    """`closed_loop_routed.served_gaps` over this driver's weights: for each
    served token of each sampled sequence, how far its logit lies below the
    reference's best at that position, and how near the reference's routing is
    to a tie there. With `chooser` (a lower precision), the token judged is the
    one that precision puts first: the control. `plant(params)` alters the
    weights the judged tokens come from (a fault planted in the control's
    place); `state_dtype` what their recurrent state is rounded to after every
    token."""
    import jax.numpy as jnp

    fam = ref.family(cfg["family"])
    params = seeded_init(weights.make_params(shapes, seed), cfg, seed)
    gaps, ties = [], []
    for tokens, prompt_len in sequences:
        new = len(tokens) - prompt_len
        padded = np.zeros(max_seq, np.int32)
        padded[:len(tokens)] = tokens
        rows = np.minimum(np.arange(max_new) + prompt_len - 1, len(tokens) - 2)
        logits, margins, edge_held = fam.logits_rows(params, cfg, padded, rows)
        if chooser is None and plant is None and state_dtype == "float32":
            judged = jnp.asarray(tokens[rows + 1])
        else:
            judged = jnp.argmax(fam.logits_rows(
                plant(params) if plant else params, cfg, padded, rows,
                precision=chooser or "float32", state_dtype=state_dtype)[0],
                axis=-1)
        gap = jnp.max(logits, axis=-1) - jnp.take_along_axis(
            logits, judged[:, None], axis=-1)[:, 0]
        tie = jnp.min(jnp.where(edge_held, margins, jnp.inf), axis=0)
        gaps.extend(float(g) for g in np.asarray(gap)[:new])
        ties.extend(float(t) for t in np.asarray(tie)[:new])
    return gaps, ties
