"""What the two serving drivers share: the Scheduler built and warmed from a
configuration's file with the benchmark's seeded weights, the record of each
request, the reduction to metrics, and the comparison with the plain
reference once the window has closed."""

import dataclasses
import gc

import numpy as np

from cellbench import harness, traffic, weights
from cellbench.reference import common as ref


@dataclasses.dataclass
class Record:
    index: int
    prompt: np.ndarray
    new_tokens: int
    due: float                # seconds from the window's start
    submitted: float = None
    done: float = None
    future: object = None
    result: object = None
    error: str = None


class Served:
    """The system under test, built once in set-up."""

    def __init__(self, run):
        import jax

        from cloud_tpu.models.decoding import bucket_length
        from cloud_tpu.parallel import runtime
        from cloud_tpu.serving import Scheduler

        cfg, mix = run.cell.config, run.cell.traffic
        assumed = cfg["assumed"]
        self.watch = harness.CompileWatch()
        runtime.reset()   # no ambient mesh: the server runs on one chip
        self.model = weights.build_model(cfg)
        self.shapes = weights.param_shapes(self.model)
        params = weights.make_params(self.shapes, run.seed)
        count = (traffic.arrival_count(mix, run.seconds)
                 if "rate_per_s" in mix else None)
        self.requests = traffic.Requests(
            mix, cfg["vocab_size"], self.model.max_seq_len, run.seed, count=count)
        self.scheduler = Scheduler(
            self.model, params, slots=int(assumed["slots"]),
            page_size=int(assumed["page_size"]), strict_no_retrace=True)
        del params
        self.scheduler.start()
        lo, hi = self.requests.prompt_range()
        cap = self.model.max_seq_len
        self.scheduler.warmup(sorted({bucket_length(n, cap)
                                      for n in range(lo, hi + 1)}))
        # The engine splits each request's key into max_new_tokens - 1 rows
        # eagerly, one small program per distinct length, which `warmup`
        # (three new tokens) does not reach and the retrace sentinel does not
        # see (PERF.md section 7). Run the mix's own lengths here, so that the
        # window compiles nothing.
        key = jax.random.split(jax.random.PRNGKey(0))[0]
        for n in sorted({int(n) for n in self.requests.news if n > 1}):
            jax.block_until_ready(jax.random.split(key, n - 1))
        if run.plant is not None:
            run.plant(self)
        self.device = jax.local_devices()[0]

    def submit(self, record, timeout=None):
        from cloud_tpu.serving import ServeRequest

        return self.scheduler.submit(ServeRequest(
            prompt=record.prompt.tolist(), max_new_tokens=record.new_tokens,
            temperature=0.0), timeout=timeout)

    def close(self):
        self.watch.close()
        self.scheduler.close()


def collect(records, t0, grace_s=60.0):
    """Waits for every submitted request, a minute past the close if need be.
    A late answer is late, not wrong; one that never comes is failed."""
    deadline = harness.now() + grace_s
    for r in records:
        if r.future is None:
            continue
        try:
            r.result = r.future.result(timeout=max(deadline - harness.now(), 0.0))
        except Exception as e:  # noqa: BLE001 - a boundary: record and count
            r.error = "{}: {}".format(type(e).__name__, e)


def finish(run, served, records, t0, t_end, before, tracer, compiles):
    """Metrics of the window, then the reference. `records` are all requests
    due in the window; `t_end` is the last completion; `compiles` is the
    compile watch's mark at the window's start."""
    cfg, mix = run.cell.config, run.cell.traffic
    compiled, compile_s = served.watch.since(compiles)
    after = served.scheduler.stats()
    done = [r for r in records if r.result is not None]
    failed = len(records) - len(done)
    window_s = t_end - t0
    out_tokens = sum(r.new_tokens for r in done)
    lateness = [r.submitted - r.due for r in records if r.submitted is not None]
    ttft = [(r.submitted - r.due) + r.result.ttft_s for r in done]
    tpot = [(r.result.latency_s - r.result.ttft_s) / (r.new_tokens - 1)
            for r in done if r.new_tokens > 1]
    # A failed request misses every limit: it stays in the tail as +inf.
    ttft_all = ttft + [float("inf")] * failed
    e2e = {"serve_tokens_per_s": out_tokens / window_s,
           "ttft_p50_ms": 1e3 * harness.percentile(ttft_all, 50) if ttft_all else None,
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
    sample = pick_sample(done, int(mix["check_requests"]), run.seed)
    sequences = [(np.asarray(r.result.tokens), len(r.prompt)) for r in sample]

    # Free the program's state before the reference touches the chip.
    served.close()
    shapes, max_seq = served.shapes, served.model.max_seq_len
    served.scheduler = served.model = None
    gc.collect()
    t_ref = harness.now()
    gaps = served_gaps(cfg, shapes, run.seed, sequences, max_seq,
                       served.requests.max_new())
    compared.add("served_logit_gap_max", max(gaps) if gaps else float("inf"),
                 run.cell.limits["served_logit_gap_max"])
    reference_s = harness.now() - t_ref

    delta = lambda key: after[key] - before[key]
    # A histogram's exact sum and count over the window (its power-of-two
    # buckets are never read).
    hist = lambda key, field, zero: (after[key].get(field, zero)
                                     - before[key].get(field, zero))
    live = sum(r.new_tokens * len(r.prompt) + r.new_tokens * (r.new_tokens - 1) // 2
               for r in done)
    geometry = after["geometry"]["per_geometry"]
    counters = {
        "requests": len(records), "completed": len(done), "out_tokens": out_tokens,
        "compared_tokens": len(gaps),
        "ticks": delta("ticks"), "tokens_emitted": delta("tokens_emitted"),
        "queue_wait_sum": hist("queue_wait", "sum", 0.0),
        "queue_wait_count": hist("queue_wait", "count", 0),
        "prefill_sum": hist("prefill", "sum", 0.0),
        "prefill_count": hist("prefill", "count", 0),
        "occupancy": {k: [g["ticks"], g["occupancy_mean"]]
                      for k, g in geometry.items()},
        "slots": int(cfg["assumed"]["slots"]),
        "live_token_ticks": live,
        "lateness_p95_ms": (1e3 * harness.percentile(lateness, 95)
                            if lateness else None),
        "ttft_ms": ladder(ttft), "tpot_ms": ladder(tpot),
        "prefix_hits": delta("prefix_hits"), "shed": after["shed"],
        "faults": after["faults"], "retraces": retraces,
        "compiles_in_window": compiled, "compile_s_in_window": compile_s,
    }
    return {
        "attempted": len(records), "failed": failed, "compared": compared,
        "end_to_end": e2e, "memory_peak_bytes": peak, "window_s": window_s,
        "reference_s": reference_s, "trace": tracer.reduced(1),
        "config": cfg, "traffic": mix, "peaks": run.peaks, "chips": 1,
        "counters": counters,
    }


def ladder(seconds):
    """Percentiles of a latency in ms, for the record beside the metric."""
    if not seconds:
        return None
    out = {"p{}".format(q): 1e3 * harness.percentile(seconds, q)
           for q in (50, 75, 90, 95, 99)}
    out["max"] = 1e3 * max(seconds)
    return out


def pick_sample(done, count, seed):
    """`count` finished requests drawn from the seed, the longest among them."""
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + r.new_tokens)
    rest = [r for r in done if r is not longest]
    order = harness.rng(seed, 7).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:max(count - 1, 0)]]


def served_gaps(cfg, shapes, seed, sequences, max_seq, max_new,
                precision="float32", chooser=None):
    """For each served token of each sampled sequence: how far its logit lies
    below the reference's best at that position. With `chooser` (a lower
    precision), the token judged is the one that precision puts first: the
    control."""
    import jax.numpy as jnp

    fam = ref.family(cfg["family"])
    params = weights.make_params(shapes, seed)
    gaps = []
    for tokens, prompt_len in sequences:
        new = len(tokens) - prompt_len
        padded = np.zeros(max_seq, np.int32)
        padded[:len(tokens)] = tokens
        rows = np.minimum(np.arange(max_new) + prompt_len - 1, len(tokens) - 2)
        logits = ref.logits_rows(fam, params, cfg, padded, rows)
        if chooser is None:
            judged = jnp.asarray(tokens[rows + 1])
        else:
            judged = jnp.argmax(ref.logits_rows(
                fam, params, cfg, padded, rows, precision=chooser), axis=-1)
        gap = jnp.max(logits, axis=-1) - jnp.take_along_axis(
            logits, judged[:, None], axis=-1)[:, 0]
        gaps.extend(float(g) for g in np.asarray(gap)[:new])
    return gaps
