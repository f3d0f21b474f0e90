"""graftlens loadgen: open-arrival traffic against a live Scheduler.

Closed-loop drivers (smoke.py's run_serve) submit the next request when
the previous one finishes, so they can never observe queueing collapse:
the system sets its own arrival rate. This generator is OPEN-LOOP — a
fixed seed draws an arrival schedule (Poisson, or bursty Gamma renewal
with CV^2 = `burstiness`), a prompt-length mix, a shared-prefix ratio,
and per-request decode budgets, then submits each request at its
scheduled wall time regardless of completions. Latency under load is
then a property of the serving stack, not of the driver.

Goodput is the serving SLO currency: the fraction of OFFERED requests
that completed AND met both targets (TTFT <= --slo-ttft, TPOT <=
--slo-tpot, TPOT = (latency - ttft) / (tokens - 1)). Shed or failed
requests count against goodput by construction.

The module is also the CI `serve-trace-smoke` driver: run with
`CLOUD_TPU_REQTRACE=1` it produces the reqtrace JSONL that
`monitoring/collect.py --serve` rolls into the per-request waterfall +
`serve_report.json`.

Usage (CPU-friendly):

    JAX_PLATFORMS=cpu CLOUD_TPU_REQTRACE=1 \\
        python -m cloud_tpu.serving.loadgen \\
        --requests 20 --rate 8 --out-dir /tmp/lens
"""

import argparse
import dataclasses
import json
import os
import queue
import threading
import time

import numpy as np

from cloud_tpu.serving.faults import fault_kind


@dataclasses.dataclass
class LoadSpec:
    """One open-arrival run. All randomness flows from `seed`, so a
    spec is a complete, reproducible description of the traffic."""
    rate: float                     # mean arrivals per second
    n_requests: int = 20
    process: str = "poisson"        # "poisson" | "bursty"
    burstiness: float = 4.0         # Gamma CV^2 (1.0 == poisson)
    # Prompt-length mix: (length, weight) pairs, normalized.
    prompt_buckets: tuple = ((6, 0.4), (12, 0.35), (24, 0.25))
    max_new_lo: int = 2
    max_new_hi: int = 8             # inclusive
    shared_prefix_ratio: float = 0.0
    shared_prefix_len: int = 16
    seed: int = 0
    submit_timeout: float = 0.05    # then shed (queue.Full -> rejected)

    def validate(self):
        if self.rate <= 0:
            raise ValueError("rate must be > 0.")
        if self.n_requests < 1:
            raise ValueError("n_requests must be >= 1.")
        if self.process not in ("poisson", "bursty"):
            raise ValueError("process must be poisson|bursty; got "
                             "{!r}.".format(self.process))
        if self.burstiness <= 0:
            raise ValueError("burstiness must be > 0.")
        if not 0.0 <= self.shared_prefix_ratio <= 1.0:
            raise ValueError("shared_prefix_ratio must be in [0, 1].")


def build_arrivals(spec):
    """Arrival times (seconds from run start), shape [n_requests].

    poisson: exponential inter-arrivals, mean 1/rate. bursty: Gamma
    inter-arrivals with shape 1/burstiness and scale burstiness/rate —
    same mean 1/rate, CV^2 = burstiness, so load comes in clumps while
    the offered rate stays comparable across processes.
    """
    spec.validate()
    rng = np.random.default_rng(spec.seed)
    if spec.process == "poisson":
        gaps = rng.exponential(1.0 / spec.rate, spec.n_requests)
    else:
        gaps = rng.gamma(1.0 / spec.burstiness,
                         spec.burstiness / spec.rate, spec.n_requests)
    return np.cumsum(gaps)


def build_requests(spec, vocab_size, max_seq_len):
    """Deterministic request list for `spec`. Token ids stay in
    [2, vocab); shared-prefix requests extend one common root (the
    radix-cache hit population) and everything fits prompt + max_new
    <= max_seq_len."""
    from cloud_tpu.serving.scheduler import ServeRequest

    spec.validate()
    rng = np.random.default_rng(spec.seed + 1)
    lengths = [int(length) for length, _ in spec.prompt_buckets]
    weights = np.asarray([w for _, w in spec.prompt_buckets], float)
    weights = weights / weights.sum()
    hi = max(2, vocab_size)
    root = rng.integers(2, hi, (spec.shared_prefix_len,)).tolist()
    requests = []
    for _ in range(spec.n_requests):
        length = int(rng.choice(lengths, p=weights))
        max_new = int(rng.integers(spec.max_new_lo,
                                   spec.max_new_hi + 1))
        length = min(length, max_seq_len - max_new)
        shared = (rng.random() < spec.shared_prefix_ratio
                  and length > spec.shared_prefix_len)
        if shared:
            tail = rng.integers(2, hi, (length
                                        - spec.shared_prefix_len,))
            prompt = root + tail.tolist()
        else:
            prompt = rng.integers(2, hi, (length,)).tolist()
        requests.append(ServeRequest(
            prompt=[int(t) for t in prompt],
            max_new_tokens=max_new, temperature=0.0,
            rng_seed=int(rng.integers(0, 2**31 - 1))))
    return requests


def _percentiles(values):
    vals = sorted(v for v in values if v is not None)
    if not vals:
        return {"count": 0, "p50": None, "p95": None, "p99": None,
                "mean": None}
    arr = np.asarray(vals, float)
    return {
        "count": len(vals),
        "p50": float(np.percentile(arr, 50)),
        "p95": float(np.percentile(arr, 95)),
        "p99": float(np.percentile(arr, 99)),
        "mean": float(arr.mean()),
    }


def _run_open_loop(scheduler, requests, arrivals, submit_timeout,
                   slo_ttft, slo_tpot, result_timeout, tags=None,
                   keep_tokens=False):
    """Open-loop core shared by every arrival scenario: submit each
    request at its scheduled offset from run start regardless of
    completions, then harvest every future. `tags` (optional, parallel
    to `requests`) is a dict merged into each per-request row — how the
    diurnal scenario stamps rows with their segment. Returns
    (rows, counts, wall_s)."""
    inflight = []
    t0 = time.monotonic()
    for i, (request, t_arr) in enumerate(zip(requests, arrivals)):
        delay = t0 + float(t_arr) - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        t_sub = time.monotonic() - t0
        try:
            future = scheduler.submit(request, timeout=submit_timeout)
        except queue.Full:
            future = None
        inflight.append((i, request, t_sub, future))

    rows = []
    completed = rejected = failed = shed = 0
    t_last_done = t0
    for i, request, t_sub, future in inflight:
        row = {
            "submit_s": round(t_sub, 6),
            "prompt_len": len(request.prompt),
            "max_new": request.max_new_tokens,
        }
        if tags is not None:
            row.update(tags[i])
        if future is None:
            rejected += 1
            row["status"] = "rejected"
            rows.append(row)
            continue
        try:
            result = future.result(timeout=result_timeout)
        except BaseException as exc:  # noqa: BLE001
            if fault_kind(exc) == "shed":
                shed += 1
                row["status"] = "shed"
                row["reason"] = getattr(exc, "reason", None)
            else:
                failed += 1
                row["status"] = "failed"
            row["error"] = "{}: {}".format(type(exc).__name__,
                                           str(exc)[:200])
            rows.append(row)
            continue
        completed += 1
        t_last_done = max(t_last_done, time.monotonic())
        n = request.max_new_tokens
        tpot = ((result.latency_s - result.ttft_s) / (n - 1)
                if n > 1 else None)
        row.update(status="complete",
                   ttft_s=round(result.ttft_s, 6),
                   latency_s=round(result.latency_s, 6),
                   tpot_s=None if tpot is None else round(tpot, 6),
                   prefix_len=int(result.prefix_len),
                   hit=bool(result.prefix_len > 0))
        if keep_tokens:
            row["tokens"] = [int(t) for t in result.tokens]
        row["good"] = bool(
            (slo_ttft is None or result.ttft_s <= slo_ttft)
            and (slo_tpot is None or tpot is None or tpot <= slo_tpot))
        rows.append(row)

    wall = max(t_last_done - t0, 1e-9)
    counts = {"completed": completed, "rejected": rejected,
              "failed": failed, "shed": shed}
    return rows, counts, wall


def run_load(scheduler, spec, slo_ttft=None, slo_tpot=None,
             result_timeout=300.0):
    """Drives one open-arrival run against a started, warmed Scheduler.

    Returns the run report dict (format cloud_tpu.loadgen.v1): offered /
    completed / rejected / failed / shed counts (shed = refused by the
    SLO admission gate, a typed ServeShed), offered vs. achieved rps,
    TTFT / TPOT / latency percentiles, goodput against the SLOs, and a
    per-request row list (the collector's cross-check against the
    reqtrace waterfall).
    """
    arrivals = build_arrivals(spec)
    requests = build_requests(spec, scheduler.engine.model.vocab_size,
                              scheduler.engine.max_seq_len)
    rows, counts, wall = _run_open_loop(
        scheduler, requests, arrivals, spec.submit_timeout,
        slo_ttft, slo_tpot, result_timeout)
    completed = counts["completed"]
    rejected = counts["rejected"]
    failed = counts["failed"]
    shed = counts["shed"]
    offered_span = max(float(arrivals[-1]), 1e-9)
    good = sum(1 for r in rows if r.get("good"))
    done_rows = [r for r in rows if r["status"] == "complete"]
    return {
        "format": "cloud_tpu.loadgen.v1",
        "spec": {
            "rate": spec.rate,
            "n_requests": spec.n_requests,
            "process": spec.process,
            "burstiness": spec.burstiness,
            "prompt_buckets": [list(b) for b in spec.prompt_buckets],
            "max_new": [spec.max_new_lo, spec.max_new_hi],
            "shared_prefix_ratio": spec.shared_prefix_ratio,
            "shared_prefix_len": spec.shared_prefix_len,
            "seed": spec.seed,
        },
        "offered": len(rows),
        "completed": completed,
        "rejected": rejected,
        "failed": failed,
        "shed": shed,
        "offered_rps": len(rows) / offered_span,
        "achieved_rps": completed / wall,
        "duration_s": wall,
        "slo": {"ttft_s": slo_ttft, "tpot_s": slo_tpot},
        "goodput": good / max(len(rows), 1),
        "ttft": _percentiles([r.get("ttft_s") for r in done_rows]),
        "tpot": _percentiles([r.get("tpot_s") for r in done_rows]),
        "latency": _percentiles([r.get("latency_s")
                                 for r in done_rows]),
        "hit_rate": (sum(1 for r in done_rows if r.get("hit"))
                     / max(len(done_rows), 1)),
        "per_request": rows,
    }


@dataclasses.dataclass
class DiurnalSpec:
    """Sinusoidal-ramp offered rate (graftflex's A/B workload): the run
    is `segments` back-to-back windows of `segment_s` seconds whose
    offered rate traces half a diurnal cycle — starts at `rate_lo`,
    peaks at `rate_hi` mid-run, and ramps back down. Within each
    segment arrivals come from the existing Poisson/bursty machinery at
    that segment's rate, so the only new ingredient is the envelope.
    The ramp-up exercises grow resizes, the ramp-down shrink resizes,
    and the per-segment goodput-vs-offered curve is the autoscale-vs-
    fixed comparison surface. All randomness flows from `seed`."""
    rate_lo: float = 2.0
    rate_hi: float = 16.0
    segments: int = 6
    segment_s: float = 2.0
    process: str = "poisson"
    burstiness: float = 4.0
    prompt_buckets: tuple = ((6, 0.4), (12, 0.35), (24, 0.25))
    max_new_lo: int = 2
    max_new_hi: int = 8             # inclusive
    shared_prefix_ratio: float = 0.0
    shared_prefix_len: int = 16
    seed: int = 0
    submit_timeout: float = 0.05

    def validate(self):
        if not 0 < self.rate_lo <= self.rate_hi:
            raise ValueError("need 0 < rate_lo <= rate_hi.")
        if self.segments < 2:
            raise ValueError("segments must be >= 2.")
        if self.segment_s <= 0:
            raise ValueError("segment_s must be > 0.")

    def segment_rates(self):
        """Offered rate per segment: raised-cosine from rate_lo up to
        rate_hi and back — segment 0 sits at the trough, the midpoint
        at the crest."""
        n = self.segments
        return [self.rate_lo + (self.rate_hi - self.rate_lo) * 0.5
                * (1.0 - float(np.cos(2.0 * np.pi * k / n)))
                for k in range(n)]


def build_diurnal(spec, vocab_size, max_seq_len):
    """The complete diurnal traffic for `spec`, sorted by arrival
    time: a list of (arrival_s, segment, request) entries. Each
    segment draws its own arrival schedule and request population from
    distinct seed streams, so two schedulers fed the same spec (an
    autoscale-vs-fixed A/B) replay identical traffic. A low-rate
    segment's tail can spill past its window; the merge-sort hands the
    submit loop one monotonic timeline."""
    spec.validate()
    entries = []
    for k, rate in enumerate(spec.segment_rates()):
        seg_spec = LoadSpec(
            rate=rate,
            n_requests=max(1, int(round(rate * spec.segment_s))),
            process=spec.process, burstiness=spec.burstiness,
            prompt_buckets=spec.prompt_buckets,
            max_new_lo=spec.max_new_lo, max_new_hi=spec.max_new_hi,
            shared_prefix_ratio=spec.shared_prefix_ratio,
            shared_prefix_len=spec.shared_prefix_len,
            seed=spec.seed + 101 * k + 1,
            submit_timeout=spec.submit_timeout)
        arrivals = build_arrivals(seg_spec) + k * spec.segment_s
        requests = build_requests(seg_spec, vocab_size, max_seq_len)
        for t_arr, request in zip(arrivals, requests):
            entries.append((float(t_arr), k, request))
    entries.sort(key=lambda e: e[0])
    return entries


def run_diurnal(scheduler, spec, slo_ttft=None, slo_tpot=None,
                result_timeout=300.0, keep_tokens=False):
    """Drives one sinusoidal-ramp run against a started, warmed
    Scheduler.

    Every per-request row is stamped with its segment and its index
    `i` into the deterministic `build_diurnal` population (how an A/B
    harness lines rows up against a solo-generate oracle);
    `keep_tokens=True` additionally records each completed request's
    token ids for bit-identity checks. Returns the run report (format
    cloud_tpu.loadgen_diurnal.v1): the overall counts/goodput/
    percentiles of run_load plus `offered_curve` — per-segment offered
    rate vs goodput vs TTFT — and `worst_ttft_p99`, the worst
    per-segment TTFT p99 (the "equal worst-case p99" side of the
    ROADMAP autoscaling gate)."""
    entries = build_diurnal(spec, scheduler.engine.model.vocab_size,
                            scheduler.engine.max_seq_len)
    rates = spec.segment_rates()
    rows, counts, wall = _run_open_loop(
        scheduler, [e[2] for e in entries], [e[0] for e in entries],
        spec.submit_timeout, slo_ttft, slo_tpot, result_timeout,
        tags=[{"segment": seg, "i": i}
              for i, (_, seg, _) in enumerate(entries)],
        keep_tokens=keep_tokens)

    curve = []
    for k, rate in enumerate(rates):
        seg_rows = [r for r in rows if r["segment"] == k]
        seg_done = [r for r in seg_rows if r["status"] == "complete"]
        good = sum(1 for r in seg_rows if r.get("good"))
        curve.append({
            "segment": k,
            "offered_rate": rate,
            "offered": len(seg_rows),
            "completed": len(seg_done),
            "good": good,
            "goodput": good / max(len(seg_rows), 1),
            "ttft": _percentiles([r.get("ttft_s") for r in seg_done]),
        })
    good = sum(1 for r in rows if r.get("good"))
    done_rows = [r for r in rows if r["status"] == "complete"]
    worst_p99 = [c["ttft"]["p99"] for c in curve
                 if c["ttft"]["p99"] is not None]
    return {
        "format": "cloud_tpu.loadgen_diurnal.v1",
        "spec": {
            "rate_lo": spec.rate_lo,
            "rate_hi": spec.rate_hi,
            "segments": spec.segments,
            "segment_s": spec.segment_s,
            "segment_rates": rates,
            "process": spec.process,
            "burstiness": spec.burstiness,
            "prompt_buckets": [list(b) for b in spec.prompt_buckets],
            "max_new": [spec.max_new_lo, spec.max_new_hi],
            "shared_prefix_ratio": spec.shared_prefix_ratio,
            "shared_prefix_len": spec.shared_prefix_len,
            "seed": spec.seed,
        },
        "offered": len(rows),
        "completed": counts["completed"],
        "rejected": counts["rejected"],
        "failed": counts["failed"],
        "shed": counts["shed"],
        "duration_s": wall,
        "slo": {"ttft_s": slo_ttft, "tpot_s": slo_tpot},
        "good": good,
        "goodput": good / max(len(rows), 1),
        "worst_ttft_p99": max(worst_p99) if worst_p99 else None,
        "ttft": _percentiles([r.get("ttft_s") for r in done_rows]),
        "tpot": _percentiles([r.get("tpot_s") for r in done_rows]),
        "latency": _percentiles([r.get("latency_s")
                                 for r in done_rows]),
        "offered_curve": curve,
        "per_request": rows,
    }


@dataclasses.dataclass
class ConversationSpec:
    """Multi-turn conversation traffic (graftpack's host-tier
    workload): N concurrent sessions, each a closed loop of T turns —
    turn t's prompt is the FULL history (turn t-1's prompt +
    continuation) plus `user_tokens` fresh tokens, submitted after a
    `think_time` gap. Between a turn's completion and the next turn's
    arrival the session's KV pages are idle — exactly the window the
    host tier demotes into, and the trie LRU evicts under pressure.
    All randomness flows from `seed`."""
    n_sessions: int = 4
    n_turns: int = 3
    user_tokens: int = 8
    max_new_lo: int = 4
    max_new_hi: int = 8             # inclusive
    think_time: float = 0.05
    seed: int = 0

    def validate(self):
        if self.n_sessions < 1 or self.n_turns < 1:
            raise ValueError("n_sessions and n_turns must be >= 1.")
        if self.user_tokens < 1:
            raise ValueError("user_tokens must be >= 1.")
        if self.think_time < 0:
            raise ValueError("think_time must be >= 0.")


def run_conversations(scheduler, spec, result_timeout=300.0):
    """Drives `spec.n_sessions` concurrent multi-turn conversations.

    Each session is closed-loop (a user cannot send turn t+1 before
    reading turn t) but sessions overlap, so resident-page pressure and
    trie eviction are real. A session ends early when the growing
    history no longer fits max_seq_len. Returns the run report
    (format cloud_tpu.loadgen_conv.v1): per-turn rows with
    session/turn/prompt_len/ttft/prefix_len, plus TTFT percentiles
    split first-turn vs follow-up — the follow-up split is the number
    the host tier exists to keep near the cache-hit floor after
    eviction."""
    from cloud_tpu.serving.scheduler import ServeRequest

    spec.validate()
    max_seq_len = scheduler.engine.max_seq_len
    vocab = scheduler.engine.model.vocab_size
    hi = max(3, vocab)
    rows_lock = threading.Lock()
    rows = []

    def session(idx):
        rng = np.random.default_rng(spec.seed + 17 * idx)
        history = []
        for turn in range(spec.n_turns):
            fresh = rng.integers(2, hi, (spec.user_tokens,)).tolist()
            prompt = history + [int(t) for t in fresh]
            max_new = int(rng.integers(spec.max_new_lo,
                                       spec.max_new_hi + 1))
            if len(prompt) + max_new > max_seq_len:
                return  # history outgrew the context window
            request = ServeRequest(prompt=prompt,
                                   max_new_tokens=max_new,
                                   temperature=0.0,
                                   rng_seed=int(rng.integers(
                                       0, 2**31 - 1)))
            row = {"session": idx, "turn": turn,
                   "prompt_len": len(prompt), "max_new": max_new}
            try:
                result = scheduler.submit(request, timeout=30).result(
                    timeout=result_timeout)
            except BaseException as exc:  # noqa: BLE001
                row["status"] = ("shed" if fault_kind(exc) == "shed"
                                 else "failed")
                row["error"] = "{}: {}".format(type(exc).__name__,
                                               str(exc)[:200])
                with rows_lock:
                    rows.append(row)
                return
            row.update(status="complete",
                       ttft_s=round(result.ttft_s, 6),
                       latency_s=round(result.latency_s, 6),
                       prefix_len=int(result.prefix_len))
            with rows_lock:
                rows.append(row)
            history = [int(t) for t in result.tokens]
            if spec.think_time:
                time.sleep(spec.think_time)

    threads = [threading.Thread(target=session, args=(i,), daemon=True)
               for i in range(spec.n_sessions)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=result_timeout)
    wall = max(time.monotonic() - t0, 1e-9)
    rows.sort(key=lambda r: (r["session"], r["turn"]))
    done = [r for r in rows if r["status"] == "complete"]
    first = [r["ttft_s"] for r in done if r["turn"] == 0]
    later = [r["ttft_s"] for r in done if r["turn"] > 0]
    return {
        "format": "cloud_tpu.loadgen_conv.v1",
        "spec": {
            "n_sessions": spec.n_sessions,
            "n_turns": spec.n_turns,
            "user_tokens": spec.user_tokens,
            "max_new": [spec.max_new_lo, spec.max_new_hi],
            "think_time": spec.think_time,
            "seed": spec.seed,
        },
        "offered": len(rows),
        "completed": len(done),
        "failed": sum(1 for r in rows if r["status"] == "failed"),
        "shed": sum(1 for r in rows if r["status"] == "shed"),
        "duration_s": wall,
        "ttft_first_turn": _percentiles(first),
        "ttft_follow_up": _percentiles(later),
        "follow_up_prefix_tokens": _percentiles(
            [float(r["prefix_len"]) for r in done if r["turn"] > 0]),
        "per_request": rows,
    }


def _build_scheduler(args):
    import jax
    import jax.numpy as jnp

    from cloud_tpu.serving.scheduler import Scheduler
    from cloud_tpu.serving.smoke import build_model

    model = build_model(num_layers=args.layers)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    pages_per_slot = model.max_seq_len // args.page_size
    num_pages = args.num_pages or None
    slots_min = getattr(args, "slots_min", None)
    slots_max = getattr(args, "slots_max", None)
    if num_pages is None and slots_min is None and slots_max is None:
        # Fixed geometry keeps the historic pool size; an elastic
        # ladder lets the Scheduler size the pool for its widest rung.
        num_pages = (args.slots + 4) * pages_per_slot + 1
    return Scheduler(model, params, slots=args.slots,
                     page_size=args.page_size,
                     num_pages=num_pages,
                     admission_window=args.slots,
                     strict_no_retrace=False,
                     kv_dtype=args.kv_dtype,
                     host_tier=args.host_tier,
                     slots_min=slots_min,
                     slots_max=slots_max,
                     admission_model=getattr(args, "admission_model",
                                             None))


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="open-arrival load generator for graftserve")
    parser.add_argument("--rate", type=float, action="append",
                        help="arrivals/sec; repeat for a load sweep "
                        "(default: one run at 8.0)")
    parser.add_argument("--requests", type=int, default=20)
    parser.add_argument("--process", default="poisson",
                        choices=("poisson", "bursty"))
    parser.add_argument("--burstiness", type=float, default=4.0)
    parser.add_argument("--shared-prefix-ratio", type=float,
                        default=0.5)
    parser.add_argument("--shared-prefix-len", type=int, default=16)
    parser.add_argument("--slo-ttft", type=float, default=None)
    parser.add_argument("--slo-tpot", type=float, default=None)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--slots", type=int, default=4)
    parser.add_argument("--page-size", type=int, default=16)
    parser.add_argument("--num-pages", type=int, default=0,
                        help="KV pool pages (0 = slots+4 sequences); "
                        "set small to force trie eviction between "
                        "conversation turns")
    parser.add_argument("--layers", type=int, default=6,
                        help="model depth (2 keeps CI fast)")
    parser.add_argument("--scenario", default="open",
                        choices=("open", "conversation", "diurnal"),
                        help="open-arrival singles, multi-turn "
                        "conversations (the host-tier workload), or a "
                        "sinusoidal-ramp offered rate (the autoscale "
                        "A/B workload)")
    parser.add_argument("--rate-lo", type=float, default=2.0,
                        help="diurnal trough arrivals/sec")
    parser.add_argument("--rate-hi", type=float, default=16.0,
                        help="diurnal crest arrivals/sec")
    parser.add_argument("--segments", type=int, default=6)
    parser.add_argument("--segment-seconds", type=float, default=2.0)
    parser.add_argument("--slots-min", type=int, default=None,
                        help="elastic ladder floor (enables graftflex "
                        "autoscaling; default: CLOUD_TPU_SERVE_"
                        "SLOTS_MIN)")
    parser.add_argument("--slots-max", type=int, default=None,
                        help="elastic ladder ceiling (default: "
                        "CLOUD_TPU_SERVE_SLOTS_MAX)")
    parser.add_argument("--admission-model", default=None,
                        help="fitted admission model JSON (default: "
                        "CLOUD_TPU_SERVE_ADMISSION_MODEL)")
    parser.add_argument("--conversations", type=int, default=4)
    parser.add_argument("--turns", type=int, default=3)
    parser.add_argument("--user-tokens", type=int, default=8)
    parser.add_argument("--think-time", type=float, default=0.05)
    parser.add_argument("--kv-dtype", default=None,
                        help="KV page dtype: '' (compute dtype) or "
                        "int8 (default: CLOUD_TPU_SERVE_KV_DTYPE)")
    parser.add_argument("--host-tier", default=None, type=int,
                        help="1 = demote finished turns to host RAM "
                        "(default: CLOUD_TPU_SERVE_HOST_TIER)")
    parser.add_argument("--out-dir", default="loadgen-out")
    args = parser.parse_args(argv)
    if args.host_tier is not None:
        args.host_tier = bool(args.host_tier)

    os.makedirs(args.out_dir, exist_ok=True)
    from cloud_tpu.serving import reqtrace
    if reqtrace.env_enabled() and reqtrace.get() is None:
        # Default the trace next to the report so one --out-dir is the
        # whole artifact (CLOUD_TPU_REQTRACE_DIR still wins).
        os.environ.setdefault("CLOUD_TPU_REQTRACE_DIR", args.out_dir)

    scheduler = _build_scheduler(args)
    scheduler.start()
    if args.scenario == "conversation":
        return _main_conversation(args, scheduler)
    if args.scenario == "diurnal":
        return _main_diurnal(args, scheduler)
    rates = args.rate or [8.0]
    specs = [LoadSpec(rate=rate, n_requests=args.requests,
                      process=args.process,
                      burstiness=args.burstiness,
                      shared_prefix_ratio=args.shared_prefix_ratio,
                      shared_prefix_len=args.shared_prefix_len,
                      seed=args.seed + i)
             for i, rate in enumerate(rates)]
    runs = []
    try:
        all_requests = []
        for spec in specs:
            all_requests.extend(build_requests(
                spec, scheduler.engine.model.vocab_size,
                scheduler.engine.max_seq_len))
        buckets = sorted({scheduler._bucket(r) for r in all_requests})
        print("[loadgen] warmup over buckets {}".format(buckets))
        scheduler.warmup(buckets,
                         sampling_configs=[(("temperature", 0.0),)])
        for spec in specs:
            print("[loadgen] {} x{} @ {:.3g} req/s".format(
                spec.process, spec.n_requests, spec.rate))
            run = run_load(scheduler, spec, slo_ttft=args.slo_ttft,
                           slo_tpot=args.slo_tpot)
            print("[loadgen]   offered {:.3g} rps, achieved {:.3g} "
                  "rps, goodput {:.3f}, ttft p95 {}".format(
                      run["offered_rps"], run["achieved_rps"],
                      run["goodput"], run["ttft"]["p95"]))
            runs.append(run)
        stats = scheduler.stats()
    finally:
        scheduler.close()
        tracer = reqtrace.get()
        if tracer is not None:
            tracer.flush()

    report = {
        "format": "cloud_tpu.loadgen_sweep.v1",
        "runs": runs,
        "scheduler_stats": {
            "queue_wait": stats["queue_wait"],
            "reserve_wait": stats["reserve_wait"],
            "ttft": stats["ttft"],
            "prefix_hit_rate": stats["prefix_hit_rate"],
        },
    }
    out_path = os.path.join(args.out_dir, "loadgen_report.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print("[loadgen] wrote {}".format(out_path))
    return 0


def _main_conversation(args, scheduler):
    """Conversation-scenario driver: warm every pow2 bucket (turn
    prompts grow at runtime, so any width can appear), run the
    sessions, report the first-turn vs follow-up TTFT split plus the
    scheduler's demote/promote census."""
    from cloud_tpu.serving import reqtrace
    spec = ConversationSpec(
        n_sessions=args.conversations, n_turns=args.turns,
        user_tokens=args.user_tokens, think_time=args.think_time,
        seed=args.seed)
    try:
        print("[loadgen] warmup (all pow2 buckets)")
        scheduler.warmup([scheduler.engine.max_seq_len],
                         sampling_configs=[(("temperature", 0.0),)])
        print("[loadgen] conversations x{} turns x{}".format(
            spec.n_sessions, spec.n_turns))
        run = run_conversations(scheduler, spec)
        stats = scheduler.stats()
        # Leak detector: every session thread has joined, so after the
        # tick thread quiesces the pool must hold nothing beyond the
        # trie's own references — the CI offload job gates on this.
        time.sleep(0.3)
        scheduler.assert_drained(clear_prefix=True)
        leaked = scheduler.pool.leak_report()
    finally:
        scheduler.close()
        tracer = reqtrace.get()
        if tracer is not None:
            tracer.flush()
    print("[loadgen]   completed {}/{}: ttft p50 first {} follow-up "
          "{}".format(run["completed"], run["offered"],
                      run["ttft_first_turn"]["p50"],
                      run["ttft_follow_up"]["p50"]))
    report = {
        "format": "cloud_tpu.loadgen_sweep.v1",
        "runs": [run],
        "scheduler_stats": {
            "ttft": stats["ttft"],
            "prefix_hit_rate": stats["prefix_hit_rate"],
            "kv": stats["kv"],
            "leaked_pages": leaked,
        },
    }
    out_path = os.path.join(args.out_dir, "loadgen_report.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print("[loadgen] wrote {}".format(out_path))
    return 0


def _main_diurnal(args, scheduler):
    """Diurnal-scenario driver: warm every bucket the per-segment
    request populations will hit (plus the resize ladder, which
    warmup() walks on its own when one is configured), run the ramp,
    and report the goodput-vs-offered curve next to the scheduler's
    geometry census."""
    from cloud_tpu.serving import reqtrace
    spec = DiurnalSpec(
        rate_lo=args.rate_lo, rate_hi=args.rate_hi,
        segments=args.segments, segment_s=args.segment_seconds,
        process=args.process, burstiness=args.burstiness,
        shared_prefix_ratio=args.shared_prefix_ratio,
        shared_prefix_len=args.shared_prefix_len, seed=args.seed)
    try:
        vocab = scheduler.engine.model.vocab_size
        max_seq_len = scheduler.engine.max_seq_len
        all_requests = []
        for k, rate in enumerate(spec.segment_rates()):
            seg_spec = LoadSpec(
                rate=rate,
                n_requests=max(1, int(round(rate * spec.segment_s))),
                process=spec.process, burstiness=spec.burstiness,
                shared_prefix_ratio=spec.shared_prefix_ratio,
                shared_prefix_len=spec.shared_prefix_len,
                seed=spec.seed + 101 * k + 1)
            all_requests.extend(build_requests(seg_spec, vocab,
                                               max_seq_len))
        buckets = sorted({scheduler._bucket(r) for r in all_requests})
        print("[loadgen] warmup over buckets {} ladder {}".format(
            buckets, list(scheduler.engine.ladder)))
        scheduler.warmup(buckets,
                         sampling_configs=[(("temperature", 0.0),)])
        print("[loadgen] diurnal {} segments x {:.3g}s, {:.3g} -> "
              "{:.3g} req/s".format(spec.segments, spec.segment_s,
                                    spec.rate_lo, spec.rate_hi))
        run = run_diurnal(scheduler, spec, slo_ttft=args.slo_ttft,
                          slo_tpot=args.slo_tpot)
        for seg in run["offered_curve"]:
            print("[loadgen]   seg {} @ {:.3g} rps: goodput {:.3f}, "
                  "ttft p99 {}".format(seg["segment"],
                                       seg["offered_rate"],
                                       seg["goodput"],
                                       seg["ttft"]["p99"]))
        stats = scheduler.stats()
    finally:
        scheduler.close()
        tracer = reqtrace.get()
        if tracer is not None:
            tracer.flush()
    geometry = stats.get("geometry", {})
    print("[loadgen]   goodput {:.3f}, worst seg ttft p99 {}, resizes "
          "{}".format(run["goodput"], run["worst_ttft_p99"],
                      geometry.get("resizes")))
    report = {
        "format": "cloud_tpu.loadgen_sweep.v1",
        "runs": [run],
        "scheduler_stats": {
            "queue_wait": stats["queue_wait"],
            "ttft": stats["ttft"],
            "prefix_hit_rate": stats["prefix_hit_rate"],
            "geometry": geometry,
            "admission_predictor": stats.get("admission_predictor"),
        },
    }
    out_path = os.path.join(args.out_dir, "loadgen_report.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print("[loadgen] wrote {}".format(out_path))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
