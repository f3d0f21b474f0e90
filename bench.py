"""Benchmark harness: ResNet50 training throughput on one TPU chip.

BASELINE.md target: Keras `model.fit` steps/sec via the launch API on
v5e-8 matching 8xV100 wall-clock. The reference publishes no numbers
(BASELINE.md "Published reference numbers: None"), so the recorded
baseline is the 8xV100 side of the driver's target: ResNet50 mixed
precision at ~2800 images/sec across 8 V100s = 350 images/sec per
V100-equivalent. This harness measures our per-chip ResNet50 train-step
throughput (bf16, NHWC) through the framework's own jitted Trainer
step; vs_baseline > 1.0 means one v5e chip beats one V100, i.e. v5e-8
beats 8xV100 wall-clock for config 2.

One process: `python bench.py` asks JAX for its devices, stamps every
record with what it ran on (`platform`, `device_kind`, `device_count`),
and runs the selected series right here — a chip belongs to one
process, so there is no parent that probes and no child that measures.
When the default backend is not a TPU it exits non-zero with a message;
a CPU run of the pipeline has to be asked for with `JAX_PLATFORMS=cpu`
and is stamped `cpu`. The persistent compile cache
(`parallel.compile_cache`) makes a repeat run skip the compiles.

Prints one JSON line:
    {"metric": ..., "value": N, "unit": "images/sec", "vs_baseline": N,
     "method": "median_chunk", "platform": "tpu",
     "device_kind": "TPU v5 lite", "device_count": 1, ...}
"""

import json
import os
import sys
import time

import numpy as np

# Named bench configs: the fair-game ResNet variants that keep
# resurfacing in sweeps get first-class names, so
# `BENCH_CONFIG=bf16_input python bench.py` reproduces the exact knob
# set a recorded series claims instead of a hand-typed env pile.
# Explicit env still beats the named config.
NAMED_CONFIGS = {
    "bf16_input": {"BENCH_BF16_INPUT": "1"},
    "space_to_depth": {"BENCH_S2D": "1"},
    "bf16_s2d": {"BENCH_BF16_INPUT": "1", "BENCH_S2D": "1"},
}
_CFG_NAME = os.environ.get("BENCH_CONFIG", "")
if _CFG_NAME:
    if _CFG_NAME not in NAMED_CONFIGS:
        sys.exit("BENCH_CONFIG=%r unknown (choose from: %s)"
                 % (_CFG_NAME, ", ".join(sorted(NAMED_CONFIGS))))
    for _k, _v in NAMED_CONFIGS[_CFG_NAME].items():
        os.environ.setdefault(_k, _v)


def _env_int(key, default):
    """os.environ int; a malformed value degrades to the default."""
    try:
        return int(os.environ.get(key, default))
    except (TypeError, ValueError):
        return default


def _env_float(key, default):
    """`_env_int`'s float sibling (BENCH_SERVE_PREFIX_SHARE etc.)."""
    try:
        return float(os.environ.get(key, default))
    except (TypeError, ValueError):
        return default

BATCH = _env_int("BENCH_BATCH", 256)
IMAGE = _env_int("BENCH_IMAGE", 224)
WARMUP_STEPS = _env_int("BENCH_WARMUP", 3)
TIMED_STEPS = _env_int("BENCH_STEPS", 20)
CHUNK = min(_env_int("BENCH_CHUNK", 5), TIMED_STEPS)
BASELINE_IMAGES_PER_SEC = 350.0  # one V100, fp16 ResNet50 (8xV100 / 8)

# ResNet50 fwd+bwd+update FLOPs per image at 224^2, for the roofline
# line when XLA's own count is unavailable. The chip's peak comes from
# telemetry.PEAK_TFLOPS by `device_kind`.
RESNET50_GFLOPS_PER_IMAGE = 12.3

METRIC = "resnet50_train_images_per_sec_per_chip"


def _device_stamp():
    """What JAX says this process runs on — attached to every record."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def _pct_peak(tflops, stamp):
    """Share of the chip's published bf16 peak, or None for a CPU run
    (which has no such number). An accelerator that is not in
    telemetry.PEAK_TFLOPS raises."""
    if stamp["platform"] == "cpu":
        return None
    from cloud_tpu.monitoring import telemetry

    return round(
        100.0 * tflops / telemetry.peak_tflops(stamp["device_kind"]), 1)


def _metric_name():
    if os.environ.get("BENCH_SWEEP", "0") == "1":
        # graftsweep series: trial throughput of a warm-cache ASHA
        # sweep (tuner/sweep.py), with the cold-vs-warm compile split
        # and guard fault census in the record.
        return "graftsweep_trials_per_hour"
    if os.environ.get("BENCH_SERVE_LOAD", "0") == "1":
        # graftlens open-loop load series: goodput (fraction of offered
        # requests meeting the TTFT+TPOT SLOs) at the highest swept
        # arrival rate, with the full offered-vs-achieved curve in the
        # record. Checked before BENCH_SERVE: the load series drives a
        # Scheduler too, but measures the SLO envelope, not raw
        # tokens/sec.
        return "graftserve_loadgen_goodput"
    if os.environ.get("BENCH_SERVE", "0") == "1":
        # A different measurement entirely (continuous-batching decode,
        # not training throughput): its own metric name. A
        # CLOUD_TPU_PAGED_KERNEL force-override is an A/B contrast
        # series — suffixed so kernel-on/off records are never read as
        # each other or as the auto flagship.
        name = "graftserve_decode_tokens_per_sec"
        forced = os.environ.get("CLOUD_TPU_PAGED_KERNEL", "")
        if forced == "1":
            name += "_pk_on"
        elif forced == "0":
            name += "_pk_off"
        # graftpack contrast series: int8 KV pages (and/or the host
        # page tier) change what a token costs, so their records are
        # suffixed, same as the kernel A/B above.
        if os.environ.get("BENCH_SERVE_KV_DTYPE",
                          "").strip().lower() == "int8":
            name += "_kvq"
        if os.environ.get("BENCH_SERVE_HOST_TIER", "0") == "1":
            name += "_host"
        return name
    # Architecture/feeding variants are suffixed so recorded numbers
    # (including failed runs) stay apples-to-apples per series.
    name = METRIC
    if os.environ.get("BENCH_S2D", "0") == "1":
        name += "_s2d"
    if os.environ.get("BENCH_BF16_INPUT", "0") == "1":
        name += "_bf16in"
    if os.environ.get("BENCH_RESIDENT", "0") == "1":
        name += "_res"
    if os.environ.get("BENCH_ASYNC_LOG", "0") == "1":
        # Async-host-loop contrast series: the timed loop hands its
        # per-chunk loss to the background metric reader instead of
        # sync-fetching it, so the sync-elimination win is its own
        # metric.
        name += "_async"
    if os.environ.get("BENCH_WARM", "0") == "1":
        # Warm-start contrast series: same measurement, but the record
        # is its own series so its compile-census fields (time to
        # first step, persistent-cache hits) are tracked against other
        # warm runs — a cold run's multi-minute compile would otherwise
        # look like a throughput regression.
        name += "_warm"
    return name


def _requested_config():
    """The fair-game measurement knobs THIS invocation was asked for.

    Attached to every record so a consumer can always tell which
    configuration the number describes.
    """
    if os.environ.get("BENCH_SWEEP", "0") == "1":
        # The sweep series' fair-game knobs: trial budget and the ASHA
        # ladder geometry. The chaos spec is recorded when set so a
        # fault-census record is self-describing.
        cfg = {
            "sweep": True,
            "trials": _env_int("BENCH_SWEEP_TRIALS", 12),
            "min_budget": _env_int("BENCH_SWEEP_MIN_BUDGET", 1),
            "eta": _env_int("BENCH_SWEEP_ETA", 3),
            "max_budget": _env_int("BENCH_SWEEP_MAX_BUDGET", 9),
        }
        if os.environ.get("CLOUD_TPU_CHAOS"):
            cfg["chaos"] = os.environ["CLOUD_TPU_CHAOS"]
        return cfg
    if os.environ.get("BENCH_SERVE_LOAD", "0") == "1":
        # The loadgen series' fair-game knobs: the arrival process and
        # the SLO envelope the goodput number is measured against.
        return {
            "serve_load": True,
            "slots": _env_int("BENCH_SERVE_LOAD_SLOTS", 8),
            "requests": _env_int("BENCH_SERVE_LOAD_REQUESTS", 24),
            "rates": os.environ.get("BENCH_SERVE_LOAD_RATES", "2,4,8"),
            "process": os.environ.get("BENCH_SERVE_LOAD_PROCESS",
                                      "poisson"),
            "shared_prefix_ratio": _env_float(
                "BENCH_SERVE_LOAD_SHARE", 0.5),
            "slo_ttft_s": _env_float("BENCH_SLO_TTFT", 0.5),
            "slo_tpot_s": _env_float("BENCH_SLO_TPOT", 0.1),
        }
    if os.environ.get("BENCH_SERVE", "0") == "1":
        # The serve series' fair-game knobs — none of the training
        # knobs apply (it measures the decode engine, not the Trainer).
        return {
            "serve": True,
            "slots": _env_int("BENCH_SERVE_SLOTS", 8),
            "waves": _env_int("BENCH_SERVE_WAVES", 0),
            # graftshare knob: fraction of short requests sharing one
            # prompt prefix (0 = no sharing, the pre-ISSUE-11 shape;
            # the sweep runs 0 / 0.5 / 0.9).
            "prefix_share": _env_float("BENCH_SERVE_PREFIX_SHARE", 0.0),
            # Paged decode-attention impl the serve series ran under
            # (ops/paged_attention.py): "on"/"off" when
            # CLOUD_TPU_PAGED_KERNEL force-overrides, else "auto"
            # (kernel on TPU, reference elsewhere). Recorded so an
            # A/B pair of serve records is self-describing.
            "paged_kernel": {"1": "on", "0": "off"}.get(
                os.environ.get("CLOUD_TPU_PAGED_KERNEL", ""), "auto"),
            # graftpack knobs: KV page dtype ("" = compute dtype) and
            # the host page tier. Each flips the record onto its own
            # suffixed series (_kvq / _host).
            "kv_dtype": os.environ.get("BENCH_SERVE_KV_DTYPE",
                                       "").strip().lower(),
            "host_tier": _env_int("BENCH_SERVE_HOST_TIER", 0),
        }
    cfg = {
        "batch": BATCH,
        "image": IMAGE,
        "steps_per_execution": max(_env_int("BENCH_SPE", 1), 1),
        "bf16_input": os.environ.get("BENCH_BF16_INPUT", "0") == "1",
        "space_to_depth": os.environ.get("BENCH_S2D", "0") == "1",
    }
    if os.environ.get("BENCH_RESIDENT", "0") == "1":
        cfg["resident"] = True
    if os.environ.get("BENCH_ASYNC_LOG", "0") == "1":
        cfg["async_log"] = True
    if os.environ.get("BENCH_WARM", "0") == "1":
        cfg["warm"] = True
    if _CFG_NAME:
        # Provenance only (the expanded knobs above are what the run
        # measured).
        cfg["named_config"] = _CFG_NAME
    return cfg


def _pct(snapshot, key):
    """Percentile from a host Histogram snapshot, None when the
    histogram is empty (p50 of nothing reads 0.0, which would record a
    fake perfect latency)."""
    return round(snapshot[key], 5) if snapshot.get("count") else None


def _serve_worker(stamp):
    """BENCH_SERVE=1: the graftserve continuous-batching series.

    Measures the decode engine the way the serving smoke does — a
    mixed-length request fleet through the Scheduler vs the
    batch-synchronous `generate()` baseline at the SAME slot count —
    but reports the numbers instead of enforcing a floor: tokens/sec
    (the `value`), speedup as `vs_baseline`, requests/sec, TTFT and
    per-token latency p50/p95/p99, plus the standard compile/transfer
    census every bench record carries.
    """
    import jax

    from cloud_tpu.parallel import compile_cache
    compile_cache.enable(min_compile_time_secs=1.0)
    import jax.numpy as jnp

    from cloud_tpu import ops
    from cloud_tpu.parallel import runtime as runtime_lib
    from cloud_tpu.serving import Scheduler
    from cloud_tpu.serving.smoke import (build_model, build_requests,
                                         run_baseline, run_serve)

    slots = _env_int("BENCH_SERVE_SLOTS", 8)
    waves = _env_int("BENCH_SERVE_WAVES", 0) or None
    prefix_share = _env_float("BENCH_SERVE_PREFIX_SHARE", 0.0)
    kv_dtype = os.environ.get("BENCH_SERVE_KV_DTYPE",
                              "").strip().lower()
    host_tier = os.environ.get("BENCH_SERVE_HOST_TIER", "0") == "1"
    model = build_model()
    requests = build_requests(slots, waves, prefix_share=prefix_share)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]

    run_baseline(model, params, requests, slots, timed=False)  # warm
    base_tokens, base_secs = run_baseline(model, params, requests,
                                          slots, timed=True)

    t_cold = time.perf_counter()
    pages_per_slot = model.max_seq_len // 16
    scheduler = Scheduler(model, params, slots=slots, page_size=16,
                          num_pages=(slots + 4) * pages_per_slot + 1,
                          admission_window=len(requests),
                          strict_no_retrace=True,
                          kv_dtype=kv_dtype,
                          host_tier=host_tier).start()
    try:
        buckets = sorted({scheduler._bucket(r) for r in requests})
        scheduler.warmup(buckets,
                         sampling_configs=[(("temperature", 0.0),)])
        # Serve's time-to-first-step analog: engine build + the whole
        # compile surface (prefill buckets, insert, tick, evict) to
        # the first warm-servable state.
        first_step_seconds = time.perf_counter() - t_cold
        warm = runtime_lib.compile_stats()
        _d2h_before = runtime_lib.transfer_stats()
        _, serve_tokens, serve_secs = run_serve(scheduler, requests)
        _d2h_after = runtime_lib.transfer_stats()
        after = runtime_lib.compile_stats()
        stats = scheduler.stats()
        # Model-exact per-tick cost of the paged decode-attention op
        # (ops/paged_attention.py cost hook), all layers.
        engine, lm = scheduler.engine, scheduler.engine.model
        tick_cost = ops.paged_attention_cost(
            engine.slots, engine.spec_k + 1 if engine.spec_on else 1,
            lm.num_heads, lm.d_model // lm.num_heads, engine.page_size,
            engine.pages_per_slot, dtype=lm.compute_dtype,
            kv_dtype=jnp.int8 if engine.page_dtype == "int8" else None)
        kernel_costs = {"paged_attention": {
            key: tick_cost[key] * lm.num_layers
            for key in ("flops", "bytes_moved")}}
    finally:
        scheduler.close()

    base_tps = base_tokens / base_secs
    serve_tps = serve_tokens / serve_secs
    _pstats = compile_cache.stats()
    record = {
        "metric": _metric_name(),
        "value": round(serve_tps, 2),
        "unit": "tokens/sec",
        # For this series the honest baseline is the run's own
        # batch-synchronous measurement: vs_baseline IS the
        # continuous-batching speedup.
        "vs_baseline": round(serve_tps / base_tps, 3),
        "method": "continuous_vs_batch_synchronous",
        "requests": len(requests),
        "slots": slots,
        "baseline_tokens_per_sec": round(base_tps, 2),
        "requests_per_sec": round(stats["requests_per_sec"], 3),
        "ttft_p50_s": round(stats["ttft"]["p50"], 4),
        "ttft_p95_s": round(stats["ttft"]["p95"], 4),
        "ttft_p99_s": round(stats["ttft"]["p99"], 4),
        "token_latency_p50_s": round(stats["token_latency"]["p50"], 5),
        "token_latency_p95_s": round(stats["token_latency"]["p95"], 5),
        "token_latency_p99_s": round(stats["token_latency"]["p99"], 5),
        # Paged decode-attention A/B field (ops/paged_attention.py):
        # which impl served this record's token latencies.
        "paged_kernel": {"1": "on", "0": "off"}.get(
            os.environ.get("CLOUD_TPU_PAGED_KERNEL", ""), "auto"),
        "paged_attention_flops_per_tick": kernel_costs[
            "paged_attention"]["flops"],
        "paged_attention_bytes_per_tick": kernel_costs[
            "paged_attention"]["bytes_moved"],
        # Chunked-prefill A/B fields (ISSUE 16): chunk size 0 = off;
        # the dispatch count and decode-gap tail make a chunked record
        # self-describing next to an unchunked one.
        "prefill_chunk": stats["prefill_chunk_size"],
        "prefill_chunks_dispatched": stats["prefill_chunks_dispatched"],
        "decode_gap_p99_s": _pct(stats["decode_gap"], "p99"),
        # graftshare census: hit/miss TTFT split + cache effectiveness.
        # Hit percentiles are None at prefix_share=0 (empty histogram).
        "prefix_share": prefix_share,
        "prefix_hit_rate": round(stats["prefix_hit_rate"], 4),
        "prefix_hits": stats["prefix_hits"],
        "prefix_misses": stats["prefix_misses"],
        "prefix_tokens_served": stats["prefix_tokens_served"],
        "ttft_hit_p50_s": _pct(stats["ttft_hit"], "p50"),
        "ttft_hit_p95_s": _pct(stats["ttft_hit"], "p95"),
        "ttft_hit_p99_s": _pct(stats["ttft_hit"], "p99"),
        "ttft_miss_p50_s": _pct(stats["ttft_miss"], "p50"),
        "ttft_miss_p95_s": _pct(stats["ttft_miss"], "p95"),
        "ttft_miss_p99_s": _pct(stats["ttft_miss"], "p99"),
        "cow_copies": stats["pool"]["cow_copies"],
        "ticks": stats["ticks"],
        # graftpack KV-hierarchy census: page dtype + per-page cost,
        # resident-session capacity at the pool's byte budget, and the
        # demote/promote traffic when the host tier is on.
        "kv_dtype": stats["kv"]["page_dtype"] or "fp",
        "kv_page_bytes": stats["kv"]["page_bytes"],
        "kv_capacity_sessions": stats["kv"]["capacity_sessions"],
        "host_tier_pages": stats["kv"]["host_tier_pages"],
        "page_demotes": stats["kv"]["page_demotes"],
        "page_promotes": stats["kv"]["page_promotes"],
        "digest_failures": stats["kv"]["digest_failures"],
        # The zero-retrace contract as numbers (also enforced live by
        # strict_no_retrace — a violation kills the run, not the lint).
        "new_traces_post_warmup": after["n_traces"] - warm["n_traces"],
        "new_compiles_post_warmup": (after["n_compiles"]
                                     - warm["n_compiles"]),
        "d2h_fetches": (_d2h_after["d2h_fetches"]
                        - _d2h_before["d2h_fetches"]),
        "d2h_bytes": _d2h_after["d2h_bytes"] - _d2h_before["d2h_bytes"],
        "n_traces": after["n_traces"],
        "n_compiles": after["n_compiles"],
        "compile_seconds": round(after["compile_seconds"], 3),
        "compile_cache_hits": after["cache_hits"],
        "persistent_cache_hits": _pstats["persistent_hits"],
        "persistent_cache_misses": _pstats["persistent_misses"],
        "time_to_first_step_seconds": round(first_step_seconds, 3),
        "requested_config": _requested_config(),
    }
    record.update(stamp)
    if compile_cache.is_enabled():
        record["compile_cache_dir"] = compile_cache.cache_dir()
    print(json.dumps(record))


def _serve_load_worker(stamp):
    """BENCH_SERVE_LOAD=1: the graftlens open-loop goodput series.

    Unlike BENCH_SERVE (a closed-loop fleet: the driver submits the
    next request when the previous finishes, so the system sets its
    own arrival rate), this series offers load on an independent clock
    — Poisson arrivals at 2-3 fixed rates from serving/loadgen.py —
    and records the SLO envelope: `value` is goodput (fraction of
    OFFERED requests completing within --slo-ttft/--slo-tpot) at the
    HIGHEST swept rate, `vs_baseline` is goodput at the lowest (the
    underload sanity point; a healthy stack reads ~1.0 there), and
    `load_curve` carries the full offered-vs-achieved sweep.
    """
    import jax

    from cloud_tpu.parallel import compile_cache
    compile_cache.enable(min_compile_time_secs=1.0)
    import jax.numpy as jnp

    from cloud_tpu.parallel import runtime as runtime_lib
    from cloud_tpu.serving import Scheduler
    from cloud_tpu.serving import loadgen
    from cloud_tpu.serving.smoke import build_model

    slots = _env_int("BENCH_SERVE_LOAD_SLOTS", 8)
    n_requests = _env_int("BENCH_SERVE_LOAD_REQUESTS", 24)
    rates = [float(r) for r in os.environ.get(
        "BENCH_SERVE_LOAD_RATES", "2,4,8").split(",") if r.strip()]
    process = os.environ.get("BENCH_SERVE_LOAD_PROCESS", "poisson")
    share = _env_float("BENCH_SERVE_LOAD_SHARE", 0.5)
    slo_ttft = _env_float("BENCH_SLO_TTFT", 0.5)
    slo_tpot = _env_float("BENCH_SLO_TPOT", 0.1)

    model = build_model()
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    specs = [loadgen.LoadSpec(rate=rate, n_requests=n_requests,
                              process=process,
                              shared_prefix_ratio=share, seed=i)
             for i, rate in enumerate(rates)]

    t_cold = time.perf_counter()
    pages_per_slot = model.max_seq_len // 16
    scheduler = Scheduler(model, params, slots=slots, page_size=16,
                          num_pages=(slots + 4) * pages_per_slot + 1,
                          admission_window=slots,
                          strict_no_retrace=True).start()
    try:
        all_requests = []
        for spec in specs:
            all_requests.extend(loadgen.build_requests(
                spec, model.vocab_size, model.max_seq_len))
        buckets = sorted({scheduler._bucket(r) for r in all_requests})
        scheduler.warmup(buckets,
                         sampling_configs=[(("temperature", 0.0),)])
        first_step_seconds = time.perf_counter() - t_cold
        warm = runtime_lib.compile_stats()
        runs = [loadgen.run_load(scheduler, spec, slo_ttft=slo_ttft,
                                 slo_tpot=slo_tpot)
                for spec in specs]
        after = runtime_lib.compile_stats()
        stats = scheduler.stats()
    finally:
        scheduler.close()

    # Sweep order is the env-var order; value/vs_baseline key on the
    # rate extremes so a reordered RATES list still records the same
    # contrast.
    lowest = min(runs, key=lambda r: r["spec"]["rate"])
    highest = max(runs, key=lambda r: r["spec"]["rate"])
    _pstats = compile_cache.stats()
    record = {
        "metric": _metric_name(),
        "value": round(highest["goodput"], 4),
        "unit": "goodput_frac",
        # Goodput under the lightest offered load: the run's own
        # underload control, not a cached foreign number.
        "vs_baseline": round(lowest["goodput"], 4),
        "method": "open_loop_loadgen",
        "slots": slots,
        "requests_per_rate": n_requests,
        "process": process,
        "shared_prefix_ratio": share,
        "slo_ttft_s": slo_ttft,
        "slo_tpot_s": slo_tpot,
        "load_curve": [{
            "rate": run["spec"]["rate"],
            "offered_rps": round(run["offered_rps"], 3),
            "achieved_rps": round(run["achieved_rps"], 3),
            "goodput": round(run["goodput"], 4),
            "completed": run["completed"],
            "rejected": run["rejected"],
            "failed": run["failed"],
            "ttft_p95_s": _pct(run["ttft"], "p95"),
            "tpot_p95_s": _pct(run["tpot"], "p95"),
            "hit_rate": round(run["hit_rate"], 4),
        } for run in runs],
        "prefix_hit_rate": round(stats["prefix_hit_rate"], 4),
        "queue_wait_p95_s": _pct(stats["queue_wait"], "p95"),
        "reserve_wait_p95_s": _pct(stats["reserve_wait"], "p95"),
        "prefill_chunk": stats["prefill_chunk_size"],
        "prefill_chunks_dispatched": stats["prefill_chunks_dispatched"],
        "decode_gap_p99_s": _pct(stats["decode_gap"], "p99"),
        "ticks": stats["ticks"],
        "new_traces_post_warmup": after["n_traces"] - warm["n_traces"],
        "new_compiles_post_warmup": (after["n_compiles"]
                                     - warm["n_compiles"]),
        "n_traces": after["n_traces"],
        "n_compiles": after["n_compiles"],
        "compile_seconds": round(after["compile_seconds"], 3),
        "compile_cache_hits": after["cache_hits"],
        "persistent_cache_hits": _pstats["persistent_hits"],
        "persistent_cache_misses": _pstats["persistent_misses"],
        "time_to_first_step_seconds": round(first_step_seconds, 3),
        "requested_config": _requested_config(),
    }
    record.update(stamp)
    if compile_cache.is_enabled():
        record["compile_cache_dir"] = compile_cache.cache_dir()
    print(json.dumps(record))


def _sweep_worker(stamp):
    """BENCH_SWEEP=1: the graftsweep trial-throughput series.

    Runs the CI smoke's sweep shape — an ASHA ladder over a
    runtime-only learning-rate axis on the CPU-scale MLP, so every
    trial after the first rides the cold trial's warm executables —
    and reports trials/hour as the `value`. `vs_baseline` is the run's
    own cold-vs-warm contrast (cold trial wall over mean warm trial
    wall: the multiplicative win the shared compile cache buys per
    trial), and the guard fault/retry census fields make a
    CLOUD_TPU_CHAOS run self-describing.
    """
    import tempfile

    import jax

    from cloud_tpu.parallel import compile_cache
    compile_cache.enable(min_compile_time_secs=1.0)
    import optax

    from cloud_tpu.models.mnist import MLP
    from cloud_tpu.parallel import runtime as runtime_lib
    from cloud_tpu.training import Trainer
    from cloud_tpu.tuner import (ASHA, HyperParameters, Objective,
                                 RandomOracle, Sweep)

    trials = _env_int("BENCH_SWEEP_TRIALS", 12)
    min_budget = _env_int("BENCH_SWEEP_MIN_BUDGET", 1)
    eta = _env_int("BENCH_SWEEP_ETA", 3)
    max_budget = _env_int("BENCH_SWEEP_MAX_BUDGET", 9)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 16)).astype(np.float32)
    y = rng.integers(0, 8, size=256).astype(np.int32)
    hp = HyperParameters()
    hp.Float("learning_rate", 1e-3, 1e-1, sampling="log")

    def build(hp):
        return Trainer(
            MLP(hidden=32, num_classes=8),
            optimizer=optax.inject_hyperparams(optax.sgd)(
                learning_rate=hp.get("learning_rate")),
            metrics=())

    objective = Objective("loss", "min")
    sweep = Sweep(build, hp, objective,
                  directory=tempfile.mkdtemp(prefix="bench_sweep_"),
                  oracle=RandomOracle(hp, trials, seed=7),
                  scheduler=ASHA(objective, min_budget=min_budget,
                                 eta=eta, max_budget=max_budget),
                  shape_keys=(), seed=0, name="bench")
    result = sweep.run(x, y, batch_size=64, verbose=False)

    rows = result["trials"]
    cold_walls = [t["wall_s"] for t in rows if t["cold"]]
    warm_walls = [t["wall_s"] for t in rows if not t["cold"]]
    mean_warm = (sum(warm_walls) / len(warm_walls)) if warm_walls else None
    trials_per_hour = (len(rows) / (result["wall_s"] / 3600.0)
                       if result["wall_s"] else 0.0)
    _pstats = compile_cache.stats()
    compile_stats = runtime_lib.compile_stats()
    record = {
        "metric": _metric_name(),
        "value": round(trials_per_hour, 2),
        "unit": "trials/hour",
        "vs_baseline": (round(cold_walls[0] / mean_warm, 3)
                        if cold_walls and mean_warm else None),
        "method": "warm_vs_cold_trial_wall",
        "trials": len(rows),
        "statuses": result["statuses"],
        "best_score": (result["best"] or {}).get("score"),
        "budgets": list(sweep.scheduler.budgets),
        "sweep_wall_s": result["wall_s"],
        "train_s": result["train_s"],
        # The multiplicative compile win, as numbers: ONE cold start
        # for the whole sweep, zero compiles on every warm trial.
        "cold_trials": result["compile"]["cold_trials"],
        "warm_trials": result["compile"]["warm_trials"],
        "cold_compile_seconds": result["compile"]["cold_seconds"],
        "warm_compile_seconds": result["compile"]["warm_seconds"],
        "warm_new_compiles": result["compile"]["warm_new_compiles"],
        "warm_new_traces": result["compile"]["warm_new_traces"],
        "cold_trial_wall_s": (round(cold_walls[0], 4)
                              if cold_walls else None),
        "mean_warm_trial_wall_s": (round(mean_warm, 4)
                                   if mean_warm else None),
        # Guard census (zeros on a clean run; the CLOUD_TPU_CHAOS
        # contrast shows the recovery-path tax per series).
        "faults": result["census"]["faults"],
        "retries": result["census"]["retries"],
        "rollbacks": result["census"]["rollbacks"],
        "resumes": result["census"]["resumes"],
        "fault_kinds": result["census"]["by_kind"],
        "lost_trials": len(result["census"]["lost_trials"]),
        "n_traces": compile_stats["n_traces"],
        "n_compiles": compile_stats["n_compiles"],
        "compile_seconds": round(compile_stats["compile_seconds"], 3),
        "compile_cache_hits": compile_stats["cache_hits"],
        "persistent_cache_hits": _pstats["persistent_hits"],
        "persistent_cache_misses": _pstats["persistent_misses"],
        "requested_config": _requested_config(),
    }
    record.update(stamp)
    if compile_cache.is_enabled():
        record["compile_cache_dir"] = compile_cache.cache_dir()
    print(json.dumps(record))


def worker(stamp):
    """Runs the series the BENCH_* env selects and prints its record,
    stamped with `stamp` (`_device_stamp()`)."""
    if os.environ.get("BENCH_SWEEP", "0") == "1":
        _sweep_worker(stamp)
        return
    if os.environ.get("BENCH_SERVE_LOAD", "0") == "1":
        _serve_load_worker(stamp)
        return
    if os.environ.get("BENCH_SERVE", "0") == "1":
        _serve_worker(stamp)
        return
    import jax

    # Persistent compilation cache: a repeat run (or the sweep's next
    # config) skips the multi-minute ResNet50 compile entirely.
    from cloud_tpu.parallel import compile_cache
    compile_cache.enable(min_compile_time_secs=1.0)
    import optax

    from cloud_tpu.models import ResNet50
    from cloud_tpu.training import Trainer

    rng = np.random.default_rng(0)
    x = rng.normal(size=(BATCH, IMAGE, IMAGE, 3)).astype(np.float32)
    y = rng.integers(0, 1000, size=BATCH).astype(np.int32)
    bf16_input = os.environ.get("BENCH_BF16_INPUT", "0") == "1"
    if bf16_input:
        # Feed bf16. In THIS bench the batch is device-resident and
        # reused every step, so steady-state H2D is zero either way —
        # the measured effect is the stem's input HBM read width (the
        # model casts to compute dtype at the stem regardless,
        # cloud_tpu/models/resnet.py). A real input pipeline feeding
        # fresh batches additionally halves its per-step H2D bytes.
        import ml_dtypes
        x = x.astype(ml_dtypes.bfloat16)

    s2d = os.environ.get("BENCH_S2D", "0") == "1"
    trainer = Trainer(
        ResNet50(num_classes=1000, conv0_space_to_depth=s2d),
        optimizer=optax.sgd(0.1, momentum=0.9),
        train_kwargs={"train": True},
        eval_kwargs={"train": False},
        metrics=())
    trainer.build(x)

    # In-graph multi-step (steps_per_execution): BENCH_SPE optimizer
    # steps per dispatch via lax.scan over the SAME resident batch,
    # amortizing the per-dispatch host cost across the chunk.
    spe = max(_env_int("BENCH_SPE", 1), 1)
    resident_mode = os.environ.get("BENCH_RESIDENT", "0") == "1"
    async_log = os.environ.get("BENCH_ASYNC_LOG", "0") == "1"
    resident = None
    from cloud_tpu.parallel import runtime as runtime_lib
    if resident_mode:
        # _res series: measure the Trainer's actual device-resident
        # executable — per-epoch threefry permutation + in-graph
        # gather over a multi-batch uploaded dataset — instead of
        # re-feeding one host batch. The H2D counter fields attached
        # to the record prove the pipeline's claim: one upload, zero
        # steady-state host->device bytes.
        import jax.numpy as jnp

        from cloud_tpu.training.data import (ArrayDataset,
                                             DeviceResidentDataset)
        n_examples = max(
            _env_int("BENCH_RESIDENT_EXAMPLES", BATCH * 2) // BATCH,
            1) * BATCH
        reps = -(-n_examples // BATCH)
        xr = np.concatenate([x] * reps, axis=0)[:n_examples]
        yr = np.concatenate([y] * reps, axis=0)[:n_examples]
        dataset = ArrayDataset(xr, yr, batch_size=BATCH, shuffle=True,
                               seed=0)
        runtime_lib.reset_transfer_stats()
        resident = DeviceResidentDataset(dataset)
        step_fn = trainer._make_resident_run(
            spe, resident.steps_per_epoch, resident, weighted=False)
        # Fixed device scalars: position wraps modulo steps_per_epoch
        # as state.step advances, cycling the uploaded epoch.
        step_inputs = (resident.data,
                       jnp.array(trainer.state.step, copy=True),
                       jnp.asarray(0, dtype=jnp.int32))
    elif spe > 1:
        inner = trainer._make_train_step_body()

        def chunk_fn(state, batch):
            def body(s, _):
                s, logs = inner(s, batch)
                return s, logs

            state, logs = jax.lax.scan(body, state, None, length=spe)
            return state, {k: v[-1] for k, v in logs.items()}

        step_fn = runtime_lib.instrumented_jit(chunk_fn, donate_argnums=0)
    else:
        step_fn = trainer._make_train_step()

    if not resident_mode:
        step_inputs = (trainer._feed((x, y)),)
    state = trainer.state

    # Time-to-first-step: everything between "step function exists"
    # and "step 1's loss is on the host" — trace + XLA compile (or a
    # persistent-cache hit) + the first dispatch. THE warm-vs-cold
    # contrast number: on a cache-hit restart it collapses from the
    # multi-minute ResNet50 compile to one dispatch.
    first_step_seconds = None
    _t_cold = time.perf_counter()

    # XLA's own FLOP count for one compiled step: turns the roofline
    # line from a hand constant (12.3 GFLOPs/image) into a
    # compiler-derived number. AOT-compile once and reuse the
    # executable for the timed loop (no second trace/compile).
    xla_flops = None
    try:
        compiled = step_fn.lower(state, *step_inputs).compile()
        flops = compiled.cost_analysis().get("flops")
        if flops and flops > 0:
            xla_flops = float(flops)
            step_fn = compiled
    except Exception as e:  # noqa: BLE001 - analysis is best-effort
        print("# cost_analysis unavailable: {}".format(e),
              file=sys.stderr)

    def sync(logs):
        """Barrier: wait for the step that produced `logs`.

        `block_until_ready` waits on this machine — established on the
        v5e by chip run (PERF.md, "Bring-up on the v5e"): enqueue of 20
        8192^3 matmuls returns in 0.2 ms, block_until_ready after
        127 ms, a value fetch of the same result after 128 ms.
        """
        jax.block_until_ready(logs["loss"])

    for _i in range(WARMUP_STEPS):
        state, logs = step_fn(state, *step_inputs)
        if _i == 0:
            sync(logs)
            first_step_seconds = time.perf_counter() - _t_cold
    if WARMUP_STEPS:
        sync(logs)

    # Steady-state d2h census covers the timed loop only: delta against
    # this snapshot, NOT a reset — the _res series' h2d fields need the
    # counters running since their pre-upload reset.
    _d2h_before = runtime_lib.transfer_stats()
    n_chunks = max(TIMED_STEPS // CHUNK, 1)
    if async_log:
        # _async series: the chunk loop never sync-fetches — each
        # chunk's loss goes to the background metric reader
        # (one coalesced off-thread fetch per chunk, the Trainer's
        # async_logging regime) and the loop runs on. The WHOLE loop
        # is timed through drain(): the last chunk's fetched value
        # depends on the entire donated-state chain, so the clock
        # can't stop before every step has executed. Median-chunk
        # doesn't apply (there is
        # no per-chunk barrier to time against) — method says so.
        from cloud_tpu.training.async_logs import AsyncMetricReader

        reader = AsyncMetricReader()
        futures = []
        t0 = time.perf_counter()
        for _ in range(n_chunks):
            for _ in range(CHUNK):
                state, logs = step_fn(state, *step_inputs)
            futures.append(reader.submit({"loss": logs["loss"]}))
        reader.drain()
        futures[-1].result()
        total_elapsed = time.perf_counter() - t0
        reader.close()
        method = "async_total"
        images_per_sec = BATCH * CHUNK * n_chunks * spe / total_elapsed
    else:
        # Median contiguous chunk: robust to one-off host stalls
        # while still reporting sustained — not peak — throughput,
        # comparable with the sustained-average baseline.
        chunk_times = []
        for _ in range(n_chunks):
            t0 = time.perf_counter()
            for _ in range(CHUNK):
                state, logs = step_fn(state, *step_inputs)
            sync(logs)
            chunk_times.append(time.perf_counter() - t0)
        median_elapsed = sorted(chunk_times)[len(chunk_times) // 2]
        method = "median_chunk"
        images_per_sec = BATCH * CHUNK * spe / median_elapsed
    tflops = images_per_sec * RESNET50_GFLOPS_PER_IMAGE / 1000.0
    if xla_flops is not None:
        # cost_analysis counts a lax.scan/while body ONCE (verified on
        # this jax: scan(8) reports the same flops as one step), so the
        # spe>1 executable's true work is body_flops * spe. ResNet50
        # itself has no internal loops, so this is the only scaling
        # needed. dispatches/sec * per-dispatch flops = honest rate.
        dispatches_per_sec = images_per_sec / (BATCH * spe)
        tflops = dispatches_per_sec * (xla_flops * spe) / 1e12
    _d2h_after = runtime_lib.transfer_stats()
    # Compile census (whole worker process, not just the timed loop —
    # the timed loop's own invariant is "zero", which the steady-state
    # tests pin; the record's job is cold-vs-warm provenance).
    _cstats = runtime_lib.compile_stats()
    _pstats = compile_cache.stats()
    record = {
        "metric": _metric_name(),
        "value": round(images_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(images_per_sec / BASELINE_IMAGES_PER_SEC, 3),
        "method": method,
        "chunk": CHUNK,
        "steps": n_chunks * CHUNK * spe,
        # The async-host-loop claim as numbers: device->host round
        # trips the timed loop performed (one coalesced fetch per
        # chunk in both regimes; _async just takes them off-thread).
        "d2h_fetches": (_d2h_after["d2h_fetches"]
                        - _d2h_before["d2h_fetches"]),
        "d2h_bytes": _d2h_after["d2h_bytes"] - _d2h_before["d2h_bytes"],
        "batch": BATCH,
        "image": IMAGE,
        "tflops": round(tflops, 3),
        "pct_peak": _pct_peak(tflops, stamp),
        "flops_source": ("xla_cost_analysis" if xla_flops is not None
                         else "estimate_12.3gflops_per_image"),
        # The compile-as-a-counted-resource claim, as numbers
        # (runtime.compile_stats doctrine): what this process traced
        # and compiled, what the persistent cache absorbed.
        "n_traces": _cstats["n_traces"],
        "n_compiles": _cstats["n_compiles"],
        "compile_seconds": round(_cstats["compile_seconds"], 3),
        "compile_cache_hits": _cstats["cache_hits"],
        "persistent_cache_hits": _pstats["persistent_hits"],
        "persistent_cache_misses": _pstats["persistent_misses"],
        "requested_config": _requested_config(),
    }
    record.update(stamp)
    # graftscope: the census MFU number IS the telemetry MFU gauge —
    # one denominator (telemetry.PEAK_TFLOPS by device_kind), one
    # value, surfaced both as `pct_peak` here and as
    # cloud_tpu_mfu_pct_peak in the Prometheus textfile when a
    # telemetry session is live.
    _telemetry = sys.modules.get("cloud_tpu.monitoring.telemetry")
    if (_telemetry is not None and _telemetry.enabled()
            and record["pct_peak"] is not None):
        _tele = _telemetry.get()
        _tele.registry.gauge(_telemetry.MFU_GAUGE).set(
            record["pct_peak"])
        _tele.flush()
    if first_step_seconds is not None:
        record["time_to_first_step_seconds"] = round(first_step_seconds, 3)
    if compile_cache.is_enabled():
        record["compile_cache_dir"] = compile_cache.cache_dir()
    if os.environ.get("BENCH_WARM", "0") == "1":
        record["warm"] = True
    if xla_flops is not None:
        record["xla_flops_per_dispatch"] = xla_flops
    if spe > 1:
        record["steps_per_execution"] = spe
    if async_log:
        record["async_log"] = True
    if s2d:
        record["stem"] = "space_to_depth"
    if bf16_input:
        record["input_dtype"] = "bfloat16"
    if resident_mode:
        stats = runtime_lib.transfer_stats()
        record["resident"] = True
        record["resident_examples"] = resident.num_examples
        record["h2d_upload_bytes"] = resident.upload_bytes
        # The pipeline's whole claim, as a number: counted bytes past
        # the one-time upload (0 when the resident path holds).
        record["h2d_steady_bytes"] = (stats["h2d_bytes"]
                                      - resident.upload_bytes)
        record["h2d_transfers"] = stats["h2d_transfers"]
    # graftguard provenance: a record produced by a run that survived
    # faults is not the same measurement as a clean one — retries mean
    # the wall clock includes backoff and re-entry. Only stamped when
    # the resilience module is live AND saw at least one fault
    # (sys.modules.get keeps the common no-fault bench import-free).
    _resilience = sys.modules.get("cloud_tpu.training.resilience")
    if _resilience is not None:
        _gstats = _resilience.guard_stats()
        if _gstats["faults"]:
            record["guard_faults"] = _gstats["faults"]
            record["guard_retries"] = _gstats["retries"]
            record["guard_rollbacks"] = _gstats["rollbacks"]
            record["guard_last_fault"] = _gstats["last_fault"]
    print(json.dumps(record))


def main():
    stamp = _device_stamp()
    if (stamp["platform"] != "tpu" and os.environ.get(
            "JAX_PLATFORMS", "").strip().lower() != "cpu"):
        sys.exit(
            "bench.py measures on a TPU, and JAX's default backend is "
            "{platform!r} ({device_count} x {device_kind!r}). There is "
            "no fallback; for a CPU run of the pipeline say so with "
            "JAX_PLATFORMS=cpu (its records are stamped cpu).".format(
                **stamp))
    worker(stamp)


if __name__ == "__main__":
    main()
