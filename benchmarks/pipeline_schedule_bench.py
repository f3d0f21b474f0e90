"""Pipeline schedule measurement: peak memory + step time vs num_microbatches.

The no-1F1B rationale in `cloud_tpu/models/pipelined.py`
("the checkpointed scan caps live activations; the bubble is
microbatch-bound either way") was asserted, not measured. This script
measures it:

- **Peak memory** from XLA's own compiled-buffer analysis
  (`jitted.lower(...).compile().memory_analysis()`): argument + output +
  temp + generated-code bytes per device. This is the allocator's
  liveness result, available on ANY backend — the CPU-mesh numbers
  already decide the scaling question (does peak activation memory grow
  with M?), and on TPU the same script yields the HBM numbers.
- **Step time** (value-fetch sync, median of chunks) when `--run` is
  given.

The 1F1B comparison point: 1F1B's documented advantage over GPipe is
peak activation memory — per device it keeps at most `n_stages`
microbatches' worth of live forward activations, while unrematerialized
GPipe keeps all `M`. The rationale claims GPipe + per-tick
`jax.checkpoint` already removes that advantage (live activations = one
tick's recompute window + the scan's carry checkpoints). If measured
peak memory is ~flat in M (the carry-checkpoint term (M+n-1)*mb*S*d is
batch-proportional and dtype-thin), the rationale holds and 1F1B would
buy only schedule complexity; if it grows steeply in M beyond the
batch-proportional term, the rationale is contradicted and 1F1B (or
interleaved scheduling) goes back on the table.

Usage (four chips, or four virtual CPU devices):
    python benchmarks/pipeline_schedule_bench.py [--run]
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python benchmarks/pipeline_schedule_bench.py [--run]

Prints one JSON line per (schedule, M) config.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

_CHIP_LOCK = None  # held for the process lifetime once acquired


def measure(pp_stages, num_micro, run_steps, batch, seq, d_model,
            vocab):
    import jax
    import jax.numpy as jnp
    import optax

    from cloud_tpu.models import PipelinedLM, pipelined_lm_rules
    from cloud_tpu.training import Trainer

    model = PipelinedLM(vocab_size=vocab, d_model=d_model,
                        num_heads=d_model // 64 or 2,
                        pp_stages=pp_stages, layers_per_stage=2,
                        max_seq_len=seq, num_microbatches=num_micro,
                        compute_dtype=jnp.bfloat16)
    trainer = Trainer((model.init, model.apply),
                      optimizer=optax.sgd(1e-2),
                      loss="sparse_categorical_crossentropy",
                      metrics=(),
                      param_sharding_rules=pipelined_lm_rules())
    rng = np.random.default_rng(0)
    x = rng.integers(0, vocab, size=(batch, seq)).astype(np.int32)
    y = rng.integers(0, vocab, size=(batch, seq)).astype(np.int32)
    trainer.build(x)
    step = trainer._make_train_step()
    batch_fed = trainer._feed((x, y))

    # XLA's compiled-buffer analysis: peak = what the allocator actually
    # reserves beyond the live arguments/outputs (the temp term is where
    # schedule-dependent activation liveness lands). Lower the jitted
    # step DIRECTLY so donation/shardings are the production ones — a
    # re-jit of the raw body would drop donate_argnums and measure a
    # different executable than the one timed below.
    compiled = step.lower(trainer.state, batch_fed).compile()
    mem = compiled.memory_analysis()
    record = {
        "schedule": "gpipe_remat",
        "pp_stages": pp_stages,
        "num_microbatches": num_micro,
        "batch": batch, "seq": seq, "d_model": d_model,
        "argument_mb": round(mem.argument_size_in_bytes / 2**20, 2),
        "output_mb": round(mem.output_size_in_bytes / 2**20, 2),
        "temp_mb": round(mem.temp_size_in_bytes / 2**20, 2),
        "code_mb": round(mem.generated_code_size_in_bytes / 2**20, 2),
        "platform": jax.default_backend(),
    }
    if run_steps:
        state = trainer.state
        state, logs = step(state, batch_fed)
        jax.block_until_ready(logs["loss"])
        times = []
        for _ in range(run_steps):
            t0 = time.perf_counter()
            state, logs = step(state, batch_fed)
            jax.block_until_ready(logs["loss"])
            times.append(time.perf_counter() - t0)
        record["step_ms"] = round(
            1e3 * sorted(times)[len(times) // 2], 1)
    print(json.dumps(record), flush=True)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--run", action="store_true",
                    help="also time steps (not just compile analysis)")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--pp", type=int, default=4)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--microbatches", type=int, nargs="+",
                    default=[4, 8, 16])
    args = ap.parse_args()

    from cloud_tpu.parallel import runtime

    records = []
    for m in args.microbatches:
        runtime.reset()
        runtime.initialize(strategy="tpu_slice", axis_names=("pp",),
                           mesh_shape=(args.pp,))
        try:
            records.append(measure(
                args.pp, m, args.steps if args.run else 0,
                args.batch, args.seq, args.d_model, args.vocab))
        finally:
            runtime.reset()
    # Scaling verdict: compare temp bytes at the M extremes after
    # removing the batch-proportional outputs/carry term (batch is
    # constant across M here, so any steep growth IS schedule overhead).
    if len(records) >= 2:
        records = sorted(records, key=lambda r: r["num_microbatches"])
        lo, hi = records[0], records[-1]
        growth = (hi["temp_mb"] / lo["temp_mb"]
                  if lo["temp_mb"] else float("inf"))
        print(json.dumps({
            "verdict": "temp_growth_{}x_from_M{}_to_M{}".format(
                round(growth, 2), lo["num_microbatches"],
                hi["num_microbatches"]),
            "rationale_holds": growth < 1.5,
        }), flush=True)


if __name__ == "__main__":
    main()
