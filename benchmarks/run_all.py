"""The BASELINE.md benchmark configs plus kernel benches, one JSON line each.

The driver-facing single-metric harness stays at the repo root
(`bench.py`, config 2 — the flagship). This suite covers the full
BASELINE.md table for local measurement:

1. MNIST Sequential-equivalent (models.MLP) via Trainer.fit
2. ResNet50 single-chip train step (same as bench.py)
3. Multi-device data-parallel LM step (pod-shape simulated on the
   available devices; real pods use the same code over jax.distributed)
4. Tuner trial loop (CloudTuner against an in-process oracle fake)
5. Custom-training-loop (user-managed jit step, the CTL escape hatch)
6. Pallas flash-attention kernel vs jnp reference (incl. masked path)
7. Ring attention (sp-sharded) vs single-device reference
8. Ulysses attention (same shape as 7 for row-to-row comparison)
9. Autoregressive generation: prefill + KV-cache decode tokens/sec

All configs run in THIS process, one after another: it holds the chip
and starts no child. Every record is stamped with the device JAX
reports; a CPU run is whatever `JAX_PLATFORMS=cpu` in the caller's
environment made it.

Usage: python benchmarks/run_all.py [config_numbers...]
"""

import json
import os
import sys
import time

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)


def _sync(out):
    """Barrier: `block_until_ready` waits on this machine (bench.py's
    sync(), PERF.md "Bring-up on the v5e")."""
    import jax
    jax.block_until_ready(out)


def _timed(fn, *args, reps=10):
    """Median-free simple timing: jit, warm once, time `reps` calls
    ending on one `_sync` barrier."""
    import jax
    f = jax.jit(fn)
    out = f(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args)
    _sync(out)
    return (time.perf_counter() - t0) / reps


def _bench_loop(step, state, batch, steps=20, warmup=3):
    for _ in range(warmup):
        state, out = step(state, batch)
    _sync(out)
    chunks = []
    for _ in range(max(steps // 5, 1)):
        t0 = time.perf_counter()
        for _ in range(5):
            state, out = step(state, batch)
        _sync(out)
        chunks.append((time.perf_counter() - t0) / 5)
    return sorted(chunks)[len(chunks) // 2]


def config1_mnist():
    import optax

    from cloud_tpu.models import MLP
    from cloud_tpu.training import Trainer

    B = 512
    rng = np.random.default_rng(0)
    x = rng.normal(size=(B, 28, 28)).astype(np.float32)
    y = rng.integers(0, 10, size=B).astype(np.int32)
    tr = Trainer(MLP(), optimizer=optax.adam(1e-3),
                 loss="sparse_categorical_crossentropy", metrics=())
    tr.build(x)
    step = tr._make_train_step()
    sec = _bench_loop(lambda s, b: step(s, b), tr.state,
                      tr._feed((x, y)))
    return {"metric": "mnist_mlp_steps_per_sec", "value": round(1 / sec, 2),
            "unit": "steps/sec", "batch": B}


def config2_resnet50():
    import optax

    from cloud_tpu.models import ResNet50
    from cloud_tpu.training import Trainer

    B = 256
    rng = np.random.default_rng(0)
    x = rng.normal(size=(B, 224, 224, 3)).astype(np.float32)
    y = rng.integers(0, 1000, size=B).astype(np.int32)
    tr = Trainer(ResNet50(num_classes=1000),
                 optimizer=optax.sgd(0.1, momentum=0.9),
                 train_kwargs={"train": True},
                 eval_kwargs={"train": False}, metrics=())
    tr.build(x)
    step = tr._make_train_step()
    sec = _bench_loop(lambda s, b: step(s, b), tr.state, tr._feed((x, y)))
    return {"metric": "resnet50_train_images_per_sec", "value":
            round(B / sec, 2), "unit": "images/sec", "batch": B}


def config3_dp_pod_shape():
    import jax
    import optax

    from cloud_tpu.models import TransformerLM
    from cloud_tpu.parallel import runtime
    from cloud_tpu.training import Trainer

    runtime.reset()
    runtime.initialize(strategy="tpu_slice", axis_names=("dp",))
    n = len(jax.devices())
    B = 8 * n
    model = TransformerLM(vocab_size=8192, num_layers=4, num_heads=8,
                          d_model=256, d_ff=1024, max_seq_len=256)
    import optax as _o

    def lm_loss(logits, labels):
        return _o.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean(axis=-1)

    tr = Trainer(model, optimizer=optax.adam(1e-3), loss=lm_loss,
                 metrics=())
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 8192, size=(B, 256)).astype(np.int32)
    tr.build(toks)
    step = tr._make_train_step()
    sec = _bench_loop(lambda s, b: step(s, b), tr.state,
                      tr._feed((toks, np.roll(toks, -1, 1))))
    runtime.reset()
    return {"metric": "lm_dp%d_tokens_per_sec" % n,
            "value": round(B * 256 / sec, 2), "unit": "tokens/sec",
            "devices": n}


def config4_tuner_loop():
    import optax

    from cloud_tpu.models import MLP
    from cloud_tpu.training import Trainer
    from cloud_tpu.tuner import CloudTuner, HyperParameters

    sys.path.insert(0, os.path.join(_REPO_ROOT, "examples"))
    from tuner_search import FakeVizier

    hps = HyperParameters()
    hps.Float("learning_rate", 1e-4, 1e-2, sampling="log")

    def build(hp):
        return Trainer(MLP(hidden=128),
                       optimizer=optax.adam(hp.get("learning_rate")),
                       loss="sparse_categorical_crossentropy", metrics=())

    rng = np.random.default_rng(0)
    x = rng.normal(size=(512, 28, 28)).astype(np.float32)
    y = rng.integers(0, 10, size=512).astype(np.int32)
    import tempfile
    t0 = time.perf_counter()
    tuner = CloudTuner(build, directory=tempfile.mkdtemp(),
                       project_id="bench", region="us-central1",
                       objective="accuracy", hyperparameters=hps,
                       max_trials=3, study_id="bench",
                       client=FakeVizier(hps))
    tuner.search(x=x, y=y, epochs=1, batch_size=128, verbose=False)
    elapsed = time.perf_counter() - t0
    return {"metric": "tuner_trials_per_min",
            "value": round(3 / (elapsed / 60), 2), "unit": "trials/min"}


def config5_ctl():
    import jax
    import jax.numpy as jnp
    import optax

    from cloud_tpu.models import MLP

    B = 512
    model = MLP()
    optimizer = optax.adam(1e-3)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, 28, 28)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=B), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x[:1])
    opt_state = optimizer.init(params)

    @jax.jit
    def step(carry, batch):
        params, opt_state = carry
        bx, by = batch

        def loss_fn(p):
            return optax.softmax_cross_entropy_with_integer_labels(
                model.apply(p, bx), by).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state), loss

    sec = _bench_loop(step, (params, opt_state), (x, y))
    return {"metric": "ctl_mnist_steps_per_sec",
            "value": round(1 / sec, 2), "unit": "steps/sec", "batch": B}


def config6_flash_attention():
    """Pallas flash kernel vs jnp reference wall-clock (a TPU timing
    for the compiled kernel, incl. the masked fast path)."""
    import jax
    import jax.numpy as jnp

    from cloud_tpu.ops import attention

    on_tpu = jax.default_backend() == "tpu"
    # Interpret-mode pallas on CPU is orders of magnitude slower than
    # compiled; keep CPU shapes tiny so the harness stays runnable
    # everywhere while TPU measures the real operating point.
    B, H, S, D = (8, 16, 2048, 64) if on_tpu else (1, 2, 256, 32)
    rng = np.random.default_rng(0)
    # Framework layout: [batch, seq, heads, head_dim].
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
               for _ in range(3))
    # Padded batch: last quarter of the keys invalid for half the
    # examples — exercises the per-example key-mask fast path.
    mask = np.ones((B, S), np.int32)
    mask[: B // 2, (3 * S) // 4:] = 0
    mask = jnp.asarray(mask)

    flash = _timed(lambda q, k, v: attention(q, k, v, causal=True,
                                             impl="flash"), q, k, v)
    ref = _timed(lambda q, k, v: attention(q, k, v, causal=True,
                                           impl="reference"), q, k, v)
    flash_masked = _timed(
        lambda q, k, v, m: attention(q, k, v, causal=True, mask=m,
                                     impl="flash"), q, k, v, mask)
    return {"metric": "flash_attention_speedup_vs_reference",
            "value": round(ref / flash, 2), "unit": "x",
            "flash_ms": round(flash * 1e3, 2),
            "flash_masked_ms": round(flash_masked * 1e3, 2),
            "reference_ms": round(ref * 1e3, 2),
            "shape": [B, H, S, D]}


def config7_ring_attention():
    """Ring attention (sequence parallelism over the sp axis) vs the
    single-device reference on the same global shape — records the
    memory-for-collectives trade.

    On the virtual CPU mesh the collectives are memcpys, so the
    speedup column is only meaningful on real ICI; the recorded value
    is primarily the wall-clock of the sp-sharded path itself.
    """
    import jax
    import jax.numpy as jnp

    from cloud_tpu.ops.attention import mha_reference
    from cloud_tpu.parallel import runtime
    from cloud_tpu.parallel.ring_attention import (
        sequence_parallel_attention)

    runtime.reset()
    n = len(jax.devices())
    sp = 4 if n % 4 == 0 else (2 if n % 2 == 0 else 1)
    runtime.initialize(strategy="tpu_slice",
                       axis_names=("dp", "sp"),
                       mesh_shape=(n // sp, sp))
    on_tpu = jax.default_backend() == "tpu"
    B, H, S, D = (2, 8, 8192, 64) if on_tpu else (2, 4, 1024, 32)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
               for _ in range(3))

    ring = _timed(lambda q, k, v: sequence_parallel_attention(
        q, k, v, causal=True), q, k, v)
    # mha_reference takes the same [B, S, H, D] layout.
    ref = _timed(lambda q, k, v: mha_reference(q, k, v, causal=True),
                 q, k, v)
    runtime.reset()
    return {"metric": "ring_attention_sp%d_ms" % sp,
            "value": round(ring * 1e3, 2), "unit": "ms",
            "single_device_reference_ms": round(ref * 1e3, 2),
            "shape": [B, H, S, D], "sp": sp}


def config8_ulysses_attention():
    """Ulysses (all-to-all) sequence parallelism on the same shape as
    config 7, so ring vs Ulysses is a direct row-to-row comparison.

    Like config 7, the collectives are memcpys on the virtual CPU mesh;
    on real ICI the all-to-all cost model (O(1) rounds vs ring's n-1
    rotations) is what this row exists to measure.
    """
    import jax
    import jax.numpy as jnp

    from cloud_tpu.ops.attention import mha_reference
    from cloud_tpu.parallel import runtime, ulysses_attention

    runtime.reset()
    n = len(jax.devices())
    sp = 4 if n % 4 == 0 else (2 if n % 2 == 0 else 1)
    runtime.initialize(strategy="tpu_slice",
                       axis_names=("dp", "sp"),
                       mesh_shape=(n // sp, sp))
    on_tpu = jax.default_backend() == "tpu"
    B, H, S, D = (2, 8, 8192, 64) if on_tpu else (2, 4, 1024, 32)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
               for _ in range(3))

    uly = _timed(lambda q, k, v: ulysses_attention(
        q, k, v, causal=True), q, k, v)
    ref = _timed(lambda q, k, v: mha_reference(q, k, v, causal=True),
                 q, k, v)
    runtime.reset()
    return {"metric": "ulysses_attention_sp%d_ms" % sp,
            "value": round(uly * 1e3, 2), "unit": "ms",
            "single_device_reference_ms": round(ref * 1e3, 2),
            "shape": [B, H, S, D], "sp": sp}


def config9_generate_decode():
    """Autoregressive generation: prefill + KV-cache decode steps.

    The round-2 verdict's gap: the decode path had tests but no number.
    Reports decode tokens/sec (the KV-cache-bound regime — decode
    attention is dense against the whole cache,
    models/transformer.py:_decode_attention) and the prefill time
    separately, since the two are different rooflines (prefill is
    MXU-bound matmuls, decode is HBM-bound cache reads).
    """
    import jax
    import jax.numpy as jnp

    from cloud_tpu.models import TransformerLM, generate

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        B, prompt_len, new_tokens = 8, 512, 128
        model = TransformerLM(vocab_size=32000, num_layers=12,
                              num_heads=12, d_model=768, d_ff=3072,
                              max_seq_len=prompt_len + new_tokens)
    else:
        # Long decode, short prompt: the decode signal must dominate
        # prefill timing noise for the subtraction below to be stable.
        B, prompt_len, new_tokens = 2, 16, 96
        model = TransformerLM(vocab_size=256, num_layers=2, num_heads=4,
                              d_model=64, d_ff=128,
                              max_seq_len=prompt_len + new_tokens,
                              compute_dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, model.vocab_size, size=(B, prompt_len)),
        jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), prompt)
    params = variables["params"]
    key = jax.random.PRNGKey(1)

    def run(n):
        out = generate(model, params, prompt, n, rng=key,
                       temperature=1.0)
        _sync(out)
        return out

    run(new_tokens)  # compile the full prefill + decode executables
    run(1)           # compile the prefill + single-sample variant

    def best_of(n, reps=3, run_fn=run):
        # min-of-N: the noise-robust latency estimator — a loaded host
        # once timed run(1) slower than run(new_tokens), producing an
        # absurd decode rate from the difference of two noisy numbers.
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            run_fn(n)
            best = min(best, time.perf_counter() - t0)
        return best

    # run(1) is prefill + one sampled token (generate(0) short-circuits
    # to the prompt without touching the model); the scan cost of the
    # remaining new_tokens - 1 steps is the decode-rate measurement.
    prefill_s = best_of(1)
    total_s = best_of(new_tokens)
    decode_s = total_s - prefill_s
    decode_tokens = new_tokens - 1
    record = {"metric": "generate_decode_tokens_per_sec",
              "unit": "tokens/sec",
              "batch": B, "prompt_len": prompt_len,
              "new_tokens": new_tokens,
              "prefill_plus_first_token_ms": round(prefill_s * 1e3, 2)}
    if decode_s < 1e-4:
        # Even min-of-N couldn't separate the two on this host: report
        # the failure instead of a differenced-noise number.
        record.update(value=0.0, error="decode time not separable "
                      "from prefill (noisy host?)")
        return record
    record.update(
        value=round(B * decode_tokens / decode_s, 1),
        decode_ms_per_token=round(decode_s * 1e3 / decode_tokens, 3))

    # Beam search on the same model: the device-resident scan loop
    # (models/beam.py — one dispatch + one fetch per generation, no
    # per-token host sync), W=4 hypotheses on the cache batch dim.
    # Same methodology as the decode metric above — prefill-subtracted
    # via a 1-token run — and explicitly batch 1 (beam_batch field):
    # the record's `batch` describes the greedy-decode rows only.
    from cloud_tpu.models import generate_beam

    beam_width = 4

    def run_beam(n):
        out, _ = generate_beam(model, params, prompt[:1], n,
                               beam_width=beam_width)
        _sync(out)

    run_beam(new_tokens)  # compile prefill + scan executables
    run_beam(1)           # compile the prefill-only variant

    beam_decode_s = (best_of(new_tokens, run_fn=run_beam)
                     - best_of(1, run_fn=run_beam))
    record.update(beam_width=beam_width, beam_batch=1)
    if beam_decode_s < 1e-4:
        record.update(beam_tokens_per_sec=0.0,
                      beam_error="beam decode time not separable "
                                 "from prefill (noisy host?)")
    else:
        record.update(beam_tokens_per_sec=round(
            (new_tokens - 1) / beam_decode_s, 1))
    return record




def config10_speculative_decode():
    """Speculative vs plain greedy decoding on the same target model.

    Measures the single-stream latency win of generate_speculative
    (models/speculative.py): a small draft proposes num_draft tokens,
    the target verifies them in one forward. Reports speculative
    tokens/sec with the plain-greedy rate and the speedup alongside —
    the output streams are token-identical (tested), so the speedup is
    the whole story.
    """
    import jax
    import jax.numpy as jnp

    from cloud_tpu.models import (TransformerLM, generate,
                                  generate_speculative)

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        prompt_len, new_tokens, num_draft = 128, 128, 4
        target = TransformerLM(vocab_size=32000, num_layers=12,
                               num_heads=12, d_model=768, d_ff=3072,
                               max_seq_len=prompt_len + new_tokens)
        draft = TransformerLM(vocab_size=32000, num_layers=2,
                              num_heads=12, d_model=768, d_ff=3072,
                              max_seq_len=prompt_len + new_tokens)
    else:
        prompt_len, new_tokens, num_draft = 16, 64, 4
        target = TransformerLM(vocab_size=256, num_layers=4,
                               num_heads=4, d_model=64, d_ff=128,
                               max_seq_len=prompt_len + new_tokens,
                               compute_dtype=jnp.float32)
        draft = TransformerLM(vocab_size=256, num_layers=1, num_heads=4,
                              d_model=64, d_ff=128,
                              max_seq_len=prompt_len + new_tokens,
                              compute_dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, target.vocab_size, size=(1, prompt_len)),
        jnp.int32)
    t_params = target.init(jax.random.PRNGKey(0), prompt)["params"]
    # An UNTRAINED random draft is the worst case for acceptance; a
    # distilled draft only improves the speedup. Self-drafting (same
    # weights) gives the best case; report both rates' inputs.
    d_params = draft.init(jax.random.PRNGKey(1), prompt)["params"]

    def plain():
        out = generate(target, t_params, prompt, new_tokens,
                       temperature=0.0)
        _sync(out)
        return np.asarray(out)

    def spec(dm, dp):
        out = generate_speculative(target, t_params, dm, dp, prompt,
                                   new_tokens, num_draft=num_draft)
        _sync(out)
        return np.asarray(out)

    plain_out = plain()                      # compile + reference
    spec_out = spec(draft, d_params)         # compile
    spec(target, t_params)                   # compile self-draft
    # Measured (not assumed) token parity: in bf16 a near-exact argmax
    # tie could differ between the chunked verification forward and
    # generate()'s single-token steps (models/speculative.py).
    match_fraction = float((plain_out == spec_out).mean())

    def spec_stochastic(dm, dp):
        # Leviathan accept/reject composing with temperature+top-p;
        # the committed stream is distributed as target-only sampling
        # (models/speculative.py), so the interesting numbers are the
        # rate and the measured acceptance.
        out, stats = generate_speculative(
            target, t_params, dm, dp, prompt, new_tokens,
            num_draft=num_draft, rng=jax.random.PRNGKey(0),
            temperature=0.8, top_p=0.95, return_stats=True)
        _sync(out)
        return stats

    stoch_stats = spec_stochastic(draft, d_params)     # compile
    stoch_self_stats = spec_stochastic(target, t_params)

    def best_of(fn, reps=3):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    plain_s = best_of(plain)
    spec_s = best_of(lambda: spec(draft, d_params))
    self_s = best_of(lambda: spec(target, t_params))
    stoch_s = best_of(lambda: spec_stochastic(draft, d_params))
    return {
        "metric": "speculative_decode_tokens_per_sec",
        "unit": "tokens/sec",
        "value": round(new_tokens / spec_s, 1),
        "plain_tokens_per_sec": round(new_tokens / plain_s, 1),
        "speedup_vs_plain": round(plain_s / spec_s, 3),
        "self_draft_tokens_per_sec": round(new_tokens / self_s, 1),
        "num_draft": num_draft, "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "token_match_vs_plain": round(match_fraction, 4),
        "stochastic_tokens_per_sec": round(new_tokens / stoch_s, 1),
        "stochastic_acceptance_rate": round(
            stoch_stats["acceptance_rate"], 4),
        "stochastic_self_draft_acceptance_rate": round(
            stoch_self_stats["acceptance_rate"], 4),
        "stochastic_sampling": "temperature=0.8 top_p=0.95",
        "note": "random (undistilled) draft = worst-case acceptance; "
                "self-draft row = acceptance upper bound",
    }

CONFIGS = {1: config1_mnist, 2: config2_resnet50, 3: config3_dp_pod_shape,
           4: config4_tuner_loop, 5: config5_ctl,
           6: config6_flash_attention, 7: config7_ring_attention,
           8: config8_ulysses_attention, 9: config9_generate_decode,
           10: config10_speculative_decode}


def main(argv):
    import jax

    device = jax.devices()[0]
    wanted = [int(a) for a in argv] or sorted(CONFIGS)
    for i in wanted:
        result = CONFIGS[i]()
        result.update(config=i, platform=device.platform,
                      device_kind=device.device_kind,
                      device_count=len(jax.devices()))
        print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1:])
