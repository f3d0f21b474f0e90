"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

One process, no arguments, no subprocesses. It drives the two main
paths once through the entry points a user calls, at the published
widths of one model each (seeded random weights; serving depth is cut,
widths are not):

  device   JAX's default backend is a TPU, or exit non-zero.
  kernels  every Pallas kernel `auto` selects on a TPU, compiled,
           against its in-tree reference at the widths below.
  train    `runtime.initialize("tpu_slice")` over every local chip,
           then `Trainer(LlamaLM(...)).fit(...)` at Qwen2.5-0.5B widths.
  serve    `Scheduler(TransformerLM(...)).start()`, `warmup`, a few
           requests at GPT-2 XL widths, checked against `generate()`.

Each phase prints one line; any failed check raises, so the run cannot
reach exit code 0. The last line of standard output is the JSON result.
The timings printed are smoke observations (is it alive, did the cache
hit), not benchmark numbers. The phase functions take the widths as
arguments so tier-1 runs them at toy widths on the CPU
(tests/unit/test_chip_smoke.py); `main()` has no such switch.
"""

import json
import time

# Qwen2.5-0.5B as published (config.json of Qwen/Qwen2.5-0.5B): the
# train phase's model, all 24 layers.
QWEN25_05B = dict(
    vocab_size=151936, num_layers=24, num_heads=14, num_kv_heads=2,
    d_model=896, d_ff=4864, max_seq_len=1024, rope_theta=1e6,
    rope_style="rotate_half", qkv_bias=True, norm_eps=1e-6)

# GPT-2 XL as published (48 layers, 1600 wide, 25 heads of 64, context
# 1024) — the only family DecodeEngine accepts. Depth is cut to 12 so
# that warm-up's dozen prefill buckets compile inside the time limit;
# every width, the context and the page geometry are the deployed ones.
GPT2_XL = dict(
    vocab_size=50257, num_layers=12, num_heads=25, d_model=1600,
    d_ff=6400, max_seq_len=1024, norm_eps=1e-5)

PROMPT_LENGTHS = (32, 48, 100, 128, 250, 384, 511, 768)
NEW_TOKENS = 32

# Kernel-vs-reference bound, as max|got - want| / max|want|. bf16 keeps
# 8 significant bits (2**-8 = 0.4 %) and the two sides round at
# different points of a sum over up to 1024 keys or 4864 hidden units;
# a kernel that reads the wrong block is wrong by the whole magnitude.
BF16_TOL = 4e-2
# Greedy decode: where the reference's two best logits are closer than
# this share of the row's largest magnitude (4 bf16 ulps), the paged
# kernel (f32 probabilities, online softmax) and `generate()` (bf16
# probabilities, one softmax) may each pick either.
TIE_TOL = 2.0 ** -6


def _check(cond, message):
    if not cond:
        raise AssertionError(message)


def _rel_err(got, want):
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    _check(got.shape == want.shape,
           "shape {} != {}".format(got.shape, want.shape))
    _check(np.isfinite(got).all(), "non-finite kernel output")
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def device_phase():
    """The device stamp, as JAX reports it. Raises off-TPU."""
    from importlib import metadata

    import jax
    import jaxlib

    devices = jax.devices()
    stamp = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if stamp["platform"] != "tpu":
        raise RuntimeError(
            "chip_smoke needs a TPU; JAX's default backend is {!r} "
            "({} device(s)).".format(stamp["platform"], stamp["count"]))
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "unknown"
    print("device: platform={platform} device_kind={kind!r} "
          "count={count}".format(**stamp)
          + " jax={} jaxlib={} libtpu={}".format(
              jax.__version__, jaxlib.__version__, libtpu), flush=True)
    return stamp


def kernels_phase(train_widths, serve_widths, seq=1024, slots=8,
                  page_size=16, interpret=False, tol=BF16_TOL):
    """Each Pallas kernel against its reference at the two models'
    widths: flash fwd+bwd (causal, masked, the train model's GQA
    group), fused RMSNorm fwd+bwd, fused SwiGLU fwd, paged decode over
    bf16 and int8 pages for the plain tick and a verify window.
    `interpret` is for the CPU test only. Returns {kernel: worst
    relative error}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cloud_tpu import ops

    rng = np.random.default_rng(0)
    bf16 = jnp.bfloat16
    normal = lambda *shape, scale=1.0, dtype=bf16: jnp.asarray(
        rng.standard_normal(shape) * scale, dtype)
    f32sum = lambda tree: sum(
        jnp.sum(leaf.astype(jnp.float32))
        for leaf in jax.tree_util.tree_leaves(tree))
    errs = {}

    def compare(name, kernel, reference, args, diff_argnums):
        """Forward and (where asked) gradients of sum(outputs)."""
        worst = 0.0
        got = jax.jit(kernel)(*args)
        want = jax.jit(reference)(*args)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            worst = max(worst, _rel_err(g, w))
        if diff_argnums:
            grad = lambda fn: jax.jit(jax.grad(
                lambda *a: f32sum(fn(*a)), diff_argnums))
            for g, w in zip(grad(kernel)(*args), grad(reference)(*args)):
                worst = max(worst, _rel_err(g, w))
        errs[name] = worst

    # -- flash attention: the train model's heads, causal and masked.
    heads, kv_heads = train_widths["num_heads"], train_widths[
        "num_kv_heads"]
    head_dim = train_widths["d_model"] // heads
    q = normal(2, seq, heads, head_dim)
    k = normal(2, seq, kv_heads, head_dim)
    v = normal(2, seq, kv_heads, head_dim)
    key_mask = jnp.asarray(
        np.arange(seq)[None, :] < np.array([[seq], [seq - seq // 3]]))
    for name, mask in (("flash_causal_gqa{}".format(heads // kv_heads),
                        None), ("flash_masked", key_mask)):
        compare(
            name,
            lambda q, k, v: ops.flash_attention(
                q, k, v, causal=True, mask=mask, interpret=interpret),
            lambda q, k, v: ops.mha_reference(q, k, v, causal=True,
                                              mask=mask),
            (q, k, v), (0, 1, 2))

    # -- fused RMSNorm + residual at the train model's width.
    d_model = train_widths["d_model"]
    x = normal(2, seq, d_model)
    compare(
        "fused_rmsnorm",
        lambda x, r, s: ops.fused_rmsnorm(
            x, s, residual=r, eps=train_widths["norm_eps"],
            impl="fused", interpret=interpret),
        lambda x, r, s: ops.rmsnorm_residual_reference(
            x, s, residual=r, eps=train_widths["norm_eps"]),
        (x, normal(2, seq, d_model),
         1.0 + normal(d_model, scale=0.1, dtype=jnp.float32)),
        (0, 1, 2))

    # -- fused SwiGLU forward at the train model's MLP.
    d_ff = train_widths["d_ff"]
    w = lambda a, b: normal(a, b, scale=a ** -0.5, dtype=jnp.float32)
    compare(
        "fused_swiglu",
        lambda x, wg, wu, wd: ops.fused_swiglu(
            x, wg, wu, wd, compute_dtype=bf16, impl="fused",
            interpret=interpret),
        lambda x, wg, wu, wd: ops.swiglu_reference(
            x, wg, wu, wd, compute_dtype=bf16),
        (x, w(d_model, d_ff), w(d_model, d_ff), w(d_ff, d_model)), ())

    # -- paged decode at the serve model's heads and page geometry:
    # slots at staggered depths over a pool with one page to spare.
    heads = serve_widths["num_heads"]
    head_dim = serve_widths["d_model"] // heads
    cache_len = serve_widths["max_seq_len"]
    pages_per_slot = cache_len // page_size
    num_pages = slots * pages_per_slot + 1
    pool = (num_pages, page_size, heads * head_dim)
    page_table = jnp.asarray(
        1 + rng.permutation(slots * pages_per_slot).reshape(
            slots, pages_per_slot), jnp.int32)
    pools = (normal(*pool), normal(*pool))
    int8 = lambda: jnp.asarray(rng.integers(-127, 128, pool), jnp.int8)
    scales = lambda: jnp.asarray(
        rng.uniform(0.5, 1.5, (num_pages, heads)) / 127.0, jnp.float32)
    quantized = (int8(), int8(), scales(), scales())
    # The plain tick (one query row) and a verify window of four: row
    # i of a window sees the slot's depth less the rows after it.
    for window, tag in ((1, ""), (4, "_seq4")):
        depth = np.linspace(window, cache_len, slots).astype(int)
        upto = depth[:, None] - (window - 1) + np.arange(window)
        allowed = jnp.asarray(
            np.arange(cache_len)[None, None, :] < upto[:, :, None])
        q = normal(slots, window, heads, head_dim)
        paged_args = (q,) + pools + (page_table, allowed)
        if window == 1:
            tick_args = paged_args
        compare(
            "paged_bf16" + tag,
            lambda *a: ops.paged_decode_attention(*a,
                                                  interpret=interpret),
            ops.paged_attention_reference, paged_args, ())
        compare(
            "paged_int8" + tag,
            lambda q, kp, vp, pt, al, ks, vs: ops.paged_decode_attention(
                q, kp, vp, pt, al, interpret=interpret, key_scales=ks,
                value_scales=vs),
            lambda q, kp, vp, pt, al, ks, vs:
            ops.paged_attention_reference(
                q, kp, vp, pt, al, key_scales=ks, value_scales=vs),
            (q,) + quantized[:2] + (page_table, allowed)
            + quantized[2:], ())

    bad = sorted(k for k, v in errs.items() if not v <= tol)
    print("kernels: {} tol={:g} ".format("FAILED" if bad else "ok", tol)
          + " ".join("{}={:.2e}".format(k, v) for k, v in errs.items()),
          flush=True)
    _check(not bad, "kernels beyond the bound: {}".format(bad))
    if not interpret:
        # What was checked above is what `auto` dispatches to here.
        picks = {
            "attention": lambda: ops.attention(*tick_args[:1] * 3),
            "fused_rmsnorm": lambda: ops.fused_rmsnorm(
                x, jnp.ones((d_model,), jnp.float32)),
            "fused_swiglu": lambda: ops.fused_swiglu(
                x, *(jnp.zeros(s, bf16) for s in (
                    (d_model, 128), (d_model, 128), (128, d_model)))),
            "paged_attention": lambda: ops.paged_attention(*tick_args),
        }
        for name, call in picks.items():
            _check("pallas_call" in str(jax.make_jaxpr(call)()),
                   "impl='auto' did not select the {} kernel".format(
                       name))
    return errs


def train_phase(widths, batch_per_chip=2, seq=1024, steps=6,
                learning_rate=1e-3):
    """The generated runner's path (core/preprocess.py): ambient
    `tpu_slice` mesh over every local device, `Trainer.fit` on one
    repeated seeded batch. Returns the observations it printed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from cloud_tpu.models import LlamaLM
    from cloud_tpu.parallel import runtime
    from cloud_tpu.training import Trainer

    runtime.reset()
    mesh = runtime.initialize(strategy="tpu_slice").mesh
    n = mesh.size
    batch = batch_per_chip * n
    tokens = np.random.default_rng(1).integers(
        0, widths["vocab_size"], (batch, seq + 1)).astype(np.int32)
    x, y = tokens[:, :-1], tokens[:, 1:]
    trainer = Trainer(
        LlamaLM(compute_dtype=jnp.bfloat16, **widths),
        optimizer=optax.adamw(learning_rate), metrics=())

    def fit(epochs):
        # One step an epoch, so the history is the per-step loss.
        t0 = time.perf_counter()
        history = trainer.fit(x, y, epochs=epochs, batch_size=batch,
                              shuffle=False, verbose=False,
                              on_retrace="raise")
        jax.block_until_ready(trainer.state.params)
        return history["loss"], time.perf_counter() - t0

    # fit() would build lazily; built here so that the seeded init
    # (eager, op by op) is timed apart from the step's compile.
    t0 = time.perf_counter()
    jax.block_until_ready(trainer.build(x).params)
    build_s = time.perf_counter() - t0
    first, compile_s = fit(1)
    rest, steady_s = fit(steps - 1)
    losses = [float(v) for v in list(first) + list(rest)]
    _check(np.isfinite(losses).all(), "loss not finite: {}".format(
        losses))
    _check(losses[-1] < losses[0],
           "loss did not fall on a repeated batch: {}".format(losses))
    step = trainer._jit_train_step
    _check(step.n_traces == 1,
           "train step traced {} times".format(step.n_traces))

    # Every parameter and the batch on every chip, the batch split.
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            trainer.state.params)[0]:
        _check(leaf.sharding.device_set == set(mesh.devices.flat),
               "param {} lives on {} of {} devices".format(
                   jax.tree_util.keystr(path),
                   len(leaf.sharding.device_set), n))
    fed = trainer._feed((x, y))
    shard_rows = sorted(s.data.shape[0]
                        for s in fed[0].addressable_shards)
    _check(len(fed[0].sharding.device_set) == n
           and shard_rows == [batch_per_chip] * n,
           "batch rows per device {} (want {} x {})".format(
               shard_rows, n, batch_per_chip))
    all_reduces = None
    if n > 1:
        # The gradient all-reduce, as the compiler placed it.
        hlo = step.lower(trainer.state, fed).compile().as_text()
        all_reduces = hlo.count(" all-reduce(") + hlo.count(
            " all-reduce-start(")
        _check(all_reduces > 0, "no all-reduce in the dp={} step".format(
            n))
    stats = jax.local_devices()[0].memory_stats() or {}
    out = {"mesh": dict(mesh.shape), "losses": losses,
           "build_s": build_s, "compile_s": compile_s,
           "step_s": steady_s / (steps - 1),
           "peak_bytes": stats.get("peak_bytes_in_use"),
           "all_reduces": all_reduces}
    print("train: ok mesh={} batch={}x{} rows/chip={} loss {:.4f}->{:.4f}"
          " traces=1 all_reduce_ops={} build_s={:.1f} compile_s={:.1f} "
          "step_s={:.3f} peak_bytes_in_use={}".format(
              dict(mesh.shape), batch, seq, batch_per_chip, losses[0],
              losses[-1], all_reduces, build_s, compile_s,
              out["step_s"], out["peak_bytes"]), flush=True)
    runtime.reset()
    return out


def serve_phase(widths, slots=8, page_size=16,
                prompt_lengths=PROMPT_LENGTHS, new_tokens=NEW_TOKENS,
                tie_tol=TIE_TOL):
    """The server on one chip: start, warm up, serve the prompts
    greedily, then hold every request's tokens against `generate()`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cloud_tpu.models import TransformerLM, generate
    from cloud_tpu.models.decoding import bucket_length
    from cloud_tpu.parallel import runtime
    from cloud_tpu.serving import Scheduler, ServeRequest

    runtime.reset()  # no ambient mesh: the server runs on one chip
    model = TransformerLM(compute_dtype=jnp.bfloat16, **widths)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, widths["vocab_size"], n).tolist()
               for n in prompt_lengths]

    t0 = time.perf_counter()
    scheduler = Scheduler(model, params, slots=slots,
                          page_size=page_size, strict_no_retrace=True)
    try:
        scheduler.start()
        scheduler.warmup(sorted({bucket_length(n, widths["max_seq_len"])
                                 for n in prompt_lengths}))
        warm_s = time.perf_counter() - t0
        # The tick donates the cache; the weights say where it runs.
        device = next(iter(jax.tree_util.tree_leaves(
            params)[0].devices()))
        t0 = time.perf_counter()
        futures = [scheduler.submit(ServeRequest(
            prompt=p, max_new_tokens=new_tokens, temperature=0.0))
            for p in prompts]
        served = [np.asarray(f.result(timeout=600).tokens)
                  for f in futures]
        serve_s = time.perf_counter() - t0
        scheduler.engine.check_no_retrace()
        scheduler.assert_drained()
    finally:
        scheduler.close()

    # Full-context reference logits for arbitrating a first mismatch
    # (causal, so right padding does not reach earlier rows).
    context = widths["max_seq_len"]
    forward = jax.jit(lambda params, tokens: model.apply(
        {"params": params}, tokens))

    def reference_logits(prefix):
        padded = np.zeros((1, context), np.int32)
        padded[0, :len(prefix)] = prefix
        return np.asarray(forward(params, jnp.asarray(padded))[
            0, len(prefix) - 1])

    ties = 0
    for prompt, tokens in zip(prompts, served):
        _check(tokens.shape == (len(prompt) + new_tokens,),
               "request of {} tokens returned shape {}".format(
                   len(prompt), tokens.shape))
        _check(tokens[:len(prompt)].tolist() == prompt,
               "prompt not echoed")
        want = np.asarray(generate(
            model, params, jnp.asarray([prompt], jnp.int32), new_tokens,
            temperature=0.0))[0]
        differ = np.nonzero(tokens != want)[0]
        if differ.size:
            # Equal up to the first difference; there the reference's
            # logits for the two tokens must be a tie (TIE_TOL). Past a
            # tie the continuations are different sequences.
            at = int(differ[0])
            row = reference_logits(tokens[:at])
            gap = abs(float(row[tokens[at]]) - float(row[want[at]]))
            bound = tie_tol * float(np.max(np.abs(row)))
            _check(gap <= bound,
                   "prompt {}: token {} is {} but generate() gives {}; "
                   "logit gap {:.4g} > tie bound {:.4g}".format(
                       len(prompt), at - len(prompt), tokens[at],
                       want[at], gap, bound))
            ties += 1
    out = {"device": str(device), "warmup_s": warm_s,
           "serve_s": serve_s, "ties": ties}
    print("serve: ok device={} requests={} prompts={} new_tokens={} "
          "greedy==generate() on {} of {} (rest split at a logit tie "
          "<= {:g} of max|logit|) traces_after_warmup=0 drained "
          "warmup_s={:.1f} serve_s={:.2f}".format(
              device, len(prompts), list(prompt_lengths), new_tokens,
              len(prompts) - ties, len(prompts), tie_tol, warm_s,
              serve_s), flush=True)
    return out


def main():
    # First, so that a directory without the package fails before
    # anything is printed.
    from cloud_tpu.parallel import compile_cache

    stamp = device_phase()
    cache_dir = compile_cache.enable()
    kernels_phase(QWEN25_05B, GPT2_XL)
    train_phase(QWEN25_05B)
    serve_phase(GPT2_XL)
    cache = compile_cache.stats()
    print("compile_cache: dir={} persistent_hits={} "
          "persistent_misses={}".format(
              cache_dir, cache["persistent_hits"],
              cache["persistent_misses"]), flush=True)
    print(json.dumps({"ok": True, "device": stamp}), flush=True)


if __name__ == "__main__":
    main()
