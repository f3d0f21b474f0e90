"""Decoder-only Transformer LM with tensor/sequence-parallel layouts.

The long-context/model-parallel flagship (absent from the reference, which
stops at data parallelism — SURVEY §2.3; built here because a pjit mesh
makes TP/SP natural extension points). Design is MXU/ICI-first:

- All matmuls batched and bfloat16; params float32.
- Megatron-style tensor parallelism expressed as sharding *rules* over
  the ambient mesh (qkv/mlp-in kernels split on "tp" columns, proj/mlp-out
  on "tp" rows), so XLA inserts exactly the two all-reduces per block.
- Causal attention with static shapes; `cloud_tpu.ops` provides the
  flash/pallas path and `cloud_tpu.parallel.ring_attention` the
  sequence-parallel path for long context.
"""

import functools
from typing import Optional

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from cloud_tpu.parallel import SEQUENCE_PARALLEL_IMPLS
from cloud_tpu.parallel import runtime


class CausalSelfAttention(nn.Module):
    num_heads: int
    compute_dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"  # auto | flash | reference | ring | ulysses
    decode: bool = False  # autoregressive KV-cache mode
    cache_len: int = 0  # cache size (tokens); set by TransformerLM
    causal: bool = True  # False = bidirectional (encoder) attention
    # Paged-pool decode (serving): > 0 swaps the per-example dense cache
    # for a shared physical page pool with per-slot page tables and
    # per-slot write pointers (continuous batching; serving/kvpool.py).
    page_size: int = 0
    num_pages: int = 0
    # "" = pages in compute_dtype; "int8" = quantized pages with
    # per-page per-head f32 scales (key_scales/value_scales cache
    # variables), dequantized inside ops.paged_attention's block loads.
    page_dtype: str = ""

    @nn.compact
    def __call__(self, x, mask=None):
        from cloud_tpu import ops

        d_model = x.shape[-1]
        head_dim = d_model // self.num_heads
        dense = lambda feats, name: nn.DenseGeneral(
            feats, axis=-1, dtype=self.compute_dtype, name=name)

        # [B, S, H, D] per-head projections.
        q = dense((self.num_heads, head_dim), "query")(x)
        k = dense((self.num_heads, head_dim), "key")(x)
        v = dense((self.num_heads, head_dim), "value")(x)

        if self.decode:
            # mask (optional [B, S]) marks REAL incoming tokens — the
            # left-padded-prompt contract (generate(prompt_mask=)).
            if self.page_size:
                out = self._paged_decode_attention(q, k, v, mask)
            else:
                out = self._decode_attention(q, k, v, mask)
        elif self.attention_impl in SEQUENCE_PARALLEL_IMPLS:
            # Sequence-parallel long-context paths over the mesh's "sp"
            # axis: "ring" rotates K/V around a ppermute ring
            # (parallel/ring_attention.py); "ulysses" all-to-alls into
            # head-sharded full-sequence layout and runs the flash
            # kernel (parallel/ulysses.py).
            from cloud_tpu.parallel import sp_attention
            out = sp_attention(self.attention_impl, q, k, v,
                               causal=self.causal, mask=mask)
        else:
            # "auto" uses the Pallas flash kernel on TPU, the jnp
            # reference elsewhere; direction follows self.causal
            # (False = bidirectional encoder attention), scale
            # 1/sqrt(D).
            out = ops.attention(q, k, v, causal=self.causal, mask=mask,
                                impl=self.attention_impl)
        out = out.astype(self.compute_dtype)
        return nn.DenseGeneral(d_model, axis=(-2, -1),
                               dtype=self.compute_dtype, name="out")(out)

    def _decode_attention(self, q, k, v, mask=None):
        """KV-cache attention: append this call's K/V to the cache, then
        attend q against everything cached so far.

        One code path serves both phases of generation: prefill (the
        whole prompt in one call, cache index 0) and single-token decode
        steps (S=1). Causality is slot order (append-only writes);
        `slot_valid` excludes left-padded prompt slots (mask=0) and the
        never-written tail. O(cache_len) work per step — the standard
        autoregressive trade.
        """
        import jax.lax as lax

        from cloud_tpu.models.decoding import decode_slot_update

        batch, seq, heads, head_dim = q.shape
        if not self.cache_len:
            raise ValueError("decode=True needs cache_len > 0.")
        cached_k = self.variable(
            "cache", "cached_key", jnp.zeros,
            (batch, self.cache_len, heads, head_dim), self.compute_dtype)
        cached_v = self.variable(
            "cache", "cached_value", jnp.zeros,
            (batch, self.cache_len, heads, head_dim), self.compute_dtype)

        idx, _, allowed = decode_slot_update(
            self, mask, batch, seq, self.cache_len)
        cached_k.value = lax.dynamic_update_slice(
            cached_k.value, k.astype(self.compute_dtype), (0, idx, 0, 0))
        cached_v.value = lax.dynamic_update_slice(
            cached_v.value, v.astype(self.compute_dtype), (0, idx, 0, 0))
        scale = 1.0 / np.sqrt(head_dim)
        # f32 MXU accumulation, like every training attention path —
        # bf16 logits would round before the argmax/softmax.
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, cached_k.value,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(allowed[:, None], logits, -1e30)
        weights = nn.softmax(logits, axis=-1).astype(self.compute_dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, cached_v.value)

    def _paged_decode_attention(self, q, k, v, mask=None):
        """Decode over the paged KV pool (continuous batching): the
        write of this call's K/V rows and the read through
        `ops.paged_attention`, shared with `GQAttention`
        (`decoding.paged_kv_attention` holds the contract). Per-slot
        math is EXACTLY `_decode_attention`'s per-row math over the
        gathered logical view, which is what makes engine tokens
        bit-identical to solo `generate()` — see
        tests/unit/test_serving.py."""
        from cloud_tpu.models.decoding import paged_kv_attention
        return paged_kv_attention(
            self, q, k, v, mask, cache_len=self.cache_len,
            page_size=self.page_size, num_pages=self.num_pages,
            page_dtype=self.page_dtype, store_dtype=self.compute_dtype,
            sm_scale=1.0 / np.sqrt(q.shape[-1]),
            impl=self.attention_impl)


class TransformerBlock(nn.Module):
    num_heads: int
    d_ff: int
    dropout_rate: float = 0.0
    compute_dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    moe_experts: int = 0  # > 0 swaps the dense MLP for a Switch MoE
    decode: bool = False
    cache_len: int = 0
    causal: bool = True
    norm_eps: float = 1e-6  # GPT-2 checkpoints use 1e-5
    page_size: int = 0  # paged-pool decode (serving); see attention
    num_pages: int = 0
    page_dtype: str = ""

    @nn.compact
    def __call__(self, x, mask=None, deterministic=True):
        y = nn.LayerNorm(epsilon=self.norm_eps,
                         dtype=self.compute_dtype, name="ln_attn")(x)
        y = CausalSelfAttention(self.num_heads, self.compute_dtype,
                                self.attention_impl,
                                decode=self.decode,
                                cache_len=self.cache_len,
                                causal=self.causal,
                                page_size=self.page_size,
                                num_pages=self.num_pages,
                                page_dtype=self.page_dtype,
                                name="attention")(y, mask)
        if self.dropout_rate:
            y = nn.Dropout(self.dropout_rate)(y, deterministic=deterministic)
        x = x + y

        y = nn.LayerNorm(epsilon=self.norm_eps,
                         dtype=self.compute_dtype, name="ln_mlp")(x)
        if self.moe_experts:
            from cloud_tpu.models.moe import MoEMLP
            y, aux_loss = MoEMLP(num_experts=self.moe_experts,
                                 d_ff=self.d_ff,
                                 compute_dtype=self.compute_dtype,
                                 name="moe")(y, deterministic)
            # Surfaced via mutable=["losses"]; summed into the training
            # loss by Trainer when present.
            self.sow("losses", "moe_aux_loss", aux_loss,
                     reduce_fn=lambda a, b: a + b, init_fn=lambda: 0.0)
        else:
            y = nn.Dense(self.d_ff, dtype=self.compute_dtype,
                         name="mlp_in")(y)
            y = nn.gelu(y)
            y = nn.Dense(x.shape[-1], dtype=self.compute_dtype,
                         name="mlp_out")(y)
        if self.dropout_rate:
            y = nn.Dropout(self.dropout_rate)(y, deterministic=deterministic)
        return x + y


class TransformerLM(nn.Module):
    """GPT-style decoder-only language model."""

    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    d_model: int = 512
    d_ff: int = 2048
    max_seq_len: int = 2048
    dropout_rate: float = 0.0
    compute_dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    moe_experts: int = 0
    decode: bool = False  # autoregressive KV-cache mode (see generate())
    norm_eps: float = 1e-6  # GPT-2 checkpoints use 1e-5
    # Paged-pool decode (serving/engine.py): kv_page_size > 0 swaps the
    # dense per-example cache for the shared page pool with per-slot
    # page tables (requires decode=True; batch dim becomes slots).
    kv_page_size: int = 0
    kv_num_pages: int = 0
    kv_page_dtype: str = ""  # "int8" = quantized pages (graftpack)

    @nn.compact
    def __call__(self, tokens, mask=None, deterministic=True):
        seq = tokens.shape[1]
        if seq > self.max_seq_len:
            raise ValueError(
                "Sequence length {} exceeds max_seq_len {}.".format(
                    seq, self.max_seq_len))
        x = nn.Embed(self.vocab_size, self.d_model,
                     dtype=self.compute_dtype, name="embed")(tokens)
        if self.decode:
            # Per-example LOGICAL positions (only real tokens count),
            # so left-padded prompts look up the same position rows as
            # their unpadded equivalents; padded entries reuse row 0
            # harmlessly (their slots are never attended).
            batch = tokens.shape[0]
            pos_count = self.variable("cache", "pos_count",
                                      jnp.zeros, (batch,), jnp.int32)
            m = (jnp.ones((batch, seq), jnp.int32) if mask is None
                 else mask.astype(jnp.int32))
            positions = pos_count.value[:, None] + jnp.cumsum(m, 1) - m
            pos_count.value = pos_count.value + m.sum(axis=1)
        else:
            positions = jnp.arange(seq)[None, :]
        pos = nn.Embed(self.max_seq_len, self.d_model,
                       dtype=self.compute_dtype,
                       name="pos_embed")(positions)
        x = x + pos
        for i in range(self.num_layers):
            x = TransformerBlock(self.num_heads, self.d_ff,
                                 self.dropout_rate, self.compute_dtype,
                                 self.attention_impl, self.moe_experts,
                                 decode=self.decode,
                                 cache_len=self.max_seq_len,
                                 norm_eps=self.norm_eps,
                                 page_size=self.kv_page_size,
                                 num_pages=self.kv_num_pages,
                                 page_dtype=self.kv_page_dtype,
                                 name="block_%d" % i)(
                                     x, mask, deterministic)
        x = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.compute_dtype,
                         name="ln_final")(x)
        # Tied-free output head; vocab dim sharded on tp by the rules.
        logits = nn.Dense(self.vocab_size, use_bias=False,
                          dtype=self.compute_dtype, name="lm_head")(x)
        return logits.astype(jnp.float32)

    def promoted_at_use(self, path):
        """Whether the module that owns the parameter at `path` (its
        keys below "params", e.g. `("block_0", "mlp_in", "kernel")`)
        casts it to `compute_dtype` at every use, so that a holder who
        only applies the model may cast it once and keep that: the
        `nn.Dense` / `nn.DenseGeneral` kernels and biases and the
        `nn.Embed` tables (flax's `promote_dtype`), and `MoEMLP`'s
        expert stacks (its own `astype`). `nn.LayerNorm` multiplies by
        its scale and adds its bias in float32 and `MoEMLP` routes in
        float32, so those are used as given. The serving engine asks
        (`serving/engine.py` `held_params`); `__call__`, `generate()`
        and `Trainer` keep the tree they are handed.
        tests/unit/test_served_params.py holds the rule to the logits,
        bit for bit."""
        return not (path[-2].startswith("ln_") or path[-1] == "router")


class TransformerEncoder(nn.Module):
    """BERT-style bidirectional encoder.

    The encoder counterpart of TransformerLM (same blocks, same tp
    sharding rules, bidirectional attention): per-token hidden states,
    or a pooled classification / masked-LM head.

    head: None -> [B, S, d_model] hidden states;
          "classify" -> [B, num_classes] (masked-mean pooled);
          "mlm" -> [B, S, vocab_size] token logits.
    mask: optional [B, S] validity mask (1 = real token). Padding is
        excluded from attention keys AND from the classify pooling.
    """

    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    d_model: int = 512
    d_ff: int = 2048
    max_seq_len: int = 512
    num_classes: int = 2
    head: Optional[str] = "classify"
    dropout_rate: float = 0.0
    compute_dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"

    @nn.compact
    def __call__(self, tokens, mask=None, deterministic=True):
        seq = tokens.shape[1]
        if seq > self.max_seq_len:
            raise ValueError(
                "Sequence length {} exceeds max_seq_len {}.".format(
                    seq, self.max_seq_len))
        if self.head not in (None, "classify", "mlm"):
            raise ValueError("Unknown head: {!r}".format(self.head))
        x = nn.Embed(self.vocab_size, self.d_model,
                     dtype=self.compute_dtype, name="embed")(tokens)
        pos = nn.Embed(self.max_seq_len, self.d_model,
                       dtype=self.compute_dtype,
                       name="pos_embed")(jnp.arange(seq)[None, :])
        x = x + pos
        for i in range(self.num_layers):
            x = TransformerBlock(self.num_heads, self.d_ff,
                                 self.dropout_rate, self.compute_dtype,
                                 self.attention_impl, causal=False,
                                 name="block_%d" % i)(
                                     x, mask, deterministic)
        x = nn.LayerNorm(dtype=self.compute_dtype, name="ln_final")(x)
        if self.head is None:
            return x.astype(jnp.float32)
        if self.head == "mlm":
            logits = nn.Dense(self.vocab_size, use_bias=False,
                              dtype=self.compute_dtype,
                              name="lm_head")(x)
            return logits.astype(jnp.float32)
        # Pool in f32: bf16 can't count >256 valid tokens exactly, and
        # summing hundreds of tokens in bf16 rounds the features.
        xf = x.astype(jnp.float32)
        if mask is not None:
            m = mask.astype(jnp.float32)[:, :, None]
            pooled = jnp.sum(xf * m, axis=1) / jnp.maximum(
                jnp.sum(m, axis=1), 1.0)
        else:
            pooled = jnp.mean(xf, axis=1)
        logits = nn.Dense(self.num_classes, dtype=self.compute_dtype,
                          name="classifier")(pooled.astype(
                              self.compute_dtype))
        return logits.astype(jnp.float32)


def generate(model,
             params,
             prompt,
             max_new_tokens,
             rng=None,
             temperature=1.0,
             top_k=None,
             top_p=None,
             eos_token=None,
             prompt_mask=None,
             bucket_prompts=True):
    """Autoregressive sampling with a KV cache.

    The inference counterpart of Trainer.fit for `TransformerLM` (no
    reference equivalent — the reference delegates inference to Keras).
    XLA-friendly by construction: one jitted prefill call over the
    whole prompt, then a `lax.scan` of single-token steps over a
    static-size cache, so the whole generation compiles to two
    executables regardless of length.

    Args:
        model: A `TransformerLM` (decode=False training instance; a
            decode clone is derived internally).
        params: The trained "params" pytree.
        prompt: [B, S] int32 prompt tokens (S >= 1).
        max_new_tokens: How many tokens to sample beyond the prompt.
        rng: PRNGKey for sampling; required unless temperature == 0.
        temperature: 0 = greedy argmax; otherwise softmax temperature.
        top_k: Optional truncation to the k highest-probability tokens.
        top_p: Optional nucleus sampling: keep the smallest
            highest-probability set whose cumulative probability
            reaches top_p (computed after temperature and any top_k
            truncation, the HF warper order). (0, 1]; 1.0 = no-op.
        eos_token: Optional stop token: positions after a sampled eos
            are filled with eos_token.
        prompt_mask: Optional [B, S] bool marking REAL prompt tokens —
            the variable-length-batch contract. Prompts must be
            LEFT-padded (every example's last column real): padded
            slots are never attended, and positions (learned table or
            RoPE) count only real tokens, so each row generates
            exactly as its unpadded equivalent would.
        bucket_prompts: Pad the prompt LEFT to the next power-of-two
            bucket (capped at `max_seq_len - max_new_tokens`) before
            prefill, so varied prompt lengths share executables
            instead of minting one per length. The left-padded-mask
            contract makes the padding output-invisible; the returned
            array keeps the ORIGINAL prompt width. False = compile at
            the exact prompt length.

    Returns:
        [B, S + max_new_tokens] int32: prompt + generated continuation
        (left-padded rows keep their padding in the prompt columns).
    """
    import jax

    if model.attention_impl in SEQUENCE_PARALLEL_IMPLS:
        raise NotImplementedError(
            "generate() decodes on a single mesh shard; use a "
            "non-sequence-parallel attention_impl for inference.")
    batch, prompt_len = prompt.shape
    if max_new_tokens < 0:
        raise ValueError("max_new_tokens must be >= 0; got {}.".format(
            max_new_tokens))
    if max_new_tokens == 0:
        return prompt
    total = prompt_len + max_new_tokens
    if total > model.max_seq_len:
        raise ValueError(
            "prompt ({}) + max_new_tokens ({}) exceeds max_seq_len {}."
            .format(prompt_len, max_new_tokens, model.max_seq_len))
    if temperature and rng is None:
        raise ValueError("Sampling (temperature > 0) needs `rng`.")
    if top_k is not None and not 1 <= top_k <= model.vocab_size:
        raise ValueError(
            "top_k must be in [1, vocab_size={}]; got {}.".format(
                model.vocab_size, top_k))
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(
            "top_p must be in (0, 1]; got {}.".format(top_p))
    if prompt_mask is not None:
        from cloud_tpu.models.decoding import validate_prompt_mask
        validate_prompt_mask(prompt_mask, batch, prompt_len, "sampling")
    if rng is None:
        rng = jax.random.PRNGKey(0)

    from cloud_tpu.models.decoding import (acquire_cache, bucket_length,
                                           release_cache)

    decoder = model.clone(decode=True, dropout_rate=0.0)
    # Reuse pool, not a fresh HBM allocation per call: a parked cache
    # from a previous generate() is re-zeroed in place when available.
    cache = acquire_cache(decoder, batch)

    prefill, decode_steps = _decode_fns(
        decoder, float(temperature),
        None if top_k is None else int(top_k),
        None if top_p is None else float(top_p),
        None if eos_token is None else int(eos_token))

    rng, prefill_rng = jax.random.split(rng)
    mask_arg = (None if prompt_mask is None
                else jnp.asarray(prompt_mask, bool))
    prefill_tokens = prompt
    if bucket_prompts:
        # Left-pad to the bucket; the mask keeps padded slots out of
        # attention and position counting, so outputs match the
        # unbucketed call exactly. The final concatenate below uses the
        # ORIGINAL prompt, so the extra columns never escape.
        bucket = bucket_length(prompt_len,
                               model.max_seq_len - max_new_tokens)
        if bucket > prompt_len:
            pad = bucket - prompt_len
            prefill_tokens = jnp.pad(prompt, ((0, 0), (pad, 0)))
            real = (jnp.ones((batch, prompt_len), bool)
                    if mask_arg is None else mask_arg)
            mask_arg = jnp.pad(real, ((0, 0), (pad, 0)))
    from cloud_tpu.models.decoding import (decode_latency_finish,
                                           decode_latency_start)

    latency = decode_latency_start()
    cache, first = prefill(params, cache, prefill_tokens, prefill_rng,
                           mask_arg)
    out = [first[:, None]]
    if max_new_tokens > 1:
        cache, toks = decode_steps(
            params, cache, first,
            jax.random.split(rng, max_new_tokens - 1))
        out.append(jnp.transpose(toks, (1, 0)))
    result = jnp.concatenate([prompt] + out, axis=1)
    # Park the final cache for the next call's acquire (its contents
    # are dead weight; the acquire re-zeros it in place).
    release_cache(decoder, batch, cache)
    decode_latency_finish(latency, max_new_tokens, result)
    return result


@functools.lru_cache(maxsize=64)
def _decode_fns(decoder, temperature, top_k, top_p, eos_token):
    """Jitted (prefill, decode_steps) for one decoder/sampling config.

    Cached so repeated generate() calls reuse the compiled executables
    (jit keys on function identity; a fresh closure per call would
    re-trace every time). params/cache/tokens are arguments, not
    captured constants, so one compilation serves any weights of the
    same shapes; distinct prompt lengths or scan lengths still compile
    their own specializations, as they must under static shapes.
    """
    import jax

    def sample(logits, rng):
        logits = logits.astype(jnp.float32)
        if not temperature:
            # top-k/top-p never change the argmax; greedy skips them.
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # Shared warper (models/decoding.py): top-k → temperature →
        # top-p with sorted-order nucleus membership, the exact
        # distribution the speculative accept/reject math targets.
        from cloud_tpu.models.decoding import warp_logits
        warped = warp_logits(logits, temperature, top_k, top_p)
        return jax.random.categorical(rng, warped,
                                      axis=-1).astype(jnp.int32)

    # donate_argnums=1: the caller never reuses the passed-in cache
    # (prefill gets the fresh empty cache; decode_steps consumes
    # prefill's), so XLA can update the KV buffers in place instead of
    # holding two cache-sized allocations across the call.
    @functools.partial(runtime.instrumented_jit, donate_argnums=1)
    def prefill(params, cache, prompt, rng, prompt_mask=None):
        logits, vars_ = decoder.apply({"params": params, "cache": cache},
                                      prompt, prompt_mask,
                                      mutable=["cache"])
        return vars_["cache"], sample(logits[:, -1], rng)

    @functools.partial(runtime.instrumented_jit, donate_argnums=1)
    def decode_steps(params, cache, first_token, step_rngs):
        def step(carry, step_rng):
            cache, tok, done = carry
            logits, vars_ = decoder.apply(
                {"params": params, "cache": cache}, tok[:, None],
                mutable=["cache"])
            nxt = sample(logits[:, 0], step_rng)
            if eos_token is not None:
                nxt = jnp.where(done, eos_token, nxt)
                done = done | (nxt == eos_token)
            return (vars_["cache"], nxt, done), nxt

        done = (first_token == eos_token) if eos_token is not None \
            else jnp.zeros(first_token.shape, bool)
        (cache, _, _), toks = jax.lax.scan(
            step, (cache, first_token, done), step_rngs)
        # The final cache rides back out so generate() can park it in
        # the reuse pool (donation aliases it over the input buffers).
        return cache, toks  # toks: [T-1, B]

    from cloud_tpu.models.decoding import best_effort_donation
    return best_effort_donation(prefill), best_effort_donation(
        decode_steps)


def tensor_parallel_rules(tp_axis: str = "tp"):
    """Megatron-style sharding rules for Trainer(param_sharding_rules=...).

    Column-parallel qkv/mlp-in, row-parallel out-proj/mlp-out: exactly one
    all-reduce after attention and one after the MLP per block, riding ICI.
    """
    return [
        # Attention projections: split heads across tp.
        (r"attention/(query|key|value)/kernel", P(None, tp_axis, None)),
        (r"attention/out/kernel", P(tp_axis, None, None)),
        # MLP: column-parallel in, row-parallel out.
        (r"mlp_in/kernel", P(None, tp_axis)),
        (r"mlp_out/kernel", P(tp_axis, None)),
        # Embeddings / head: vocab-sharded.
        (r"(^|/)embed/embedding", P(tp_axis, None)),
        (r"lm_head/kernel", P(None, tp_axis)),
    ]
