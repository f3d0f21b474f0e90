"""Llama-family decoder LM: RMSNorm + RoPE + SwiGLU + grouped-query attention.

The modern-LLM counterpart of `TransformerLM` (which is GPT-2-shaped:
LayerNorm, learned positions, GELU, full MHA). No reference equivalent —
the reference stops at Keras models (SURVEY §0) — but a complete TPU
framework needs the architecture family that today's open checkpoints
(Llama/Mistral/Gemma-style) actually use:

- **RMSNorm** instead of LayerNorm: one fewer HBM pass (no mean
  subtraction / bias), fuses into the adjacent matmul under XLA.
- **Rotary position embeddings** instead of a learned table: positions
  are a closed-form rotation of q/k, so the KV cache carries them for
  free and long-context extension is a theta change, not a re-train.
- **SwiGLU MLP**: two column-parallel input projections (gate, up) and
  one row-parallel output — same two-collective Megatron layout as the
  GELU MLP, expressed in `llama_tensor_parallel_rules`.
- **GQA**: `num_kv_heads < num_heads` shrinks the KV cache (the decode
  memory bound) by H/H_kv while the q heads keep full MXU tiles. K/V
  are broadcast to the q-head grouping only at the attention op, never
  stored expanded.
- **Sliding-window attention** (`sliding_window=`): Mistral-style
  banded causal masking, mapped onto the flash kernel's tile-skip grid
  (ops.attention window=) in training and the cache band mask in
  decode.
- **RoPE frequency scaling** (`rope_scaling=RopeScaling(...)`):
  Llama-3.1 "llama3" banded scheme and plain linear compression for
  long-context checkpoints.
- **Decoupled head_dim** (`head_dim=`): attention width independent of
  d_model/num_heads (Mistral-Nemo-style checkpoints).
- **Family switches**: `qkv_bias=` (Qwen2), `mlp_activation=`
  ("gelu_tanh" GeGLU) + `scale_embed=` (Gemma), `post_block_norms=` +
  `attn_logit_softcap=`/`final_logit_softcap=` + `attn_scale=` +
  `attn_kinds=` local/global patterns (Gemma2), `qk_norm=` +
  `rope_theta_local=` (Gemma3) — one architecture serves the
  Llama/Mistral/Qwen/Gemma-1/2/3 checkpoint families via
  `models.hf_import`.

`LlamaLM` keeps `TransformerLM`'s module contract (same attribute
names, same "cache" collection shape conventions), so `generate()` —
the jitted prefill + `lax.scan` decode loop in
`cloud_tpu/models/transformer.py` — drives it unchanged.

RoPE convention: the default `rope_style="interleaved"` rotates
(even, odd) feature pairs — the GPT-NeoX layout. Real Llama/Mistral
checkpoints were trained against the rotate-half pairing (first half
vs second half); the two are related by a fixed permutation of
head_dim features, which from-scratch training absorbs into the
learned q/k projections. To run imported weights, build the model with
`rope_style="rotate_half"` — `models.hf_import.import_hf_llama` does
this for you and converts HF param layouts to this module's.
"""

from typing import NamedTuple, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from cloud_tpu.parallel import SEQUENCE_PARALLEL_IMPLS


class RopeScaling(NamedTuple):
    """Long-context RoPE frequency-scaling recipe (HF `rope_scaling`).

    kind selects the transform applied to the base inv-frequencies:
      - "linear": every frequency divided by `factor` (positions
        effectively compressed by `factor`).
      - "llama3": Llama-3.1's banded scheme — high frequencies (short
        wavelengths, local syntax) untouched, low frequencies (long
        wavelengths, past `original_max_len`) divided by `factor`, a
        smooth interpolation between the `high_freq_factor` and
        `low_freq_factor` wavelength cutoffs.
      - "yarn": NTK-by-parts (YaRN, arXiv 2309.00071): dimensions
        rotating faster than `beta_fast` turns over `original_max_len`
        keep their frequency (extrapolation), slower than `beta_slow`
        are divided by `factor` (interpolation), with a linear ramp
        between; the rotated vectors are additionally scaled by an
        attention factor (`attention_factor`, or derived from factor
        and the DeepSeek `mscale`/`mscale_all_dim` pair).

    A NamedTuple (not a dict) so flax module fields carrying it stay
    hashable/comparable; `models.hf_import` translates the HF config
    dict form.
    """
    kind: str
    factor: float
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_len: int = 8192
    # yarn-only fields:
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None
    mscale: Optional[float] = None
    mscale_all_dim: Optional[float] = None
    truncate: bool = True


def _yarn_mscale(scale, mscale=1.0):
    if scale <= 1.0:
        return 1.0
    return 0.1 * mscale * float(np.log(scale)) + 1.0


def yarn_attention_factor(scaling: RopeScaling):
    """The cos/sin magnitude factor a yarn recipe applies to the
    rotated q/k (HF _compute_yarn_parameters attention_factor)."""
    if scaling.attention_factor is not None:
        return float(scaling.attention_factor)
    if scaling.mscale and scaling.mscale_all_dim:
        return (_yarn_mscale(scaling.factor, scaling.mscale)
                / _yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    return _yarn_mscale(scaling.factor)


def _scale_rope_freqs(freqs, scaling: RopeScaling, theta, head_dim):
    """Applies a RopeScaling recipe to base inv-frequencies [D/2]."""
    if scaling.kind == "linear":
        return freqs / scaling.factor
    if scaling.kind == "llama3":
        wavelen = 2.0 * np.pi / freqs
        low_wl = scaling.original_max_len / scaling.low_freq_factor
        high_wl = scaling.original_max_len / scaling.high_freq_factor
        smooth = ((scaling.original_max_len / wavelen
                   - scaling.low_freq_factor)
                  / (scaling.high_freq_factor - scaling.low_freq_factor))
        blended = (1.0 - smooth) * freqs / scaling.factor + smooth * freqs
        return jnp.where(
            wavelen < high_wl, freqs,
            jnp.where(wavelen > low_wl, freqs / scaling.factor, blended))
    if scaling.kind == "yarn":
        # Dimension index below which a frequency completes `rot` turns
        # over the original context (HF find_correction_dim).
        def correction_dim(rot):
            return (head_dim * np.log(
                scaling.original_max_len / (rot * 2.0 * np.pi))
                / (2.0 * np.log(theta)))

        low = correction_dim(scaling.beta_fast)
        high = correction_dim(scaling.beta_slow)
        if scaling.truncate:
            low, high = np.floor(low), np.ceil(high)
        low = max(low, 0.0)
        high = min(high, head_dim - 1.0)
        if high == low:
            high += 0.001  # HF's singularity guard
        ramp = jnp.clip(
            (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
            / (high - low), 0.0, 1.0)
        extrapolation_factor = 1.0 - ramp
        return (freqs / scaling.factor * (1.0 - extrapolation_factor)
                + freqs * extrapolation_factor)
    raise ValueError(
        "Unknown RopeScaling kind {!r}; expected 'linear', 'llama3', "
        "or 'yarn'.".format(scaling.kind))


def apply_rope(x, positions, theta: float = 10000.0,
               style: str = "interleaved",
               scaling: Optional[RopeScaling] = None):
    """Rotary position embedding over the last (head_dim) axis.

    x: [B, S, H, D] (D even); positions: [S] or [B, S] int32.
    Rotates feature pairs by pos * theta^(-2i/D) — f32 rotation math
    regardless of input dtype (bf16 angles at position ~10k would
    quantize to whole radians).

    style selects which features pair up (the two conventions are
    related by a fixed permutation of head_dim features):
      - "interleaved": (even, odd) pairs — the GPT-NeoX layout, this
        framework's from-scratch default.
      - "rotate_half": (i, i + D/2) pairs — the Llama/HF layout;
        REQUIRED for weights imported from real Llama/Mistral
        checkpoints (`models.hf_import`), whose q/k projections were
        trained against this pairing.
    """
    head_dim = x.shape[-1]
    if head_dim % 2:
        raise ValueError("RoPE needs an even head_dim; got %d." % head_dim)
    freqs = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                      / head_dim)
    if scaling is not None:
        freqs = _scale_rope_freqs(freqs, scaling, theta, head_dim)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, S, 1, D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    if style == "interleaved":
        x1 = x[..., 0::2].astype(jnp.float32)
        x2 = x[..., 1::2].astype(jnp.float32)
        rotated = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                            axis=-1).reshape(x.shape)
    elif style == "rotate_half":
        half = head_dim // 2
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:].astype(jnp.float32)
        rotated = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    else:
        raise ValueError(
            "Unknown RoPE style {!r}; expected 'interleaved' or "
            "'rotate_half'.".format(style))
    if scaling is not None and scaling.kind == "yarn":
        # YaRN scales the rotary cos/sin magnitudes (both q and k, so
        # attention logits scale by the factor squared).
        rotated = rotated * yarn_attention_factor(scaling)
    return rotated.astype(x.dtype)


# Re-exported from ops (canonical home; the parallel layer uses it too
# without importing the models package).
from cloud_tpu.ops.attention import repeat_kv  # noqa: E402,F401


class GQAttention(nn.Module):
    """Grouped-query attention with RoPE and an H_kv-sized decode cache."""

    num_heads: int
    num_kv_heads: int
    compute_dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"  # auto | flash | reference | ring | ulysses
    rope_theta: float = 10000.0
    rope_style: str = "interleaved"  # 'rotate_half' for HF-layout weights
    decode: bool = False
    cache_len: int = 0
    head_dim: Optional[int] = None  # None -> d_model // num_heads
    rope_scaling: Optional[RopeScaling] = None
    sliding_window: Optional[int] = None  # Mistral-style band width
    qkv_bias: bool = False  # Qwen2-style biased q/k/v (out stays bias-free)
    attn_scale: Optional[float] = None  # None -> 1/sqrt(head_dim)
    logit_softcap: Optional[float] = None  # Gemma2 tanh cap on logits
    qk_norm: bool = False  # Gemma3 per-head RMSNorm on q/k (pre-RoPE)
    norm_eps: float = 1e-6  # eps for the qk norms
    use_rope: bool = True  # False: q/k unrotated (a NoPE layer)
    param_dtype: jnp.dtype = jnp.float32  # projections' stored dtype
    # Paged-pool decode (serving/engine.py), as CausalSelfAttention's:
    # page rows are H_kv * head_dim wide.
    page_size: int = 0
    num_pages: int = 0
    page_dtype: str = ""

    def _rope(self, x, positions):
        if not self.use_rope:
            return x
        return apply_rope(x, positions, self.rope_theta, self.rope_style,
                          self.rope_scaling)

    @nn.compact
    def __call__(self, x, mask=None):
        from cloud_tpu import ops

        d_model = x.shape[-1]
        # Decoupled head_dim (Mistral-Nemo-style checkpoints): the
        # attention width need not be d_model/H; the out projection
        # maps H*head_dim back to d_model either way.
        head_dim = self.head_dim or d_model // self.num_heads
        dense = lambda feats, name: nn.DenseGeneral(
            feats, axis=-1, use_bias=self.qkv_bias,
            dtype=self.compute_dtype, param_dtype=self.param_dtype,
            name=name)
        q = dense((self.num_heads, head_dim), "query")(x)
        k = dense((self.num_kv_heads, head_dim), "key")(x)
        v = dense((self.num_kv_heads, head_dim), "value")(x)

        if self.qk_norm:
            # Gemma3: RMSNorm over head_dim (scale shared across heads),
            # applied BEFORE RoPE — replaces Gemma2's attention softcap
            # as the logit-magnitude control.
            q = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.compute_dtype,
                           name="q_norm")(q)
            k = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.compute_dtype,
                           name="k_norm")(k)

        if self.decode:
            # mask (optional [B, S]) marks REAL incoming tokens — the
            # left-padded-prompt contract (generate(prompt_mask=)):
            # padded slots are never attended and don't advance the
            # per-example logical position.
            if self.page_size:
                out = self._paged_decode_attention(q, k, v, mask)
            else:
                out = self._decode_attention(q, k, v, mask)
        else:
            positions = jnp.arange(x.shape[1])
            q = self._rope(q, positions)
            k = self._rope(k, positions)
            if self.attention_impl in SEQUENCE_PARALLEL_IMPLS:
                if self.sliding_window or self.logit_softcap or \
                        self.attn_scale:
                    raise NotImplementedError(
                        "sliding_window / logit_softcap / attn_scale "
                        "are not supported by the sequence-parallel "
                        "impls ({}); use flash/reference/auto."
                        .format(self.attention_impl))
                # RoPE composes with sequence parallelism for free: the
                # rotation above ran on the *global* [B, S, H, D] arrays
                # (traced shapes under jit are global), so every shard
                # carries its true absolute positions into the SP path.
                # K/V stay at H_kv width: ulysses exchanges them grouped
                # (when H_kv divides sp), ring expands internally.
                from cloud_tpu.parallel import sp_attention
                out = sp_attention(self.attention_impl, q, k, v,
                                   causal=True, mask=mask)
            else:
                # flash/reference take the grouped H_kv layout natively.
                out = ops.attention(q, k, v, causal=True, mask=mask,
                                    sm_scale=self.attn_scale,
                                    window=self.sliding_window,
                                    logit_softcap=self.logit_softcap,
                                    impl=self.attention_impl)
        out = out.astype(self.compute_dtype)
        return nn.DenseGeneral(d_model, axis=(-2, -1), use_bias=False,
                               dtype=self.compute_dtype,
                               param_dtype=self.param_dtype,
                               name="out")(out)

    def _paged_decode_attention(self, q, k, v, mask=None):
        """Decode over the paged KV pool: the write and the read
        `CausalSelfAttention` makes, at H_kv-wide rows, with RoPE at
        each slot's own depth and a window layer's band
        (`decoding.paged_kv_attention` holds the contract)."""
        from cloud_tpu.models.decoding import paged_kv_attention
        if self.logit_softcap:
            raise NotImplementedError(
                "logit_softcap is not supported over the paged pool.")
        return paged_kv_attention(
            self, q, k, v, mask, cache_len=self.cache_len,
            page_size=self.page_size, num_pages=self.num_pages,
            page_dtype=self.page_dtype, store_dtype=self.compute_dtype,
            sm_scale=self.attn_scale or 1.0 / np.sqrt(q.shape[-1]),
            impl=self.attention_impl, rotate=self._rope,
            window=self.sliding_window)

    def _flash_selected(self):
        """What `ops.attention` would pick for this module's impl."""
        import jax
        return self.attention_impl == "flash" or (
            self.attention_impl == "auto"
            and jax.default_backend() == "tpu")

    def _decode_attention(self, q, k, v, mask=None):
        """KV-cache attention at H_kv width (the point of GQA: the cache
        is num_heads/num_kv_heads times smaller than MHA's).

        Mirrors `CausalSelfAttention._decode_attention`
        (transformer.py): one path serves prefill (whole prompt, index
        0) and per-token steps (S=1). The cache is SLOT-addressed
        (write pointer `cache_index`), but RoPE angles and the sliding
        window band use per-example LOGICAL positions (`slot_pos`,
        counting only real tokens), so left-padded prompts rotate and
        band exactly like their unpadded equivalents; padded slots are
        marked invalid and never attended.
        """
        import jax.lax as lax

        from cloud_tpu.models.decoding import decode_slot_update

        batch, seq, _, head_dim = q.shape
        if not self.cache_len:
            raise ValueError("decode=True needs cache_len > 0.")
        cached_k = self.variable(
            "cache", "cached_key", jnp.zeros,
            (batch, self.cache_len, self.num_kv_heads, head_dim),
            self.compute_dtype)
        cached_v = self.variable(
            "cache", "cached_value", jnp.zeros,
            (batch, self.cache_len, self.num_kv_heads, head_dim),
            self.compute_dtype)

        idx, positions, allowed = decode_slot_update(
            self, mask, batch, seq, self.cache_len)
        q = self._rope(q, positions)
        k = self._rope(k, positions)

        cached_k.value = lax.dynamic_update_slice(
            cached_k.value, k.astype(self.compute_dtype), (0, idx, 0, 0))
        cached_v.value = lax.dynamic_update_slice(
            cached_v.value, v.astype(self.compute_dtype), (0, idx, 0, 0))

        if seq > 1 and self._flash_selected():
            # A prefill window where the flash kernel runs: the dense
            # einsum below holds [H, seq, L] float32 scores (4 GB for
            # 64 heads of a 4096-token window), the kernel none. The
            # queries are laid at their own rows of an L-long frame
            # and the kernel runs over the cache as self-attention:
            # causal in cache order, which is the order of logical
            # positions because a prompt's real tokens are contiguous
            # (pads lie to one side and are invalid), so the window's
            # band over rows is the band over positions. Rows outside
            # the window compute nothing that is read; on a window
            # layer the tiles outside the band are skipped.
            from cloud_tpu.ops.attention import flash_attention
            frame = lax.dynamic_update_slice(
                jnp.zeros((batch, self.cache_len) + q.shape[2:], q.dtype),
                q, (0, idx, 0, 0))
            out = flash_attention(
                frame, cached_k.value, cached_v.value, causal=True,
                sm_scale=self.attn_scale,
                mask=self.get_variable("cache", "slot_valid"),
                window=self.sliding_window,
                logit_softcap=self.logit_softcap)
            return lax.dynamic_slice(
                out, (0, idx, 0, 0), (batch, seq) + q.shape[2:])

        if self.sliding_window:
            # Same band as the training-time kernel, on LOGICAL
            # positions: keys in (pos - window, pos]. Cached entries
            # older than the window are masked (not evicted — the
            # cache stays slot-addressed; rolling eviction is a memory
            # optimization this path doesn't need at cache_len scale).
            slot_pos = self.get_variable("cache", "slot_pos")
            allowed = allowed & (slot_pos[:, None, :]
                                 > positions[:, :, None]
                                 - self.sliding_window)
        scale = self.attn_scale or 1.0 / np.sqrt(head_dim)
        group = self.num_heads // self.num_kv_heads
        # Grouped einsum: q reshaped [B,S,H_kv,G,D] attends its own kv
        # head — no materialized repeat of the cache.
        qg = q.reshape(batch, seq, self.num_kv_heads, group, head_dim)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, cached_k.value,
                            preferred_element_type=jnp.float32) * scale
        if self.logit_softcap:
            cap = float(self.logit_softcap)
            logits = cap * jnp.tanh(logits / cap)
        logits = jnp.where(allowed[:, None, None], logits, -1e30)
        weights = nn.softmax(logits, axis=-1).astype(self.compute_dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", weights, cached_v.value)
        return out.reshape(batch, seq, self.num_heads, head_dim)


_GATE_ACTIVATIONS = {
    "silu": nn.silu,  # Llama/Mistral/Qwen
    "gelu_tanh": lambda x: nn.gelu(x, approximate=True),  # Gemma
    "gelu": lambda x: nn.gelu(x, approximate=False),
}


class _DenseKernel(nn.Module):
    """Bare kernel-param holder: creates `<name>/kernel` exactly where
    `nn.Dense(use_bias=False)` would — same path, shape, param dtype,
    and initializer, so the param tree, checkpoints, AND path-derived
    init rng are unchanged when a fused op consumes the weight
    directly instead of calling the Dense module."""

    features: int
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, in_features):
        return self.param("kernel",
                          nn.linear.default_kernel_init,
                          (in_features, self.features), self.param_dtype)


class SwiGLU(nn.Module):
    """Gated MLP: down(act(gate(x)) * up(x)), all bias-free.

    activation selects the gate nonlinearity: "silu" (the SwiGLU
    proper, Llama/Mistral/Qwen) or "gelu_tanh"/"gelu" (GeGLU, the
    Gemma family). The tail runs through `ops.fused_swiglu` — a
    single-VMEM-pass Pallas kernel on TPU, the bitwise lax reference
    elsewhere (`impl` follows the block's `attention_impl`) — with
    the gate/up/down kernel params exactly where the three `nn.Dense`
    modules kept them.
    """

    d_ff: int
    compute_dtype: jnp.dtype = jnp.bfloat16
    activation: str = "silu"
    impl: str = "auto"
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        if self.activation not in _GATE_ACTIVATIONS:
            raise ValueError(
                "Unknown mlp activation {!r}; expected one of {}."
                .format(self.activation, sorted(_GATE_ACTIVATIONS)))
        from cloud_tpu.ops import fused_swiglu
        features = x.shape[-1]
        kernel = lambda feats, name: _DenseKernel(
            feats, self.param_dtype, name=name)
        w_gate = kernel(self.d_ff, "gate")(features)
        w_up = kernel(self.d_ff, "up")(features)
        w_down = kernel(features, "down")(self.d_ff)
        impl = "reference" if self.impl == "reference" else "auto"
        return fused_swiglu(x, w_gate, w_up, w_down,
                            activation=self.activation,
                            compute_dtype=self.compute_dtype,
                            impl=impl)


class FusedRMSNorm(nn.Module):
    """`nn.RMSNorm` stand-in backed by the fused Pallas tail
    (ops/fused_norm.py): same param ("scale", [features] f32 — so
    checkpoints and hf_import layouts are unchanged), same f32
    statistics, bitwise the flax output wherever the lax reference is
    selected. Called with a `residual`, it ALSO returns the updated
    residual stream `h = x + residual` — the pre-norm block tail
    `x = x + y; y = norm(x)` collapses into one HBM pass.

    `impl` follows the block's `attention_impl` ("reference" forces the
    lax path; anything else auto-selects — Pallas on TPU, lax
    elsewhere)."""

    epsilon: float = 1e-6
    dtype: Optional[jnp.dtype] = None
    impl: str = "auto"

    @nn.compact
    def __call__(self, x, residual=None):
        from cloud_tpu.ops import fused_rmsnorm
        scale = self.param("scale", nn.initializers.ones,
                           (x.shape[-1],), jnp.float32)
        normed, h = fused_rmsnorm(x, scale, residual=residual,
                                  eps=self.epsilon,
                                  out_dtype=self.dtype, impl=self.impl)
        if residual is None:
            return normed
        return normed, h


class LlamaBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    d_ff: int
    compute_dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    rope_theta: float = 10000.0
    rope_style: str = "interleaved"
    norm_eps: float = 1e-6
    dropout_rate: float = 0.0
    decode: bool = False
    cache_len: int = 0
    head_dim: Optional[int] = None
    rope_scaling: Optional[RopeScaling] = None
    sliding_window: Optional[int] = None
    qkv_bias: bool = False
    mlp_activation: str = "silu"
    post_norms: bool = False  # Gemma2/3: extra norm after attn and MLP
    attn_scale: Optional[float] = None
    logit_softcap: Optional[float] = None
    qk_norm: bool = False
    moe_experts: int = 0  # > 0: a top-k MoE replaces the MLP
    moe_top_k: int = 2
    moe_capacity_factor: Optional[float] = 2.0  # None = drop-free
    moe_norm_topk: bool = True  # False for some Qwen3-MoE checkpoints
    moe_router: str = "softmax"  # see LlamaLM
    moe_d_ff: Optional[int] = None
    moe_shared_experts: int = 1
    moe_routed_scale: float = 1.0
    moe_held_experts: Optional[Tuple[int, ...]] = None
    pre_norms: bool = True  # False: no norm on the sub-layers' inputs
    use_rope: bool = True
    param_dtype: jnp.dtype = jnp.float32
    page_size: int = 0  # paged-pool decode (serving); see attention
    num_pages: int = 0
    page_dtype: str = ""

    @nn.compact
    def __call__(self, x, mask=None, deterministic=True):
        norm = lambda name: nn.RMSNorm(
            epsilon=self.norm_eps, dtype=self.compute_dtype, name=name)
        fnorm = lambda name: FusedRMSNorm(
            epsilon=self.norm_eps, dtype=self.compute_dtype,
            impl=self.attention_impl, name=name)
        y = fnorm("norm_attn")(x) if self.pre_norms else x
        y = GQAttention(self.num_heads, self.num_kv_heads,
                        self.compute_dtype, self.attention_impl,
                        self.rope_theta, rope_style=self.rope_style,
                        decode=self.decode,
                        cache_len=self.cache_len,
                        head_dim=self.head_dim,
                        rope_scaling=self.rope_scaling,
                        sliding_window=self.sliding_window,
                        qkv_bias=self.qkv_bias,
                        attn_scale=self.attn_scale,
                        logit_softcap=self.logit_softcap,
                        qk_norm=self.qk_norm,
                        norm_eps=self.norm_eps,
                        use_rope=self.use_rope,
                        param_dtype=self.param_dtype,
                        page_size=self.page_size,
                        num_pages=self.num_pages,
                        page_dtype=self.page_dtype,
                        name="attention")(y, mask)
        if self.post_norms:
            # Gemma2/3 sandwich norms: each sublayer's OUTPUT is
            # normalized before the residual add (the residual stream
            # itself stays un-normalized). With `pre_norms=False` it
            # is the only norm of the sub-layer (EXAONE 4.0).
            y = norm("norm_attn_post")(y)
        if self.dropout_rate:
            # Dropout sits between the sublayer output and the residual
            # add, so the fused tail (add + norm in one pass) does not
            # apply; the param tree is identical either way.
            y = nn.Dropout(self.dropout_rate)(y, deterministic=deterministic)
        if not self.pre_norms:
            x = x + y
            y = x
        elif self.dropout_rate:
            x = x + y
            y = norm("norm_mlp")(x)
        else:
            y, x = fnorm("norm_mlp")(y, residual=x)
        if self.moe_experts and self.moe_router == "sigmoid":
            from cloud_tpu.models.deepseek import DeepseekMoE
            y, aux_loss = DeepseekMoE(
                num_experts=self.moe_experts, top_k=self.moe_top_k,
                d_ff=self.moe_d_ff or self.d_ff,
                norm_topk_prob=self.moe_norm_topk,
                routed_scaling_factor=self.moe_routed_scale,
                n_shared_experts=self.moe_shared_experts,
                capacity_factor=self.moe_capacity_factor,
                compute_dtype=self.compute_dtype,
                activation=self.mlp_activation,
                held_experts=self.moe_held_experts,
                param_dtype=self.param_dtype, name="moe")(
                    y, deterministic, token_mask=mask)
            self.sow("losses", "moe_aux_loss", aux_loss,
                     reduce_fn=lambda a, b: a + b, init_fn=lambda: 0.0)
        elif self.moe_experts:
            if self.moe_router != "softmax":
                raise ValueError(
                    "moe_router must be 'softmax' or 'sigmoid'; got "
                    "{!r}.".format(self.moe_router))
            from cloud_tpu.models.moe import TopKMoEMLP
            y, aux_loss = TopKMoEMLP(
                num_experts=self.moe_experts, top_k=self.moe_top_k,
                d_ff=self.moe_d_ff or self.d_ff,
                capacity_factor=self.moe_capacity_factor,
                compute_dtype=self.compute_dtype,
                activation=self.mlp_activation,
                norm_topk=self.moe_norm_topk, name="moe")(
                    y, deterministic)
            # Surfaced via mutable=["losses"] and summed into the
            # training loss by Trainer, same as TransformerBlock's
            # Switch-MoE path.
            self.sow("losses", "moe_aux_loss", aux_loss,
                     reduce_fn=lambda a, b: a + b, init_fn=lambda: 0.0)
        else:
            y = SwiGLU(self.d_ff, self.compute_dtype,
                       activation=self.mlp_activation,
                       impl=self.attention_impl,
                       param_dtype=self.param_dtype, name="mlp")(y)
        if self.post_norms:
            y = norm("norm_mlp_post")(y)
        if self.dropout_rate:
            y = nn.Dropout(self.dropout_rate)(y, deterministic=deterministic)
        return x + y


class LlamaLM(nn.Module):
    """Llama-style decoder-only LM.

    Drop-in peer of `TransformerLM` for Trainer / `generate()` /
    checkpointing; differs in the block recipe (RMSNorm, RoPE, SwiGLU,
    GQA) and in having no learned position table.
    """

    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: Optional[int] = None  # None -> num_heads (full MHA)
    d_model: int = 512
    d_ff: int = 1408  # ~2/3 * 4 * d_model, the SwiGLU convention
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    rope_style: str = "interleaved"  # 'rotate_half' for HF-layout weights
    norm_eps: float = 1e-6  # HF rms_norm_eps (Llama-2/Mistral use 1e-5)
    dropout_rate: float = 0.0
    compute_dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    decode: bool = False
    head_dim: Optional[int] = None  # None -> d_model // num_heads
    rope_scaling: Optional[RopeScaling] = None  # long-context extension
    sliding_window: Optional[int] = None  # Mistral-style band width
    qkv_bias: bool = False  # Qwen2-style biased q/k/v projections
    mlp_activation: str = "silu"  # "gelu_tanh" for the Gemma family
    scale_embed: bool = False  # Gemma: hidden = embed * sqrt(d_model)
    # Gemma2/3 family switches (all default off):
    post_block_norms: bool = False  # extra norm after attn/MLP outputs
    attn_scale: Optional[float] = None  # query_pre_attn_scalar ** -0.5
    attn_logit_softcap: Optional[float] = None  # Gemma2: 50.0
    final_logit_softcap: Optional[float] = None  # Gemma2: 30.0
    qk_norm: bool = False  # Gemma3: per-head RMSNorm on q/k
    # Per-layer local/global attention pattern, cycled over layers:
    # e.g. ("local", "global") = Gemma2's alternating sliding/full;
    # ("local",)*5 + ("global",) = Gemma3's 5:1. "local" layers use the
    # sliding_window band and (rope_theta_local, rope_scaling_local);
    # "global" layers attend fully with (rope_theta, rope_scaling).
    # None = every layer identical (sliding_window applies to all).
    attn_kinds: Optional[Tuple[str, ...]] = None
    rope_theta_local: Optional[float] = None  # Gemma3: 10_000
    rope_scaling_local: Optional[RopeScaling] = None
    # Mixtral/Qwen3-MoE family: top-k routed MoE FFN in every block
    # past the first `first_k_dense`. moe_router "softmax" is
    # `TopKMoEMLP` (softmax then top-k); "sigmoid" is `DeepseekMoE`
    # (DeepSeek-V3 / EXAONE-MoE: sigmoid scores, a selection bias,
    # gates normalized over the chosen and scaled by
    # `moe_routed_scale`, `moe_shared_experts` always-on experts, and
    # optionally only `moe_held_experts` of the routed ones held
    # here). `moe_d_ff` is an expert's width (None = d_ff).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: Optional[float] = 2.0  # None = drop-free
    moe_norm_topk: bool = True
    moe_router: str = "softmax"
    moe_d_ff: Optional[int] = None
    moe_shared_experts: int = 1
    moe_routed_scale: float = 1.0
    moe_held_experts: Optional[Tuple[int, ...]] = None
    first_k_dense: int = 0  # leading blocks that keep the dense MLP
    # EXAONE 4.0 family switches: with `post_block_norms`, no norm on
    # the sub-layers' inputs (the norm sits on their outputs alone);
    # "global" layers of an `attn_kinds` pattern unrotated (NoPE).
    pre_block_norms: bool = True
    global_rope: bool = True
    # Stored dtype of the matrices (embedding, projections, MLPs,
    # experts, head); norm scales and the router stay float32. A
    # served model sets the served dtype here so that `model.init`
    # declares the shapes in it.
    param_dtype: jnp.dtype = jnp.float32
    # Paged-pool decode (serving/engine.py), as TransformerLM's.
    kv_page_size: int = 0
    kv_num_pages: int = 0
    kv_page_dtype: str = ""  # "int8" = quantized pages (graftpack)

    def __post_init__(self):
        # Module fields key jit and lru caches, so they stay hashable:
        # a configuration file's lists become tuples, its
        # "LLLG"-style pattern the kinds, its dtype name a dtype.
        kinds = self.attn_kinds
        if isinstance(kinds, str):
            kinds = tuple({"L": "local", "G": "global"}.get(c, c)
                          for c in kinds)
        elif isinstance(kinds, list):
            kinds = tuple(kinds)
        object.__setattr__(self, "attn_kinds", kinds)
        if isinstance(self.moe_held_experts, list):
            object.__setattr__(self, "moe_held_experts",
                               tuple(self.moe_held_experts))
        object.__setattr__(self, "param_dtype",
                           jnp.dtype(self.param_dtype))
        super().__post_init__()

    def _layer_attn(self, i):
        """(window, theta, scaling, rotated) for layer i under
        attn_kinds."""
        if self.attn_kinds is None:
            return (self.sliding_window, self.rope_theta,
                    self.rope_scaling, True)
        kind = self.attn_kinds[i % len(self.attn_kinds)]
        if kind == "global":
            return (None, self.rope_theta, self.rope_scaling,
                    self.global_rope)
        if kind != "local":
            raise ValueError(
                "attn_kinds entries must be 'local' or 'global'; got "
                "{!r}.".format(kind))
        if not self.sliding_window:
            raise ValueError(
                "attn_kinds includes 'local' layers but sliding_window "
                "is not set.")
        return (self.sliding_window,
                self.rope_theta_local or self.rope_theta,
                self.rope_scaling_local, True)

    @nn.compact
    def __call__(self, tokens, mask=None, deterministic=True):
        seq = tokens.shape[1]
        if seq > self.max_seq_len:
            raise ValueError(
                "Sequence length {} exceeds max_seq_len {}.".format(
                    seq, self.max_seq_len))
        num_kv = self.num_kv_heads or self.num_heads
        x = nn.Embed(self.vocab_size, self.d_model,
                     dtype=self.compute_dtype,
                     param_dtype=self.param_dtype, name="embed")(tokens)
        if self.scale_embed:
            # Gemma convention: the normalizer is cast to the compute
            # dtype BEFORE multiplying (a bf16-rounded sqrt(d), matching
            # checkpoints trained that way).
            x = x * jnp.asarray(self.d_model ** 0.5, self.compute_dtype)
        for i in range(self.num_layers):
            window, theta, scaling, rotated = self._layer_attn(i)
            x = LlamaBlock(self.num_heads, num_kv, self.d_ff,
                           self.compute_dtype, self.attention_impl,
                           theta, self.rope_style,
                           self.norm_eps, self.dropout_rate,
                           decode=self.decode,
                           cache_len=self.max_seq_len,
                           head_dim=self.head_dim,
                           rope_scaling=scaling,
                           sliding_window=window,
                           qkv_bias=self.qkv_bias,
                           mlp_activation=self.mlp_activation,
                           post_norms=self.post_block_norms,
                           attn_scale=self.attn_scale,
                           logit_softcap=self.attn_logit_softcap,
                           qk_norm=self.qk_norm,
                           moe_experts=(self.moe_experts
                                        if i >= self.first_k_dense
                                        else 0),
                           moe_top_k=self.moe_top_k,
                           moe_capacity_factor=self.moe_capacity_factor,
                           moe_norm_topk=self.moe_norm_topk,
                           moe_router=self.moe_router,
                           moe_d_ff=self.moe_d_ff,
                           moe_shared_experts=self.moe_shared_experts,
                           moe_routed_scale=self.moe_routed_scale,
                           moe_held_experts=self.moe_held_experts,
                           pre_norms=self.pre_block_norms,
                           use_rope=rotated,
                           param_dtype=self.param_dtype,
                           page_size=self.kv_page_size,
                           num_pages=self.kv_num_pages,
                           page_dtype=self.kv_page_dtype,
                           name="block_%d" % i)(x, mask, deterministic)
        x = FusedRMSNorm(epsilon=self.norm_eps,
                         dtype=self.compute_dtype,
                         impl=self.attention_impl,
                         name="norm_final")(x)
        logits = nn.Dense(self.vocab_size, use_bias=False,
                          dtype=self.compute_dtype,
                          param_dtype=self.param_dtype,
                          name="lm_head")(x)
        logits = logits.astype(jnp.float32)
        if self.final_logit_softcap:
            cap = float(self.final_logit_softcap)
            logits = cap * jnp.tanh(logits / cap)
        return logits


def llama_tensor_parallel_rules(tp_axis: str = "tp"):
    """Megatron layout for LlamaLM: same two-collective-per-block shape
    as `tensor_parallel_rules` (transformer.py), with SwiGLU's gate/up
    both column-parallel and kv projections head-sharded (requires
    num_kv_heads % tp == 0)."""
    return [
        (r"attention/(query|key|value)/kernel", P(None, tp_axis, None)),
        (r"attention/(query|key|value)/bias", P(tp_axis, None)),
        (r"attention/out/kernel", P(tp_axis, None, None)),
        (r"mlp/(gate|up)/kernel", P(None, tp_axis)),
        (r"mlp/down/kernel", P(tp_axis, None)),
        (r"(^|/)embed/embedding", P(tp_axis, None)),
        (r"lm_head/kernel", P(None, tp_axis)),
    ]
