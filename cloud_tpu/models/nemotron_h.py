"""Nemotron-H-style hybrid decoder LM: Mamba-2, attention and expert
layers in one stack, ONE mixer a block.

The fourth LM family (after `TransformerLM`, `LlamaLM`, `DeepseekLM`),
for NVIDIA's `nemotron_h` checkpoints (Nemotron-H, arXiv:2504.03624;
Nemotron 3 Super). Every block is `h + Mixer(RMSNorm(h))`, and the
pattern string says which mixer a block holds:

    M   `mamba2.Mamba2Mixer`: a state-space layer whose per-sequence
        state has a fixed size (no key/value rows)
    *   `llama.GQAttention` with NO positional rotation: the family's
        attention layers carry no position embedding, the state-space
        layers before them order the tokens
    E   `deepseek.DeepseekMoE` as a LatentMoE: sigmoid router over all
        experts with a selection bias, gates normalized over the
        chosen and scaled; plain `relu2` experts in a latent space
        (`moe_latent` wide, projected down before and up after), a
        plain shared expert at full width; optionally only
        `moe_held_experts` of the routed ones held here

A final RMSNorm, an untied head. The module keeps the decode contract
of the other families (`decode=`, the "cache" collection, `kv_page_*`
for the paged pool), so `serving.Scheduler` serves it; its Mamba-2
layers add a per-sequence state to the cache that no page holds
(serving/engine.py).
"""

from typing import Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from cloud_tpu.models.deepseek import DeepseekMoE
from cloud_tpu.models.llama import FusedRMSNorm, GQAttention
from cloud_tpu.models.mamba2 import Mamba2Mixer

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


class NemotronHBlock(nn.Module):
    """`h + Mixer(RMSNorm(h))` with the mixer of `kind`; `cfg` is the
    model (its fields are the block's numbers)."""

    kind: str
    cfg: "NemotronHLM"

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        y = FusedRMSNorm(epsilon=cfg.norm_eps, dtype=cfg.compute_dtype,
                         impl=cfg.attention_impl, name="norm")(x)
        if self.kind == MAMBA:
            y = Mamba2Mixer(
                cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups,
                cfg.ssm_state, cfg.conv_kernel, cfg.chunk_size,
                cfg.norm_eps, cfg.compute_dtype, cfg.param_dtype,
                decode=cfg.decode, time_step_min=cfg.time_step_min,
                time_step_max=cfg.time_step_max,
                time_step_floor=cfg.time_step_floor,
                name="mamba")(y, mask)
        elif self.kind == ATTENTION:
            y = GQAttention(
                cfg.num_heads, cfg.num_kv_heads, cfg.compute_dtype,
                cfg.attention_impl, decode=cfg.decode,
                cache_len=cfg.max_seq_len, head_dim=cfg.head_dim,
                norm_eps=cfg.norm_eps, use_rope=False,
                param_dtype=cfg.param_dtype,
                page_size=cfg.kv_page_size, num_pages=cfg.kv_num_pages,
                page_dtype=cfg.kv_page_dtype, name="attention")(y, mask)
        else:
            y, _ = DeepseekMoE(
                num_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                d_ff=cfg.moe_d_ff, norm_topk_prob=cfg.moe_norm_topk,
                routed_scaling_factor=cfg.moe_routed_scale,
                compute_dtype=cfg.compute_dtype,
                activation=cfg.mlp_activation,
                held_experts=cfg.moe_held_experts,
                param_dtype=cfg.param_dtype, latent_size=cfg.moe_latent,
                shared_d_ff=cfg.moe_shared_d_ff, name="moe")(
                    y, token_mask=mask)
        return x + y


class NemotronHLM(nn.Module):
    vocab_size: int = 32000
    d_model: int = 512
    pattern: str = "M*E"
    max_seq_len: int = 2048
    norm_eps: float = 1e-5
    # `*` layers
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: Optional[int] = None
    # `M` layers
    mamba_heads: int = 16
    mamba_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001   # of the `dt_bias` initialisation
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # `E` layers
    moe_experts: int = 8          # what the router scores
    moe_top_k: int = 2
    moe_d_ff: int = 512
    moe_latent: Optional[int] = None
    moe_shared_d_ff: int = 1024
    moe_routed_scale: float = 1.0
    moe_norm_topk: bool = True
    moe_held_experts: Optional[Tuple[int, ...]] = None
    mlp_activation: str = "relu2"
    compute_dtype: jnp.dtype = jnp.bfloat16
    # Stored dtype of the matrices; norm scales, the router and the
    # state-space layers' per-head and convolution parameters stay
    # float32.
    param_dtype: jnp.dtype = jnp.float32
    attention_impl: str = "auto"
    dropout_rate: float = 0.0     # the decode contract's; unused
    decode: bool = False
    # Paged-pool decode (serving/engine.py), as LlamaLM's.
    kv_page_size: int = 0
    kv_num_pages: int = 0
    kv_page_dtype: str = ""

    def __post_init__(self):
        if isinstance(self.moe_held_experts, list):
            object.__setattr__(self, "moe_held_experts",
                               tuple(self.moe_held_experts))
        object.__setattr__(self, "param_dtype",
                           jnp.dtype(self.param_dtype))
        unknown = set(self.pattern) - {MAMBA, ATTENTION, EXPERTS}
        if unknown or not self.pattern:
            raise ValueError(
                "pattern must be a string of 'M', '*', 'E'; got "
                "{!r}.".format(self.pattern))
        super().__post_init__()

    @property
    def num_layers(self):
        return len(self.pattern)

    @property
    def attention_layers(self):
        """Layers that hold pages (serving/engine.py)."""
        return self.pattern.count(ATTENTION)

    @property
    def state_layers(self):
        """Layers that keep a recurrent state a slot beside the pool
        (serving/engine.py)."""
        return self.pattern.count(MAMBA)

    @nn.compact
    def __call__(self, tokens, mask=None, deterministic=True):
        del deterministic
        seq = tokens.shape[1]
        if seq > self.max_seq_len:
            raise ValueError(
                "Sequence length {} exceeds max_seq_len {}.".format(
                    seq, self.max_seq_len))
        x = nn.Embed(self.vocab_size, self.d_model,
                     dtype=self.compute_dtype,
                     param_dtype=self.param_dtype, name="embed")(tokens)
        # The block reads the model's numbers from an unbound copy (a
        # bound module as a field would be adopted as a submodule).
        cfg = self.clone(parent=None)
        for i, kind in enumerate(self.pattern):
            x = NemotronHBlock(kind, cfg, name="block_%d" % i)(x, mask)
        x = FusedRMSNorm(epsilon=self.norm_eps, dtype=self.compute_dtype,
                         impl=self.attention_impl, name="norm_final")(x)
        logits = nn.Dense(self.vocab_size, use_bias=False,
                          dtype=self.compute_dtype,
                          param_dtype=self.param_dtype,
                          name="lm_head")(x)
        return logits.astype(jnp.float32)
