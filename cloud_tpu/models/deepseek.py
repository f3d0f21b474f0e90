"""DeepSeek-V2/V3-style decoder LM: multi-head latent attention + MoE.

The third LM architecture family (after `TransformerLM` and `LlamaLM`),
for the DeepSeek checkpoint line. No reference equivalent — the
reference stops at Keras models (SURVEY §0) — but the two ideas this
family contributes are exactly the ones that matter at TPU scale:

- **MLA (multi-head latent attention)**: k/v are generated from a
  low-rank compressed latent (`kv_lora_rank` ~ 512 vs H*(nope+v) ~ 32k
  in DeepSeek-V3), so the decode cache stores the LATENT plus a small
  shared rope key — a ~50x KV-cache reduction, which is the decode
  memory bound. Queries optionally go through their own low-rank
  bottleneck (`q_lora_rank`). Attention runs at `qk_head_dim` =
  nope+rope width per head; only the rope slice is rotated, and the
  rope key is SHARED across heads (multi-query for the positional
  part). The value width (`v_head_dim`) can differ from the key width:
  v is zero-padded to the key width so the flash kernel's single-D
  layout serves MLA unchanged, and the pad is sliced off after (zero
  columns of V contribute zeros to the output — exact, not
  approximate; HF's flash path does the same).
- **DeepSeek MoE**: sigmoid router scores with a (non-learned) score
  correction bias used for SELECTION only, node-limited group routing
  (`n_group`/`topk_group`: only groups whose top-2 summed scores rank
  highest stay eligible), gates = the UNBIASED scores at the selected
  experts (normalized, then scaled by `routed_scaling_factor`), and a
  dense always-on shared expert alongside the routed ones. Expert
  compute reuses the same dense-dispatch einsums as `TopKMoEMLP`
  (`moe.routed_expert_ffn`) — static shapes, MXU-tiled, "ep"-shardable
  via `expert_parallel_rules`.

`DeepseekLM` keeps the `TransformerLM`/`LlamaLM` module contract
(decode=/cache collection/max_seq_len/vocab_size), so `generate()`
drives it unchanged — with the compressed-latent cache, not an
expanded one. Weights import from HF `DeepseekV3ForCausalLM` via
`models.hf_import.import_hf_deepseek` (rope_interleave -> the
"interleaved" rope style; rotate-half otherwise).
"""

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from cloud_tpu.models.llama import (_GATE_ACTIVATIONS, RopeScaling,
                                    SwiGLU, apply_rope)


class MLAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3).

    Projections (all bias-free, matching `attention_bias=False`):
      q:  x -> [q_a -> RMSNorm -> q_b] (or direct `query` when
          q_lora_rank is None) -> [B, S, H, nope+rope]
      kv: x -> kv_a -> split(latent [kv_lora_rank], k_rot [rope]);
          latent -> RMSNorm -> kv_b -> [B, S, H, nope+v]
    The rope slices of q and the shared k_rot are rotated; attention
    runs over concat(nope, rope) keys with v zero-padded to the same
    width (sliced off after — exact).
    """

    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    q_lora_rank: Optional[int] = None  # None = direct q projection
    compute_dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"  # auto | flash | reference
    rope_theta: float = 10000.0
    rope_style: str = "interleaved"  # HF rope_interleave=True
    rope_scaling: Optional[RopeScaling] = None  # yarn for long context
    attn_scale: Optional[float] = None  # None -> qk_head_dim**-0.5;
    # DeepSeek yarn checkpoints fold the mscale^2 factor in here.
    norm_eps: float = 1e-6
    decode: bool = False
    cache_len: int = 0

    def _rope(self, x, positions):
        return apply_rope(x, positions, self.rope_theta, self.rope_style,
                          self.rope_scaling)

    @nn.compact
    def __call__(self, x, mask=None):
        from cloud_tpu import ops
        from cloud_tpu.parallel import SEQUENCE_PARALLEL_IMPLS

        if self.attention_impl in SEQUENCE_PARALLEL_IMPLS:
            raise NotImplementedError(
                "MLA's shared rope key / mixed head widths are not "
                "wired into the sequence-parallel impls ({}); use "
                "flash/reference/auto.".format(self.attention_impl))
        d_model = x.shape[-1]
        qk_head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        dense = lambda feats, name: nn.DenseGeneral(
            feats, axis=-1, use_bias=False, dtype=self.compute_dtype,
            name=name)

        if self.q_lora_rank is None:
            q = dense((self.num_heads, qk_head_dim), "query")(x)
        else:
            q = dense((self.q_lora_rank,), "q_a")(x)
            q = nn.RMSNorm(epsilon=self.norm_eps,
                           dtype=self.compute_dtype, name="q_a_norm")(q)
            q = dense((self.num_heads, qk_head_dim), "q_b")(q)
        q_nope = q[..., :self.qk_nope_head_dim]
        q_rot = q[..., self.qk_nope_head_dim:]

        ckv = dense((self.kv_lora_rank + self.qk_rope_head_dim,),
                    "kv_a")(x)
        latent = ckv[..., :self.kv_lora_rank]
        k_rot = ckv[..., None, self.kv_lora_rank:]  # [B, S, 1, rope]
        latent = nn.RMSNorm(epsilon=self.norm_eps,
                            dtype=self.compute_dtype,
                            name="kv_a_norm")(latent)

        kv_b = dense((self.num_heads,
                      self.qk_nope_head_dim + self.v_head_dim), "kv_b")

        if self.decode:
            # mask (optional [B, S]) marks REAL incoming tokens — the
            # left-padded-prompt contract (generate(prompt_mask=)).
            out = self._decode_attention(q_nope, q_rot, latent, k_rot,
                                         kv_b, mask)
        else:
            positions = jnp.arange(x.shape[1])
            q_rot = self._rope(q_rot, positions)
            k_rot = self._rope(k_rot, positions)
            kv = kv_b(latent)  # [B, S, H, nope+v]
            k_nope = kv[..., :self.qk_nope_head_dim]
            v = kv[..., self.qk_nope_head_dim:]
            q_full = jnp.concatenate([q_nope, q_rot], axis=-1)
            k_full = jnp.concatenate(
                [k_nope, jnp.broadcast_to(
                    k_rot, k_nope.shape[:-1] + (self.qk_rope_head_dim,))],
                axis=-1)
            # Zero-pad v to the key width so the single-D flash kernel
            # applies; zero columns contribute zeros — slice after.
            v_pad = jnp.pad(
                v, ((0, 0), (0, 0), (0, 0),
                    (0, qk_head_dim - self.v_head_dim)))
            out = ops.attention(
                q_full, k_full, v_pad, causal=True,
                sm_scale=self.attn_scale or qk_head_dim ** -0.5,
                mask=mask, impl=self.attention_impl)
            out = out[..., :self.v_head_dim]
        out = out.astype(self.compute_dtype)
        return nn.DenseGeneral(d_model, axis=(-2, -1), use_bias=False,
                               dtype=self.compute_dtype, name="out")(out)

    def _decode_attention(self, q_nope, q_rot, latent, k_rot, kv_b,
                          mask=None):
        """KV-cache attention over the COMPRESSED latent.

        The cache stores [B, L, kv_lora_rank] latents plus the shared
        [B, L, 1, rope] rotated key — the MLA memory win (~H*(nope+v)
        / (kv_lora_rank+rope) smaller than an expanded cache). Each
        step re-expands the cached latents through kv_b; that matmul
        is the same O(L) cost order as the attention itself.
        """
        import jax.lax as lax

        from cloud_tpu.models.decoding import decode_slot_update

        batch, seq = q_nope.shape[:2]
        if not self.cache_len:
            raise ValueError("decode=True needs cache_len > 0.")
        cached_latent = self.variable(
            "cache", "cached_latent", jnp.zeros,
            (batch, self.cache_len, self.kv_lora_rank),
            self.compute_dtype)
        cached_rope = self.variable(
            "cache", "cached_rope", jnp.zeros,
            (batch, self.cache_len, 1, self.qk_rope_head_dim),
            self.compute_dtype)

        idx, positions, allowed = decode_slot_update(
            self, mask, batch, seq, self.cache_len)
        q_rot = self._rope(q_rot, positions)
        k_rot = self._rope(k_rot, positions)

        cached_latent.value = lax.dynamic_update_slice(
            cached_latent.value, latent.astype(self.compute_dtype),
            (0, idx, 0))
        cached_rope.value = lax.dynamic_update_slice(
            cached_rope.value, k_rot.astype(self.compute_dtype),
            (0, idx, 0, 0))

        kv = kv_b(cached_latent.value)  # [B, L, H, nope+v]
        k_nope = kv[..., :self.qk_nope_head_dim]
        v = kv[..., self.qk_nope_head_dim:]
        scale = self.attn_scale or (
            self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        # Two logit contributions, f32 on the MXU: per-head nope keys
        # and the head-shared rope key (multi-query on the rope part).
        logits = (
            jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bqhd,bkd->bhqk", q_rot, cached_rope.value[..., 0, :],
                         preferred_element_type=jnp.float32)) * scale
        logits = jnp.where(allowed[:, None], logits, -1e30)
        weights = nn.softmax(logits, axis=-1).astype(self.compute_dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


class PlainMLP(nn.Module):
    """`down(act(up(x)))`, bias-free, with an activation of
    `moe.PLAIN_ACTIVATIONS`: the expert that is not gated."""

    d_ff: int
    compute_dtype: jnp.dtype = jnp.bfloat16
    activation: str = "relu2"
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from cloud_tpu.models import moe

        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=self.compute_dtype,
            param_dtype=self.param_dtype, name=name)
        hidden = moe.PLAIN_ACTIVATIONS[self.activation](
            dense(self.d_ff, "up")(x))
        return dense(x.shape[-1], "down")(hidden)


class DeepseekMoE(nn.Module):
    """DeepSeek-V3 MoE: sigmoid group-limited routing + shared expert.

    Routing (HF DeepseekV3TopkRouter semantics, re-expressed with
    static-shape jax ops):
      scores      = sigmoid(x @ router)                  (f32)
      choice      = scores + router_bias  (selection ONLY; the bias is
                    the aux-loss-free load-balancing control, a
                    non-learned buffer in checkpoints)
      group score = sum of each group's top-2 choice scores; only the
                    topk_group best groups stay eligible
      top_k selection over eligible choice scores; gates = UNBIASED
      scores at the winners, optionally sum-normalized, then scaled by
      routed_scaling_factor.
    Routed output + always-on shared SwiGLU expert (d_ff scaled by
    n_shared_experts). Returns (output, aux_loss): the aux loss is the
    Switch/Mixtral-style balance term over per-token-NORMALIZED scores
    (checkpoint forward outputs are unaffected — it is only sown by
    DeepseekBlock into "losses"). V3 checkpoints were TRAINED with
    aux-free bias updates instead, so when fine-tuning an imported
    model to match HF exactly set Trainer(aux_loss_weight=0); for
    from-scratch training the aux term is what counteracts router
    collapse (this implementation does not update the selection bias).
    """

    num_experts: int = 8
    top_k: int = 2
    d_ff: int = 256  # moe_intermediate_size (per routed expert)
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    n_shared_experts: int = 1
    capacity_factor: Optional[float] = None  # None = drop-free
    compute_dtype: jnp.dtype = jnp.bfloat16
    activation: str = "silu"
    # Family switches: V3 = sigmoid scores + top-2-sum group scores +
    # the e_score_correction_bias buffer; V2 = softmax scores +
    # group-MAX scores (group_limited_greedy) + no bias.
    scoring: str = "sigmoid"  # "softmax" for DeepSeek-V2
    group_select: str = "top2sum"  # "max" for DeepSeek-V2
    route_bias: bool = True  # V3 e_score_correction_bias
    # Expert-parallel share: the ids of the routed experts this holder
    # has (None = all). The router keeps its `num_experts` outputs and
    # its `top_k` choices and the gates are normalized over all the
    # chosen; only the chosen experts held here are computed (plus the
    # shared expert, which every holder has whole), and that partial
    # sum is the output — what an exchange would add is not stood in
    # for. Summed over the holders, the shared expert counted once,
    # the parts give the uncut layer (tests/unit/test_moe.py).
    held_experts: Optional[Tuple[int, ...]] = None
    param_dtype: jnp.dtype = jnp.float32  # experts + shared expert
    # LatentMoE (Nemotron-H): the routed experts work in a space
    # `latent_size` wide — `latent_down` before them, `latent_up` after
    # their weighted sum — while the router and the shared expert read
    # the layer's input at full width. None = experts at full width.
    latent_size: Optional[int] = None
    # Width of the shared expert (None = d_ff * n_shared_experts).
    shared_d_ff: Optional[int] = None
    # An `activation` of `moe.PLAIN_ACTIVATIONS` ("relu2") makes the
    # experts, shared one included, plain two-product MLPs.

    @nn.compact
    def __call__(self, x, deterministic=True, token_mask=None):
        """x: [batch, seq, d]; token_mask: optional [batch, seq], real
        tokens (a pad is routed nowhere and counted nowhere)."""
        del deterministic
        from cloud_tpu.models import moe

        batch, seq, d_model = x.shape
        tokens = batch * seq
        if self.num_experts % self.n_group:
            raise ValueError(
                "num_experts={} must divide into n_group={} groups."
                .format(self.num_experts, self.n_group))
        group_size = self.num_experts // self.n_group
        gated = self.activation not in moe.PLAIN_ACTIVATIONS
        act = (_GATE_ACTIVATIONS if gated
               else moe.PLAIN_ACTIVATIONS)[self.activation]
        shared_d_ff = self.shared_d_ff or self.d_ff * self.n_shared_experts

        router_kernel = self.param(
            "router", nn.initializers.lecun_normal(),
            (d_model, self.num_experts), jnp.float32)
        x2d = x.reshape(tokens, d_model)
        # NOTE: a non-learned load-balancing buffer in V3
        # checkpoints. It only feeds the (non-differentiable)
        # selection, so it gets zero gradient — but a
        # weight-decaying optimizer (adamw) would still erode it;
        # exclude it when fine-tuning, e.g.
        # Trainer(trainable=lambda p: "router_bias" not in p).
        router_bias = self.param(
            "router_bias", nn.initializers.zeros,
            (self.num_experts,), jnp.float32) if self.route_bias else None
        with jax.named_scope(moe.MOE_ROUTER):
            top_idx, gates, aux_loss = self._route(
                x2d, router_kernel, router_bias, group_size)
        if token_mask is not None:
            token_mask = token_mask.reshape(tokens)
        if not self.is_initializing():
            real = (tokens if token_mask is None
                    else jnp.sum(token_mask.astype(jnp.int32)))
            self.sow(moe.MOE_STATS, "pairs_routed", real * self.top_k)

        if self.capacity_factor is None:
            capacity = None
        else:
            capacity = max(1, int(self.capacity_factor * tokens
                                  * self.top_k / self.num_experts))
        latent = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=self.compute_dtype,
            param_dtype=self.param_dtype, name=name)
        routed_in = x2d
        if self.latent_size:
            with jax.named_scope(moe.MOE_LATENT_DOWN):
                routed_in = latent(self.latent_size, "latent_down")(x2d)
        with jax.named_scope(moe.MOE_ROUTED_EXPERTS):
            routed = moe.routed_expert_ffn(
                self, routed_in, top_idx, gates, self.num_experts,
                self.d_ff, capacity, act, self.compute_dtype,
                held_experts=self.held_experts, token_mask=token_mask,
                param_dtype=self.param_dtype, gated=gated)
        if self.latent_size:
            with jax.named_scope(moe.MOE_LATENT_UP):
                routed = latent(d_model, "latent_up")(routed)
        with jax.named_scope(moe.MOE_SHARED_EXPERT):
            if gated:
                shared = SwiGLU(shared_d_ff, self.compute_dtype,
                                activation=self.activation,
                                param_dtype=self.param_dtype,
                                name="shared")(x)
            else:
                shared = PlainMLP(shared_d_ff, self.compute_dtype,
                                  activation=self.activation,
                                  param_dtype=self.param_dtype,
                                  name="shared")(x)
        out = (routed.reshape(batch, seq, d_model) + shared).astype(
            x.dtype)
        return out, aux_loss

    def _route(self, x2d, router_kernel, router_bias, group_size):
        """(top_idx [T, k], gates [T, k], aux_loss) over all
        `num_experts`, in float32."""
        tokens = x2d.shape[0]
        # HIGHEST: on the TPU a float32 product otherwise rounds its
        # operands to bfloat16, and the choice of experts turns on
        # differences of scores far below that.
        logits = jnp.dot(jnp.asarray(x2d, jnp.float32), router_kernel,
                         precision=jax.lax.Precision.HIGHEST)
        if self.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)               # [T, E]
        elif self.scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        else:
            raise ValueError(
                "Unknown scoring {!r}; expected 'sigmoid' or "
                "'softmax'.".format(self.scoring))
        choice = (scores if router_bias is None
                  else scores + router_bias[None, :])

        if self.n_group > 1:
            grouped = choice.reshape(tokens, self.n_group, group_size)
            if self.group_select == "top2sum":
                group_scores = jax.lax.top_k(
                    grouped, min(2, group_size))[0].sum(axis=-1)
            elif self.group_select == "max":
                group_scores = grouped.max(axis=-1)       # [T, G]
            else:
                raise ValueError(
                    "Unknown group_select {!r}; expected 'top2sum' or "
                    "'max'.".format(self.group_select))
            _, group_idx = jax.lax.top_k(group_scores, self.topk_group)
            group_mask = jax.nn.one_hot(
                group_idx, self.n_group, dtype=jnp.float32).sum(axis=1)
            eligible = jnp.repeat(group_mask, group_size, axis=-1)
            choice = jnp.where(eligible > 0, choice, 0.0)

        _, top_idx = jax.lax.top_k(choice, self.top_k)    # [T, k]
        gates = jnp.take_along_axis(scores, top_idx, axis=-1)
        if self.norm_topk_prob:
            gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
        gates = gates * self.routed_scaling_factor

        # Balance term at the Mixtral scale (num_experts * sum f_e*P_e,
        # = top_k when uniform), over per-token-normalized scores so
        # sigmoid and softmax scoring share a scale. The assignment
        # counts are a scatter-add over the choices: no [T, k, E]
        # one-hot.
        counts = jnp.zeros((self.num_experts,), jnp.float32).at[
            top_idx.reshape(-1)].add(1.0)
        norm_scores = scores / (scores.sum(axis=-1, keepdims=True)
                                + 1e-20)
        aux_loss = self.num_experts * jnp.sum(
            counts / tokens * norm_scores.mean(axis=0))
        return top_idx, gates, aux_loss


class DeepseekBlock(nn.Module):
    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int  # dense-MLP width (dense layers)
    q_lora_rank: Optional[int] = None
    compute_dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    rope_theta: float = 10000.0
    rope_style: str = "interleaved"
    rope_scaling: Optional[RopeScaling] = None
    attn_scale: Optional[float] = None
    norm_eps: float = 1e-6
    decode: bool = False
    cache_len: int = 0
    mlp_activation: str = "silu"
    dropout_rate: float = 0.0
    # MoE (this block uses a dense SwiGLU when moe_experts == 0):
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_d_ff: int = 256
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    n_shared_experts: int = 1
    moe_capacity_factor: Optional[float] = None
    moe_scoring: str = "sigmoid"
    moe_group_select: str = "top2sum"
    moe_route_bias: bool = True

    @nn.compact
    def __call__(self, x, mask=None, deterministic=True):
        norm = lambda name: nn.RMSNorm(
            epsilon=self.norm_eps, dtype=self.compute_dtype, name=name)
        y = norm("norm_attn")(x)
        y = MLAttention(self.num_heads, self.kv_lora_rank,
                        self.qk_nope_head_dim, self.qk_rope_head_dim,
                        self.v_head_dim, q_lora_rank=self.q_lora_rank,
                        compute_dtype=self.compute_dtype,
                        attention_impl=self.attention_impl,
                        rope_theta=self.rope_theta,
                        rope_style=self.rope_style,
                        rope_scaling=self.rope_scaling,
                        attn_scale=self.attn_scale,
                        norm_eps=self.norm_eps,
                        decode=self.decode, cache_len=self.cache_len,
                        name="attention")(y, mask)
        if self.dropout_rate:
            y = nn.Dropout(self.dropout_rate)(y, deterministic=deterministic)
        x = x + y
        y = norm("norm_mlp")(x)
        if self.moe_experts:
            y, aux_loss = DeepseekMoE(
                num_experts=self.moe_experts,
                top_k=self.moe_top_k, d_ff=self.moe_d_ff,
                n_group=self.n_group,
                topk_group=self.topk_group,
                norm_topk_prob=self.norm_topk_prob,
                routed_scaling_factor=self.routed_scaling_factor,
                n_shared_experts=self.n_shared_experts,
                capacity_factor=self.moe_capacity_factor,
                compute_dtype=self.compute_dtype,
                activation=self.mlp_activation,
                scoring=self.moe_scoring,
                group_select=self.moe_group_select,
                route_bias=self.moe_route_bias,
                name="moe")(y, deterministic)
            # Summed into the training loss by Trainer when "losses"
            # is mutable; set aux_loss_weight=0 to fine-tune imported
            # checkpoints exactly like HF (which emits no aux term).
            self.sow("losses", "moe_aux_loss", aux_loss,
                     reduce_fn=lambda a, b: a + b, init_fn=lambda: 0.0)
        else:
            y = SwiGLU(self.d_ff, self.compute_dtype,
                       activation=self.mlp_activation, name="mlp")(y)
        if self.dropout_rate:
            y = nn.Dropout(self.dropout_rate)(y, deterministic=deterministic)
        return x + y


class DeepseekLM(nn.Module):
    """DeepSeek-style decoder LM: MLA attention, dense-then-MoE stack.

    Layers below `first_k_dense` use a dense SwiGLU MLP; the rest use
    `DeepseekMoE` (set moe_experts=0 for an all-dense MLA model).
    Same Trainer/`generate()` contract as `TransformerLM`/`LlamaLM`.
    """

    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    d_model: int = 512
    d_ff: int = 1408
    max_seq_len: int = 2048
    kv_lora_rank: int = 64
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    q_lora_rank: Optional[int] = None
    rope_theta: float = 10000.0
    rope_style: str = "interleaved"
    rope_scaling: Optional[RopeScaling] = None
    attn_scale: Optional[float] = None
    norm_eps: float = 1e-6
    compute_dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    decode: bool = False
    mlp_activation: str = "silu"
    dropout_rate: float = 0.0
    # MoE stack shape:
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_d_ff: int = 256
    first_k_dense: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    n_shared_experts: int = 1
    moe_capacity_factor: Optional[float] = None
    moe_scoring: str = "sigmoid"  # "softmax" = DeepSeek-V2
    moe_group_select: str = "top2sum"  # "max" = DeepSeek-V2
    moe_route_bias: bool = True  # False = DeepSeek-V2

    @nn.compact
    def __call__(self, tokens, mask=None, deterministic=True):
        seq = tokens.shape[1]
        if seq > self.max_seq_len:
            raise ValueError(
                "Sequence length {} exceeds max_seq_len {}.".format(
                    seq, self.max_seq_len))
        x = nn.Embed(self.vocab_size, self.d_model,
                     dtype=self.compute_dtype, name="embed")(tokens)
        for i in range(self.num_layers):
            moe = (self.moe_experts
                   if i >= self.first_k_dense else 0)
            x = DeepseekBlock(
                self.num_heads, self.kv_lora_rank,
                self.qk_nope_head_dim, self.qk_rope_head_dim,
                self.v_head_dim, self.d_ff,
                q_lora_rank=self.q_lora_rank,
                compute_dtype=self.compute_dtype,
                attention_impl=self.attention_impl,
                rope_theta=self.rope_theta,
                rope_style=self.rope_style,
                rope_scaling=self.rope_scaling,
                attn_scale=self.attn_scale,
                norm_eps=self.norm_eps,
                decode=self.decode, cache_len=self.max_seq_len,
                mlp_activation=self.mlp_activation,
                dropout_rate=self.dropout_rate,
                moe_experts=moe, moe_top_k=self.moe_top_k,
                moe_d_ff=self.moe_d_ff, n_group=self.n_group,
                topk_group=self.topk_group,
                norm_topk_prob=self.norm_topk_prob,
                routed_scaling_factor=self.routed_scaling_factor,
                n_shared_experts=self.n_shared_experts,
                moe_capacity_factor=self.moe_capacity_factor,
                moe_scoring=self.moe_scoring,
                moe_group_select=self.moe_group_select,
                moe_route_bias=self.moe_route_bias,
                name="block_%d" % i)(x, mask, deterministic)
        x = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.compute_dtype,
                       name="norm_final")(x)
        logits = nn.Dense(self.vocab_size, use_bias=False,
                          dtype=self.compute_dtype, name="lm_head")(x)
        return logits.astype(jnp.float32)


def deepseek_tensor_parallel_rules(tp_axis: str = "tp"):
    """Megatron-style layout for DeepseekLM, the MLA counterpart of
    `llama_tensor_parallel_rules` (for `Trainer(param_sharding_rules=)`,
    first-match-wins):

    - the low-rank bottlenecks (q_a, kv_a) stay REPLICATED: they are
      tiny, their RMSNorms need the full latent vector, and the shared
      rope key must exist on every shard;
    - the head-expanding projections (q_b / query / kv_b) are
      column-parallel over heads and `out` is row-parallel — the same
      two-collective block shape as the dense families (requires
      num_heads % tp == 0);
    - the always-on shared expert and the dense first-k MLPs split
      gate/up column- and down row-parallel; the router (and its bias)
      replicate, and the routed expert stacks are left for
      `expert_parallel_rules` ("ep") to shard — compose the two rule
      lists for tp x ep meshes.
    """
    from jax.sharding import PartitionSpec as P

    return [
        (r"attention/(q_b|query|kv_b)/kernel", P(None, tp_axis, None)),
        (r"attention/out/kernel", P(tp_axis, None, None)),
        (r"moe/shared/(gate|up)/kernel", P(None, tp_axis)),
        (r"moe/shared/down/kernel", P(tp_axis, None)),
        (r"mlp/(gate|up)/kernel", P(None, tp_axis)),
        (r"mlp/down/kernel", P(tp_axis, None)),
        (r"(^|/)embed/embedding", P(tp_axis, None)),
        (r"lm_head/kernel", P(None, tp_axis)),
    ]


__all__ = ["MLAttention", "DeepseekMoE", "DeepseekBlock", "DeepseekLM",
           "deepseek_tensor_parallel_rules"]
