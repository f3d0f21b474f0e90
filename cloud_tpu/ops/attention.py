"""Flash attention as a Pallas TPU kernel.

The compute-path counterpart the reference never had: its attention runs
wherever `tf.distribute` puts Keras layers (reference core/preprocess.py
picks a strategy, TF picks kernels). Here the hot op is a hand-written
TPU kernel: blockwise online-softmax attention that never materializes
the [S, S] score matrix in HBM, keeps the matmuls on the MXU in bf16/f32,
and streams K/V blocks through VMEM.

Design notes (see /opt/skills/guides/pallas_guide.md):
- Grid is (batch*heads, q_blocks, k_blocks) with the k dimension
  innermost; VMEM scratch (acc, m, l) carries the online-softmax state
  across k steps, and the output block is written on the last k step.
- m/l live in (block_q, 128) lane-broadcast scratch, and the saved
  logsumexp residual is materialized lane-broadcast ([BH, S, 128]) so the
  backward kernels can read it without cross-lane relayouts (Mosaic has
  no cheap (N,1)<->(1,N) transpose).
- Causal blocks strictly above the diagonal are skipped via `pl.when`.
- Backward = two kernels (dq over k-blocks; dk/dv over q-blocks), the
  standard FlashAttention-2 recomputation split, wired through
  `jax.custom_vjp`.
- Sequences are padded to a block multiple outside the custom_vjp, so
  autodiff of pad/slice handles the edges; padded keys are masked inside
  the kernel, padded dO rows are zero so they contribute nothing.

On non-TPU backends the kernels run in Pallas interpret mode (tests), so
the same code path is exercised everywhere. Under a device mesh the
kernels run per shard (ops/partition.py).
"""

import functools
import math
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from cloud_tpu.ops import partition

#: The kernels' declared names (table in monitoring/spans.py): the
#: trace's op text carries them, whatever module calls the kernel.
FLASH_FWD = "flash_fwd"
FLASH_BWD_DQ = "flash_bwd_dq"
FLASH_BWD_DKV = "flash_bwd_dkv"

#: `pl.pallas_call(name=)` is the innermost scope, and XLA:TPU names
#: the custom call by it (`%<name>.N`). The benchmark's accepted
#: `flash_roofline` finds these kernels as custom calls whose name
#: starts `attention.` (the flax scope they used to be named by), so
#: the calls pass the declared name behind that prefix until a
#: `benchmark` PR moves the reader to the declared names.
_CALL_PREFIX = "attention."

_NEG_INF = -1e30
_LANES = 128


class _Config(NamedTuple):
    causal: bool
    sm_scale: float
    block_q: int
    block_k: int
    kv_len: int  # true (unpadded) sequence length
    heads: int   # q heads, folded into the grid's leading batch*heads dim
    has_mask: bool  # per-example key mask streamed as [B, 1, S_pad] blocks
    interpret: bool
    kv_group: int = 1  # q heads per kv head (grouped-query attention)
    window: int = 0  # sliding-window width; 0 = full causal
    softcap: float = 0.0  # Gemma2-style tanh logit cap; 0 = off


def repeat_kv(k, num_heads):
    """Broadcast [B, S, H_kv, D] key/value heads to num_heads groups.

    GQA's compute-side expansion: each kv head serves
    num_heads // H_kv query heads. Prefer passing H_kv-width k/v
    straight to `flash_attention`/`mha_reference` (both take the
    grouped layout natively); this helper is for paths that need the
    materialized expansion (e.g. sharding heads across a mesh axis).
    """
    h_kv = k.shape[2]
    if num_heads == h_kv:
        return k
    if num_heads % h_kv:
        raise ValueError(
            "num_heads=%d must be a multiple of num_kv_heads=%d."
            % (num_heads, h_kv))
    return jnp.repeat(k, num_heads // h_kv, axis=2)


def mha_reference(q, k, v, causal=True, sm_scale=None, mask=None,
                  window=None, logit_softcap=None):
    """Pure-jnp multi-head attention, layout [B, S, H, D].

    The correctness oracle for the kernel and the fallback path for
    shapes/backends the kernel does not cover. Grouped-query attention:
    k/v may carry H_kv < H heads (H divisible by H_kv); they are
    broadcast to the q-head grouping here. window: sliding-window
    (Mistral-style) attention — row i attends keys (i-window, i];
    requires causal=True. logit_softcap: Gemma2-style tanh capping,
    logits -> cap * tanh(logits / cap), applied after the softmax scale
    and before any masking (the HF Gemma2 order).
    """
    head_dim = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    if v.shape != k.shape:
        raise ValueError("k and v must have identical shapes; got "
                         "{} vs {}.".format(k.shape, v.shape))
    if window is not None and not causal:
        raise ValueError("window (sliding-window attention) requires "
                         "causal=True.")
    if k.shape[2] != q.shape[2]:
        heads, h_kv = q.shape[2], k.shape[2]
        if heads % h_kv:
            raise ValueError(
                "q heads {} must be a multiple of kv heads {}.".format(
                    heads, h_kv))
        k = jnp.repeat(k, heads // h_kv, axis=2)
        v = jnp.repeat(v, heads // h_kv, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * sm_scale
    logits = logits.astype(jnp.float32)
    if logit_softcap:
        cap = float(logit_softcap)
        logits = cap * jnp.tanh(logits / cap)
    seq_q, seq_k = q.shape[1], k.shape[1]
    if causal:
        allowed = jnp.tril(jnp.ones((seq_q, seq_k), dtype=bool))
        if window is not None:
            # Band: col in (row - window, row]. HF Mistral's convention
            # (sliding_window keys INCLUDING self are visible).
            row = jnp.arange(seq_q)[:, None]
            col = jnp.arange(seq_k)[None, :]
            allowed = allowed & (col > row - int(window))
        logits = jnp.where(allowed, logits, _NEG_INF)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :], logits, _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    if causal or mask is not None:
        # Fully-masked rows output ZEROS (and zero grads) — the flash
        # convention, unified here (round 4) so the oracle and kernel
        # agree on every row and the sp strategies (ring zeros via its
        # lse sentinel; ulysses delegates to whichever local kernel the
        # backend picked) behave identically on any backend. Without
        # this, softmax over all-(-1e30) logits is a uniform average.
        all_masked = jnp.max(logits, axis=-1,
                             keepdims=True) <= _NEG_INF / 2
        weights = jnp.where(all_masked, 0.0, weights)
    weights = weights.astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _block_mask(config, qi, ki, mask_ref):
    """Combined validity mask for one (block_q, block_k) tile: global
    kv padding, causal structure, and (when present) the per-example
    key mask block."""
    block_q, block_k = config.block_q, config.block_k
    col = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = col < config.kv_len
    if config.causal:
        row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = mask & (col <= row)
        if config.window:
            # Sliding-window band: col in (row - window, row] — the HF
            # Mistral convention (window keys visible including self).
            mask = mask & (col > row - config.window)
    if mask_ref is not None:
        valid = mask_ref[...].reshape(1, block_k) != 0
        mask = mask & jnp.broadcast_to(valid, (block_q, block_k))
    return mask


def _tile_live(config, qi, ki):
    """Causal tile-skip condition: a (qi, ki) tile runs only if it
    intersects the visible region — at or below the diagonal, and
    (with a sliding window) not entirely below the band."""
    cond = (ki * config.block_k <= qi * config.block_q
            + config.block_q - 1)
    if config.window:
        cond = jnp.logical_and(
            cond, (ki + 1) * config.block_k - 1
            > qi * config.block_q - config.window)
    return cond


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, config, num_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * config.sm_scale
        if config.softcap:
            # Gemma2 logit soft-capping, cap * tanh(s / cap) — before
            # masking (the HF order; masked entries go to -inf either
            # way, so the capped value never leaks).
            s = config.softcap * jnp.tanh(s / config.softcap)
        mask = _block_mask(config, qi, ki, mask_ref)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_curr = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        alpha = jnp.exp(m_prev - m_next)
        # Explicit zero where masked: exp(s - m) underflows to 0 for
        # normal rows, but a fully-masked row has m == s == -inf and
        # exp(0) == 1 would leak mass (such rows output 0 instead).
        p = jnp.where(mask, jnp.exp(s - m_next), 0.0)
        l_next = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_next, l_ref.shape)

    if config.causal:
        @pl.when(_tile_live(config, qi, ki))
        def _masked_step():
            _step()
    else:
        _step()

    @pl.when(ki == num_k - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(safe_l)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape)


def _mask_spec(config, transposed=False):
    """BlockSpec for the [B, 1, S_pad] key-mask: one (1, 1, block_k)
    strip per k-block, indexed by the example this program serves.

    The mask rides with a singleton middle axis so the block's
    second-to-last dim (1) EQUALS the array dim — Mosaic requires the
    last two block dims be (divisible by 8, divisible by 128) or equal
    to the array dims, and a rank-2 [B, S_pad] layout with (1, block_k)
    blocks violates the sublane rule whenever B > 1 (caught by the
    round-4 on-TPU parity smoke; interpret mode never checks this)."""
    heads = config.heads
    if transposed:  # dk/dv grid: (b over B*H_kv, j, t)
        heads_kv = config.heads // config.kv_group
        return pl.BlockSpec((1, 1, config.block_k),
                            lambda b, j, t: (b // heads_kv, 0, j))
    return pl.BlockSpec((1, 1, config.block_k),
                        lambda b, i, j: (b // heads, 0, j))


def _maybe_mask(config, kernel):
    """Adapts a mask-taking kernel body to the unmasked arg list."""
    if config.has_mask:
        return kernel

    def adapted(q_ref, k_ref, v_ref, *rest):
        return kernel(q_ref, k_ref, v_ref, None, *rest)
    return adapted


def _flash_forward(config, q, k, v, kmask):
    """q: [B*H, S_pad, D]; k/v: [B*H_kv, S_pad, D] (H_kv = H/kv_group);
    kmask: [B, 1, S_pad] int32 or None ->
    (out [B*H, S_pad, D], lse [B*H, S_pad, 128]).

    GQA streams each kv head's blocks to its group of q-head programs
    via the index map (b // kv_group) — the H-wide expansion is never
    materialized in HBM."""
    vma = partition.vma_of(q, k, v, kmask)
    bh, seq, head_dim = q.shape
    num_q = seq // config.block_q
    num_k = seq // config.block_k
    grid = (bh, num_q, num_k)
    group = config.kv_group
    kernel = _maybe_mask(
        config, functools.partial(_fwd_kernel, config=config, num_k=num_k))
    in_specs = [
        pl.BlockSpec((1, config.block_q, head_dim),
                     lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, config.block_k, head_dim),
                     lambda b, i, j: (b // group, j, 0)),
        pl.BlockSpec((1, config.block_k, head_dim),
                     lambda b, i, j: (b // group, j, 0)),
    ]
    inputs = [q, k, v]
    if config.has_mask:
        in_specs.append(_mask_spec(config))
        inputs.append(kmask)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, config.block_q, head_dim),
                         lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, config.block_q, _LANES),
                         lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, head_dim), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, seq, _LANES), jnp.float32,
                                 vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((config.block_q, head_dim), jnp.float32),
            pltpu.VMEM((config.block_q, _LANES), jnp.float32),
            pltpu.VMEM((config.block_q, _LANES), jnp.float32),
        ],
        interpret=config.interpret,
        name=_CALL_PREFIX + FLASH_FWD,
    )(*inputs)
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _attn_probs(config, qi, ki, q, k, lse_col, mask_ref):
    """Recomputes the (block_q, block_k) probability block.

    Returns (p, dcap): dcap is the softcap chain-rule factor
    d(cap*tanh(s/cap))/ds = 1 - tanh^2(s/cap) to fold into dS, or None
    when soft-capping is off.
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * config.sm_scale
    dcap = None
    if config.softcap:
        t = jnp.tanh(s / config.softcap)
        dcap = 1.0 - t * t
        s = config.softcap * t
    mask = _block_mask(config, qi, ki, mask_ref)
    # Explicit zero (not just -inf logits): a fully-masked row carries
    # lse == -inf and exp(-inf - -inf) == 1 would fabricate mass.
    p = jnp.where(mask, jnp.exp(jnp.where(mask, s, _NEG_INF) - lse_col),
                  0.0)
    return p, dcap


def _dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, *, config, num_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        p, dcap = _attn_probs(config, qi, ki, q, k, lse_ref[0][:, :1],
                              mask_ref)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1]) * config.sm_scale
        if dcap is not None:
            ds = ds * dcap
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if config.causal:
        @pl.when(_tile_live(config, qi, ki))
        def _masked_step():
            _step()
    else:
        _step()

    @pl.when(ki == num_k - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_acc, dv_acc, *, config, num_q):
    """Grid (B*H_kv, num_k, kv_group*num_q): each kv head's dk/dv block
    accumulates over every q block of every q head in its group — the
    GQA sum over the group happens in the same VMEM accumulator that
    already sums over q blocks. t decomposes as g*num_q + i."""
    ki = pl.program_id(1)
    t = pl.program_id(2)
    qi = jax.lax.rem(t, num_q)

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        p, dcap = _attn_probs(config, qi, ki, q, k, lse_ref[0][:, :1],
                              mask_ref)
        # dV += P^T dO   (contract over the q rows)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1]) * config.sm_scale
        if dcap is not None:
            ds = ds * dcap
        # dK += dS^T Q
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if config.causal:
        @pl.when(_tile_live(config, qi, ki))
        def _masked_step():
            _step()
    else:
        _step()

    @pl.when(t == config.kv_group * num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(config, q, k, v, kmask, out, lse, g):
    vma = partition.vma_of(q, k, v, g)
    bh, seq, head_dim = q.shape
    bh_kv = k.shape[0]
    num_q = seq // config.block_q
    num_k = seq // config.block_k
    group = config.kv_group

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (bh, seq, _LANES))

    q_spec = pl.BlockSpec((1, config.block_q, head_dim),
                          lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, config.block_q, _LANES),
                            lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, config.block_k, head_dim),
                          lambda b, i, j: (b // group, j, 0))

    in_specs = [q_spec, k_spec, k_spec]
    inputs = [q, k, v]
    if config.has_mask:
        in_specs.append(_mask_spec(config))
        inputs.append(kmask)

    dq = pl.pallas_call(
        _maybe_mask(config, functools.partial(
            _dq_kernel, config=config, num_k=num_k)),
        grid=(bh, num_q, num_k),
        in_specs=in_specs + [q_spec, row_spec, row_spec],
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma)],
        scratch_shapes=[
            pltpu.VMEM((config.block_q, head_dim), jnp.float32)],
        interpret=config.interpret,
        name=_CALL_PREFIX + FLASH_BWD_DQ,
    )(*inputs, g, lse, delta)[0]

    # dk/dv: one program per kv head and k-block; the innermost dim t
    # fuses (group, q_blocks) so the group sum lands in the accumulator
    # (see _dkdv_kernel). Index maps lift t -> (q head b*group + t//num_q,
    # q block t%num_q).
    qT_spec = pl.BlockSpec(
        (1, config.block_q, head_dim),
        lambda b, j, t: (b * group + t // num_q, t % num_q, 0))
    rowT_spec = pl.BlockSpec(
        (1, config.block_q, _LANES),
        lambda b, j, t: (b * group + t // num_q, t % num_q, 0))
    kT_spec = pl.BlockSpec((1, config.block_k, head_dim),
                           lambda b, j, t: (b, j, 0))
    inT_specs = [qT_spec, kT_spec, kT_spec]
    if config.has_mask:
        inT_specs.append(_mask_spec(config, transposed=True))
    dk, dv = pl.pallas_call(
        _maybe_mask(config, functools.partial(
            _dkdv_kernel, config=config, num_q=num_q)),
        grid=(bh_kv, num_k, group * num_q),
        in_specs=inT_specs + [qT_spec, rowT_spec, rowT_spec],
        out_specs=[kT_spec, kT_spec],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
            jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((config.block_k, head_dim), jnp.float32),
            pltpu.VMEM((config.block_k, head_dim), jnp.float32),
        ],
        interpret=config.interpret,
        name=_CALL_PREFIX + FLASH_BWD_DKV,
    )(*inputs, g, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_attention(config, q, k, v):
    out, _ = _flash_forward(config, q, k, v, None)
    return out


def _flash_attention_fwd(config, q, k, v):
    out, lse = _flash_forward(config, q, k, v, None)
    return out, (q, k, v, out, lse)


def _flash_attention_bwd(config, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_backward(config, q, k, v, None, out, lse, g)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_attention_masked(config, q, k, v, kmask):
    out, _ = _flash_forward(config, q, k, v, kmask)
    return out


def _flash_attention_masked_fwd(config, q, k, v, kmask):
    out, lse = _flash_forward(config, q, k, v, kmask)
    return out, (q, k, v, kmask, out, lse)


def _flash_attention_masked_bwd(config, residuals, g):
    import numpy as np

    q, k, v, kmask, out, lse = residuals
    dq, dk, dv = _flash_backward(config, q, k, v, kmask, out, lse, g)
    # Integer mask: the cotangent is the symbolic zero, float0.
    return dq, dk, dv, np.zeros(kmask.shape, jax.dtypes.float0)


_flash_attention_masked.defvjp(_flash_attention_masked_fwd,
                               _flash_attention_masked_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, causal=True, sm_scale=None, mask=None,
                    window=None, logit_softcap=None, block_q=None,
                    block_k=None, interpret: Optional[bool] = None):
    """Blockwise flash attention, layout [batch, seq, heads, head_dim].

    Args:
        q, k, v: [B, S, H, D] arrays (any float dtype; compute is f32 on
            the MXU, output in the input dtype). Grouped-query
            attention: k/v may carry H_kv < H heads (H divisible by
            H_kv) — each kv head serves H/H_kv consecutive q heads, and
            the kernel streams kv blocks per group instead of
            materializing the H-wide expansion in HBM.
        causal: Apply a causal (autoregressive) mask.
        window: Sliding-window (Mistral-style) attention — row i
            attends keys in (i-window, i]; requires causal=True. Tiles
            entirely below the band are skipped in the grid
            (_tile_live), so long-sequence cost scales with S*window,
            not S^2.
        sm_scale: Softmax temperature; default 1/sqrt(D).
        logit_softcap: Gemma2-style tanh logit capping — logits become
            cap * tanh(logits / cap) after the softmax scale and before
            masking (the HF Gemma2 order); the backward kernels fold
            the tanh derivative into dS. None/0 = off.
        mask: Optional [B, S] boolean key mask (True = attend). The
            padded-batch fast path: masked keys are excluded inside the
            kernel, so Keras-parity workloads with per-example padding
            never leave the flash path. Any pattern is supported, not
            just contiguous prefixes. Rows whose keys are ALL masked
            output zeros — and since round 4 `mha_reference` adopts the
            same convention, kernel and oracle agree on every row.
        block_q / block_k: Kernel tile sizes along the sequence. S is
            padded up to a multiple internally. Default (None) is 128,
            overridable process-wide via CLOUD_TPU_FLASH_BLOCK_Q /
            CLOUD_TPU_FLASH_BLOCK_K — the deployment hook for a
            `benchmarks/flash_autotune.py` pin, so a measured best
            config applies without touching call sites.
        interpret: Force Pallas interpret mode. Default: interpret
            everywhere except on real TPU backends.

    Returns:
        [B, S, H, D] attention output, differentiable w.r.t. q/k/v.
    """
    batch, seq, heads, head_dim = q.shape
    h_kv = k.shape[2]
    if v.shape != k.shape:
        raise ValueError("k and v must have identical shapes; got "
                         "{} vs {}.".format(k.shape, v.shape))
    if heads % h_kv:
        raise ValueError(
            "q heads {} must be a multiple of kv heads {}.".format(
                heads, h_kv))
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    if window is not None and not causal:
        raise ValueError("window (sliding-window attention) requires "
                         "causal=True.")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_q is None:
        block_q = int(os.environ.get("CLOUD_TPU_FLASH_BLOCK_Q", 128))
    if block_k is None:
        block_k = int(os.environ.get("CLOUD_TPU_FLASH_BLOCK_K", 128))

    block = max(block_q, block_k)
    if block_q % min(block_q, block_k) or block_k % min(block_q, block_k):
        raise ValueError(
            "block_q={} and block_k={} must divide one another.".format(
                block_q, block_k))
    seq_pad = -(-seq // block) * block
    block_q = min(block_q, seq_pad)
    block_k = min(block_k, seq_pad)

    if mask is not None and mask.shape != (batch, seq):
        raise ValueError(
            "mask must be [batch, seq] = {}; got {}.".format(
                (batch, seq), mask.shape))

    def kernel(q, k, v, *kmask):
        """One device's [B', S, H', D] block."""
        batch, _, heads, _ = q.shape
        config = _Config(causal=bool(causal), sm_scale=float(sm_scale),
                         block_q=block_q, block_k=block_k, kv_len=seq,
                         heads=heads, has_mask=bool(kmask),
                         interpret=bool(interpret),
                         kv_group=heads // k.shape[2],
                         window=int(window or 0),
                         softcap=float(logit_softcap or 0.0))

        def fold(x):
            n_heads = x.shape[2]
            x = jnp.transpose(x, (0, 2, 1, 3)).reshape(
                batch * n_heads, seq, head_dim)
            if seq_pad != seq:
                x = jnp.pad(x, ((0, 0), (0, seq_pad - seq), (0, 0)))
            return x

        operands = [fold(q), fold(k), fold(v)]
        if kmask:
            # [B, 1, S_pad]: the singleton axis makes the
            # (1, 1, block_k) mask blocks legal under Mosaic's sublane
            # rule (_mask_spec).
            operands.append(jnp.pad(
                kmask[0].astype(jnp.int32),
                ((0, 0), (0, seq_pad - seq)))[:, None, :])
        attend = _flash_attention_masked if kmask else _flash_attention
        out = attend(config, *partition.common_vma(*operands))
        out = out[:, :seq].reshape(batch, heads, seq, head_dim)
        return jnp.transpose(out, (0, 2, 1, 3))

    def plan(mesh):
        """Batch over the data axis, heads over the model axis."""
        dp = partition.data_axis(mesh, batch)
        tp = partition.model_axis(mesh, heads, h_kv)
        spec = P(dp, None, tp, None)
        in_specs = (spec,) * 3 + ((P(dp, None),) if mask is not None
                                  else ())
        return in_specs, spec, None

    args = (q, k, v) + ((mask,) if mask is not None else ())
    return partition.per_shard(kernel, args, plan, interpret)


def attention(q, k, v, causal=True, sm_scale=None, mask=None,
              window=None, logit_softcap=None, impl="auto"):
    """Dispatching attention: pallas flash kernel or jnp reference.

    impl: "auto" picks the flash kernel on TPU (with or without a key
    mask — padded batches stay on the fast path), the jnp reference
    elsewhere; "flash"/"reference" force a path. window: sliding-window
    width; logit_softcap: Gemma2 tanh capping (both paths honor both).
    """
    kwargs = dict(causal=causal, sm_scale=sm_scale, mask=mask,
                  window=window, logit_softcap=logit_softcap)
    if impl == "flash":
        return flash_attention(q, k, v, **kwargs)
    if impl == "reference":
        return mha_reference(q, k, v, **kwargs)
    if impl != "auto":
        raise ValueError("Unknown attention impl: {!r}".format(impl))
    if jax.default_backend() == "tpu":
        return flash_attention(q, k, v, **kwargs)
    return mha_reference(q, k, v, **kwargs)
