"""Mamba-2 mixer: a selective state-space layer (SSD, arXiv:2405.21060)
as the Nemotron-H family runs it.

For a token `u` (d_model wide), with `heads` heads of `head_dim`,
`groups` groups sharing B and C, a state of `state_size` a head and a
causal depthwise convolution of `conv_kernel` taps:

    [z | xBC | dt] = W_in u          (inner | inner + 2 groups state | heads)
    xBC_t = silu(b_c + sum_k w_c[k] xBC_{t-K+1+k})
    x, B, C = split(xBC)             (inner | groups state | groups state)
    dt_t = softplus(dt_t + dt_bias),  A = -exp(A_log)          (a head)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t     S: heads x head_dim x state
    y_t = S_t C_t + D x_t
    out = W_out GroupRMSNorm(y_t silu(z_t))          (norm over each group)

A sequence runs the chunked form: inside a chunk of `chunk_size` tokens
the quadratic (attention-like) form, between chunks the state. One
token a slot (the serving tick) runs `ops.ssm_decode_update`.

Under `decode=True` the layer keeps two cache variables, batch-major so
that the serving engine can treat a row as a slot's state:

    conv_state  [batch, conv_kernel - 1, inner + 2 groups state]
                the last inputs of the convolution (before it)
    ssm_state   [batch, heads / pack, state, pack x head_dim] float32
                S in the packed layout of ops/ssm.py

`mask` [batch, seq] marks real tokens: a pad neither moves the state
(its `dt` is 0) nor enters the convolution's window (its input is 0 and
the window kept is the one that ends at the last real token), so a
prompt right-padded to a bucket leaves the state a prefill of the bare
prompt leaves, and an inactive slot of a tick keeps its state.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from cloud_tpu.ops import ssm as ssm_ops

#: Declared scopes of the mixer's parts (table "Scopes" in
#: monitoring/spans.py).
SSM_IN_PROJ = "ssm_in_proj"
SSM_CONV = "ssm_conv"
SSM_SCAN = "ssm_scan"
SSM_GATE_NORM = "ssm_gate_norm"
SSM_OUT_PROJ = "ssm_out_proj"


def chunked_scan(x, dt, a, b, c, chunk, initial_state=None,
                 compute_dtype=jnp.float32):
    """The recurrence over a sequence, a chunk at a time.

    x [B, S, H, P]; dt [B, S, H] (after softplus; 0 on a pad); a [H]
    (negative); b, c [B, S, G, N]; initial_state [B, H, P, N] float32
    or None (zeros). Returns (y [B, S, H, P] float32 WITHOUT the `D x`
    term, the state after the last token [B, H, P, N] float32).

    Inside a chunk, token i reads token j <= i through
    `C_i . B_j exp(sum_{j<k<=i} dt_k A) dt_j x_j`: three products a
    chunk, operands in `compute_dtype`, sums and decays in float32.
    Between chunks the state is carried by a scan over the chunks.
    """
    batch, seq, heads, head_dim = x.shape
    groups, n = b.shape[2:]
    per_group = heads // groups
    pad = -seq % chunk
    if pad:
        # A pad's dt is 0: it decays nothing and adds nothing.
        widen = lambda v: jnp.pad(v, [(0, 0), (0, pad)]
                                  + [(0, 0)] * (v.ndim - 2))
        x, dt, b, c = widen(x), widen(dt), widen(b), widen(c)
    chunks = (seq + pad) // chunk
    f32 = jnp.float32
    xc = x.reshape(batch, chunks, chunk, groups, per_group, head_dim)
    dtc = dt.astype(f32).reshape(batch, chunks, chunk, groups, per_group)
    bc = b.reshape(batch, chunks, chunk, groups, n)
    cc = c.reshape(batch, chunks, chunk, groups, n)
    log_decay = dtc * a.astype(f32).reshape(groups, per_group)
    cum = jnp.cumsum(log_decay, axis=2)             # [B, c, L, G, Hg]
    total = cum[:, :, -1]                           # [B, c, G, Hg]

    cast = lambda v: v.astype(compute_dtype)
    dtx = dtc[..., None] * xc.astype(f32)           # [B, c, L, G, Hg, P]
    # Within a chunk.
    scores = jnp.einsum("bcign,bcjgn->bcgij", cast(cc), cast(bc),
                        preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    by_head = jnp.moveaxis(cum, 2, -1)              # [B, c, G, Hg, L]
    # exp of a masked difference: the upper triangle would overflow.
    decay = jnp.exp(jnp.where(
        causal, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    y = jnp.einsum("bcghij,bcjghp->bcighp",
                   cast(scores[:, :, :, None] * decay), cast(dtx),
                   preferred_element_type=f32)
    # What a chunk adds to the state, and the state it starts from.
    to_end = jnp.exp(total[:, :, None] - cum)       # [B, c, L, G, Hg]
    added = jnp.einsum("bcjghp,bcjgn->bcghpn",
                       cast(dtx * to_end[..., None]), cast(bc),
                       preferred_element_type=f32)
    if initial_state is None:
        initial_state = jnp.zeros((batch, heads, head_dim, n), f32)
    start = initial_state.astype(f32).reshape(batch, groups, per_group,
                                              head_dim, n)

    def carry(state, inputs):
        chunk_total, chunk_added = inputs
        nxt = jnp.exp(chunk_total)[..., None, None] * state + chunk_added
        return nxt, state

    final, starts = jax.lax.scan(
        carry, start, (jnp.moveaxis(total, 1, 0),
                       jnp.moveaxis(added, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)             # [B, c, G, Hg, P, N]
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bcign,bcghpn->bcighp", cast(cc), cast(starts),
        preferred_element_type=f32)
    y = y.reshape(batch, chunks * chunk, heads, head_dim)[:, :seq]
    return y, final.reshape(batch, heads, head_dim, n)


def _a_log_init(key, shape, dtype):
    """`A = -U(1, 16)` a head: the family's published initialisation."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(lo, hi, floor):
    """`dt = exp(U(log lo, log hi))`, floored, kept as the inverse of
    softplus (the config's `time_step_*` keys)."""
    def init(key, shape, dtype):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(lo),
                                        jnp.log(hi)))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


def _conv_init(key, shape, dtype):
    """Uniform in +-1/sqrt(taps): a depthwise convolution's fan-in."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class Mamba2Mixer(nn.Module):
    """One Mamba-2 layer (see the module docstring)."""

    num_heads: int = 128
    head_dim: int = 64
    groups: int = 8
    state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    norm_eps: float = 1e-5
    compute_dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4

    @nn.compact
    def __call__(self, u, mask=None):
        batch, seq, d_model = u.shape
        heads, head_dim, groups, n = (self.num_heads, self.head_dim,
                                      self.groups, self.state_size)
        inner = heads * head_dim
        conv_dim = inner + 2 * groups * n
        taps = self.conv_kernel
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=self.compute_dtype,
            param_dtype=self.param_dtype, name=name)
        f32 = jnp.float32

        with jax.named_scope(SSM_IN_PROJ):
            zxbcdt = dense(2 * inner + 2 * groups * n + heads,
                           "in_proj")(u)
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:inner + conv_dim]
        dt = zxbcdt[..., inner + conv_dim:]
        real = (jnp.ones((batch, seq), bool) if mask is None
                else mask.reshape(batch, seq).astype(bool))

        conv_w = self.param("conv_kernel", _conv_init, (taps, conv_dim),
                            f32)
        conv_b = self.param("conv_bias", nn.initializers.zeros,
                            (conv_dim,), f32)
        a_log = self.param("A_log", _a_log_init, (heads,), f32)
        dt_bias = self.param(
            "dt_bias", _dt_bias_init(self.time_step_min,
                                     self.time_step_max,
                                     self.time_step_floor), (heads,), f32)
        d_skip = self.param("D", nn.initializers.ones, (heads,), f32)
        a = -jnp.exp(a_log)

        if self.decode:
            conv_state = self.variable(
                "cache", "conv_state", jnp.zeros,
                (batch, taps - 1, conv_dim), self.compute_dtype)
            ssm_state = self.variable(
                "cache", "ssm_state", jnp.zeros,
                (batch,) + ssm_ops.packed_shape(heads, groups, head_dim,
                                                n), f32)
            before = conv_state.value
        else:
            before = jnp.zeros((batch, taps - 1, conv_dim), xbc.dtype)

        with jax.named_scope(SSM_CONV):
            xbc = jnp.where(real[..., None], xbc, jnp.zeros((), xbc.dtype))
            window = jnp.concatenate([before.astype(xbc.dtype), xbc], 1)
            conv = conv_b + sum(
                conv_w[k] * window[:, k:k + seq].astype(f32)
                for k in range(taps))
            xbc_act = nn.silu(conv).astype(self.compute_dtype)
            if self.decode:
                # The window that ends at the last real token: rows
                # [last + 1, last + taps) of `window` (all of the old
                # state where no token is real).
                last = jnp.max(jnp.where(real, jnp.arange(seq), -1), 1)
                conv_state.value = jax.vmap(
                    lambda w, at: jax.lax.dynamic_slice_in_dim(
                        w, at + 1, taps - 1, 0))(window, last).astype(
                            self.compute_dtype)
        x = xbc_act[..., :inner].reshape(batch, seq, heads, head_dim)
        b = xbc_act[..., inner:inner + groups * n].reshape(
            batch, seq, groups, n)
        c = xbc_act[..., inner + groups * n:].reshape(batch, seq, groups, n)
        dt = jnp.where(real[..., None],
                       jax.nn.softplus(dt.astype(f32) + dt_bias), 0.0)

        with jax.named_scope(SSM_SCAN):
            if self.decode and seq == 1:
                y, ssm_state.value = ssm_ops.ssm_decode_update(
                    ssm_state.value, x[:, 0], dt[:, 0], a, d_skip,
                    b[:, 0], c[:, 0])
                y = y[:, None]
            else:
                start = None
                if self.decode:
                    start = ssm_ops.unpack_state(ssm_state.value, heads,
                                                 groups, head_dim)
                y, final = chunked_scan(x, dt, a, b, c, self.chunk_size,
                                        start, self.compute_dtype)
                y = y + d_skip[:, None] * x.astype(f32)
                if self.decode:
                    ssm_state.value = ssm_ops.pack_state(final, groups)

        with jax.named_scope(SSM_GATE_NORM):
            scale = self.param("scale", nn.initializers.ones,
                               (inner,), f32)
            gated = (y.reshape(batch, seq, inner)
                     * nn.silu(z.astype(f32)))
            grouped = gated.reshape(batch, seq, groups, inner // groups)
            var = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
            normed = (grouped * jax.lax.rsqrt(var + self.norm_eps)
                      ).reshape(batch, seq, inner) * scale
        with jax.named_scope(SSM_OUT_PROJ):
            return dense(d_model, "out_proj")(
                normed.astype(self.compute_dtype))
