"""The Mamba-2 mixer (models/mamba2.py) and its decode kernel (ops/ssm.py),
at toy widths in float32 on the CPU, with the family's published
initialisation of the state-space parameters (the module's own).

The chunked form is the sequential recurrence; a prefill right-padded to a
bucket followed by one-token steps is the full sequence; a prefill in pieces
is a prefill in one; a pad moves neither the state nor the convolution's
window; the kernel (interpreted) is the `jax.numpy` form.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.models import mamba2
from cloud_tpu.ops import ssm

F32 = jnp.float32
HEADS, HEAD_DIM, GROUPS, STATE, CHUNK, D_MODEL = 4, 8, 2, 16, 8, 24


def sequential(x, dt, a, b, c, start):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t."""
    per_group = x.shape[2] // b.shape[2]
    bh, ch = (jnp.repeat(v, per_group, axis=2) for v in (b, c))

    def step(state, token):
        x_t, dt_t, b_t, c_t = token
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    rows = lambda v: jnp.moveaxis(v, 1, 0)
    final, y = jax.lax.scan(step, start, (rows(x), rows(dt), rows(bh),
                                          rows(ch)))
    return jnp.moveaxis(y, 0, 1), final


def scan_inputs(seq, batch=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (batch, seq, HEADS, HEAD_DIM))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, HEADS)) - 2)
    a = -jnp.exp(jax.random.normal(keys[2], (HEADS,)))
    b = jax.random.normal(keys[3], (batch, seq, GROUPS, STATE))
    c = jax.random.normal(keys[4], (batch, seq, GROUPS, STATE))
    start = jax.random.normal(keys[5], (batch, HEADS, HEAD_DIM, STATE))
    return x, dt, a, b, c, start


@pytest.mark.parametrize("seq", [1, 5, 8, 16, 37, 48])
def test_chunked_form_is_the_sequential_recurrence(seq):
    """Lengths that are and are not multiples of the chunk (8), from a state
    that is not zero."""
    x, dt, a, b, c, start = scan_inputs(seq)
    want_y, want_state = sequential(x, dt, a, b, c, start)
    y, state = mamba2.chunked_scan(x, dt, a, b, c, CHUNK, start)
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=1e-5)


def test_a_token_whose_dt_is_zero_moves_no_state():
    x, dt, a, b, c, start = scan_inputs(12)
    dt = dt.at[:, 5:].set(0.0)
    _, state = mamba2.chunked_scan(x, dt, a, b, c, CHUNK, start)
    _, want = mamba2.chunked_scan(x[:, :5], dt[:, :5], a, b[:, :5], c[:, :5],
                                  CHUNK, start)
    np.testing.assert_allclose(state, want, atol=1e-6)


@pytest.fixture(scope="module")
def mixer():
    model = mamba2.Mamba2Mixer(HEADS, HEAD_DIM, GROUPS, STATE, chunk_size=CHUNK,
                               compute_dtype=F32)
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 29, D_MODEL))
    params = model.init(jax.random.PRNGKey(4), u)["params"]
    # The published initialisation: A in -[1, 16], dt in [1e-3, 0.1].
    assert np.all(np.exp(params["A_log"]) >= 1.0)
    assert np.all(np.exp(params["A_log"]) <= 16.0)
    dt = jax.nn.softplus(params["dt_bias"])
    assert float(dt.min()) >= 1e-3 - 1e-9 and float(dt.max()) <= 0.1 + 1e-6
    return model, params, u


def decode_apply(model, params, cache, u, mask=None):
    out, new = model.clone(decode=True).apply(
        {"params": params, "cache": cache}, u, mask, mutable=["cache"])
    return out, new["cache"]


def empty_cache(model, params, batch=1):
    shapes = jax.eval_shape(
        lambda: model.clone(decode=True).init(
            jax.random.PRNGKey(0), jnp.zeros((batch, 1, D_MODEL))))["cache"]
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


@pytest.mark.parametrize("prompt,bucket", [(13, 16), (16, 16), (5, 8)])
def test_right_padded_prefill_then_steps_is_the_full_sequence(mixer, prompt,
                                                              bucket):
    model, params, u = mixer
    want = model.apply({"params": params}, u)
    padded = jnp.zeros((1, bucket, D_MODEL)).at[:, :prompt].set(u[:, :prompt])
    # Pads carry anything: they are masked, not zero.
    padded = padded.at[:, prompt:].set(7.0)
    mask = (jnp.arange(bucket) < prompt)[None]
    out, cache = decode_apply(model, params, empty_cache(model, params),
                              padded, mask)
    np.testing.assert_allclose(out[:, :prompt], want[:, :prompt], atol=2e-5)
    for t in range(prompt, u.shape[1]):
        out, cache = decode_apply(model, params, cache, u[:, t:t + 1])
        np.testing.assert_allclose(out[:, 0], want[:, t], atol=2e-5)


def test_prefill_in_pieces_is_prefill_in_one(mixer):
    model, params, u = mixer
    _, whole = decode_apply(model, params, empty_cache(model, params), u)
    cache = empty_cache(model, params)
    for lo, hi in ((0, 8), (8, 16), (16, 29)):
        _, cache = decode_apply(model, params, cache, u[:, lo:hi])
    for name in ("conv_state", "ssm_state"):
        np.testing.assert_allclose(cache[name], whole[name], atol=2e-5)


def test_pads_and_idle_slots_move_nothing(mixer):
    """A padded prefill leaves the state of the bare prompt; a tick's masked
    row keeps state and window bit for bit while its neighbour advances."""
    model, params, u = mixer
    _, bare = decode_apply(model, params, empty_cache(model, params), u[:, :11])
    padded = jnp.full((1, 16, D_MODEL), 3.0).at[:, :11].set(u[:, :11])
    _, cache = decode_apply(model, params, empty_cache(model, params), padded,
                            (jnp.arange(16) < 11)[None])
    for name in ("conv_state", "ssm_state"):
        np.testing.assert_allclose(cache[name], bare[name], atol=1e-6)
    two = jax.tree_util.tree_map(lambda a: jnp.concatenate([a, a]), cache)
    step = jnp.concatenate([u[:, 11:12], u[:, 11:12]])
    _, after = decode_apply(model, params, two, step,
                            jnp.asarray([[True], [False]]))
    for name in ("conv_state", "ssm_state"):
        assert np.array_equal(after[name][1], cache[name][0])
        assert not np.array_equal(after[name][0], cache[name][0])


@pytest.mark.parametrize("shape", [(3, 4, 8, 16, 2), (2, 8, 64, 128, 2)],
                         ids=["toy", "lane_dense"])
def test_decode_kernel_is_the_jnp_form(shape):
    """(slots, heads, head_dim, state, groups); the second fills whole lanes
    as the published widths do (two heads of 64 side by side)."""
    slots, heads, head_dim, n, groups = shape
    keys = jax.random.split(jax.random.PRNGKey(1), 7)
    natural = jax.random.normal(keys[0], (slots, heads, head_dim, n))
    x = jax.random.normal(keys[1], (slots, heads, head_dim))
    dt = jax.nn.softplus(jax.random.normal(keys[2], (slots, heads)) - 2)
    dt = dt.at[1].set(0.0)                      # an idle slot
    a = -jnp.exp(jax.random.normal(keys[3], (heads,)))
    d = jax.random.normal(keys[4], (heads,))
    b = jax.random.normal(keys[5], (slots, groups, n))
    c = jax.random.normal(keys[6], (slots, groups, n))
    want_y, want_state = sequential(
        x[:, None], dt[:, None], a, b[:, None], c[:, None], natural)
    want_y = want_y[:, 0] + d[:, None] * x
    packed = ssm.pack_state(natural, groups)
    assert packed.shape[1:] == ssm.packed_shape(heads, groups, head_dim, n)
    assert np.array_equal(ssm.unpack_state(packed, heads, groups, head_dim),
                          natural)
    assert ssm.kernel_fits(packed.shape, groups) == (head_dim == 64)
    for impl in ("reference", "kernel"):
        y, state = ssm.ssm_decode_update(packed, x, dt, a, d, b, c, impl=impl)
        np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(
            ssm.unpack_state(state, heads, groups, head_dim), want_state,
            atol=1e-6, rtol=1e-6)
        assert np.array_equal(state[1], packed[1])
    with pytest.raises(ValueError, match="impl"):
        ssm.ssm_decode_update(packed, x, dt, a, d, b, c, impl="fast")
