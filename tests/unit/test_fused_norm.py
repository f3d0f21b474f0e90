"""Fused RMSNorm(+residual) tail vs flax and the lax reference.

cloud_tpu/ops/fused_norm.py fuses the decoder block's residual add and
pre-norm into one HBM pass. The contract tested here: the lax
reference is BITWISE flax `nn.RMSNorm` (so swapping llama.py's norm
sites changes nothing when the kernel is off), the interpret-mode
Pallas kernel matches to tolerance, gradients flow through the
custom_vjp matching autodiff-of-reference, and the row-padding path
(row count not a block multiple) never leaks pad rows.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.ops import fused_norm

TOL = 1e-5


def _data(rows=6, features=256, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, features)), dtype)
    r = jnp.asarray(rng.normal(size=(rows, features)), dtype)
    scale = jnp.asarray(rng.normal(size=(features,)) * 0.1 + 1.0,
                        jnp.float32)
    return x, r, scale


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_reference_is_bitwise_flax(dtype):
    """The no-residual reference must be indistinguishable from the
    flax module it replaces in llama.py — bitwise, in f32 AND bf16."""
    x, _, scale = _data(dtype=dtype)
    mod = nn.RMSNorm(epsilon=1e-6, dtype=dtype)
    want = mod.apply({"params": {"scale": scale}}, x)
    got, h = fused_norm.rmsnorm_residual_reference(x, scale)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(h), np.asarray(x))


def test_residual_reference_is_flax_of_sum():
    """With a residual, the reference == flax(x + r) and h == x + r —
    the fusion changes memory traffic, not math."""
    x, r, scale = _data()
    mod = nn.RMSNorm(epsilon=1e-6, dtype=jnp.float32)
    want = mod.apply({"params": {"scale": scale}}, x + r)
    got, h = fused_norm.rmsnorm_residual_reference(x, scale,
                                                   residual=r)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(h), np.asarray(x + r))


@pytest.mark.parametrize("with_residual", [False, True])
def test_kernel_parity_f32(with_residual):
    x, r, scale = _data()
    res = r if with_residual else None
    want, want_h = fused_norm.rmsnorm_residual_reference(
        x, scale, residual=res)
    got, got_h = fused_norm.fused_rmsnorm(x, scale, residual=res,
                                          impl="fused", interpret=True)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(got_h, want_h, atol=TOL, rtol=TOL)


def test_kernel_parity_bf16():
    """bf16 activations (the serving/training compute dtype): stats in
    f32 inside the kernel, so parity holds to 1-ulp of bf16."""
    x, r, scale = _data(dtype=jnp.bfloat16)
    want, _ = fused_norm.rmsnorm_residual_reference(x, scale,
                                                    residual=r)
    got, _ = fused_norm.fused_rmsnorm(x, scale, residual=r,
                                      impl="fused", interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=0.05, rtol=0.05)


def test_padding_path():
    """rows not a multiple of block_rows: pad rows are zero-filled in,
    sliced away, and must not perturb the real rows."""
    x, r, scale = _data(rows=5)
    want, _ = fused_norm.rmsnorm_residual_reference(x, scale,
                                                    residual=r)
    got, _ = fused_norm.fused_rmsnorm(x, scale, residual=r,
                                      impl="fused", interpret=True,
                                      block_rows=4)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_3d_leading_dims():
    """llama.py calls the tail on [batch, seq, D]; the row-fold must
    round-trip arbitrary leading dims."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 5, 128)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(2, 5, 128)), jnp.float32)
    scale = jnp.ones((128,), jnp.float32)
    want, want_h = fused_norm.rmsnorm_residual_reference(x, scale,
                                                         residual=r)
    got, got_h = fused_norm.fused_rmsnorm(x, scale, residual=r,
                                          impl="fused", interpret=True)
    assert got.shape == x.shape and got_h.shape == x.shape
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(got_h, want_h, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("with_residual", [False, True])
def test_gradients_match_reference(with_residual):
    """custom_vjp backward vs autodiff of the reference, for x,
    residual, and scale."""
    x, r, scale = _data(rows=4, features=128, seed=1)
    res = r if with_residual else None
    g = jnp.asarray(np.random.default_rng(2).normal(size=x.shape),
                    jnp.float32)

    def fused_loss(*operands):
        if with_residual:
            xx, rr, ss = operands
            normed, h = fused_norm.fused_rmsnorm(
                xx, ss, residual=rr, impl="fused", interpret=True)
        else:
            xx, ss = operands
            normed, h = fused_norm.fused_rmsnorm(
                xx, ss, impl="fused", interpret=True)
        return jnp.sum(normed * g) + jnp.sum(h * g)

    def ref_loss(*operands):
        if with_residual:
            xx, rr, ss = operands
            normed, h = fused_norm.rmsnorm_residual_reference(
                xx, ss, residual=rr)
        else:
            xx, ss = operands
            normed, h = fused_norm.rmsnorm_residual_reference(xx, ss)
        return jnp.sum(normed * g) + jnp.sum(h * g)

    operands = (x, r, scale) if with_residual else (x, scale)
    argnums = tuple(range(len(operands)))
    got = jax.grad(fused_loss, argnums=argnums)(*operands)
    want = jax.grad(ref_loss, argnums=argnums)(*operands)
    for gg, ww in zip(got, want):
        np.testing.assert_allclose(gg, ww, atol=1e-4, rtol=1e-4)


def test_shape_validation():
    x, r, scale = _data()
    with pytest.raises(ValueError, match="scale must be"):
        fused_norm.fused_rmsnorm(x, scale[:-1])
    with pytest.raises(ValueError, match="residual must match"):
        fused_norm.fused_rmsnorm(x, scale, residual=r[:-1])


def test_llama_block_param_tree_unchanged():
    """Swapping llama.py's norm sites to FusedRMSNorm must not change
    the param tree: 'scale' under the same names, so existing
    checkpoints load unchanged."""
    from cloud_tpu.models.llama import LlamaLM

    model = LlamaLM(vocab_size=64, num_layers=1, num_heads=2,
                    d_model=32, d_ff=64, max_seq_len=16)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    block = params["block_0"]
    for name in ("norm_attn", "norm_mlp"):
        assert set(block[name]) == {"scale"}, block[name].keys()
    assert set(params["norm_final"]) == {"scale"}
