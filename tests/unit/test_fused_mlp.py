"""Fused SwiGLU MLP tail vs flax and the lax reference.

cloud_tpu/ops/fused_mlp.py fuses the gated MLP `down(act(gate(x)) *
up(x))` — the last unfused hot op in the Llama block — into one VMEM
pass. The contract tested here: the lax reference is BITWISE the three
bias-free flax `nn.Dense` projections it replaces in llama.py (so
swapping the SwiGLU tail changes nothing when the kernel is off), the
interpret-mode Pallas kernel matches to tolerance in f32 and bf16,
gradients flow through the custom_vjp matching autodiff-of-reference
for x and all three weights, the row-padding path never leaks pad
rows, and the llama param tree keeps gate/up/down kernels exactly
where the Dense modules kept them.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu import ops
from cloud_tpu.ops import fused_mlp

TOL = 1e-5

_FLAX_ACTS = {
    "silu": nn.silu,
    "gelu_tanh": lambda x: nn.gelu(x, approximate=True),
    "gelu": lambda x: nn.gelu(x, approximate=False),
}


class _FlaxSwiGLU(nn.Module):
    """The three-Dense gated MLP the fused op replaces: bias-free
    gate/up/down projections with `dtype=compute_dtype`, activation on
    the projected values — llama.py's SwiGLU math, module-for-module."""
    d_ff: int
    d_out: int
    dtype: object = None
    activation: str = "silu"

    @nn.compact
    def __call__(self, x):
        act = _FLAX_ACTS[self.activation]
        g = nn.Dense(self.d_ff, use_bias=False, dtype=self.dtype,
                     name="gate")(x)
        u = nn.Dense(self.d_ff, use_bias=False, dtype=self.dtype,
                     name="up")(x)
        return nn.Dense(self.d_out, use_bias=False, dtype=self.dtype,
                        name="down")(act(g) * u)


def _data(rows=6, features=64, d_ff=128, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, features)), dtype)
    w_gate = jnp.asarray(rng.normal(size=(features, d_ff)) * 0.1,
                         jnp.float32)
    w_up = jnp.asarray(rng.normal(size=(features, d_ff)) * 0.1,
                       jnp.float32)
    w_down = jnp.asarray(rng.normal(size=(d_ff, features)) * 0.1,
                         jnp.float32)
    return x, w_gate, w_up, w_down


def _flax_apply(x, w_gate, w_up, w_down, dtype, activation="silu"):
    mod = _FlaxSwiGLU(d_ff=w_gate.shape[1], d_out=w_down.shape[1],
                      dtype=dtype, activation=activation)
    params = {"gate": {"kernel": w_gate}, "up": {"kernel": w_up},
              "down": {"kernel": w_down}}
    return mod.apply({"params": params}, x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_reference_is_bitwise_flax(dtype):
    """The reference must be indistinguishable from the three flax
    Dense modules it replaces in llama.py — bitwise, in f32 AND bf16
    (same casts, same contractions, same activation point)."""
    x, w_gate, w_up, w_down = _data(dtype=dtype)
    want = _flax_apply(x, w_gate, w_up, w_down, dtype)
    got = fused_mlp.swiglu_reference(x, w_gate, w_up, w_down,
                                     compute_dtype=dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("activation", ["gelu_tanh", "gelu"])
def test_activation_variants_bitwise_flax(activation):
    """The Gemma-family gate activations route through the same
    reference, still bitwise flax."""
    x, w_gate, w_up, w_down = _data(seed=2)
    want = _flax_apply(x, w_gate, w_up, w_down, jnp.float32,
                       activation=activation)
    got = fused_mlp.swiglu_reference(x, w_gate, w_up, w_down,
                                     activation=activation,
                                     compute_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_parity_f32():
    x, w_gate, w_up, w_down = _data()
    want = fused_mlp.swiglu_reference(x, w_gate, w_up, w_down)
    got = fused_mlp.fused_swiglu(x, w_gate, w_up, w_down,
                                 impl="fused", interpret=True)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_kernel_parity_bf16():
    """bf16 activations (the serving/training compute dtype): the
    kernel keeps the reference's rounding points, so parity holds to
    bf16 tolerance."""
    x, w_gate, w_up, w_down = _data(dtype=jnp.bfloat16)
    want = fused_mlp.swiglu_reference(x, w_gate, w_up, w_down,
                                      compute_dtype=jnp.bfloat16)
    got = fused_mlp.fused_swiglu(x, w_gate, w_up, w_down,
                                 compute_dtype=jnp.bfloat16,
                                 impl="fused", interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=0.05, rtol=0.05)


def test_padding_path():
    """rows not a multiple of block_rows: pad rows are zero-filled in,
    sliced away, and must not perturb the real rows."""
    x, w_gate, w_up, w_down = _data(rows=5)
    want = fused_mlp.swiglu_reference(x, w_gate, w_up, w_down)
    got = fused_mlp.fused_swiglu(x, w_gate, w_up, w_down,
                                 impl="fused", interpret=True,
                                 block_rows=4)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_3d_leading_dims():
    """llama.py calls the tail on [batch, seq, D]; the row-fold must
    round-trip arbitrary leading dims."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 5, 64)), jnp.float32)
    _, w_gate, w_up, w_down = _data()
    want = fused_mlp.swiglu_reference(x, w_gate, w_up, w_down)
    got = fused_mlp.fused_swiglu(x, w_gate, w_up, w_down,
                                 impl="fused", interpret=True)
    assert got.shape == x.shape[:-1] + (w_down.shape[1],)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_gradients_match_reference():
    """custom_vjp backward vs autodiff of the reference, for x and all
    three weight matrices."""
    x, w_gate, w_up, w_down = _data(rows=4, seed=1)
    g = jnp.asarray(
        np.random.default_rng(2).normal(size=(4, w_down.shape[1])),
        jnp.float32)

    def fused_loss(xx, wg, wu, wd):
        out = fused_mlp.fused_swiglu(xx, wg, wu, wd, impl="fused",
                                     interpret=True)
        return jnp.sum(out * g)

    def ref_loss(xx, wg, wu, wd):
        out = fused_mlp.swiglu_reference(xx, wg, wu, wd)
        return jnp.sum(out * g)

    got = jax.grad(fused_loss, argnums=(0, 1, 2, 3))(
        x, w_gate, w_up, w_down)
    want = jax.grad(ref_loss, argnums=(0, 1, 2, 3))(
        x, w_gate, w_up, w_down)
    for gg, ww in zip(got, want):
        np.testing.assert_allclose(gg, ww, atol=1e-4, rtol=1e-4)


def test_shape_validation():
    x, w_gate, w_up, w_down = _data()
    with pytest.raises(ValueError, match="w_gate must be"):
        fused_mlp.fused_swiglu(x, w_gate[:-1], w_up, w_down)
    with pytest.raises(ValueError, match="w_up must match"):
        fused_mlp.fused_swiglu(x, w_gate, w_up[:, :-1], w_down)
    with pytest.raises(ValueError, match="w_down must be"):
        fused_mlp.fused_swiglu(x, w_gate, w_up, w_down[:-1])


def test_unknown_activation_raises():
    x, w_gate, w_up, w_down = _data()
    with pytest.raises(ValueError, match="Unknown mlp activation"):
        fused_mlp.swiglu_reference(x, w_gate, w_up, w_down,
                                   activation="swish2")
    with pytest.raises(ValueError, match="Unknown mlp activation"):
        fused_mlp.fused_swiglu(x, w_gate, w_up, w_down,
                               activation="swish2", impl="fused",
                               interpret=True)


def test_llama_block_param_tree_unchanged():
    """Swapping llama.py's SwiGLU tail to the fused op must not change
    the param tree: gate/up/down kernels under the same names, so
    existing checkpoints load unchanged."""
    from cloud_tpu.models.llama import LlamaLM

    model = LlamaLM(vocab_size=64, num_layers=1, num_heads=2,
                    d_model=32, d_ff=64, max_seq_len=16)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    mlp = params["block_0"]["mlp"]
    assert set(mlp) == {"gate", "up", "down"}, mlp.keys()
    for name in ("gate", "up", "down"):
        assert set(mlp[name]) == {"kernel"}, mlp[name].keys()
    assert mlp["gate"]["kernel"].shape == (32, 64)
    assert mlp["down"]["kernel"].shape == (64, 32)


def test_llama_forward_matches_reference_impl(monkeypatch):
    """An end-to-end llama forward with the kernel forced on (interpret
    mode) agrees with the forward with it forced off to bf16 rounding
    — the wiring never changes the math."""
    from cloud_tpu.models.llama import LlamaLM

    model = LlamaLM(vocab_size=64, num_layers=1, num_heads=2,
                    d_model=32, d_ff=64, max_seq_len=16)
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, (1, 8)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    # SwiGLU imports `cloud_tpu.ops.fused_swiglu` at call time.
    entered = []

    def side(impl):
        def call(*args, **kwargs):
            entered.append(impl)
            kwargs["impl"] = impl
            return fused_mlp.fused_swiglu(*args, **kwargs)
        return call

    monkeypatch.setattr(ops, "fused_swiglu", side("reference"))
    want = model.apply({"params": params}, tokens)
    monkeypatch.setattr(ops, "fused_swiglu", side("fused"))
    got = model.apply({"params": params}, tokens)
    assert entered == ["reference", "fused"]
    # bf16 logits: two ulps (2 * 2**-8) at the largest magnitude.
    atol = 2 * 2.0 ** -8 * float(np.abs(np.asarray(want)).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=atol, rtol=0)
