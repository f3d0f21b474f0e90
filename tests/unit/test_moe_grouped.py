"""The grouped (sort/segment) expert dispatch of `moe.routed_expert_ffn`:
against the dense one-hot dispatch it replaced (kept here as the oracle, on
the shapes the one-hot's own tests used), with a binding capacity, under a
held-experts share, and the shares of an expert-parallel layout summed back
to the uncut layer. Small sizes, float32, seeded weights, on the CPU."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.models import moe
from cloud_tpu.models.deepseek import DeepseekMoE
from cloud_tpu.models.llama import SwiGLU

F32 = jnp.float32
B, S, D, FF = 2, 16, 8, 16


def one_hot_expert_ffn(x2d, top_idx, gates, w_gate, w_up, w_down, capacity,
                       act=jax.nn.silu):
    """The dense-dispatch computation `routed_expert_ffn` had before the
    grouped one: dispatch[t, e, c] one-hots, slot-major capacity."""
    tokens = x2d.shape[0]
    num_experts, k = w_gate.shape[0], top_idx.shape[1]
    sel = jax.nn.one_hot(top_idx, num_experts, dtype=F32)
    sel_sm = jnp.transpose(sel, (1, 0, 2)).reshape(k * tokens, num_experts)
    position = (jnp.cumsum(sel_sm, axis=0) - 1.0) * sel_sm
    keep = (position < capacity).astype(F32) * sel_sm
    slot = jnp.sum(position * keep, axis=-1).astype(jnp.int32)
    slot_oh = jax.nn.one_hot(slot, capacity, dtype=F32)
    disp = (keep[:, :, None] * slot_oh[:, None, :]).reshape(
        k, tokens, num_experts, capacity)
    dispatch = disp.sum(axis=0)
    gates_sm = jnp.transpose(gates, (1, 0)).reshape(k, tokens)
    combine = (disp * gates_sm[:, :, None, None]).sum(axis=0)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x2d)
    g = jnp.einsum("ecd,edf->ecf", expert_in, w_gate)
    u = jnp.einsum("ecd,edf->ecf", expert_in, w_up)
    expert_out = jnp.einsum("ecf,efd->ecd", act(g) * u, w_down)
    return jnp.einsum("tec,ecd->td", combine, expert_out)


class Routed(nn.Module):
    """`routed_expert_ffn` under a routing the test gives."""
    num_experts: int
    capacity: int = None
    held: tuple = None

    @nn.compact
    def __call__(self, x2d, top_idx, gates, token_mask=None):
        return moe.routed_expert_ffn(
            self, x2d, top_idx, gates, self.num_experts, FF, self.capacity,
            jax.nn.silu, F32, held_experts=self.held, token_mask=token_mask)


def _routing(num_experts, top_k, tokens=B * S, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(tokens, D)), F32)
    scores = rng.random((tokens, num_experts))
    top_idx = jnp.asarray(np.argsort(-scores, axis=1)[:, :top_k], jnp.int32)
    gates = jnp.asarray(rng.random((tokens, top_k)), F32)
    return x, top_idx, gates


CASES = [  # (experts, top_k, capacity): None = drop-free
    (4, 1, None), (4, 2, None), (4, 3, None), (8, 2, None),
    (4, 2, 4), (4, 2, 1), (8, 6, 8)]


@pytest.mark.parametrize("num_experts,top_k,capacity", CASES)
def test_grouped_dispatch_equals_the_one_hot(num_experts, top_k, capacity):
    x, top_idx, gates = _routing(num_experts, top_k)
    model = Routed(num_experts, capacity)
    params = model.init(jax.random.PRNGKey(2), x, top_idx, gates)
    got = model.apply(params, x, top_idx, gates)
    p = params["params"]
    want = one_hot_expert_ffn(
        x, top_idx, gates, p["expert_gate"], p["expert_up"],
        p["expert_down"], x.shape[0] if capacity is None else capacity)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-5)
    if capacity == 1:        # the capacity binds: something was shed
        free = Routed(num_experts).apply(params, x, top_idx, gates)
        assert not np.allclose(np.asarray(free), np.asarray(got))


class RoutedPlain(nn.Module):
    """`routed_expert_ffn` with experts that are not gated (`relu2`)."""
    num_experts: int
    held: tuple = None

    @nn.compact
    def __call__(self, x2d, top_idx, gates):
        return moe.routed_expert_ffn(
            self, x2d, top_idx, gates, self.num_experts, FF, None,
            moe.PLAIN_ACTIVATIONS["relu2"], F32, held_experts=self.held,
            gated=False)


@pytest.mark.parametrize("held", [None, (1, 4, 6)], ids=["all", "share"])
def test_plain_experts_are_two_grouped_products(held):
    """`down(relu(up(x))^2)` a chosen expert, weighted and summed: no gate
    parameter, the same sort, the same held share and counters."""
    experts, top_k = 8, 3
    x, top_idx, gates = _routing(experts, top_k)
    model = RoutedPlain(experts, held)
    params = model.init(jax.random.PRNGKey(2), x, top_idx, gates)["params"]
    rows = experts if held is None else len(held)
    assert set(params) == {"expert_up", "expert_down"}
    assert params["expert_up"].shape == (rows, D, FF)
    got, sown = model.apply({"params": params}, x, top_idx, gates,
                            mutable=[moe.MOE_STATS])
    want = jnp.zeros_like(x)
    for row, expert in enumerate(range(experts) if held is None else held):
        weight = jnp.sum(jnp.where(top_idx == expert, gates, 0.0), axis=-1)
        hidden = jnp.square(jax.nn.relu(x @ params["expert_up"][row]))
        want = want + weight[:, None] * (hidden @ params["expert_down"][row])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-5)
    pairs = int(np.isin(np.asarray(top_idx),
                        range(experts) if held is None else held).sum())
    assert int(sown[moe.MOE_STATS]["pairs_held"][0]) == pairs


def test_no_token_by_expert_mask_is_built():
    """No array of the dispatch has a tokens x experts (x anything)
    shape, at a T where the one-hot held [T, E, T]."""
    tokens, experts = 96, 12
    x, top_idx, gates = _routing(experts, 3, tokens=tokens)
    model = Routed(experts)
    params = model.init(jax.random.PRNGKey(2), x, top_idx, gates)
    jaxpr = jax.make_jaxpr(lambda *a: model.apply(params, *a))(
        x, top_idx, gates)
    shapes = {tuple(v.aval.shape) for eqn in jaxpr.jaxpr.eqns
              for v in eqn.outvars}
    assert not [s for s in shapes
                if len(s) >= 2 and s[0] in (tokens, 3 * tokens)
                and s[1] == experts]


def _layer(held=None, experts=16, top_k=4):
    return DeepseekMoE(num_experts=experts, top_k=top_k, d_ff=FF,
                       routed_scaling_factor=2.5, compute_dtype=F32,
                       held_experts=held)


def _uncut(experts=16):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(B, S, D)), F32)
    full = _layer(experts=experts)
    params = full.init(jax.random.PRNGKey(4), x)["params"]
    params = dict(params, router_bias=jnp.asarray(
        0.1 * rng.normal(size=(experts,)), F32))
    return full, params, x


def _share(params, held):
    rows = jnp.asarray(held)
    return dict(params, **{name: params[name][rows] for name in (
        "expert_gate", "expert_up", "expert_down")})


def test_the_shares_add_up_to_the_uncut_layer():
    """4 holders of 4 experts each: their partial sums, the shared expert
    counted once, are the uncut layer's output."""
    full, params, x = _uncut()
    want, _ = full.apply({"params": params}, x)
    shared = SwiGLU(FF, F32).apply({"params": params["shared"]}, x)
    total = jnp.zeros_like(want)
    for first in range(0, 16, 4):
        held = tuple(range(first, first + 4))
        part, _ = _layer(held).apply(
            {"params": _share(params, held)}, x)
        total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_a_share_holds_only_its_experts_and_counts_its_pairs():
    full, params, x = _uncut()
    held = (1, 5, 6, 14)          # any ids, in this order of rows
    layer = _layer(held)
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"]
    assert shapes["expert_gate"].shape == (4, D, FF)
    assert shapes["router"].shape == (D, 16)      # routes over all 16
    mask = jnp.ones((B, S), bool).at[0, :5].set(False)     # 5 pads
    (out, _), sown = layer.apply(
        {"params": _share(params, held)}, x, token_mask=mask,
        mutable=[moe.MOE_STATS])
    stats = {k: v[0] for k, v in sown[moe.MOE_STATS].items()}
    # The layer's own choice, recomputed from the router.
    scores = jax.nn.sigmoid(x.reshape(-1, D) @ params["router"])
    _, chosen = jax.lax.top_k(scores + params["router_bias"], 4)
    real = np.asarray(mask).reshape(-1)
    chosen = np.asarray(chosen)[real]
    load = [int((chosen == e).sum()) for e in held]
    assert int(stats["pairs_routed"]) == real.sum() * 4
    assert np.asarray(stats["expert_load"]).tolist() == load
    assert int(stats["pairs_held"]) == sum(load)
    assert int(stats["experts_touched"]) == sum(n > 0 for n in load)
    # A pad's routed part is nothing: only the shared expert speaks.
    shared = SwiGLU(FF, F32).apply({"params": params["shared"]}, x)
    np.testing.assert_allclose(np.asarray(out[0, :5]),
                               np.asarray(shared[0, :5]), atol=1e-6)
    # No counter is a variable of init.
    assert set(layer.init(jax.random.PRNGKey(0), x)) == {"params"}


def test_held_ids_are_checked():
    x, top_idx, gates = _routing(4, 2)
    for held in ((0, 0), (0, 4), (-1,)):
        with pytest.raises(ValueError):
            Routed(4, held=held).init(jax.random.PRNGKey(0), x, top_idx,
                                      gates)
