"""The two forms of `moe.routed_expert_ffn`, grouped (sort/segment) and
batched over the held experts: against the dense one-hot dispatch they
replaced (kept here as the oracle, on the shapes the one-hot's own tests
used) and against each other, with a binding capacity, under a held-experts
share, the shares of an expert-parallel layout summed back to the uncut
layer, and the rule that picks a form held to the cells' own shapes. Small
sizes, seeded weights, on the CPU."""

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
    shape, at a T where the one-hot held [T, E, T] (and past the rows up to
    which the batched form, which does hold a [T, E] matrix of gates, is
    taken)."""
    tokens, experts = 512, 12
    assert not moe.batched_over_held(tokens, 3, experts, None)
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


class RoutedAny(nn.Module):
    """`routed_expert_ffn` with every argument the two forms share."""
    num_experts: int
    held: tuple
    gated: bool
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x2d, top_idx, gates, token_mask):
        act = jax.nn.silu if self.gated else moe.PLAIN_ACTIVATIONS["relu2"]
        return moe.routed_expert_ffn(
            self, x2d, top_idx, gates, self.num_experts, FF, None, act,
            self.dtype, held_experts=self.held, token_mask=token_mask,
            gated=self.gated)


def _primitives(fn, *args):
    """Names of the primitives `fn` traces to, calls walked into."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    return set(walk(jax.make_jaxpr(fn)(*args).jaxpr))


# Between the forms, and of either to the float32 sum an expert at a time.
# bfloat16: a pair's products are the same numbers on both forms (the same
# operands, accumulated in float32, rounded once); what may differ is the
# order of a token's float32 sum and so its one rounding to bfloat16, an ulp
# (2^-8) of an output of up to 8. The reference keeps float32 where the
# forms round x, the weights, the hidden row and each product.
FORMS_AGREE = {F32: dict(atol=2e-6, rtol=2e-5),
               jnp.bfloat16: dict(atol=2 ** -7, rtol=2 ** -7)}
REFERENCE = {F32: dict(atol=2e-6, rtol=2e-5),
             jnp.bfloat16: dict(atol=8e-2, rtol=2e-2)}


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("top_k", [1, 3])
@pytest.mark.parametrize("pads", [False, True], ids=["full", "pads"])
@pytest.mark.parametrize("held", [None, (6, 1, 4)], ids=["all", "share"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu2"])
def test_batched_grouped_and_one_hot_agree(monkeypatch, gated, held, pads,
                                           top_k, dtype):
    """One routing through both forms and through the one-hot sum an
    expert at a time: the same output, and the same counters but the one
    that says which form ran."""
    experts, tokens = 8, B * S
    x, top_idx, gates = _routing(experts, top_k, seed=top_k)
    mask = jnp.ones((tokens,), bool)
    if pads:
        mask = mask.at[3:9].set(False)
    model = RoutedAny(experts, held, gated, dtype)
    params = model.init(jax.random.PRNGKey(2), x, top_idx, gates,
                        mask)["params"]
    outs, sown = {}, {}
    for form, batched in (("grouped", False), ("batched", True)):
        monkeypatch.setattr(moe, "batched_over_held", lambda *a: batched)
        run = lambda *a: model.apply({"params": params}, *a,
                                     mutable=[moe.MOE_STATS])
        assert ("ragged_dot_general" in _primitives(run, x, top_idx, gates, mask)
                ) == (not batched)
        out, stats = run(x, top_idx, gates, mask)
        outs[form] = np.asarray(out, np.float32)
        sown[form] = {name: np.asarray(v[0]).tolist()
                      for name, v in stats[moe.MOE_STATS].items()}
    ids = tuple(range(experts)) if held is None else held
    want = jnp.zeros_like(x)
    act = jax.nn.silu if gated else moe.PLAIN_ACTIVATIONS["relu2"]
    for row, expert in enumerate(ids):
        weight = jnp.sum(jnp.where(top_idx == expert, gates, 0.0),
                         axis=-1) * mask
        up = x @ params["expert_up"][row]
        hidden = act(x @ params["expert_gate"][row]) * up if gated else act(up)
        want = want + weight[:, None] * (hidden @ params["expert_down"][row])
    np.testing.assert_allclose(outs["batched"], outs["grouped"],
                               **FORMS_AGREE[dtype])
    for form in outs:
        np.testing.assert_allclose(outs[form], np.asarray(want),
                                   **REFERENCE[dtype])
    assert out.dtype == dtype
    # A pad's pairs go nowhere on either form.
    assert not pads or not outs["batched"][3:9].any()
    chosen = np.asarray(top_idx)[np.asarray(mask)]
    load = [int((chosen == e).sum()) for e in ids]
    real = int(mask.sum())
    for form, dense in (("grouped", 0), ("batched", real * len(ids))):
        assert sown[form] == {
            "pairs_held": sum(load), "expert_load": load,
            "experts_touched": sum(n > 0 for n in load),
            "pairs_dense": dense}, form


# The rule at the cells' own shapes: rows, choices of experts, held,
# widths, gated, capacity -> batched?
RULE_CASES = {
    "nemotron_tick": (128, 22, 512, 128, 1024, 2688, False, None, True),
    "nemotron_prefill_256": (256, 22, 512, 128, 1024, 2688, False, None,
                             True),
    "nemotron_prefill_1024": (1024, 22, 512, 128, 1024, 2688, False, None,
                              False),
    "kexaone_tick": (32, 8, 128, 16, 6144, 2048, True, None, False),
    "kexaone_prefill_512": (512, 8, 128, 16, 6144, 2048, True, None, False),
    # A capacity factor of 2 at the nemotron tick's rows: 2 x 128 x 22 / 512.
    "capacity_factor": (128, 22, 512, 128, 1024, 2688, False, 11, False),
    # The block tick: 64 slots x 4 rows choosing 8 of 128, all held.
    "sdar_tick": (256, 8, 128, 128, 2048, 768, True, None, True),
    "sdar_prefill_512": (512, 8, 128, 128, 2048, 768, True, None, False),
    # 32 slots choosing 8 of 192, 12 held: 74 % touched.
    "axk1_tick": (32, 8, 192, 12, 7168, 2048, True, None, False),
}


class RoutedAt(nn.Module):
    shape: tuple

    @nn.compact
    def __call__(self, x2d, top_idx, gates):
        _, _, experts, held, _, d_ff, gated, capacity, _ = self.shape
        return moe.routed_expert_ffn(
            self, x2d, top_idx, gates, experts, d_ff, capacity,
            jax.nn.silu if gated else moe.PLAIN_ACTIVATIONS["relu2"],
            jnp.bfloat16, held_experts=tuple(range(held)),
            param_dtype=jnp.bfloat16, gated=gated)


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_the_form_follows_the_shapes(case):
    """Traced at the published widths (shapes only, nothing is allocated):
    `ragged_dot` is in the program exactly where the rule says grouped, and
    the weights enter a batched product as they are stored."""
    shape = RULE_CASES[case]
    tokens, top_k, experts, held, d_model, d_ff, gated, capacity, want = shape
    assert moe.batched_over_held(tokens, top_k, experts, capacity) == want
    model = RoutedAt(shape)
    args = (jax.ShapeDtypeStruct((tokens, d_model), jnp.bfloat16),
            jax.ShapeDtypeStruct((tokens, top_k), jnp.int32),
            jax.ShapeDtypeStruct((tokens, top_k), F32))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), *args)
    assert params["params"]["expert_up"].shape == (held, d_model, d_ff)
    jaxpr = jax.make_jaxpr(model.apply)(params, *args)
    names = [eqn.primitive.name for eqn in jaxpr.jaxpr.eqns]
    assert ("ragged_dot_general" in names) == (not want)
    products = [eqn for eqn in jaxpr.jaxpr.eqns
                if eqn.primitive.name == "dot_general"]
    assert len(products) == (0 if not want else 3 if gated else 2)
    for eqn in products:      # the expert is a batch dimension of both
        assert eqn.params["dimension_numbers"] == (((2,), (1,)), ((0,), (0,)))
        assert eqn.outvars[0].aval.dtype == jnp.bfloat16
    stacked = lambda v: len(getattr(v.aval, "shape", ())) == 3 and (
        v.aval.shape[0] == held)
    assert not [eqn for eqn in jaxpr.jaxpr.eqns
                if eqn.primitive.name == "transpose"
                and any(stacked(v) for v in eqn.invars)]


def test_the_ridge_is_the_chips_own():
    """`DENSE_MAX_ROWS` is the derivation beside it, from the peak the MFU
    gauge divides by and the fastest read the grouped kernel has shown on
    the chip; the ridge, from the v5e's published 819 GB/s, lies below
    it."""
    from cloud_tpu.monitoring.telemetry import PEAK_TFLOPS
    peak = PEAK_TFLOPS["TPU v5 lite"] * 1e12
    assert moe.HBM_BYTES_PER_S == 819e9
    assert int(peak * 2 / (2 * moe.HBM_BYTES_PER_S)) == 240
    assert moe.GROUPED_BEST_BYTES_PER_S == 480e9
    limit = peak * 2 / (2 * moe.GROUPED_BEST_BYTES_PER_S)
    assert moe.DENSE_MAX_ROWS == int(limit) == 410
    assert moe.batched_over_held(410, 22, 512, None)
    assert not moe.batched_over_held(411, 22, 512, None)
    assert moe.batched_over_held(241, 22, 512, None)      # past the ridge
    # (c): 0.95 of the held experts expected touched.
    assert moe.batched_over_held(64, 8, 128, None)        # 98.4 %
    assert not moe.batched_over_held(32, 8, 128, None)    # 87.3 %
    assert moe.batched_over_held(128, 22, 512, 128)       # nothing is shed
    assert not moe.batched_over_held(128, 22, 512, 127)


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
