"""`NemotronHLM` (models/nemotron_h.py) against the plain reference
(cellbench/reference/nemotron_h.py) at toy widths in float32 on the CPU, on
the benchmark's seeded weights with the published state-space initialisation;
and the expert layer's shares: the parts that four holders of a quarter of
the experts compute add up to the uncut layer.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import harness, weights
from cellbench.drivers import closed_loop_hybrid as driver
from cellbench.reference import common
from cellbench.reference import nemotron_h as reference
from cloud_tpu.models import DeepseekMoE, NemotronHLM
from tests.cellbench import toy_sizes_nemotron_h as toy

CELL = "nemotron3s_decode_reason"


@pytest.fixture(scope="module")
def built():
    cell = copy.deepcopy(harness.load_cell(CELL))
    toy.shrink(cell)
    model = weights.build_model(cell.config)
    shapes = weights.param_shapes(model)
    params = driver.seeded_init(weights.make_params(shapes, 31), cell.config, 31)
    return cell.config, model, params


def test_model_is_the_reference(built):
    """Mamba-2, expert, Mamba-2, attention (no rotation), expert: every kind
    of layer of the pattern, 4 of 16 experts held, a full sequence."""
    cfg, model, params = built
    assert isinstance(model, NemotronHLM) and model.pattern == "MEM*E"
    tokens = harness.rng(5, 1).integers(2, 256, 64).astype(np.int32)
    got = model.apply({"params": params}, jnp.asarray(tokens)[None])[0]
    want, margins, edges = reference.logits_rows(params, cfg, tokens,
                                                 np.arange(64))
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert margins.shape == edges.shape == (2, 64)


def test_seeded_init_sets_the_published_state_space_parameters(built):
    cfg, model, params = built
    plain = weights.make_params(weights.param_shapes(model), 31)
    changed = sorted({jax.tree_util.keystr(path[:3]) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(plain),
        jax.tree_util.tree_leaves(params)) if not np.array_equal(a, b)})
    want = ["['block_{}']['mamba']['{}']".format(i, name) for i in (0, 2)
            for name in ("A_log", "D", "conv_bias", "conv_kernel", "dt_bias")]
    want += ["['block_{}']['moe']['router_bias']".format(i) for i in (1, 4)]
    assert changed == sorted(want)
    mamba = params["block_2"]["mamba"]
    a = np.exp(np.asarray(mamba["A_log"]))
    dt = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    assert dt.min() >= cfg["time_step_min"] * 0.999
    assert dt.max() <= cfg["time_step_max"] * 1.001
    assert np.all(np.asarray(mamba["D"]) == 1.0)
    assert np.abs(np.asarray(mamba["conv_kernel"])).max() <= 0.5
    again = driver.seeded_init(plain, cfg, 31)
    assert np.array_equal(again["block_2"]["mamba"]["A_log"], mamba["A_log"])
    other = driver.seeded_init(plain, cfg, 32)
    assert not np.array_equal(other["block_2"]["mamba"]["A_log"], mamba["A_log"])


def test_the_shares_add_up():
    """Four holders of 4 of 16 experts each: their outputs, with the shared
    expert (which every holder computes whole) counted once, give the uncut
    reference layer. The latent up-projection is linear, so the shares'
    latent sums are projected as one."""
    experts, top_k, d_model, latent, d_ff, shared = 16, 5, 32, 12, 20, 28
    layer = lambda held: DeepseekMoE(
        num_experts=experts, top_k=top_k, d_ff=d_ff,
        routed_scaling_factor=5.0, compute_dtype=jnp.float32,
        activation="relu2", held_experts=held, latent_size=latent,
        shared_d_ff=shared)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, d_model))
    uncut = layer(None).init(jax.random.PRNGKey(1), x)["params"]
    uncut = dict(uncut, router_bias=0.01 * jax.random.normal(
        jax.random.PRNGKey(2), (experts,)))
    assert "expert_gate" not in uncut       # the experts are not gated
    assert uncut["expert_up"].shape == (experts, latent, d_ff)
    mm = common.make_mm("float32")
    u = x.reshape(-1, d_model)
    want, _, _ = reference.expert_layer(u, uncut, tuple(range(experts)), top_k,
                                        5.0, True, mm)
    once = reference.shared_expert(u, uncut["shared"], mm)
    total = 0.0
    for share in range(4):
        held = tuple(range(4 * share, 4 * share + 4))
        params = dict(uncut, expert_up=uncut["expert_up"][4 * share:4 * share + 4],
                      expert_down=uncut["expert_down"][4 * share:4 * share + 4])
        out, _ = layer(held).apply({"params": params}, x)
        # One holder against the reference given the same share.
        part, _, _ = reference.expert_layer(u, params, held, top_k, 5.0, True, mm)
        np.testing.assert_allclose(out.reshape(-1, d_model), part, atol=2e-5)
        total = total + out.reshape(-1, d_model) - once
    np.testing.assert_allclose(total + once, want, atol=5e-5)
    assert float(jnp.max(jnp.abs(want - once))) > 1e-3   # the experts count
