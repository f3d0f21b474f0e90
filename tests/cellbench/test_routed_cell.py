"""Tier-1 checks of the routed cell (`kexaone_decode_long`), on the CPU at
toy widths: the configuration loads by name and builds the program's class at
its published widths; the program agrees with the plain reference, as a whole
forward pass and through the Scheduler's prefill, paged pool and decode; the
int8 control and a planted expert swap fail the driver's comparison while a
sound run passes; the counts against hand-worked numbers; each new reader on
a synthetic trace, and on an empty one. No number here is a device metric.
"""

import copy

import numpy as np
import pytest

from cellbench import harness, tracing, weights
from cellbench.counts import exaone_moe as counts
from cellbench.drivers import closed_loop_routed as driver
from cellbench.reference import exaone_moe as reference
from cellbench.tools import readings_routed
from tests.cellbench import conftest

CELL = "kexaone_decode_long"
NEW_READERS = ("tick_mfu.moe", "moe_experts_roofline", "moe_tick_share_pct.serve",
               "paged_attn_mixed_roofline", "expert_load_max_over_mean.serve")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_configuration_loads_by_name_at_its_published_widths():
    import jax

    cell = harness.load_cell(CELL)
    cfg = cell.config
    assert cfg["family"] == "exaone_moe" and cell.traffic["driver"] == (
        "closed_loop_routed")
    assert set(cfg["reduced"]) == set(cfg["published"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    model = weights.build_model(cfg)
    assert type(model).__name__ == "LlamaLM"
    assert (model.d_model, model.num_heads, model.num_kv_heads, model.head_dim,
            model.d_ff, model.moe_d_ff, model.moe_experts, model.moe_top_k) == (
                6144, 64, 8, 128, 18432, 2048, 128, 8)
    assert model.attn_kinds == ("local", "local", "local", "global")
    assert model.moe_held_experts == tuple(range(16))
    shapes = weights.param_shapes(model)
    leaves = jax.tree_util.tree_leaves(shapes)
    assert round(sum(l.size for l in leaves) / 1e9, 2) == 3.71
    assert shapes["block_1"]["moe"]["expert_gate"].shape == (16, 6144, 2048)
    assert shapes["block_1"]["moe"]["router"].shape == (6144, 128)
    assert str(shapes["block_1"]["moe"]["expert_gate"].dtype) == "bfloat16"
    assert str(shapes["block_1"]["moe"]["router"].dtype) == "float32"
    assert "mlp" in shapes["block_0"] and "moe" not in shapes["block_0"]
    # Every limit the driver compares is in the cell's file.
    assert {"served_logit_gap_p99", "served_logit_gap_mean", "near_tie_eps",
            "near_tie_share_max"} <= set(cell.limits)


@pytest.fixture(scope="module")
def toy():
    cell = copy.deepcopy(harness.load_cell(CELL))
    conftest.shrink_exaone_moe(cell)
    cell.limits = dict(conftest.TOY_LIMITS["exaone_moe"])
    model = weights.build_model(cell.config)
    shapes = weights.param_shapes(model)
    return cell, model, shapes


def test_program_agrees_with_the_reference_logits(toy):
    """Window and full layers, q/k norm, rotation on window layers only, the
    norm on outputs, a dense layer then expert layers, sigmoid top-k with bias
    and scaling, the shared expert, 4 of 16 experts held."""
    import jax.numpy as jnp

    cell, model, shapes = toy
    params = weights.make_params(shapes, 12345)
    tokens = harness.rng(3, 1).integers(2, 256, 64).astype(np.int32)
    got = model.apply({"params": params}, jnp.asarray(tokens)[None])[0]
    want, margins, edges = reference.logits_rows(params, cell.config, tokens,
                                                 np.arange(64))
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6)
    assert margins.shape == edges.shape == (4, 64)


def test_scheduler_prefill_and_paged_decode_agree_with_the_reference(toy):
    """Prompts shorter and longer than the window (12, no multiple of the
    8-token page) through Scheduler, kvpool and the paged reads: every served
    token is the reference's first choice over prompt + served tokens."""
    from cloud_tpu.serving import Scheduler, ServeRequest

    cell, model, shapes = toy
    params = driver.neutral_bias(weights.make_params(shapes, 77))
    rng = harness.rng(5, 1)
    prompts = [rng.integers(2, 256, n).astype(np.int32) for n in (5, 13, 30, 41)]
    with Scheduler(model, params, slots=2, page_size=8) as sched:
        futures = [sched.submit(ServeRequest(
            prompt=p.tolist(), max_new_tokens=14, temperature=0.0))
            for p in prompts]
        served = [np.asarray(f.result(timeout=600).tokens) for f in futures]
    sequences = [(tokens, len(p)) for tokens, p in zip(served, prompts)]
    gaps, margins = driver.served_gaps(cell.config, shapes, 77, sequences, 64, 14)
    assert len(gaps) == 4 * 14 and max(gaps) < 1e-4, max(gaps)
    assert min(margins) >= 0


def test_control_and_planted_fault_fail_and_a_sound_run_passes(toy):
    """The reference in int8, and the reference with two held experts'
    weights swapped in one layer, put in the program's place: each reads above
    a toy limit at the positions that are no near-tie, by the numbers the
    driver compares."""
    cell, model, shapes = toy
    tokens = harness.rng(4, 1).integers(2, 256, 60).astype(np.int32)
    limits = cell.limits
    swap = lambda p: readings_routed.swap_experts(p, layer="block_2", a=1, b=2)
    read = lambda **kw: driver.numbers_compared(
        *driver.served_gaps(cell.config, shapes, 4, [(tokens, 20)], 64, 40, **kw),
        limits["near_tie_eps"])
    beyond = lambda p99, mean, share: (p99 > limits["served_logit_gap_p99"]
                                       or mean > limits["served_logit_gap_mean"])
    assert beyond(*read(chooser="int8"))
    assert beyond(*read(plant=swap))
    assert read(chooser="float32") == (0.0, 0.0, 0.0)
    assert driver.numbers_compared([0.5], [0.0], 1e-6) == (float("inf"),) * 3
    p99, mean, share = driver.numbers_compared(
        [0.0] * 98 + [1.0, 9.0], [1.0] * 99 + [0.0], 0.5)
    assert (mean, share) == (pytest.approx(1 / 99), pytest.approx(0.01))
    assert p99 == pytest.approx(0.02)   # between the 98th and 99th of 99 kept


def test_neutral_bias_zeroes_the_selection_bias_and_nothing_else(toy):
    import jax

    cell, model, shapes = toy
    params = weights.make_params(shapes, 5)
    neutral = driver.neutral_bias(params)
    assert jax.tree_util.tree_structure(neutral) == jax.tree_util.tree_structure(
        params)
    changed = [jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree_util.tree_leaves(neutral)) if not np.array_equal(a, b)]
    assert changed == ["['block_{}']['moe']['router_bias']".format(i)
                       for i in range(1, 5)]
    for i in range(1, 5):
        bias = neutral["block_{}".format(i)]["moe"]["router_bias"]
        assert bias.shape == (16,) and not np.any(np.asarray(bias))
    assert np.any(np.asarray(params["block_1"]["moe"]["router_bias"]))


@pytest.mark.parametrize("seconds,each", [(45, 4), (1.0, 1), (22.5, 2), (90, 8)])
def test_every_run_of_a_length_sends_the_same_requests(seconds, each):
    mix = harness.load_cell(CELL).traffic
    assert driver.requests_per_client(mix, seconds) == each
    # Whole cycles of the mix at the benchmark's 45 s: every seed serves the
    # same multiset of prompt lengths.
    assert (4 * mix["clients"]) % mix["cycle"] == 0


def test_routing_margin_is_in_units_of_the_router_logit():
    """Two experts compete for the last place with logits 6.00 and 5.98, where
    the sigmoid's slope is 0.0025: 5e-5 apart in s, 0.02 apart as the router
    sees them. A bias that reverses them is felt through the same slope."""
    import jax.numpy as jnp

    from cellbench.reference import common

    logits = np.array([[9.0, 6.0, 5.98, -3.0]], np.float32)
    p = {"router": jnp.asarray(logits), "router_bias": jnp.zeros(4)}
    one = jnp.ones((1, 1), jnp.float32)
    ids, weights_, margin, edge = reference.route(
        one, p, 2, 2.5, True, common.make_mm("float32"))
    assert ids.tolist() == [[0, 1]] and edge.tolist() == [[1, 2]]
    assert float(margin[0]) == pytest.approx(0.02, rel=0.05)
    assert float(jnp.sum(weights_)) == pytest.approx(2.5)
    p["router_bias"] = jnp.asarray([0.0, 0.0, 1e-4, 0.0])
    ids, _, margin, edge = reference.route(one, p, 2, 2.5, True,
                                           common.make_mm("float32"))
    assert ids.tolist() == [[0, 2]] and edge.tolist() == [[2, 1]]
    assert float(margin[0]) == pytest.approx(0.02, rel=0.1)


def test_counts_against_hand_worked_numbers():
    cfg = harness.load_cell(CELL).config
    assert counts.layer_kinds(cfg) == (4, 1, 1, 4)
    # q and o 6144 x 8192 each, k and v 6144 x 1024 each.
    assert counts.attention_params(cfg) == 2 * 6144 * 8192 + 2 * 6144 * 1024
    assert counts.expert_params(cfg) == 3 * 6144 * 2048 == 37748736
    always = (5 * 113246208 + 3 * 6144 * 18432 + 4 * (37748736 + 6144 * 128)
              + 6144 * 19200)
    assert counts.always_params(cfg) == always
    assert counts.kv_row_bytes(cfg) == 2 * 8 * 128 * 2
    # 32 slots, 70 000 live tokens, 4096 in the windows, 120 pairs in 50 experts.
    rows = 1 * 70000 + 4 * 4096
    assert counts.tick_bytes(cfg, 32, 70000, 4096, 50) == (
        2 * (always + 50 * 37748736) + 2 * 4 * 6144 * 128 + 32 * 6144 * 2
        + 4096 * rows)
    assert counts.tick_flops(cfg, 32, 70000, 4096, 120) == (
        2 * always * 32 + 2 * 37748736 * 120 + 4 * 64 * 128 * rows)
    seconds, bound = counts.experts_least_seconds(cfg, 120, 50, PEAKS)
    assert bound == "bytes" and seconds == pytest.approx(
        (50 * 37748736 * 2 + 2 * 120 * 6144 * 2) / 819e9)
    seconds, bound = counts.paged_least_seconds(cfg, 70000, 4096, PEAKS)
    assert bound == "bytes" and seconds == pytest.approx(4096 * rows / 819e9)


def _observed(trace=None, peaks=None, counters=None):
    return {"trace": trace, "peaks": peaks, "counters": counters or {},
            "config": harness.load_cell(CELL).config, "window_s": 10.0}


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_reader_returns_none_where_it_finds_nothing(metric):
    reader = harness.find("layer_metrics", metric)
    empty = tracing.reduce_events([], tracing.Events.of([]), None, 1e9)
    assert reader.read(_observed()) is None
    assert reader.read(_observed(trace=empty, peaks=PEAKS)) is None
    # A program without the expert counters (the parent): no counter is
    # taken for 0.
    plain = {"ticks": 100, "occupancy": {"32": [100, 30.0]}, "slots": 32,
             "live_token_ticks": 10 ** 6}
    assert reader.read(_observed(trace=empty, peaks=PEAKS, counters=plain)) is None


def test_new_readers_on_a_synthetic_trace():
    """Two traced ticks: a full-layer paged read of 300 us and four window
    reads of 20 us each a tick, three grouped products of 400 us a tick at
    the tick's 256 rows (and a prefill's at 8192 rows, which is not the
    tick's), a shared-expert call of 30 us beside the dense layer's 200 us,
    a router fusion of 50 us under its scope, ticks of 5 ms."""
    call = ("%{name} = bf16[32,64,128] custom-call(bf16[32,64,128] %q), "
            "custom_call_target=\"tpu_custom_call\"")
    paged = "attention._paged_decode_attention.{}.{}"
    ops = []
    for tick in range(2):
        t0 = tick * 6e6
        ops.append((call.format(name=paged.format("paged_decode", 1)), t0, 3e5))
        for layer in range(4):
            ops.append((call.format(name=paged.format("paged_decode_window",
                                                      2 + layer)),
                        t0 + 4e5 + layer * 3e4, 2e4))
        for i in range(3):
            ops.append(("%ragged-dot-none.{} = f32[256,2048] custom-call("
                        "bf16[256,6144] %x)".format(i), t0 + 1e6 + i * 5e5, 4e5))
        ops.append(("%ragged-dot-none.9 = f32[8192,2048] custom-call("
                    "bf16[8192,6144] %x)", t0 + 3e6, 9e5))
        ops.append(("%fused_swiglu_fwd.3 = bf16[32,6144] custom-call(bf16[32,6144]"
                    " %h, bf16[6144,2048] %g, bf16[6144,2048] %u)", t0 + 3.95e6,
                    3e4))
        ops.append(("%fused_swiglu_fwd.1 = bf16[32,6144] custom-call(bf16[32,6144]"
                    " %h, bf16[6144,18432] %g, bf16[6144,18432] %u)", t0 + 4.5e6,
                    2e5))
        ops.append(('%fusion.7 = f32[32,128] fusion(f32[32,6144] %h), metadata={'
                    'op_name="jit(serve_tick)/jit(main)/LlamaLM/block_1/moe/'
                    'moe_router/dot_general"}', t0 + 4e6, 5e4))
        ops.append(('%fusion.8 = f32[32,6144] fusion(f32[32,6144] %h), metadata={'
                    'op_name="jit(serve_tick)/jit(main)/LlamaLM/block_1/'
                    'norm_attn_post/mul"}', t0 + 4.2e6, 7e4))
    modules = tracing.Events.of([("jit_serve_tick(123)", 0.0, 5e6),
                                 ("jit_serve_tick(123)", 6e6, 5e6),
                                 ("jit_serve_prefill(9)", 11.5e6, 1e5)])
    trace = tracing.reduce_events([tracing.Events.of(ops)], modules, None, 12e6)
    counters = {"ticks": 1000, "occupancy": {"32": [1000, 32.0]}, "slots": 32,
                "live_token_ticks": 70000 * 1000, "window_token_ticks": 4096 * 1000,
                "moe_pairs_routed": 1024 * 1000, "moe_pairs_held": 120 * 1000,
                "moe_experts_touched": 50 * 1000,
                "moe_expert_load": [10, 10, 30, 10]}
    observed = _observed(trace, PEAKS, counters)
    cfg = observed["config"]
    read = lambda name: harness.find("layer_metrics", name).read(observed)
    least, _ = counts.paged_least_seconds(cfg, 70000, 4096, PEAKS)
    # 10 events over 5 names = 2 ticks; 2 x (300 + 4 x 20) us of kernels.
    assert read("paged_attn_mixed_roofline") == pytest.approx(
        100 * least * 2 / 760e-6)
    least, _ = counts.experts_least_seconds(cfg, 120, 50, PEAKS)
    assert read("moe_experts_roofline") == pytest.approx(100 * least * 2 / 2400e-6)
    # (router 50 + shared expert 30 + grouped products 1200 us) a 5 ms tick.
    assert read("moe_tick_share_pct.serve") == pytest.approx(100 * 1280e-6 / 5e-3)
    least, _ = counts.tick_least_seconds(cfg, 32.0, 70000, 4096, 120, 50, PEAKS)
    assert read("tick_mfu.moe") == pytest.approx(100 * least / 10e-3)
    assert read("expert_load_max_over_mean.serve") == pytest.approx(30 * 4 / 60)
