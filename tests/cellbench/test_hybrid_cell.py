"""Tier-1 checks of the hybrid cell (`nemotron3s_decode_reason`), on the CPU at
toy widths: the configuration loads by name and builds the program's class at
its published widths; the toy cell runs end to end, plain and traced; the int8
control, a planted expert swap, a state dropped at insertion and a state
rounded to bfloat16 (under the program, and in the reference's place) fail the
driver's comparison while a sound run passes; the family's counts against hand-worked numbers; the two new
readers on a synthetic trace, and on an empty one. No number here is a device
metric.
"""

import copy
import json
import time

import numpy as np
import pytest

from cellbench import harness, tracing, weights
from cellbench.counts import nemotron_h as counts
from cellbench.drivers import closed_loop_hybrid as driver
from cellbench.tools import readings_hybrid
from tests.cellbench import toy_sizes_nemotron_h as toy

CELL = "nemotron3s_decode_reason"
NEW_READERS = ("ssm_decode_roofline", "ssm_update_tick_share_pct.serve")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_configuration_loads_by_name_at_its_published_widths():
    import jax

    cell = harness.load_cell(CELL)
    cfg = cell.config
    assert cfg["family"] == "nemotron_h" and cell.traffic["driver"] == (
        "closed_loop_hybrid")
    assert set(cfg["reduced"]) == set(cfg["published"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cfg["pattern_kept"] == cfg["hybrid_override_pattern"][27:38] == (
        "MEMEMEMEM*E")
    assert cfg["layers_kept"] == list(range(27, 38))
    assert cfg["deployment"]["chips_sharing_a_layer"] == 4
    model = weights.build_model(cfg)
    assert type(model).__name__ == "NemotronHLM"
    assert (model.d_model, model.num_heads, model.num_kv_heads, model.head_dim,
            model.mamba_heads, model.mamba_head_dim, model.ssm_groups,
            model.ssm_state, model.conv_kernel, model.chunk_size) == (
                4096, 32, 2, 128, 128, 64, 8, 128, 4, 128)
    assert (model.moe_experts, model.moe_top_k, model.moe_d_ff, model.moe_latent,
            model.moe_shared_d_ff, model.moe_routed_scale) == (
                512, 22, 2688, 1024, 5376, 5)
    assert model.moe_held_experts == tuple(range(128))
    assert model.mlp_activation == "relu2" and model.vocab_size == 32768
    shapes = weights.param_shapes(model)
    leaves = jax.tree_util.tree_leaves(shapes)
    assert round(sum(l.size for l in leaves) / 1e9, 2) == 4.65
    moe, mamba = shapes["block_1"]["moe"], shapes["block_0"]["mamba"]
    assert moe["expert_up"].shape == (128, 1024, 2688)
    assert moe["expert_down"].shape == (128, 2688, 1024)
    assert "expert_gate" not in moe
    assert moe["router"].shape == (4096, 512)
    assert moe["latent_down"]["kernel"].shape == (4096, 1024)
    assert moe["shared"]["up"]["kernel"].shape == (4096, 5376)
    assert str(moe["expert_up"].dtype) == "bfloat16"
    assert str(moe["router"].dtype) == "float32"
    assert mamba["in_proj"]["kernel"].shape == (4096, 8192 + 10240 + 128)
    assert mamba["conv_kernel"].shape == (4, 10240)
    assert str(mamba["A_log"].dtype) == "float32"
    assert set(shapes["block_9"]) == {"attention", "norm"}
    assert {"served_logit_gap_p99", "served_logit_gap_mean", "near_tie_eps",
            "near_tie_share_max"} <= set(cell.limits)
    # The whole model from the same arithmetic: the published 120.7 B.
    per = {"M": sum(l.size for l in jax.tree_util.tree_leaves(shapes["block_0"])),
           "*": sum(l.size for l in jax.tree_util.tree_leaves(shapes["block_9"]))}
    expert = 2 * 1024 * 2688
    rest = sum(l.size for l in jax.tree_util.tree_leaves(
        shapes["block_1"])) - 128 * expert
    whole = (40 * per["M"] + 8 * per["*"] + 40 * (rest + 512 * expert)
             + 2 * 131072 * 4096 + 4096)
    assert round(whole / 1e9, 1) == 120.7


def toy_cell(**traffic_changes):
    cell = copy.deepcopy(harness.load_cell(CELL))
    toy.shrink(cell)
    cell.traffic.update(trace_after_s=0.2, trace_for_s=0.4)
    cell.traffic.update(traffic_changes)
    cell.limits = dict(toy.LIMITS)
    return cell


def drive(trace=False, plant=None, seed=2 ** 31 + 17):
    cell = toy_cell()
    run = harness.Run(cell=cell, seed=seed, seconds=1.0, trace=trace,
                      t_process=time.perf_counter(), plant=plant)
    observed = harness.find("drivers", cell.traffic["driver"]).run(run)
    return cell, json.loads(json.dumps(harness.result_line(cell, run, observed)))


def test_toy_cell_end_to_end_plain_and_traced():
    cell, line = drive()
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] == 4
    assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    counters = line["counters"]
    # Two state layers, a step a slot and tick in each (the tick behind the
    # last completion may not be counted yet); the gauge is all slots' state.
    assert 0 <= (2 * (counters["tokens_emitted"] - 4)
                 - counters["ssm_slot_steps"]) <= 2 * 4
    assert counters["ssm_state_bytes"] == 4 * 2 * (
        4 * 8 * 16 * 4 + 3 * (32 + 64) * 4)
    assert counters["prefix_hits"] == 0 and counters["window_token_ticks"] == 0
    assert counters["moe_pairs_routed"] > counters["moe_pairs_held"] > 0
    cell, line = drive(trace=True)
    # No TPU plane in a CPU trace and no table of peaks: the device readers
    # return nothing, the counters' readers read.
    # `decode_gap_p99_ms.serve` reads only where the program's records number
    # the window's requests: the state probes' records are not among them.
    assert {"tick_ms.serve", "slot_occupancy_pct.serve",
            "decode_gap_p99_ms.serve",
            "expert_load_max_over_mean.serve"} <= set(line["metrics"])
    assert not set(NEW_READERS) & set(line["metrics"])
    assert {m["name"] for m in cell.per_layer} >= set(NEW_READERS)


GAPS = {"served_logit_gap_p99", "served_logit_gap_mean"}


@pytest.mark.parametrize("plant,beyond,within", [
    ("state_dropped", GAPS | {"ssm_state_err_p50"}, set()),
    ("state_bfloat16", {"ssm_state_err_p50"}, GAPS)])
def test_a_fault_planted_under_the_program(plant, beyond, within):
    """A slot whose state is zeroed at insertion reads beyond the limits of the
    gaps and of the state. A state rounded to bfloat16 after every tick moves
    no served token off the reference's first choice, and is not correct by
    the state's own number."""
    _, line = drive(plant=readings_hybrid.PLANTS[plant])
    over = {name for name, row in line["compared"].items()
            if row["value"] > row["limit"]}
    assert beyond <= over and not within & over
    assert not line["correct"]


def test_control_and_expert_swap_fail_and_a_sound_run_passes():
    """The reference in int8, and the reference with two held experts' weights
    swapped in one layer, put in the program's place: each reads above a toy
    limit at the positions that are no near-tie."""
    cell = toy_cell()
    shapes = weights.param_shapes(weights.build_model(cell.config))
    tokens = harness.rng(4, 1).integers(2, 256, 60).astype(np.int32)
    limits = cell.limits
    swap = lambda p: readings_hybrid.swap_experts(p, layer="block_1", a=1, b=2)
    read = lambda **kw: driver.numbers_compared(
        *driver.served_gaps(cell.config, shapes, 4, [(tokens, 20)], 64, 40, **kw),
        limits["near_tie_eps"])
    beyond = lambda p99, mean, share: (p99 > limits["served_logit_gap_p99"]
                                       or mean > limits["served_logit_gap_mean"])
    assert beyond(*read(chooser="int8"))
    assert beyond(*read(plant=swap))
    assert read(chooser="float32") == (0.0, 0.0, 0.0)
    assert read(state_dtype="bfloat16")[:2] == (0.0, 0.0)


def test_state_controls_read_beyond_the_state_limit():
    """The reference's own state with its products in int8, and with the state
    rounded to bfloat16 after every token, each in the program's place: beyond
    the toy limit of the state's error; the reference itself reads 0."""
    cell = toy_cell()
    shapes = weights.param_shapes(weights.build_model(cell.config))
    tokens = harness.rng(4, 1).integers(2, 256, 60).astype(np.int32)
    limit = cell.limits["ssm_state_err_p50"]
    errors = lambda **kw: driver.state_errors(
        cell.config, shapes, 4, [(tokens, 47, None)], 64, **kw)
    read = lambda **kw: driver.state_numbers(*errors(**kw))["ssm_state_err_p50"]
    assert read(chooser="int8") > limit
    assert read(state_dtype="bfloat16") > limit
    assert read(chooser="float32") == 0.0
    got, rates = errors(state_dtype="bfloat16")
    assert got.shape == (1, 2, 4) and rates.shape == (2, 4)
    assert set(driver.state_numbers(got[:0], rates).values()) == {float("inf")}


def test_slow_head_ratio_reads_what_is_kept_and_not_what_is_computed():
    """128 heads of one layer, two probes. An error that is the same for every
    head reads 1 whatever its size; one that grows with what a head remembers
    reads the slowest eight heads' over the median."""
    rates = np.exp(harness.rng(5, 1).uniform(np.log(0.001), np.log(1.6),
                                             (1, 128)))
    ratio = lambda errors: driver.state_numbers(np.asarray(errors), rates)[
        "ssm_state_slow_head_err_ratio"]
    flat = np.full((2, 1, 128), 0.005)
    assert ratio(flat) == ratio(10 * flat) == 1.0
    kept = np.sqrt(flat ** 2 + (1.6e-3 ** 2) / (2 * rates))
    slow = np.argsort(rates[0])[:8]
    assert ratio(kept) == pytest.approx(
        kept[0, 0, slow].mean() / np.median(kept[0, 0]))
    assert ratio(kept) > 2.0


def test_counts_against_hand_worked_numbers():
    cfg = harness.load_cell(CELL).config
    assert counts.layer_kinds(cfg) == (5, 1, 5)
    # in 4096 x (8192 + 10240 + 128), out 8192 x 4096.
    assert counts.mamba_params(cfg) == 4096 * 18560 + 8192 * 4096 == 109576192
    assert counts.attention_params(cfg) == 4096 * 128 * (64 + 4)
    assert counts.expert_params(cfg) == 2 * 1024 * 2688 == 5505024
    always = (5 * 109576192 + 35651584
              + 5 * (2 * 4096 * (1024 + 5376) + 4096 * 512) + 4096 * 32768)
    assert counts.always_params(cfg) == always
    assert counts.state_elements(cfg) == 128 * 64 * 128
    # State float32 read and written, the window 3 x 10240 bfloat16 likewise.
    assert counts.state_step_bytes(cfg) == 2 * (4194304 + 61440)
    assert counts.kv_row_bytes(cfg) == 2 * 2 * 128 * 2
    # 128 slots, 100 000 live tokens, 3520 pairs in 640 experts.
    assert counts.tick_bytes(cfg, 128, 100000, 0, 640) == (
        2 * (always + 640 * 5505024) + 2 * 5 * 4096 * 512 + 128 * 4096 * 2
        + 8511488 * 128 * 5 + 1024 * 100000)
    assert counts.tick_flops(cfg, 128, 100000, 0, 3520) == (
        2 * always * 128 + 2 * 5505024 * 3520 + 4 * 32 * 128 * 100000
        + 5 * 1048576 * 128 * 5)
    seconds, bound = counts.tick_least_seconds(cfg, 128, 100000, 0, 3520, 640,
                                               PEAKS)
    assert bound == "bytes" and 0.016 < seconds < 0.019    # ISSUE 32: 17.5 ms
    seconds, bound = counts.experts_least_seconds(cfg, 3520, 640, PEAKS)
    assert bound == "bytes" and seconds == pytest.approx(
        (640 * 5505024 * 2 + 2 * 3520 * 1024 * 2) / 819e9)
    seconds, bound = counts.paged_least_seconds(cfg, 100000, 0, PEAKS)
    assert bound == "bytes" and seconds == pytest.approx(1024 * 100000 / 819e9)
    rows = (8192 + 2048) * 2 + (8192 + 128) * 4
    seconds, bound = counts.ssm_update_least_seconds(cfg, 128, PEAKS)
    assert bound == "bytes" and seconds == pytest.approx(
        (2 * 4194304 + rows) * 640 / 819e9)


def _observed(trace=None, peaks=None, counters=None):
    return {"trace": trace, "peaks": peaks, "counters": counters or {},
            "config": harness.load_cell(CELL).config, "window_s": 10.0}


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_reader_returns_none_where_it_finds_nothing(metric):
    reader = harness.find("layer_metrics", metric)
    empty = tracing.reduce_events([], tracing.Events.of([]), None, 1e9)
    assert reader.read(_observed()) is None
    assert reader.read(_observed(trace=empty, peaks=PEAKS)) is None
    # A program without the kernel and its counter (the parent): nothing.
    plain = {"ticks": 100, "occupancy": {"128": [100, 120.0]}, "slots": 128}
    modules = tracing.Events.of([("jit_serve_tick(1)", 0.0, 5e6)])
    ticked = tracing.reduce_events(
        [tracing.Events.of([("%fusion.1 = f32[4] fusion(f32[4] %a)", 0, 500)])],
        modules, None, 6e6)
    assert reader.read(_observed(trace=ticked, peaks=PEAKS, counters=plain)) is None


def test_new_readers_on_a_synthetic_trace():
    """Two traced ticks of 25 ms: five state updates of 1.5 ms each a tick,
    120 of 128 slots active in the window's mean."""
    call = ("%ssm_decode_update.{} = (f32[128,64,128], f32[128,64,128,128]) "
            "custom-call(f32[128,64,128] %x, f32[128,64,128,128] %s), "
            "custom_call_target=\"tpu_custom_call\"")
    ops = [(call.format(layer), tick * 30e6 + layer * 4e6, 1.5e6)
           for tick in range(2) for layer in range(5)]
    ops.append(("%fusion.9 = f32[128,64,128] fusion(f32[128,64,128] "
                "%ssm_decode_update.1)", 1e6, 9e5))      # a reader of y: not it
    modules = tracing.Events.of([("jit_serve_tick(7)", 0.0, 25e6),
                                 ("jit_serve_tick(7)", 30e6, 25e6),
                                 ("jit_serve_prefill(9)", 56e6, 1e6)])
    trace = tracing.reduce_events([tracing.Events.of(ops)], modules, None, 60e6)
    counters = {"ticks": 1000, "ssm_slot_steps": 120 * 5 * 1000,
                "occupancy": {"128": [1000, 120.0]}, "slots": 128}
    observed = _observed(trace, PEAKS, counters)
    read = lambda name: harness.find("layer_metrics", name).read(observed)
    least, _ = counts.ssm_update_least_seconds(observed["config"], 120.0, PEAKS)
    # 10 events over 5 names = 2 ticks; 10 x 1.5 ms of kernel.
    assert read("ssm_decode_roofline") == pytest.approx(100 * least * 2 / 15e-3)
    assert read("ssm_update_tick_share_pct.serve") == pytest.approx(
        100 * 15e-3 / 50e-3)
    # Under 100 % at the chip's peak: 120 slots' state is 1.23 ms a layer.
    assert 80 < read("ssm_decode_roofline") < 85
