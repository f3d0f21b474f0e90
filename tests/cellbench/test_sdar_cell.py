"""Tier-1 checks of the block-diffusion cell (`sdar_blockdiff_gen`), on the CPU
at toy widths: the configuration loads by name and builds the program's class
at its published widths; the toy cell runs end to end traced, correct, its
counters and the readers that can read on the CPU present; the int8 control
and the faults of block diffusion (attention inside a block made causal; a
block closed without its clean pass; a remasking that commits the least
confident rows) read beyond the toy's limits through `harness.Compared`, as a
run is judged; the exact check of the remasking schedule against SDAR's rule
and against schedules that leave steps out; the family's counts against
hand-worked numbers; the four new readers on a synthetic trace and on an
empty one. No number here is a device metric.
"""

import copy
import json
import time

import pytest

from cellbench import harness, tracing, weights
from cellbench.counts import sdar as counts
from cellbench.drivers import closed_loop_diffusion as driver
from cellbench.reference import sdar as ref
from tests.cellbench import toy_sizes_sdar as toy

CELL = "sdar_blockdiff_gen"
NEW_READERS = ("tick_mfu.diffusion", "block_decode_roofline",
               "diffusion_rows_per_token.serve", "block_experts_roofline")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_configuration_loads_by_name_at_its_published_widths():
    cell = harness.load_cell(CELL)
    cfg = cell.config
    assert cfg["family"] == "sdar" and cell.chips == 1
    assert cell.traffic["driver"] == "closed_loop_diffusion"
    assert cfg["reduced"] == list(cfg["published"]) == ["num_hidden_layers"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_experts"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["norm_topk_prob"], cfg["vocab_size"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["num_hidden_layers"]) == (
                2048, 32, 4, 128, 128, 768, 8, True, 151936, 1000000, 1e-6, 6)
    assumed = cfg["assumed"]
    assert (assumed["block_length"], assumed["slots"], assumed["page_size"],
            assumed["max_seq_len"]) == (4, 64, 16, 2816)
    assert assumed["mask_token"] >= 151643 > cell.traffic["token_ids_below"] - 1
    model = weights.build_model(cfg)
    assert type(model).__name__ == "SdarLM"
    assert (model.num_layers, model.block_length, model.moe_experts,
            model.moe_top_k, model.qk_norm, model.moe_router,
            model.moe_capacity_factor) == (6, 4, 128, 8, True, "softmax", None)
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    assert set(NEW_READERS) <= {m["name"] for m in cell.per_layer}
    mix = cell.traffic
    assert mix["prompt_len"]["hi"] + mix["new_tokens"]["value"] + 4 <= (
        assumed["max_seq_len"])
    assert sorted(driver.steps_grid(mix).tolist()) == [2] * 32 + [4] * 32
    assert mix["new_tokens"] == {"dist": "fixed", "value": 512}


def toy_cell():
    cell = copy.deepcopy(harness.load_cell(CELL))
    toy.shrink(cell)
    cell.traffic.update(trace_after_s=0.0, trace_for_s=0.4)
    cell.limits = dict(toy.LIMITS)
    return cell


@pytest.fixture(scope="module")
def toy_run():
    """One traced toy run, and what its reference compared."""
    kept = {}
    original = driver.served_gaps

    def keeping(cfg, shapes, seed, sequences, **kw):
        kept.update(args=(cfg, shapes, seed, sequences))
        return original(cfg, shapes, seed, sequences, **kw)

    driver.served_gaps = keeping
    try:
        cell = toy_cell()
        run = harness.Run(cell=cell, seed=2 ** 31 + 17, seconds=1.0,
                          trace=True, t_process=time.perf_counter())
        observed = driver.run(run)
    finally:
        driver.served_gaps = original
    line = json.loads(json.dumps(harness.result_line(cell, run, observed)))
    return cell, line, kept["args"]


def test_toy_cell_end_to_end(toy_run):
    """A traced run: correct, every block closed, the block tick's counters
    and the readers that can read on the CPU present."""
    _, line, _ = toy_run
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] == 4
    assert set(line["compared"]) == {
        "prompt_echoed_and_length", "every_request_answered",
        "every_block_closed", "commits_by_schedule", "slot_steps_by_schedule",
        "no_compile_in_window", "served_logit_gap_p99",
        "served_logit_gap_mean", "near_tie_share", "rank_flip_share"}
    c = line["counters"]
    assert c["diffusion_tokens_committed"] == c["out_tokens"]
    assert c["diffusion_blocks_closed"] == c["blocks"]
    assert c["diffusion_rows_computed"] == 4 * (
        c["diffusion_slot_steps"] + c["blocks"])
    assert c["rank_steps"] > 0 and c["rank_excess_max"] < 1e-5
    assert set(c["rank_flip_by_eps"].values()) == {0.0}
    assert c["kv_walked_tokens"] >= c["kv_live_tokens"] > 0
    assert set(c["denoising_steps"]) <= {"2", "4"}
    assert 0 < c["ticks_traced"] <= c["ticks"]
    assert 0 < c["kv_live_tokens_traced"] <= c["kv_live_tokens"]
    # No TPU plane in a CPU trace and no table of peaks: the device
    # readers return nothing; the counters' and the records' readers read.
    assert {"tick_ms.serve", "slot_occupancy_pct.serve",
            "decode_gap_p99_ms.serve", "expert_load_max_over_mean.serve",
            "diffusion_rows_per_token.serve"} <= set(line["metrics"])
    assert line["metrics"]["diffusion_rows_per_token.serve"]["value"] > 1
    assert not {"tick_mfu.diffusion", "block_decode_roofline",
                "block_experts_roofline"} & set(line["metrics"])
    assert 0 < c["moe_pairs_held_traced"] <= c["moe_pairs_held"]
    assert 0 < c["moe_experts_touched_traced"] <= c["moe_experts_touched"]


@pytest.mark.parametrize("upper", ["int8"] + list(ref.FAULTS)
                         + ["least_confident"])
def test_control_and_faults_read_beyond_the_limits(toy_run, upper):
    """The int8 control and each fault, put in the program's place, come
    out not correct through the comparison a run makes."""
    cell, _, (cfg, shapes, seed, sequences) = toy_run
    gaps, margins, steps = driver.served_gaps(cfg, shapes, seed, sequences)
    excess = driver.rank_excess(steps)
    if upper == "least_confident":
        excess = driver.rank_excess(steps, least=True)
        assert driver.rank_flip_share(excess, cell.limits["rank_eps"]) > 0.5
    else:
        kw = ({"chooser": upper} if upper == "int8" else {"fault": upper})
        gaps, margins, _ = driver.served_gaps(cfg, shapes, seed, sequences,
                                              **kw)
    compared = harness.Compared()
    driver.add_compared(compared, cell.limits, gaps, margins, excess)
    assert not compared.ok


def test_counts_against_hand_worked_numbers():
    cfg = harness.load_cell(CELL).config
    assert counts.kv_row_bytes(cfg) == 2048
    assert counts.attention_params(cfg) == 2048 * 128 * (64 + 8) == 18874368
    assert counts.expert_params(cfg) == 3 * 2048 * 768
    assert counts.always_params(cfg) == (
        6 * (18874368 + 2048 * 128) + 2048 * 151936)
    # The cell's tick: 64 slots x 4 rows, slots about 1 800 keys deep,
    # 16 pairs an expert and every expert touched in all 6 layers.
    rows, live = 256, 64 * 1800
    nbytes = counts.tick_bytes(cfg, rows, live, 6 * 128)
    # Of the stage's 8.72 GB a tick reads all but the embedding's table.
    assert round((counts.always_params(cfg) + 768 * 3 * 2048 * 768) * 2
                 / 1e9, 2) == 8.10
    assert nbytes == 2 * (counts.always_params(cfg) + 768 * 3 * 2048 * 768) + (
        2 * 6 * 2048 * 128 + 256 * 2048 * 2 + 2048 * 6 * live)
    seconds, bound = counts.tick_least_seconds(cfg, rows, live, 6 * 2048,
                                               6 * 128, PEAKS)
    assert bound == "bytes" and seconds == pytest.approx(nbytes / 819e9)
    # The block read alone: 4 rows x 32 heads against 2 KB a key, 32 FLOP
    # a byte, under the v5e's ridge of 240.
    seconds, bound = counts.block_decode_least_seconds(cfg, live, PEAKS)
    assert counts.attention_flops(cfg, 1) / counts.kv_row_bytes(cfg) == 32
    assert bound == "bytes" and seconds == pytest.approx(live * 2048 / 819e9)


def _observed(trace=None, peaks=None, counters=None):
    return {"trace": trace, "peaks": peaks, "counters": counters or {},
            "config": harness.load_cell(CELL).config, "window_s": 10.0}


_CALL = ("%attention._paged_decode_attention.paged_block_decode.{} = "
         "bf16[64,128,128] custom-call(bf16[64,128,128] %q), "
         "custom_call_target=\"tpu_custom_call\"")
#: A grouped product of the tick: 64 slots x 4 rows x 8 experts a row.
_PRODUCT = ("%ragged-dot.{} = bf16[2048,768]{{1,0}} custom-call(bf16[2048,"
            "2048] %x, bf16[128,2048,768] %w, s32[128] %sizes)")


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_reader_returns_none_where_it_finds_nothing(metric):
    reader = harness.find("layer_metrics", metric)
    empty = tracing.reduce_events([], tracing.Events.of([]), None, 1e9)
    assert reader.read(_observed()) is None
    assert reader.read(_observed(trace=empty, peaks=PEAKS)) is None
    # The parent: a tick's events and counters, no block tick's counter.
    modules = tracing.Events.of([("jit_serve_tick(1)", 0.0, 5e6)])
    ticked = tracing.reduce_events(
        [tracing.Events.of([("%paged_decode.1 = x custom-call(y)", 0, 500)])],
        modules, None, 6e6)
    assert reader.read(_observed(trace=ticked, peaks=PEAKS, counters={
        "ticks": 10, "kv_live_tokens": 5000, "moe_pairs_held": 10,
        "moe_experts_touched": 10})) is None


def test_new_readers_on_a_synthetic_trace():
    """Two traced ticks of 20 ms, six block reads of 1 ms each a tick, over
    the slice's 115 200 live keys a tick, and 18 grouped products of 0.8 ms
    each a tick over 6 x 2048 pairs and 6 x 128 experts; a window of 500
    ticks of 256 rows that committed 80 000 tokens. A slice that holds a
    prefill gives no expert share."""
    ops = [(_CALL.format(layer), tick * 25e6 + layer * 3e6, 1e6)
           for tick in range(2) for layer in range(6)]
    ops += [(_PRODUCT.format(i), tick * 25e6 + i * 1e6, 0.8e6)
            for tick in range(2) for i in range(18)]
    modules = tracing.Events.of([("jit_serve_tick(7)", 0.0, 20e6),
                                 ("jit_serve_tick(7)", 25e6, 20e6)])
    trace = tracing.reduce_events([tracing.Events.of(ops)], modules, None,
                                  50e6)
    counters = {"ticks": 500, "kv_live_tokens": 500 * 100000,
                "ticks_traced": 2, "kv_live_tokens_traced": 2 * 115200,
                "diffusion_rows_computed": 500 * 256,
                "diffusion_tokens_committed": 80000,
                "moe_pairs_held": 500 * 6 * 2048,
                "moe_experts_touched": 500 * 6 * 128}
    observed = _observed(trace, PEAKS, counters)
    read = lambda name: harness.find("layer_metrics", name).read(observed)
    cfg = observed["config"]
    least, _ = counts.block_decode_least_seconds(cfg, 115200, PEAKS)
    assert read("block_decode_roofline") == pytest.approx(
        100 * least * 12 / 12e-3)
    assert 0 < read("block_decode_roofline") < 100
    least, _ = counts.tick_least_seconds(cfg, 256, 100000, 6 * 2048, 6 * 128,
                                         PEAKS)
    assert read("tick_mfu.diffusion") == pytest.approx(100 * least / 20e-3)
    assert 0 < read("tick_mfu.diffusion") < 100
    assert read("diffusion_rows_per_token.serve") == pytest.approx(1.6)
    # No pair or expert counted in the slice yet: nothing to read.
    assert read("block_experts_roofline") is None
    counters.update(moe_pairs_held_traced=2 * 6 * 2048,
                    moe_experts_touched_traced=2 * 6 * 128)
    least, _ = counts.experts_least_seconds(cfg, 6 * 2048, 6 * 128, PEAKS)
    assert read("block_experts_roofline") == pytest.approx(
        100 * least * 2 / (36 * 0.8e-3))
    assert 0 < read("block_experts_roofline") < 100
    prefilled = tracing.reduce_events(
        [tracing.Events.of(ops)], tracing.Events.of(
            [("jit_serve_tick(7)", 0.0, 20e6),
             ("jit_serve_prefill(3)", 21e6, 3e6),
             ("jit_serve_tick(7)", 25e6, 20e6)]), None, 50e6)
    assert harness.find("layer_metrics", "block_experts_roofline").read(
        _observed(prefilled, PEAKS, counters)) is None


def test_schedule_is_sdars_and_held_exactly():
    """SDAR's rule by hand, and a request held to it block by block: a
    prompt of 10 (two clean rows in its last block) and 9 new tokens open
    blocks at 8, 12 and 16 with 2, 4 and 3 masked rows. The schedule kept
    passes with its steps counted; a whole block committed at its first
    step, two rows a step where one is due, or a step past the last fails."""
    assert driver.schedule(4, 4) == [1, 1, 1, 1]
    assert driver.schedule(2, 4) == [2, 2]
    assert driver.schedule(4, 3) == [1, 1, 1, 0]
    assert driver.schedule(2, 3) == [2, 1]
    assert driver.schedule(4, 1) == [1, 0, 0, 0]
    kept = lambda at, steps: driver.schedule_kept(10, 9, at, steps, 4)
    assert kept([1, 0, 2, 0, 3, 1, 0, 2, 1], 4) == (True, 2 + 4 + 3)
    assert kept([0, 1, 0, 1, 1, 0, 0, 1, 0], 2) == (True, 2 + 2 + 2)
    assert not kept([0] * 9, 4)[0]
    assert not kept([1, 0, 0, 0, 1, 1, 0, 2, 1], 4)[0]
    assert not kept([1, 0, 2, 0, 4, 1, 0, 2, 1], 4)[0]
    assert not kept([0, 1, 0, 1, 1, 0, 0, 1, 1], 2)[0]
    assert driver.blocks_of(10, 9, 4) == 3
