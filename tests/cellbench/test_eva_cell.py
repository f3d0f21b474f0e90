"""Tier-1 checks of the EVA cell (`evabyte_decode_32k`), on the CPU at toy
widths: the configuration loads by name and builds the program's class at its
published widths; the toy cell runs end to end, plain and traced; the int8
control and the three planted faults fail the driver's comparison while a sound
run passes; the family's counts against hand-worked numbers; the three new
readers on a synthetic trace, and on an empty one. No number here is a device
metric.
"""

import copy
import json
import time

import numpy as np
import pytest

from cellbench import harness, tracing, weights
from cellbench.counts import evabyte as counts
from cellbench.drivers import closed_loop_eva as driver
from cellbench.tools import readings_eva
from tests.cellbench import toy_sizes_evabyte as toy

CELL = "evabyte_decode_32k"
NEW_READERS = ("tick_mfu.eva", "eva_read_roofline",
               "eva_read_tick_share_pct.serve")
LISTED = {"tick_ms.serve", "slot_occupancy_pct.serve", "device_idle_pct.serve",
          "tick_device_ms.serve", "decode_gap_p99_ms.serve"} | set(NEW_READERS)
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_configuration_loads_by_name_at_its_published_widths():
    import jax

    cell = harness.load_cell(CELL)
    cfg = cell.config
    assert cfg["family"] == "evabyte" and cell.traffic["driver"] == (
        "closed_loop_eva") and cell.chips == 1
    assert cfg["reduced"] == list(cfg["published"]) == ["num_hidden_layers"]
    assert cfg["layers_kept"] == list(range(8))
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["intermediate_size"], cfg["vocab_size"], cfg["window_size"],
            cfg["chunk_size"], cfg["num_pred_heads"],
            cfg["max_position_embeddings"], cfg["rope_theta"]) == (
                4096, 32, 11008, 320, 2048, 16, 8, 32768, 100000)
    model = weights.build_model(cfg)
    assert type(model).__name__ == "EvaByteLM"
    assert (model.d_model, model.num_heads, model.head_size, model.d_ff,
            model.num_layers, model.max_seq_len) == (4096, 32, 128, 11008, 8,
                                                     32768)
    assert tuple(model.layout) == (2048, 16, 32768) and model.layout.rows == 4096
    shapes = weights.param_shapes(model)
    size = lambda tree: sum(l.size for l in jax.tree_util.tree_leaves(tree))
    assert round(size(shapes["block_0"]) / 1e6, 1) == 202.4
    assert round(size(shapes) / 1e9, 3) == 1.631
    # The whole model from the same arithmetic: the published 6.5 B.
    whole = 32 * size(shapes["block_0"]) + size(shapes) - 8 * size(
        shapes["block_0"])
    assert round(whole / 1e9, 2) == 6.49
    att = shapes["block_3"]["attention"]
    assert att["phi"].shape == att["mu"].shape == (32, 128)
    assert str(att["phi"].dtype) == "float32"
    assert str(att["key"]["kernel"].dtype) == "bfloat16"
    assert shapes["lm_head"]["kernel"].shape == (4096, 8 * 320)
    assert set(cell.limits) >= {"served_logit_gap_max"}
    assert {m["name"] for m in cell.per_layer} == LISTED
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    # 16 requests of the grid's lengths; 12 of them meet a window's end while
    # decoding, the longest among them.
    mix = cell.traffic
    from cellbench import traffic
    sizes = sorted(traffic.size_grid(mix["prompt_len"], mix["cycle"]))
    assert sizes[0] == 8832 and sizes[-1] == 28032 and len(sizes) == 16
    late = [(p + 1536 - 2) // 2048 > (p - 1) // 2048 for p in sizes]
    assert sum(late) == 12 and late[-1]


def toy_cell(**traffic_changes):
    cell = copy.deepcopy(harness.load_cell(CELL))
    toy.shrink(cell)
    # The toy's window is a fraction of a second: the slice starts with it.
    cell.traffic.update(trace_after_s=0.0, trace_for_s=0.4)
    cell.traffic.update(traffic_changes)
    cell.limits = dict(toy.LIMITS)
    return cell


def drive(trace=False, seed=2 ** 31 + 17):
    cell = toy_cell()
    run = harness.Run(cell=cell, seed=seed, seconds=1.0, trace=trace,
                      t_process=time.perf_counter())
    observed = harness.find("drivers", cell.traffic["driver"]).run(run)
    return cell, json.loads(json.dumps(harness.result_line(cell, run, observed)))


@pytest.mark.parametrize("trace", [False, True])
def test_toy_cell_end_to_end(trace):
    cell, line = drive(trace=trace)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] == 4
    assert set(line["compared"]) == {
        "prompt_echoed_and_length", "every_request_answered",
        "no_compile_in_window", "served_logit_gap_max"}
    counters = line["counters"]
    assert counters["eva_rows_read"] == counters["kv_live_tokens"] > (
        counters["eva_summary_rows_read"]) > 0
    assert counters["kv_walked_tokens"] >= counters["kv_live_tokens"]
    assert counters["eva_windows_closed"]["prefills"] >= 4
    assert counters["eva_windows_closed"]["ticks"] >= 1
    # Every held page is 4 rows x 64 lanes x 4 bytes x (k, v) x 2 layers.
    assert counters["eva_cache_bytes"] % (4 * 64 * 4 * 2 * 2) == 0
    assert counters["eva_cache_bytes"] > 0 and counters["prefix_hits"] == 0
    assert any(crossed for _, _, crossed in counters["sampled"][1:])
    # `phi` far from uniform (1 / 4 at the toy's chunk).
    assert counters["chunk_weight_max_median"] > 0.26
    if not trace:
        assert "ticks_traced" not in counters
        assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                        "setup_s"}
        return
    assert 0 < counters["ticks_traced"] <= counters["ticks"]
    assert 0 < counters["eva_rows_read_traced"] <= counters["eva_rows_read"]
    # No TPU plane in a CPU trace and no table of peaks: the device readers
    # return nothing, the counters' and the records' readers read.
    assert set(line["metrics"]) == {"tick_ms.serve", "slot_occupancy_pct.serve",
                                    "decode_gap_p99_ms.serve"}
    assert {m["name"] for m in cell.per_layer} == LISTED


def test_program_is_freed_before_the_reference(monkeypatch):
    """Nothing of a traced run (its timers read `stats()`) holds the engine
    when the reference starts: on the chip the reference needs the pool's
    8.6 GB, and a name that kept it died there with RESOURCE_EXHAUSTED."""
    import gc
    import weakref

    held, seen = [], []
    original = driver.served_gaps

    def checked(*args, **kw):
        gc.collect()
        seen.append(held[0]())
        return original(*args, **kw)

    monkeypatch.setattr(driver, "served_gaps", checked)
    cell = toy_cell()
    run = harness.Run(
        cell=cell, seed=5, seconds=1.0, trace=True,
        t_process=time.perf_counter(),
        plant=lambda served: held.append(weakref.ref(served.scheduler.engine)))
    observed = harness.find("drivers", cell.traffic["driver"]).run(run)
    assert observed["compared"].ok and seen == [None]


def toy_sequences():
    cell = toy_cell()
    shapes = weights.param_shapes(weights.build_model(cell.config))
    tokens = harness.rng(4, 1).integers(2, 64, 120).astype(np.int32)
    return cell, dict(cfg=cell.config, shapes=shapes, sequences=[(tokens, 70)],
                      max_seq=128, max_new=50)


@pytest.mark.parametrize("fault", ["no_summaries", "stale_ring", "phi_zero"])
def test_sound_passes_and_a_fault_reads_not_correct(fault):
    """The reference with the summaries never visible (a), with the last
    window's stale ring rows visible (b) and with phi zeroed (c), each in the
    program's place and through `harness.Compared`: not correct; the
    reference itself reads 0. (The int8 control moves no first choice at the
    toy's widths, 64 wide over 64 bytes: it is read on the chip.)"""
    cell, kept = toy_sequences()
    gaps, weight_max = driver.served_gaps(
        kept["cfg"], kept["shapes"], 4, kept["sequences"], kept["max_seq"],
        kept["max_new"], chooser="float32")
    assert max(gaps) == 0.0 and len(gaps) == 50 and weight_max > 0.26
    assert readings_eva.judged(cell.limits, gaps)["correct"]
    upper = readings_eva.upper_readings(driver, cell.limits, kept, 4,
                                        control="float32", faults=[fault])
    assert set(upper) == {"control_float32", "fault_" + fault}
    assert upper["control_float32"]["correct"]
    reading = upper["fault_" + fault]
    row = reading["compared"]["served_logit_gap_max"]
    assert not reading["correct"] and row["value"] > row["limit"] == 1e-3
    assert 0 < reading["beyond_share"] < 1


def test_seeded_vectors_are_the_familys_initialisation():
    cell, kept = toy_sequences()
    params = weights.make_params(kept["shapes"], 9)
    phi = np.asarray(params["block_1"]["attention"]["phi"], np.float64)
    seeded = driver.seeded_vectors(params)
    got = np.asarray(seeded["block_1"]["attention"]["phi"], np.float64)
    np.testing.assert_allclose(got, np.clip(phi / 0.02, -1, 1) * 16 ** -0.25,
                               rtol=1e-6)
    assert np.abs(got).max() <= 0.5 and np.abs(got).mean() > 0.25
    assert seeded["block_1"]["attention"]["key"] is (
        params["block_1"]["attention"]["key"])
    zeroed = readings_eva.phi_zero(seeded)["block_0"]["attention"]
    assert not np.asarray(zeroed["phi"]).any() and np.asarray(zeroed["mu"]).any()


def test_counts_against_hand_worked_numbers():
    cfg = harness.load_cell(CELL).config
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008
    assert counts.matmul_params(cfg) == 8 * layer + 4096 * 2560
    assert counts.row_bytes(cfg) == 2 * 4096 * 2 == 16384
    # About the mix's mean depth (19 200: 9 windows behind, 769 rows of its
    # own), at every slot.
    rows = 16 * 2160
    assert counts.tick_bytes(cfg, rows) == (
        2 * counts.matmul_params(cfg) + rows * 16384 * 8)
    assert round(counts.tick_bytes(cfg, rows) / 1e9, 2) == 7.79
    assert counts.tick_flops(cfg, 16, rows) == (
        2 * counts.matmul_params(cfg) * 16 + 4 * rows * 4096 * 8)
    seconds, bound = counts.tick_least_seconds(cfg, 16, rows, PEAKS)
    assert bound == "bytes" and seconds == pytest.approx(
        counts.tick_bytes(cfg, rows) / 819e9)
    seconds, bound = counts.rows_least_seconds(cfg, rows, PEAKS)
    assert bound == "bytes" and seconds == pytest.approx(rows * 16384 / 819e9)
    assert counts.attention_shape(cfg) == (32, 32, 128) and counts.layers(cfg) == 8


def _observed(trace=None, peaks=None, counters=None):
    return {"trace": trace, "peaks": peaks, "counters": counters or {},
            "config": harness.load_cell(CELL).config, "window_s": 10.0}


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_reader_returns_none_where_it_finds_nothing(metric):
    reader = harness.find("layer_metrics", metric)
    empty = tracing.reduce_events([], tracing.Events.of([]), None, 1e9)
    assert reader.read(_observed()) is None
    assert reader.read(_observed(trace=empty, peaks=PEAKS)) is None
    # A program without the counter (the parent; another model's window
    # layers run the same kernel): nothing.
    plain = {"ticks": 100, "occupancy": {"16": [100, 15.0]}, "slots": 16}
    modules = tracing.Events.of([("jit_serve_tick(1)", 0.0, 5e6)])
    call = ("%attention._paged_decode_attention.paged_decode_window.4 = "
            "bf16[16,1,4096] custom-call(bf16[16,1,4096] %q), "
            "custom_call_target=\"tpu_custom_call\"")
    ticked = tracing.reduce_events(
        [tracing.Events.of([(call, 0, 500)])], modules, None, 6e6)
    assert reader.read(_observed(trace=ticked, peaks=PEAKS, counters=plain)) is None


def test_new_readers_on_a_synthetic_trace():
    """Two traced ticks of 16 ms: eight reads of 1.2 ms each a tick, 15 of 16
    slots active and 32 000 rows a tick in the window's mean."""
    call = ("%attention._paged_decode_attention.paged_decode_window.{} = "
            "bf16[16,1,4096] custom-call(bf16[16,1,4096] %q), "
            "custom_call_target=\"tpu_custom_call\"")
    ops = [(call.format(layer), tick * 20e6 + layer * 2e6, 1.2e6)
           for tick in range(2) for layer in range(8)]
    ops.append(("%attention._paged_decode_attention.paged_decode.3 = bf16[16,1,4096]"
                " custom-call(bf16[16,1,4096] %q), custom_call_target="
                "\"tpu_custom_call\"", 1e6, 9e5))     # a full layer's walk: not it
    modules = tracing.Events.of([("jit_serve_tick(7)", 0.0, 16e6),
                                 ("jit_serve_tick(7)", 20e6, 16e6),
                                 ("jit_serve_prefill(9)", 40e6, 1e6)])
    trace = tracing.reduce_events([tracing.Events.of(ops)], modules, None, 60e6)
    counters = {"ticks": 500, "eva_rows_read": 32000 * 500, "ticks_traced": 110,
                "eva_rows_read_traced": 35000 * 110,
                "occupancy": {"16": [500, 15.0]}, "slots": 16}
    observed = _observed(trace, PEAKS, counters)
    read = lambda name: harness.find("layer_metrics", name).read(observed)
    # The slice's own 35 000 rows a tick, not the window's 32 000.
    least, _ = counts.rows_least_seconds(observed["config"], 35000.0, PEAKS)
    # 16 events of 1.2 ms: a layer's least time x 16 over their seconds.
    assert read("eva_read_roofline") == pytest.approx(100 * least * 16 / 19.2e-3)
    assert 55 < read("eva_read_roofline") < 60
    assert read("eva_read_tick_share_pct.serve") == pytest.approx(
        100 * 19.2e-3 / 32e-3)
    whole, _ = counts.tick_least_seconds(observed["config"], 15.0, 32000.0, PEAKS)
    # The window's 10 s over 500 ticks is 20 ms a tick.
    assert read("tick_mfu.eva") == pytest.approx(100 * whole / 20e-3)
    assert 0 < read("tick_mfu.eva") < 100
