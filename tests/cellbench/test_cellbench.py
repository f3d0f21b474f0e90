"""Tier-1 checks of the benchmark itself, on the CPU at toy widths.

They show that the harness is driven by data (every entry of BENCHMARK.json
resolves to files; a second, toy configuration loads by name), that each
driver runs end to end and prints the contract's line, that the yardstick's
arithmetic is right (counts against hand-worked numbers, the trace reduction
on a synthetic trace, the generator as a pure function of the seed), and that
`correct` comes out false when the timed path is broken underneath or the
reference is computed in a lower precision. No number here is a device
metric.
"""

import concurrent.futures
import copy
import json
import os
import re
import time

import numpy as np
import pytest

from cellbench import harness, tracing, traffic
from cellbench.counts import gpt2 as gpt2_counts
from cellbench.counts import llama as llama_counts

HERE = os.path.dirname(os.path.abspath(__file__))
TOY_ROOT = os.path.join(HERE, "toy")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = harness.load_benchmark()
# Limits of the toy sizes, set as the chip's are (PERF.md section 2) from toy
# readings: everything reads smaller through two narrow layers. Training
# (median leaf): sound runs 0.5-1.3e-4, the fp8 control 6-9e-4. Serving: sound runs under
# 0.002, the int8 control 0.04-0.09 (at TOY_CONTROL widths), a wrong token 0.4.
TOY_LIMITS = {"llama": {"grad_norm_gap_median_leaf": 3e-4, "update_norm_gap": 0.1},
              "gpt2": {"served_logit_gap_max": 0.02}}
TOY_CONTROL = dict(n_embd=128, n_inner=512, n_layer=4, n_head=4, head_dim=32,
                   vocab_size=1024, n_positions=128)


# ------------------------------------------------------------------ data

def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in names
            names.add((group, entry["name"]))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
        assert set(m.get("workloads", [])) <= cells
        # Its reader is a file of its own, found by the metric's name.
        assert callable(harness.find("layer_metrics", m["name"]).read)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_files(workload):
    cell = harness.load_cell(workload)
    entry = next(w for w in BENCH["workloads"] if w["name"] == workload)
    assert NAME.match(entry["config"]) and NAME.match(entry["traffic"])
    assert len(entry["why"]) <= 200 and entry["chips"] in (1, 4)
    config = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert sorted(config["reduced"]) == sorted(cell.config["reduced"])
    assert callable(harness.find("drivers", cell.traffic["driver"]).run)
    harness.find("reference", cell.config["family"])
    harness.find("counts", cell.config["family"])
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer and cell.limits
    for m in cell.per_layer:       # each reports the metric it should move
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_a_second_configuration_loads_by_name_with_no_edit():
    """A later PR's cell: a BENCHMARK.json entry and files of its own."""
    bench = harness.load_json(os.path.join(TOY_ROOT, "BENCHMARK.json"))
    cell = harness.load_cell("toy_train", bench=bench, root=TOY_ROOT)
    assert cell.config["hidden_size"] == 64 and cell.traffic["driver"] == "fit_window"
    assert cell.limits["grad_norm_gap_median_leaf"] > 0
    assert [m["name"] for m in cell.per_layer] == ["step_mfu"]


def test_peaks_unknown_device_kind_raises():
    assert harness.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peaks_for("cpu")
    with pytest.raises(SystemExit):      # no TPU here: no fallback to the CPU
        harness.device_stamp(1)


# ------------------------------------------------------------ arithmetic

def test_counts_against_hand_worked_numbers():
    qwen = harness.load_json(os.path.join(
        harness.ROOT, "cellbench/configs/qwen2.5-0.5b.json"))
    # per layer: q 896*896 + k,v 2*896*128 + o 896*896 + mlp 3*896*4864
    layer = 802816 + 229376 + 802816 + 13074432
    assert llama_counts.matmul_params(qwen) == 24 * layer + 896 * 151936
    # 3 x (2 x 493 961 216 + causal attention 2*1024*896*24)
    assert llama_counts.train_flops_per_token(qwen, 1024) == 3 * (
        2 * 493961216 + 44040192)
    gpt = harness.load_json(os.path.join(
        harness.ROOT, "cellbench/configs/gpt2-xl.json"))
    params = 24 * (4 * 1600 * 1600 + 2 * 1600 * 6400) + 1600 * 50257
    assert gpt2_counts.matmul_params(gpt) == params == 817691200
    assert gpt2_counts.kv_bytes_per_token(gpt) == 153600
    assert gpt2_counts.tick_bytes(gpt, 10000) == params * 4 + 1536000000
    assert gpt2_counts.tick_flops(gpt, 16, 10000) == (
        2 * params * 16 + 4 * 10000 * 1600 * 24)
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = gpt2_counts.tick_least_seconds(gpt, 16, 10000, peaks)
    assert bound == "bytes" and seconds == pytest.approx(4806764800 / 819e9)


def test_kernel_counts_against_hand_worked_numbers():
    from cellbench.counts import flash_attention, paged_attention

    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # One layer of the train cell: 4 x 14 heads x 1024^2 x 64, causal half,
    # 2 FLOPs a multiply-add, 2 products forward and 4 backward.
    assert flash_attention.train_flops(4, 14, 1024, 64) == 6 * 3758096384
    # q, o, do, dq 7 340 032 B each; k, v, dk, dv 1 048 576 B each (2 kv heads).
    assert flash_attention.train_bytes(4, 14, 2, 1024, 64) == 6 * 7340032 + 3 * 2097152
    seconds, bound = flash_attention.train_least_seconds(4, 14, 2, 1024, 64, peaks)
    assert bound == "flops" and seconds == pytest.approx(22548578304 / 197e12)
    assert paged_attention.tick_bytes(10000, 1600, 2) == 64000000
    assert paged_attention.tick_flops(10000, 1600) == 64000000
    seconds, bound = paged_attention.tick_least_seconds(10000, 1600, 2, peaks)
    assert bound == "bytes" and seconds == pytest.approx(64e6 / 819e9)


def test_roofline_readers_on_a_synthetic_trace():
    """Kernels are found by the names the trace gives them today; a reader
    that finds none returns nothing, never 0."""
    from cellbench.layer_metrics import flash_roofline, paged_attn_roofline

    flash = "%attention.{} = (bf16[56,1024,64]) custom-call(bf16[56,1024,64] %b)"
    paged = ("%attention._paged_decode_attention.{} = bf16[16,1,1600] "
             "custom-call(s32[16,64] %c)")
    other = "%fusion.1 = f32[4] fusion(f32[4] %a), kind=kLoop"
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    qwen, gpt = (harness.load_json(os.path.join(harness.ROOT, "cellbench/configs", f))
                 for f in ("qwen2.5-0.5b.json", "gpt2-xl.json"))

    # Two steps traced: 72 call sites (24 layers x fwd, dq, dk/dv), 1 ms each.
    ops = tracing.Events.of(
        [(flash.format(i), 1000 * (2 * i + step), 1_000_000)
         for i in range(72) for step in range(2)] + [(other, 0, 500)])
    reduced = tracing.reduce_events([ops], tracing.Events.of([]), None, 10 ** 9)
    observed = {"trace": reduced, "peaks": peaks, "config": qwen, "chips": 1,
                "counters": {"batch": 4, "seq": 1024}}
    least = 22548578304 / 197e12 * 24 * 2
    assert flash_roofline.read(observed) == pytest.approx(100 * least / 0.144)
    assert paged_attn_roofline.read(dict(observed, counters={"ticks": 3})) is None

    # Three ticks traced, 24 layers, 1 ms each; 10 000 live tokens a tick.
    ops = tracing.Events.of(
        [(paged.format(i), 1000 * (3 * i + tick), 1_000_000)
         for i in range(24) for tick in range(3)] + [(other, 0, 500)])
    reduced = tracing.reduce_events([ops], tracing.Events.of([]), None, 10 ** 9)
    observed = {"trace": reduced, "peaks": peaks, "config": gpt, "chips": 1,
                "counters": {"ticks": 100, "live_token_ticks": 1_000_000}}
    assert paged_attn_roofline.read(observed) == pytest.approx(
        100 * (64e6 / 819e9) * 72 / 0.072)
    assert flash_roofline.read(dict(observed, counters={"batch": 4, "seq": 8})) is None
    assert paged_attn_roofline.read(dict(observed, peaks=None)) is None


def test_compile_watch_counts_what_reaches_the_compiler():
    import jax
    import jax.numpy as jnp

    watch = harness.CompileWatch()
    try:
        mark = watch.mark()
        fn = jax.jit(lambda x: x * 3 + 1)
        fn(jnp.ones(7)).block_until_ready()
        assert watch.since(mark)[0] >= 1
        mark = watch.mark()
        fn(jnp.ones(7)).block_until_ready()      # compiled already
        assert watch.since(mark) == (0, 0.0)
    finally:
        watch.close()


def test_trace_reduction_on_a_synthetic_trace():
    ops = tracing.Events.of([("fusion.1", 0, 100), ("flash", 50, 100),
                             ("fusion.1", 400, 100), ("flash", 900, 50)])
    modules = tracing.Events.of([("jit_step", 0, 150), ("jit_step", 400, 100),
                                 ("jit_other", 600, 10), ("jit_step", 900, 50)])
    host = tracing.Events.of([("dispatch", 160, 200), ("fetch", 520, 300)])
    r = tracing.reduce_events([ops], modules, host, 1000)
    assert r.window_s == pytest.approx(1e-6)
    assert r.busy_s == pytest.approx(300e-9)          # union, overlap once
    assert r.op_seconds["flash"] == pytest.approx(150e-9)
    assert r.op_counts == {"fusion.1": 2, "flash": 2}
    assert r.gaps[0] == ("fetch", pytest.approx(400e-9))
    assert r.gaps[1] == ("dispatch", pytest.approx(250e-9))
    assert r.module_gaps_s(lambda n: n == "jit_step") == pytest.approx(
        [250e-9, 400e-9])
    assert r.breakdown()["device_ops"][0][0] == "fusion.1"
    two = tracing.reduce_events([ops, ops], modules, host, 1000)
    assert two.busy_s == pytest.approx(r.busy_s)      # mean over chips
    assert two.op_seconds["flash"] == pytest.approx(150e-9)


def test_generator_is_a_pure_function_of_the_seed():
    mix = harness.load_traffic("open_loop_chat")
    a = traffic.arrival_times(mix, 2 ** 31 + 5, 30.0)
    assert np.array_equal(a, traffic.arrival_times(mix, 2 ** 31 + 5, 30.0))
    b = traffic.arrival_times(mix, 6, 30.0)
    assert not np.array_equal(a[:20], b[:20])
    assert np.all(np.diff(a) > 0) and 29.5 < a[-1] < 30.0
    # Every seed offers the window the same number of requests and the same
    # gaps, in another order; the last ones keep the mix's own order.
    n = traffic.arrival_count(mix, 30.0)
    assert len(a) == len(b) == n == int(mix["rate_per_s"] * 30)
    gaps = lambda t: np.diff(np.concatenate([[0.0], t]))
    assert np.allclose(np.sort(gaps(a)), np.sort(gaps(b)))
    assert np.allclose(gaps(a)[-traffic.PINNED:], gaps(b)[-traffic.PINNED:])
    ra = traffic.Requests(mix, 50257, 1024, 11, count=n)
    rb = traffic.Requests(mix, 50257, 1024, 12, count=n)
    sizes = lambda r: [(len(r[i][0]), r[i][1]) for i in range(n)]
    assert sorted(p for p, _ in sizes(ra)) == sorted(p for p, _ in sizes(rb))
    assert sorted(t for _, t in sizes(ra)) == sorted(t for _, t in sizes(rb))
    assert sizes(ra) != sizes(rb)
    assert sizes(ra)[-traffic.PINNED:] == sizes(rb)[-traffic.PINNED:]
    assert np.array_equal(ra[3][0], traffic.Requests(mix, 50257, 1024, 11, count=n)[3][0])
    assert all(len(ra[i][0]) + ra[i][1] <= 1024 for i in range(2 * n))
    lo, hi = ra.prompt_range()
    assert lo >= mix["prompt_len"]["lo"] and hi <= mix["prompt_len"]["hi"]
    # A closed loop draws cycle after cycle: the same sizes in each.
    closed = harness.load_traffic("closed_loop_decode")
    rc = traffic.Requests(closed, 50257, 1024, 11)
    cycle = closed["cycle"]
    lens = lambda lo: sorted(len(rc[i][0]) for i in range(lo, lo + cycle))
    assert lens(0) == lens(cycle) and {rc[i][1] for i in range(cycle)} == {128}
    bursty = traffic.gap_grid({"process": "gamma", "cv2": 4.0}, 5.0, 128)
    assert bursty.mean() == pytest.approx(0.2)
    assert bursty.std() / bursty.mean() > 1.5


# ------------------------------------------------------ drivers, end to end

def toy_cell(workload, **traffic_changes):
    """The accepted cell's metrics and limits over a toy configuration."""
    cell = copy.deepcopy(harness.load_cell(workload))
    cfg, mix = cell.config, cell.traffic
    if cfg["family"] == "llama":
        cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                   vocab_size=256)
        cfg["assumed"]["seq_len"] = 32
        mix.update(steps_per_epoch=8, batch_per_chip=1)
    else:
        cfg.update(n_embd=64, n_inner=128, n_layer=2, n_head=4, head_dim=16,
                   vocab_size=256, n_positions=64)
        cfg["assumed"].update(slots=4, page_size=8)
        mix.update(prompt_len={"dist": "uniform", "lo": 8, "hi": 40},
                   new_tokens={"dist": "uniform", "lo": 4, "hi": 12},
                   cycle=16, clients=4, rate_per_s=20.0, check_requests=3)
    mix.update(trace_after_s=0.2, trace_for_s=0.4)
    mix.update(traffic_changes)
    cell.limits = dict(TOY_LIMITS[cfg["family"]])
    return cell


def drive(workload, trace=False, plant=None, seconds=1.0, seed=2 ** 31 + 17,
          **traffic_changes):
    cell = toy_cell(workload, **traffic_changes)
    run = harness.Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
                      t_process=time.perf_counter(), plant=plant)
    observed = harness.find("drivers", cell.traffic["driver"]).run(run)
    return cell, json.loads(json.dumps(harness.result_line(cell, run, observed)))


def check_line(cell, line, trace):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["attempted"] > 0 and line["failed"] == 0
    for row in line["compared"].values():
        assert set(row) == {"value", "limit"}
    wanted = cell.per_layer if trace else cell.end_to_end
    units = {m["name"]: m["unit"] for m in wanted}
    assert set(line["metrics"]) <= set(units)
    for name, got in line["metrics"].items():
        assert got["unit"] == units[name] and got["value"] > 0


TRAIN = "qwen25_05b_train_1chip"
SERVE = [w["name"] for w in BENCH["workloads"] if w["name"] != TRAIN]


def test_train_driver_end_to_end():
    cell, line = drive(TRAIN)
    check_line(cell, line, trace=False)
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert {"grad_norm_gap_median_leaf", "update_norm_gap", "no_compile_in_window",
            "window_steps_ran"} == set(line["compared"])


def test_train_driver_traced_reports_only_what_it_can_read():
    cell, line = drive(TRAIN, trace=True)
    check_line(cell, line, trace=True)
    # No TPU plane in a CPU trace and no table of peaks: the readers return
    # nothing rather than a 0, and no device metric is printed from a CPU run.
    assert line["metrics"] == {}


@pytest.mark.parametrize("workload", SERVE)
def test_serve_driver_end_to_end(workload):
    cell, line = drive(workload)
    check_line(cell, line, trace=False)
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}


@pytest.mark.parametrize("workload", SERVE[:1])
def test_serve_driver_traced_counters(workload):
    cell, line = drive(workload, trace=True)
    check_line(cell, line, trace=True)
    assert {"tick_ms.serve", "slot_occupancy_pct.serve"} <= set(line["metrics"])
    assert "tick_mfu" not in line["metrics"]      # needs the chip's peaks
    assert 0 < line["metrics"]["slot_occupancy_pct.serve"]["value"] <= 100


# ---------------------------------------- `correct` has been shown to fail

class _BrokenStep:
    """The trainer's compiled step with `call` in place of its call; every
    other attribute (warm, lower, n_traces) is the step's own."""

    def __init__(self, step, call):
        self._step, self._call = step, call

    def __call__(self, state, batch):
        return self._call(self._step, state, batch)

    def __getattr__(self, name):
        return getattr(self._step, name)


def _break_step(built, call):
    make = built.trainer._make_train_step
    built.trainer._make_train_step = lambda *a, **k: _BrokenStep(make(*a, **k), call)


def plant_state_unchanged(built):
    import jax
    import jax.numpy as jnp

    def unchanged(step, state, batch):
        kept = jax.tree_util.tree_map(jnp.copy, state)   # the step donates it
        return kept, step(state, batch)[1]
    _break_step(built, unchanged)


def plant_half_batch(built):
    import jax
    import jax.numpy as jnp

    def halved(step, state, batch):
        half = batch[0].shape[0] // 2
        return step(state, tuple(jax.device_put(
            jnp.concatenate([b[:half], b[:half]]), b.sharding) for b in batch))
    _break_step(built, halved)


@pytest.mark.parametrize("plant", [plant_state_unchanged, plant_half_batch])
def test_train_fault_comes_out_not_correct(plant):
    _, line = drive(TRAIN, plant=plant, batch_per_chip=2)
    assert not line["correct"], line["compared"]


def plant_altered_token(served):
    submit = served.scheduler.submit

    def altered(request, timeout=None):
        inner, outer = submit(request, timeout=timeout), concurrent.futures.Future()

        def relay(f):
            if f.exception() is not None:
                return outer.set_exception(f.exception())
            result = f.result()
            tokens = np.array(result.tokens)
            tokens[-2] = (tokens[-2] + 1) % 256
            result.tokens = tokens
            outer.set_result(result)
        inner.add_done_callback(relay)
        return outer
    served.scheduler.submit = altered


@pytest.mark.parametrize("workload", SERVE[:1])
def test_serve_altered_token_comes_out_not_correct(workload):
    _, line = drive(workload, plant=plant_altered_token)
    assert not line["correct"]
    assert (line["compared"]["served_logit_gap_max"]["value"]
            > line["compared"]["served_logit_gap_max"]["limit"])


def test_stalled_scheduler_raises_ttft_from_due_time():
    """Times count from when a request was due: a stall in the server shows in
    the requests behind it even though each was 'submitted' late."""
    if "gpt2xl_chat_open" not in SERVE:
        pytest.skip("no open-loop cell")

    def stall(served):
        submit, state = served.scheduler.submit, {"n": 0}

        def slow(request, timeout=None):
            state["n"] += 1
            if state["n"] == 3:
                time.sleep(0.8)        # the generator is held, later ones are late
            return submit(request, timeout=timeout)
        served.scheduler.submit = slow
    _, fast = drive("gpt2xl_chat_open", seed=5)
    _, slow = drive("gpt2xl_chat_open", seed=5, plant=stall)
    assert (slow["metrics"]["ttft_p50_ms"]["value"]
            > fast["metrics"]["ttft_p50_ms"]["value"] + 100)


def test_control_in_lower_precision_comes_out_not_correct():
    """The reference computed in fp8 and put in the program's place fails the
    training cell's limits (the chip's readings at full size are in PERF.md)."""
    from cellbench import weights
    from cellbench.drivers import fit_window as fw

    cell = toy_cell(TRAIN)
    cfg, opt = cell.config, dict(cell.config["assumed"]["optimizer"])
    opt.pop("name")
    shapes = weights.param_shapes(weights.build_model(cfg))
    data = harness.rng(3, 1).integers(0, 256, (6, 33)).astype(np.int32)
    batches = [(data[i:i + 2, :-1], data[i:i + 2, 1:]) for i in (0, 2, 4)]
    want = fw.reference_readings(cfg, batches, shapes, 3, opt)
    control = fw.reference_readings(cfg, batches, shapes, 3, opt, precision="fp8")
    compared = harness.Compared()
    fw.compare_readings(compared, control, want, cell.limits)
    assert not compared.ok, compared.as_dict()
    same = harness.Compared()
    fw.compare_readings(same, want, want, cell.limits)
    assert same.ok


def test_serve_control_in_lower_precision_reads_above_the_limit():
    """At each position of a sequence, the token that int8 puts first lies
    further below the reference's best than the limit allows; the one that
    bfloat16 (what the configuration states) puts first does not."""
    from cellbench import weights
    from cellbench.drivers import serving

    cell = toy_cell(SERVE[0]) if SERVE else pytest.skip("no serve cell")
    cfg = cell.config
    cfg.update(TOY_CONTROL)
    shapes = weights.param_shapes(weights.build_model(cfg))
    tokens = harness.rng(4, 1).integers(2, 1024, 96).astype(np.int32)
    gaps = {chooser: max(serving.served_gaps(
        cfg, shapes, 4, [(tokens, 32)], 128, 64, chooser=chooser))
        for chooser in ("bfloat16", "int8")}
    limit = cell.limits["served_logit_gap_max"]
    assert gaps["bfloat16"] < limit < gaps["int8"]
