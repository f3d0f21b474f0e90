"""The readers that find kernels and programs by the names the program
declares, and requests by the program's own records: each on a synthetic
reduced trace or synthetic records, the value by hand, nothing on nothing,
nothing on a count that does not match. No number here is a device metric.
"""

import os

import pytest

from cellbench import harness, kernel_events, request_records, tracing
from cellbench.layer_metrics import (decode_gap_p99_ms_serve, flash_bwd_roofline,
                                     flash_fwd_roofline, flash_roofline,
                                     prefill_device_ms_chat, swiglu_roofline,
                                     tick_device_ms_serve)
from cloud_tpu.serving import reqtrace

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
QWEN = harness.load_json(os.path.join(harness.ROOT, "cellbench/configs",
                                      "qwen2.5-0.5b.json"))
CALL = "%{}.{} = (bf16[56,1024,64]) custom-call(bf16[56,1024,64] %b)"
# 4 x 14 heads x 1024 x 1024 x 64 multiply-adds, halved by the mask, 2 FLOPs.
PRODUCT = 2 * 4 * 14 * 1024 * 1024 * 64 // 2


def reduced(op_triples, module_triples=()):
    return tracing.reduce_events([tracing.Events.of(list(op_triples))],
                                 tracing.Events.of(list(module_triples)), None,
                                 10 ** 9)


def train_observed(trace, **changes):
    observed = {"trace": trace, "peaks": PEAKS, "config": QWEN, "chips": 1,
                "counters": {"batch": 4, "seq": 1024}}
    observed.update(changes)
    return observed


def kernel_events_of(name, sites=24, steps=2, ms=1.0):
    return [(CALL.format(name, i), 1000 * (steps * i + s), int(ms * 1e6))
            for i in range(sites) for s in range(steps)]


# ------------------------------------------------------------ the finder

@pytest.mark.parametrize("text,declared,found", [
    (CALL.format("attention.flash_fwd", 3), "flash_fwd", True),
    (CALL.format("attention.flash_bwd_dq", 3), "flash_fwd", False),
    (CALL.format("fused_rmsnorm_residual", 3), "fused_rmsnorm", False),
    (CALL.format("fused_rmsnorm", 3), "fused_rmsnorm", True),
    (CALL.format("fused_swiglu_fwd", "7.remat"), "fused_swiglu_fwd", True),
    # A consumer of the kernel's result is not the kernel.
    ("%fusion.9 = bf16[8] fusion(bf16[8] %fused_swiglu_fwd.7), kind=kLoop",
     "fused_swiglu_fwd", False),
    ("%fused_rmsnorm_residual.2 = bf16[8] custom-call(bf16[8] %fused_swiglu_fwd.7)",
     "fused_swiglu_fwd", False),
    # A transform mangled the instruction's name: the metadata decides.
    ('%jvp_flash_fwd_.1 = bf16[8] custom-call(bf16[8] %a), metadata={op_name='
     '"jit(f)/attention/jvp(flash_fwd)/pallas_call"}', "flash_fwd", True),
    ('%jvp__.1 = bf16[8] custom-call(bf16[8] %a), metadata={op_name='
     '"jit(f)/attention/jvp()/pallas_call"}', "flash_fwd", False),
])
def test_kernel_is_found_by_its_declared_name_alone(text, declared, found):
    assert kernel_events.is_kernel(text, declared) is found


# --------------------------------------------------------------- kernels

def test_flash_passes_split_what_the_whole_reads():
    trace = reduced(kernel_events_of("attention.flash_fwd")
                    + kernel_events_of("attention.flash_bwd_dq")
                    + kernel_events_of("attention.flash_bwd_dkv")
                    + [("%fusion.1 = f32[4] fusion(f32[4] %a), kind=kLoop", 0, 500)])
    observed = train_observed(trace)
    forward = 2 * PRODUCT / 197e12            # FLOP-bound, both passes
    backward = 4 * PRODUCT / 197e12
    assert flash_fwd_roofline.read(observed) == pytest.approx(
        100 * forward * 24 * 2 / 0.048)
    assert flash_bwd_roofline.read(observed) == pytest.approx(
        100 * backward * 24 * 2 / 0.096)
    # The accepted reader sees the same events under their kept prefix.
    assert flash_roofline.read(observed) == pytest.approx(
        100 * (forward + backward) * 24 * 2 / 0.144)


def test_flash_pass_readers_return_nothing_on_nothing():
    forward_only = train_observed(reduced(kernel_events_of("attention.flash_fwd")))
    assert flash_fwd_roofline.read(forward_only) is not None
    assert flash_bwd_roofline.read(forward_only) is None       # no dq, no dk/dv
    # The parent's names carry no declared name.
    parent = train_observed(reduced(kernel_events_of("attention")))
    assert flash_fwd_roofline.read(parent) is None
    assert flash_bwd_roofline.read(parent) is None
    assert flash_fwd_roofline.read(train_observed(None)) is None
    assert flash_fwd_roofline.read(dict(forward_only, peaks=None)) is None


def test_swiglu_roofline_by_hand():
    trace = reduced(kernel_events_of("fused_swiglu_fwd")
                    + kernel_events_of("fused_rmsnorm_residual", ms=9.0))
    least = 3 * 2 * 4096 * 896 * 4864 / 197e12
    assert swiglu_roofline.read(train_observed(trace)) == pytest.approx(
        100 * least * 24 * 2 / 0.048)
    assert swiglu_roofline.read(train_observed(
        reduced(kernel_events_of("mlp")))) is None
    assert swiglu_roofline.read(train_observed(None)) is None
    gpt = harness.load_json(os.path.join(harness.ROOT, "cellbench/configs",
                                         "gpt2-xl.json"))
    assert swiglu_roofline.read(train_observed(trace, config=gpt)) is None


# -------------------------------------------------------------- programs

def test_program_device_time_by_declared_name():
    modules = [("jit_serve_tick(7)", 0, 30_000_000),
               ("jit_serve_prefill(9)", 31_000_000, 8_000_000),
               ("jit_serve_tick(7)", 40_000_000, 40_000_000),
               ("jit_slot_insert(3)", 81_000_000, 1_000_000),
               ("jit_serve_prefill(11)", 83_000_000, 4_000_000)]
    observed = {"trace": reduced([], modules)}
    assert tick_device_ms_serve.read(observed) == pytest.approx(35.0)
    assert prefill_device_ms_chat.read(observed) == pytest.approx(6.0)
    parent = {"trace": reduced([], [("jit__tick_impl(7)", 0, 30_000_000),
                                    ("jit_prefill(9)", 31_000_000, 8_000_000)])}
    assert tick_device_ms_serve.read(parent) is None
    assert prefill_device_ms_chat.read(parent) is None
    assert tick_device_ms_serve.read({"trace": None}) is None


# -------------------------------------------------------------- requests

def record_of(server, rid, queue, window, reserve, prefill, gaps):
    r = reqtrace.RequestRecord(rid, server, 8, len(gaps) + 1, 100.0)
    r.t_dequeued = r.t_submit + queue
    r.t_admit = r.t_dequeued + window
    r.t_reserved = r.t_admit + reserve
    r.t_first = r.t_reserved + prefill
    r.t_insert = r.t_first
    t = r.t_first
    for gap in gaps:
        t += gap
        r.token_times.append(t)
    r.t_done, r.new_tokens = t, len(gaps) + 1
    return r


@pytest.fixture
def twenty_records():
    """Eighteen requests with a first token after 20 ms, two after 200 and
    300 ms, held back in another phase each."""
    reqtrace.clear()
    server = reqtrace.new_server()
    for i in range(18):
        reqtrace.publish(record_of(server, "q%d" % i, 0.004, 0.001, 0.001, 0.014,
                                   [0.040, 0.041]))
    reqtrace.publish(record_of(server, "slow_window", 0.010, 0.150, 0.002, 0.038,
                               [0.040, 0.200]))
    reqtrace.publish(record_of(server, "slow_reserve", 0.020, 0.010, 0.230, 0.040,
                               [0.045, 0.040]))
    yield {"counters": {"completed": 20}}
    reqtrace.clear()


def test_slow_decile_phases_add_up_to_its_ttft(twenty_records):
    observed = twenty_records
    # The 90th percentile of 18 x 20 ms, 200, 300 lies between 20 and 200 ms:
    # the slow decile is the two stalled requests.
    want = {"queue": 15.0, "window": 80.0, "reserve": 116.0, "prefill": 39.0}
    got = {}
    for phase in request_records.PHASES:
        reader = harness.find("layer_metrics", "ttft_slow_%s_ms.chat" % phase)
        got[phase] = reader.read(observed)
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx((200.0 + 300.0) / 2)


def test_decode_gap_is_over_tokens_not_requests(twenty_records):
    gaps = [0.040, 0.041] * 18 + [0.040, 0.200, 0.045, 0.040]
    assert decode_gap_p99_ms_serve.read(twenty_records) == pytest.approx(
        1e3 * harness.percentile(gaps, 99))
    # One stalled token of forty: the 99th percentile over tokens sees it,
    # a mean gap a request would have read 120 ms for one request of twenty.
    assert decode_gap_p99_ms_serve.read(twenty_records) > 100.0


@pytest.mark.parametrize("metric", [
    "decode_gap_p99_ms.serve", "ttft_slow_queue_ms.chat",
    "ttft_slow_window_ms.chat", "ttft_slow_reserve_ms.chat",
    "ttft_slow_prefill_ms.chat"])
def test_request_readers_return_nothing_on_a_count_mismatch(twenty_records,
                                                            metric):
    reader = harness.find("layer_metrics", metric)
    assert reader.read(twenty_records) is not None
    assert reader.read({"counters": {"completed": 19}}) is None
    assert reader.read({"counters": {}}) is None
    reqtrace.new_server()            # another server started: none of its own
    assert reader.read(twenty_records) is None
    reqtrace.clear()
    assert reader.read({"counters": {"completed": 0}}) is None


def test_request_readers_return_nothing_from_a_program_without_records(
        twenty_records, monkeypatch):
    monkeypatch.delattr(reqtrace, "recent")          # the parent commit
    assert request_records.finished(twenty_records) is None
    assert decode_gap_p99_ms_serve.read(twenty_records) is None


# ------------------------------------------------- tools/spans.py, by hand

def test_span_tool_self_time_and_gap_attribution():
    from cellbench.tools import spans as span_tool

    tick = sorted([(0, 100, "serve_tick"), (5, 40, "tick_dispatch"),
                   (40, 95, "tick_fetch"), (50, 90, "d2h_fetch"),
                   (100, 130, "tick_commit"), (130, 200, "tick_admit"),
                   (200, 300, "serve_tick"), (205, 240, "tick_dispatch")])
    rows = span_tool.self_times(tick)
    assert rows["serve_tick"] == [2, 200.0, 200.0 - 35 - 55 - 35]
    assert rows["tick_fetch"] == [1, 55.0, 15.0]
    assert rows["d2h_fetch"] == [1, 40.0, 40.0]
    assert span_tool.innermost_at(tick, 60) == "d2h_fetch"
    assert span_tool.innermost_at(tick, 96) == "serve_tick"
    assert span_tool.innermost_at(tick, 150) == "tick_admit"
    assert span_tool.innermost_at(tick, 999) is None
    assert span_tool.role(tick, 0) == "tick"
    assert span_tool.role([(0, 1, "admit"), (0, 1, "serve_prefill")], 1) == "admission"
    assert span_tool.role([(0, 1, "train_step")], 2) == "train"
    assert span_tool.role([(0, 1, "d2h_fetch")], 3) == "thread3"

    ms = 1_000_000
    # Busy 0-10 ms, idle to 13 ms (the gap starts inside tick_commit), busy
    # to 20 ms, idle 0.5 ms (too short to list), busy, then idle 2 ms with no
    # span of the tick thread over the gap's start.
    ops = tracing.Events.of([("a", 0, 10 * ms), ("b", 13 * ms, 7 * ms),
                             ("c", 20.5 * ms, 4.5 * ms), ("d", 27 * ms, ms)])
    spans_of_tick = [(9 * ms, 12 * ms, "tick_commit"), (12 * ms, 14 * ms, "tick_admit")]
    assert span_tool.gaps_by_span(ops, spans_of_tick) == {
        "tick_commit": [1, 3.0 * ms, 3.0 * ms], "(no span)": [1, 2.0 * ms, 2.0 * ms]}
    assert span_tool.gaps_by_span(None, spans_of_tick) == {}
