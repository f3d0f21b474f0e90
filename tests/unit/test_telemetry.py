"""graftscope unified telemetry: spans, registry, exporters, wiring.

Pins the ISSUE-6 acceptance contract: with CLOUD_TPU_TELEMETRY=1 a CPU
fit() emits a Chrome trace whose spans nest correctly and cover >=95%
of measured step wall time, plus a Prometheus textfile with step-latency
percentiles and an MFU gauge; with telemetry off, NO hooks are
installed (the graftsan zero-cost discipline, extended).
"""

import json
import os
import threading

import numpy as np
import optax
import pytest

from cloud_tpu.monitoring import export, spans, telemetry
from cloud_tpu.parallel import runtime
from cloud_tpu.training import Trainer


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts and ends with no ambient telemetry, no span
    tracer, and an empty observer seam."""
    telemetry.disable()
    spans.uninstall()
    yield
    telemetry.disable()
    spans.uninstall()
    runtime.set_observer(None)
    runtime.set_phase(None)


def _mlp():
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(2)(nn.relu(nn.Dense(8)(x)))

    return MLP()


def _toy_data(n=128):
    rng = np.random.RandomState(0)
    x = rng.randn(n, 4).astype("float32")
    y = (rng.rand(n) > 0.5).astype("int32")
    return x, y


# -- span tracer --------------------------------------------------------


class TestSpanTracer:
    def test_span_records_name_tid_and_duration(self):
        tracer = spans.SpanTracer()
        with tracer.span("work"):
            pass
        ((name, tid, t0, dur),) = tracer.events()
        assert name == "work"
        assert tid == threading.get_ident()
        assert t0 > 0 and dur >= 0

    def test_listener_fires_on_completion_and_errors_are_swallowed(self):
        tracer = spans.SpanTracer()
        seen = []
        tracer.add_listener(lambda *args: seen.append(args))
        tracer.add_listener(lambda *args: 1 / 0)  # must not propagate
        with tracer.span("a"):
            pass
        ((name, _t0, _dur, tid),) = seen
        assert name == "a" and tid == threading.get_ident()

    def test_buffer_bounded_and_drop_counted(self):
        tracer = spans.SpanTracer(max_events=2)
        for i in range(5):
            tracer.complete("s{}".format(i), 0, 1)
        assert len(tracer.events()) == 2
        assert tracer.dropped() == 3
        assert tracer.chrome_trace()["metadata"]["dropped_events"] == 3

    def test_chrome_trace_format(self):
        tracer = spans.SpanTracer()
        with tracer.span("outer"):
            with tracer.span("inner"):
                pass
        trace = tracer.chrome_trace()
        assert trace["displayTimeUnit"] == "ms"
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        metas = [e for e in trace["traceEvents"] if e["ph"] == "M"]
        assert {e["name"] for e in xs} == {"outer", "inner"}
        assert {e["name"] for e in metas} == {
            "thread_name", "process_name", "process_sort_index"}
        inner = next(e for e in xs if e["name"] == "inner")
        outer = next(e for e in xs if e["name"] == "outer")
        # Time containment is how the viewers nest.
        assert outer["ts"] <= inner["ts"]
        assert (inner["ts"] + inner["dur"]
                <= outer["ts"] + outer["dur"] + 1e-3)

    def test_chrome_trace_process_lane_identity(self, monkeypatch):
        """PR 7: the pid is the PROCESS INDEX (CLOUD_TPU_PROCESS_ID
        contract), never a hardcoded 1, and process_name metadata
        labels the lane host/pN (pid OSPID) — merged multi-host traces
        must land on distinct, labeled Perfetto lanes."""
        import os
        import socket

        monkeypatch.setenv("CLOUD_TPU_PROCESS_ID", "3")
        tracer = spans.SpanTracer()
        with tracer.span("work"):
            pass
        trace = tracer.chrome_trace()
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert all(e["pid"] == 3 for e in xs)
        pname = next(e for e in trace["traceEvents"]
                     if e.get("name") == "process_name")
        assert pname["pid"] == 3
        assert pname["args"]["name"] == "{}/p3 (pid {})".format(
            socket.gethostname(), os.getpid())
        sort = next(e for e in trace["traceEvents"]
                    if e.get("name") == "process_sort_index")
        assert sort["args"]["sort_index"] == 3

    def test_chrome_trace_default_lane_is_process_zero(self,
                                                       monkeypatch):
        monkeypatch.delenv("CLOUD_TPU_PROCESS_ID", raising=False)
        tracer = spans.SpanTracer()
        with tracer.span("work"):
            pass
        trace = tracer.chrome_trace()
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert all(e["pid"] == 0 for e in xs)

    def test_write_round_trips_json(self, tmp_path):
        tracer = spans.SpanTracer()
        with tracer.span("x"):
            pass
        path = tracer.write(str(tmp_path / "trace.json"))
        assert json.load(open(path))["traceEvents"]

    def test_module_seam_noop_when_disabled(self):
        assert not spans.enabled()
        # begin() opens the profiler annotation tracer or no tracer,
        # so its handle is never None; end() still takes a None.
        spans.end(spans.begin("x"))
        spans.end(None)  # no-op, must not raise
        spans.complete("x", 0, 1)  # dropped, must not raise
        with spans.span("x"):
            pass
        assert spans.current_tracer() is None

    def test_install_is_idempotent_and_uninstall_returns(self):
        tracer = spans.install()
        assert spans.install() is tracer
        assert spans.enabled()
        assert spans.uninstall() is tracer
        assert not spans.enabled()

    def test_trace_steps_tiles_the_loop(self):
        tracer = spans.install()
        consumed = list(spans.trace_steps([1, 2, 3]))
        assert consumed == [1, 2, 3]
        names = [name for name, _, _, _ in tracer.events()]
        assert names.count("train_step") == 3
        assert names.count("data_wait") == 3
        # Each data_wait shares its train_step's start and fits inside.
        events = tracer.events()
        waits = [e for e in events if e[0] == "data_wait"]
        steps = [e for e in events if e[0] == "train_step"]
        for (_, _, w_t0, w_dur), (_, _, s_t0, s_dur) in zip(waits, steps):
            assert w_t0 == s_t0
            assert w_dur <= s_dur

    def test_trace_steps_passthrough_when_disabled(self):
        gen = spans.trace_steps([1, 2])
        assert list(gen) == [1, 2]

    def test_trace_steps_consumer_break_closes_span(self):
        tracer = spans.install()
        for item in spans.trace_steps([1, 2, 3]):
            break  # GeneratorExit at the yield
        names = [name for name, _, _, _ in tracer.events()]
        assert names.count("train_step") == 1


# -- metrics registry ---------------------------------------------------


class TestRegistry:
    def test_counter_and_gauge(self):
        reg = telemetry.Registry()
        reg.counter("c").inc()
        reg.counter("c").inc(4)
        reg.gauge("g").set(2.5)
        snap = reg.snapshot()
        assert snap["counters"]["c"] == 5
        assert snap["gauges"]["g"] == 2.5

    def test_histogram_percentiles_bracket_the_values(self):
        hist = telemetry.Histogram("h", start=1e-3, factor=2.0,
                                   buckets=20)
        for ms in range(1, 101):  # 1ms .. 100ms uniform
            hist.observe(ms / 1e3)
        assert hist.count == 100
        p50 = hist.percentile(50)
        p99 = hist.percentile(99)
        # Exponential buckets: <=2x relative error per read.
        assert 0.025 <= p50 <= 0.1
        assert 0.05 <= p99 <= 0.2
        assert p50 <= hist.percentile(95) <= p99

    def test_histogram_weighted_observe(self):
        hist = telemetry.Histogram("h")
        hist.observe(0.5, count=10)
        assert hist.count == 10
        assert hist.sum == pytest.approx(5.0)

    def test_histogram_overflow_reports_max(self):
        hist = telemetry.Histogram("h", start=1e-3, factor=2.0,
                                   buckets=2)
        hist.observe(99.0)  # way past the last bound
        assert hist.percentile(99) == pytest.approx(99.0)

    def test_empty_histogram_percentile_zero(self):
        assert telemetry.Histogram("h").percentile(99) == 0.0

    def test_registry_get_or_create_returns_same_metric(self):
        reg = telemetry.Registry()
        assert reg.histogram("h") is reg.histogram("h")


# -- exporters ----------------------------------------------------------


class TestPrometheusRender:
    def test_render_counters_gauges_histograms(self):
        reg = telemetry.Registry()
        reg.counter("cloud_tpu_h2d_transfers_total").inc(3)
        reg.gauge("cloud_tpu_mfu_pct_peak").set(27.2)
        hist = reg.histogram("cloud_tpu_step_latency_seconds")
        hist.observe(0.01, count=20)
        text = export.render_prometheus(reg.snapshot())
        assert "# TYPE cloud_tpu_h2d_transfers_total counter" in text
        assert "cloud_tpu_h2d_transfers_total 3" in text
        assert "cloud_tpu_mfu_pct_peak 27.2" in text
        assert ("# TYPE cloud_tpu_step_latency_seconds histogram"
                in text)
        assert 'cloud_tpu_step_latency_seconds_bucket{le="+Inf"} 20' \
            in text
        assert "cloud_tpu_step_latency_seconds_count 20" in text
        # Percentiles as companion gauges, not {quantile=} labels.
        for quantile in ("p50", "p95", "p99"):
            assert ("cloud_tpu_step_latency_seconds_" + quantile
                    in text)

    def test_textfile_write_is_atomic_artifact(self, tmp_path):
        tele = telemetry.Telemetry(str(tmp_path))
        exporter = export.PrometheusTextfileExporter(
            str(tmp_path / "metrics.prom"))
        tele.registry.counter("cloud_tpu_d2h_fetches_total").inc()
        exporter.export(tele)
        text = open(str(tmp_path / "metrics.prom")).read()
        assert "cloud_tpu_d2h_fetches_total 1" in text
        assert not os.path.exists(str(tmp_path / "metrics.prom.tmp"))


class TestFlushWorker:
    def test_blocking_flush_runs_the_pass(self):
        ran = []
        worker = export.FlushWorker(lambda: ran.append(1))
        worker.request(wait=True)
        assert ran == [1]
        worker.close(flush=False)

    def test_flush_errors_never_raise(self):
        worker = export.FlushWorker(lambda: 1 / 0)
        worker.request(wait=True)  # must not raise
        worker.close(flush=False)

    def test_close_runs_final_flush(self):
        ran = []
        worker = export.FlushWorker(lambda: ran.append(1))
        worker.close(flush=True)
        assert ran == [1]


class TestNativeExporter:
    def test_counter_deltas_and_percentile_gauges(self, monkeypatch):
        from cloud_tpu.monitoring import native

        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_fallback", native._PyFallback())
        tele = telemetry.Telemetry("unused")
        tele.registry.counter("cloud_tpu_h2d_bytes_total").inc(100)
        tele.registry.histogram(
            "cloud_tpu_step_latency_seconds").observe(0.01)
        exporter = export.NativeExporter()
        exporter.export(tele)
        exporter.export(tele)  # no movement -> no double count
        assert native._fallback.counters[
            "/cloud_tpu/telemetry/h2d_bytes_total"] == 100
        assert ("/cloud_tpu/telemetry/step_latency_seconds/p99"
                in native._fallback.gauges)
        tele.registry.counter("cloud_tpu_h2d_bytes_total").inc(11)
        exporter.export(tele)
        assert native._fallback.counters[
            "/cloud_tpu/telemetry/h2d_bytes_total"] == 111


# -- runtime observer stacking ------------------------------------------


class TestObserverStacking:
    def test_two_observers_both_see_events(self):
        class Spy:
            def __init__(self):
                self.h2d = 0

            def on_h2d(self, transfers, nbytes):
                self.h2d += transfers

        a, b = Spy(), Spy()
        runtime.add_observer(a)
        runtime.add_observer(b)
        try:
            runtime.record_h2d({"x": np.zeros((4,), np.float32)})
            assert a.h2d == 1 and b.h2d == 1
        finally:
            runtime.remove_observer(a)
            runtime.remove_observer(b)
        assert runtime.get_observer() is None

    def test_partial_observer_does_not_break_fanout(self):
        class OnlyH2D:
            def __init__(self):
                self.n = 0

            def on_h2d(self, transfers, nbytes):
                self.n += 1

        class Full:
            def __init__(self):
                self.epochs = []

            def on_h2d(self, transfers, nbytes):
                pass

            def on_epoch(self, epoch):
                self.epochs.append(epoch)

        partial, full = OnlyH2D(), Full()
        runtime.add_observer(partial)
        runtime.add_observer(full)
        try:
            runtime.notify_epoch(3)  # partial lacks on_epoch
            assert full.epochs == [3]
        finally:
            runtime.remove_observer(partial)
            runtime.remove_observer(full)

    def test_single_observer_is_direct_dispatch(self):
        class Spy:
            pass

        spy = Spy()
        runtime.add_observer(spy)
        try:
            assert runtime.get_observer() is spy
        finally:
            runtime.remove_observer(spy)

    def test_telemetry_and_sanitizer_stack(self, tmp_path):
        from cloud_tpu.analysis import sanitizer

        tele = telemetry.enable(str(tmp_path))
        with sanitizer.sanitize(mode="warn") as san:
            assert san in runtime.observers()
            runtime.record_h2d({"x": np.zeros((8,), np.float32)})
        # Both counted the same transfer.
        assert tele.registry.snapshot()["counters"][
            "cloud_tpu_h2d_transfers_total"] == 1
        assert any("h2d" in kinds for kinds in
                   san.site_counts().values())
        # The sanitize scope removed only itself.
        assert san not in runtime.observers()
        assert len(runtime.observers()) == 1

    def test_sanitizer_env_scope_not_suppressed_by_telemetry(
            self, tmp_path, monkeypatch):
        # env_scope suppression keys on "a Sanitizer is active", not
        # "any observer is installed" — telemetry on the seam must not
        # swallow CLOUD_TPU_SANITIZE.
        from cloud_tpu.analysis import sanitizer

        telemetry.enable(str(tmp_path))
        monkeypatch.setenv("CLOUD_TPU_SANITIZE", "warn")
        with sanitizer.env_scope():
            assert any(isinstance(o, sanitizer.Sanitizer)
                       for o in runtime.observers())
        assert not any(isinstance(o, sanitizer.Sanitizer)
                       for o in runtime.observers())


# -- lifecycle ----------------------------------------------------------


class TestLifecycle:
    def test_enable_disable_install_and_remove_hooks(self, tmp_path):
        tele = telemetry.enable(str(tmp_path))
        assert telemetry.enabled()
        assert spans.enabled()
        assert len(runtime.observers()) == 1
        assert telemetry.enable() is tele  # idempotent
        telemetry.disable()
        assert not telemetry.enabled()
        assert not spans.enabled()
        assert runtime.observers() == ()

    def test_env_scope_noop_when_unset(self, monkeypatch):
        monkeypatch.delenv("CLOUD_TPU_TELEMETRY", raising=False)
        with telemetry.env_scope() as tele:
            assert tele is None
        assert not spans.enabled()

    def test_record_epoch_feeds_counters_and_mfu(self, tmp_path):
        tele = telemetry.Telemetry(str(tmp_path), peak_tflops=197.0)
        tele.set_step_flops(1e12)
        tele.record_epoch(steps=10, examples=320, elapsed_secs=2.0)
        tele.flush(wait=True)
        snap = tele.registry.snapshot()
        assert snap["counters"]["cloud_tpu_training_steps_total"] == 10
        assert snap["counters"][
            "cloud_tpu_training_examples_total"] == 320
        assert snap["gauges"]["cloud_tpu_steps_per_sec"] == 5.0
        # 10 steps x 1e12 flops / 2 s = 5e12 flops/s over the peak.
        assert snap["gauges"]["cloud_tpu_mfu_pct_peak"] == pytest.approx(
            100.0 * 5e12 / 197e12)

    def test_no_utilization_gauge_on_the_cpu(self, tmp_path):
        """A CPU run has no chip peak to be a share of: the session
        looks the device up, finds the CPU, and writes no MFU."""
        tele = telemetry.Telemetry(str(tmp_path))
        assert tele.peak_flops is None
        tele.set_step_flops(1e12)
        tele.record_epoch(steps=10, examples=320, elapsed_secs=2.0)
        gauges = tele.registry.snapshot()["gauges"]
        assert "cloud_tpu_mfu_pct_peak" not in gauges
        assert not any("pct_peak" in name for name in gauges)

    def test_peak_table_is_keyed_by_device_kind(self):
        assert telemetry.peak_tflops("TPU v5 lite") == 197.0
        with pytest.raises(ValueError, match="No published peak"):
            telemetry.peak_tflops("TPU v99")

    def test_observe_decode_weights_by_token(self, tmp_path):
        tele = telemetry.enable(str(tmp_path))
        tele.observe_decode(n_tokens=8, elapsed_secs=0.4)
        hist = tele.registry.histogram(telemetry.DECODE_TOKEN_HISTOGRAM)
        assert hist.count == 8
        assert hist.percentile(50) == pytest.approx(0.05, rel=1.0)

    def test_decode_latency_helpers(self, tmp_path):
        import jax.numpy as jnp

        from cloud_tpu.models.decoding import (decode_latency_finish,
                                               decode_latency_start)

        assert decode_latency_start() is None  # off -> zero-cost None
        tele = telemetry.enable(str(tmp_path))
        start = decode_latency_start()
        assert start is not None  # a spans.begin() handle
        decode_latency_finish(start, 4, jnp.ones((2, 2)))
        hist = tele.registry.histogram(telemetry.DECODE_TOKEN_HISTOGRAM)
        assert hist.count == 4
        names = [n for n, _, _, _ in tele.tracer.events()]
        assert "decode" in names


# -- the acceptance contract: fit() end to end --------------------------


def _span_events(trace, name):
    return [e for e in trace["traceEvents"]
            if e["ph"] == "X" and e["name"] == name]


class TestFitEndToEnd:
    @pytest.fixture()
    def telemetry_env(self, tmp_path, monkeypatch):
        out = str(tmp_path / "tele")
        monkeypatch.setenv("CLOUD_TPU_TELEMETRY", "1")
        monkeypatch.setenv("CLOUD_TPU_TELEMETRY_DIR", out)
        yield out

    def _fit(self, epochs=2):
        x, y = _toy_data()
        trainer = Trainer(model=_mlp(), optimizer=optax.sgd(1e-2),
                          loss="sparse_categorical_crossentropy")
        trainer.fit(x, y, epochs=epochs, batch_size=16, verbose=False)
        return trainer

    def test_artifacts_exist_when_fit_returns(self, telemetry_env):
        self._fit()
        assert os.path.exists(os.path.join(telemetry_env, "trace.json"))
        assert os.path.exists(os.path.join(telemetry_env,
                                           "metrics.prom"))
        assert os.path.exists(os.path.join(telemetry_env,
                                           "telemetry.jsonl"))

    def test_trace_spans_nest_and_cover_step_wall_time(self,
                                                      telemetry_env):
        self._fit(epochs=2)
        trace = json.load(open(os.path.join(telemetry_env,
                                            "trace.json")))
        names = {e["name"] for e in trace["traceEvents"]
                 if e["ph"] == "X"}
        for required in ("step", "boundary", "train_step", "data_wait",
                        "dispatch", "d2h_fetch"):
            assert required in names, "missing span: " + required

        # Nesting: every data_wait/dispatch is contained (same thread)
        # in a train_step; every train_step in a step section.
        def contained(inner, outers, slack=1.0):  # slack in usecs
            return any(o["tid"] == inner["tid"]
                       and o["ts"] <= inner["ts"] + slack
                       and (inner["ts"] + inner["dur"]
                            <= o["ts"] + o["dur"] + slack)
                       for o in outers)

        train_steps = _span_events(trace, "train_step")
        step_sections = _span_events(trace, "step")
        assert len(step_sections) == 2  # one per epoch
        for name in ("data_wait", "dispatch"):
            for event in _span_events(trace, name):
                assert contained(event, train_steps), (
                    "{} escapes train_step".format(name))
        for event in train_steps:
            assert contained(event, step_sections)

        # Coverage: within each epoch's step section, the train_step
        # spans tile >=95% of the measured step wall time (first
        # train_step start -> last train_step end).
        for section in step_sections:
            inside = [e for e in train_steps
                      if contained(e, [section])]
            assert inside
            lo = min(e["ts"] for e in inside)
            hi = max(e["ts"] + e["dur"] for e in inside)
            covered = sum(e["dur"] for e in inside)
            assert covered / max(hi - lo, 1e-9) >= 0.95

    def test_prometheus_textfile_contract(self, telemetry_env):
        self._fit(epochs=2)
        text = open(os.path.join(telemetry_env, "metrics.prom")).read()
        values = {}
        for line in text.splitlines():
            if line.startswith("#") or not line.strip():
                continue
            key, _, value = line.partition(" ")
            values[key] = float(value)
        for quantile in ("p50", "p95", "p99"):
            key = "cloud_tpu_step_latency_seconds_" + quantile
            assert key in values
        assert values["cloud_tpu_step_latency_seconds_p99"] > 0
        assert values["cloud_tpu_step_latency_seconds_count"] == 16
        # No MFU on the CPU: there is no chip peak to be a share of.
        assert "cloud_tpu_mfu_pct_peak" not in values
        # The transfer/compile counter adapters mirrored the runtime
        # census.
        assert values["cloud_tpu_h2d_transfers_total"] > 0
        assert values["cloud_tpu_d2h_fetches_total"] > 0
        assert values["cloud_tpu_traces_total"] > 0

    def test_jsonl_rollups_logged(self, telemetry_env):
        from cloud_tpu.utils import events

        self._fit(epochs=2)
        records = events.read_job_events(
            os.path.join(telemetry_env, "telemetry.jsonl"))
        assert records
        assert all(r["kind"] == "telemetry" for r in records)
        final = records[-1]["payload"]
        assert final["counters"]["cloud_tpu_training_steps_total"] == 16
        assert "cloud_tpu_step_latency_seconds" in final["histograms"]

    def test_no_hooks_when_env_unset(self, monkeypatch):
        monkeypatch.delenv("CLOUD_TPU_TELEMETRY", raising=False)
        self._fit(epochs=1)
        assert runtime.observers() == ()
        assert not spans.enabled()
        assert not telemetry.enabled()

    def test_stacks_with_sanitize_env(self, telemetry_env, monkeypatch):
        monkeypatch.setenv("CLOUD_TPU_SANITIZE", "warn")
        self._fit(epochs=1)
        # Telemetry stayed ambient; the sanitizer tore down after fit.
        assert len(runtime.observers()) == 1
        text = open(os.path.join(telemetry_env, "metrics.prom")).read()
        assert "cloud_tpu_step_latency_seconds_p99" in text
