"""graftwatch: stall detection, flight recorder, zero-cost seam.

What's pinned here is the PR 7 acceptance contract: an injected
dispatch hang on a plain CPU fit() yields a typed BackendUnavailable
within the configured deadline (seconds, not an outer timeout) plus a
blackbox.json naming the stuck thread and the last completed step; and
with CLOUD_TPU_WATCH unset, fit() installs zero hooks/threads — the
same zero-cost discipline graftscope and graftsan are held to.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from cloud_tpu.monitoring import watch
from cloud_tpu.parallel import runtime


@pytest.fixture(autouse=True)
def _watch_isolation(monkeypatch):
    """No ambient watchdog or watch env leaks between tests."""
    for key in ("CLOUD_TPU_WATCH", "CLOUD_TPU_WATCH_DEADLINE",
                "CLOUD_TPU_WATCH_STARTUP_DEADLINE",
                "CLOUD_TPU_WATCH_INTERVAL", "CLOUD_TPU_WATCH_DIR",
                "CLOUD_TPU_WATCH_FATAL", "CLOUD_TPU_EVENT_LOG"):
        monkeypatch.delenv(key, raising=False)
    yield
    watch.uninstall()


def _spin(deadline_s):
    """A Python-level wedge: interruptible by the async raise (a C-call
    wedge wouldn't be — watch.py documents that honestly)."""
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        sum(range(1000))


class TestWatchdogStall:
    def test_stall_delivers_typed_error_and_blackbox(self, tmp_path):
        caught = []

        def victim():
            w = watch.Watchdog(stall_deadline=0.4,
                               startup_deadline=0.4,
                               poll_interval=0.05,
                               out_dir=str(tmp_path))
            w.start()
            try:
                try:
                    _spin(30)
                except runtime.BackendUnavailable as e:
                    caught.append(w.take_pending() or e)
            finally:
                w.stop()

        t = threading.Thread(target=victim, name="victim-thread")
        t0 = time.monotonic()
        t.start()
        t.join(timeout=20)
        assert not t.is_alive(), "stall was never interrupted"
        assert time.monotonic() - t0 < 15
        (error,) = caught
        assert isinstance(error, runtime.BackendUnavailable)
        assert error.blackbox == str(tmp_path / "blackbox.json")
        assert os.path.exists(error.blackbox)
        blackbox = json.load(open(error.blackbox))
        assert blackbox["reason"] == "stall"
        assert blackbox["last_step"] == 0
        (stuck,) = [th for th in blackbox["threads"] if th["stuck"]]
        assert stuck["name"] == "victim-thread"
        assert any(f["function"] == "_spin" for f in stuck["stack"])
        # The stuck thread sorts first — the artifact leads with the
        # culprit.
        assert blackbox["threads"][0]["stuck"]

    def test_blackbox_carries_counters_spans_and_faulthandler(
            self, tmp_path):
        from cloud_tpu.monitoring import spans

        tracer = spans.install()
        try:
            with spans.span("dispatch"):
                pass
            path = watch.write_blackbox(
                str(tmp_path / "blackbox.json"), "stall",
                last_step=7)
        finally:
            spans.uninstall()
        blackbox = json.load(open(path))
        assert blackbox["last_step"] == 7
        assert "d2h_fetches" in blackbox["transfer_stats"]
        assert "n_compiles" in blackbox["compile_stats"]
        assert blackbox["faulthandler"]
        assert [s["name"] for s in blackbox["spans_tail"]] == [
            "dispatch"]

    def test_blackbox_event_tail_skips_torn_lines(self, tmp_path,
                                                  monkeypatch):
        from cloud_tpu.utils import events

        log = str(tmp_path / "job.jsonl")
        monkeypatch.setenv("CLOUD_TPU_EVENT_LOG", log)
        events.log_job_event("healthy", {"i": 1}, path=log)
        with open(log, "a") as f:
            f.write('{"kind": "torn", "payl')
        path = watch.write_blackbox(str(tmp_path / "blackbox.json"),
                                    "crash")
        tail = json.load(open(path))["job_events_tail"]
        assert [r["kind"] for r in tail] == ["healthy"]

    def test_stall_logs_job_event(self, tmp_path, monkeypatch):
        from cloud_tpu.utils import events

        log = str(tmp_path / "job.jsonl")
        monkeypatch.setenv("CLOUD_TPU_EVENT_LOG", log)
        caught = []

        def victim():
            w = watch.Watchdog(stall_deadline=0.3,
                               startup_deadline=0.3,
                               poll_interval=0.05,
                               out_dir=str(tmp_path))
            w.start()
            try:
                try:
                    _spin(30)
                except runtime.BackendUnavailable:
                    caught.append(True)
            finally:
                w.stop()

        t = threading.Thread(target=victim)
        t.start()
        t.join(timeout=20)
        assert caught
        stall_events = [r for r in events.read_job_events(log)
                        if r["kind"] == "graftwatch"]
        assert stall_events
        assert stall_events[0]["payload"]["event"] == "stall"

    def test_check_raises_when_async_delivery_failed(self, tmp_path):
        w = watch.Watchdog(stall_deadline=0.2, startup_deadline=0.2,
                           poll_interval=0.05,
                           out_dir=str(tmp_path))
        # A tid that no longer exists: the async raise targets nothing,
        # so check() is the delivery point (the scope-exit guarantee).
        w.start(watched_tid=2 ** 31 + 12345)
        try:
            deadline = time.monotonic() + 10
            while not w.fired and time.monotonic() < deadline:
                time.sleep(0.02)
            assert w.fired
            with pytest.raises(runtime.BackendUnavailable):
                w.check()
        finally:
            w.stop()

    def test_notify_step_resets_deadline(self, tmp_path):
        w = watch.Watchdog(stall_deadline=0.5, startup_deadline=0.5,
                           poll_interval=0.05,
                           out_dir=str(tmp_path))
        w.start()
        try:
            for _ in range(8):
                time.sleep(0.1)
                w.notify_step()
            assert not w.fired
            assert w.last_step == 8
        finally:
            w.stop()

    def test_reentry_rearms_startup_deadline(self, tmp_path):
        """graftguard re-entry contract (ISSUE 9): after
        notify_reentry, the generous STARTUP deadline governs again —
        restore + rebuild must not trip the tight steady-state stall
        deadline."""
        w = watch.Watchdog(stall_deadline=0.3, startup_deadline=3.0,
                           poll_interval=0.05,
                           out_dir=str(tmp_path))
        # Bogus tid: a firing would latch without async-raising into
        # this test thread.
        w.start(watched_tid=2 ** 31 + 12345)
        try:
            w.notify_step()  # leave startup: stall deadline governs
            w.notify_reentry()
            time.sleep(0.9)  # 3x the stall deadline, inside startup
            assert not w.fired
            # First completed step ends the startup grace again...
            w.notify_step()
            deadline = time.monotonic() + 10
            while not w.fired and time.monotonic() < deadline:
                time.sleep(0.02)
            # ...so a quiet 0.3s now IS a stall.
            assert w.fired
        finally:
            w.stop()

    def test_reentry_clears_fired_latch(self, tmp_path):
        w = watch.Watchdog(stall_deadline=0.2, startup_deadline=0.2,
                           poll_interval=0.05,
                           out_dir=str(tmp_path))
        w.start(watched_tid=2 ** 31 + 12345)
        try:
            deadline = time.monotonic() + 10
            while not w.fired and time.monotonic() < deadline:
                time.sleep(0.02)
            assert w.fired
            w.notify_reentry()
            assert not w.fired
            assert w.take_pending() is None
            w.check()  # latched error was cleared: must not raise
        finally:
            w.stop()


class TestModuleSeam:
    def test_disabled_helpers_are_noops(self):
        assert watch.current() is None
        assert not watch.enabled()
        watch.heartbeat()  # must not raise
        watch.notify_step()
        watch.check()

    def test_env_enabled_grammar(self, monkeypatch):
        for value in ("", "0", "off", "false", "none"):
            monkeypatch.setenv("CLOUD_TPU_WATCH", value)
            assert not watch.env_enabled()
        for value in ("1", "on", "true"):
            monkeypatch.setenv("CLOUD_TPU_WATCH", value)
            assert watch.env_enabled()

    def test_env_scope_noop_when_disabled(self):
        before = threading.active_count()
        with watch.env_scope() as w:
            assert w is None
            assert watch.current() is None
        assert threading.active_count() == before

    def test_env_scope_installs_and_tears_down(self, tmp_path,
                                               monkeypatch):
        monkeypatch.setenv("CLOUD_TPU_WATCH", "1")
        monkeypatch.setenv("CLOUD_TPU_WATCH_DIR", str(tmp_path))
        with watch.env_scope() as w:
            assert w is watch.current()
            names = [t.name for t in threading.enumerate()]
            assert "cloud-tpu-watchdog" in names
        assert watch.current() is None
        names = [t.name for t in threading.enumerate()]
        assert "cloud-tpu-watchdog" not in names

    def test_nested_env_scope_rides_the_outer_watchdog(self, tmp_path,
                                                       monkeypatch):
        monkeypatch.setenv("CLOUD_TPU_WATCH", "1")
        monkeypatch.setenv("CLOUD_TPU_WATCH_DIR", str(tmp_path))
        with watch.env_scope() as outer:
            with watch.env_scope() as inner:
                assert inner is outer
            # Inner exit tears nothing down.
            assert watch.current() is outer
        assert watch.current() is None

    def test_env_scope_writes_crash_blackbox(self, tmp_path,
                                             monkeypatch):
        monkeypatch.setenv("CLOUD_TPU_WATCH", "1")
        monkeypatch.setenv("CLOUD_TPU_WATCH_DIR", str(tmp_path))
        with pytest.raises(RuntimeError, match="boom"):
            with watch.env_scope():
                raise RuntimeError("boom")
        blackbox = json.load(open(tmp_path / "blackbox.json"))
        assert blackbox["reason"] == "crash"
        assert "boom" in blackbox["error"]


class TestTrainerIntegration:
    def _fit_data(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 8)).astype(np.float32)
        y = rng.integers(0, 4, size=64).astype(np.int32)
        return x, y

    def _trainer(self):
        from cloud_tpu.models import MLP
        from cloud_tpu.training import Trainer

        return Trainer(MLP(hidden=8, num_classes=4))

    def test_unset_env_installs_zero_hooks_and_threads(self,
                                                       monkeypatch):
        """The graftscope/graftsan zero-cost contract, extended: with
        CLOUD_TPU_WATCH unset, fit() starts no monitor thread and
        installs no watchdog."""
        monkeypatch.delenv("CLOUD_TPU_WATCH", raising=False)
        x, y = self._fit_data()
        trainer = self._trainer()
        seen = []

        class Spy:
            def on_epoch_end(self, epoch, logs=None):
                seen.append((watch.current(),
                             [t.name for t in threading.enumerate()]))

            def __getattr__(self, name):
                return lambda *a, **k: None

        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False,
                    callbacks=[Spy()])
        assert seen
        current, names = seen[0]
        assert current is None
        assert "cloud-tpu-watchdog" not in names

    def test_injected_hang_yields_typed_error_and_blackbox(
            self, tmp_path, monkeypatch):
        """The headline acceptance criterion: a hung dispatch on a
        plain CPU fit() becomes a typed BackendUnavailable within the
        deadline, with the flight recorder naming the stuck step."""
        monkeypatch.setenv("CLOUD_TPU_WATCH", "1")
        monkeypatch.setenv("CLOUD_TPU_WATCH_DEADLINE", "2")
        monkeypatch.setenv("CLOUD_TPU_WATCH_STARTUP_DEADLINE", "2")
        monkeypatch.setenv("CLOUD_TPU_WATCH_INTERVAL", "0.25")
        monkeypatch.setenv("CLOUD_TPU_WATCH_DIR", str(tmp_path))
        x, y = self._fit_data()
        trainer = self._trainer()
        # Build the jitted step once (healthy fit), THEN wedge it: the
        # injection patches the step CACHE because _ensure_host_steps
        # reinstalls self._jit_train_step from it on every fit.
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        real_step, scalar_set = trainer._train_step_cache[False]
        calls = {"n": 0}

        def hung_step(state, batch):
            calls["n"] += 1
            if calls["n"] >= 3:
                _spin(120)
            return real_step(state, batch)

        trainer._train_step_cache[False] = (hung_step, scalar_set)
        t0 = time.monotonic()
        with pytest.raises(runtime.BackendUnavailable) as info:
            trainer.fit(x, y, epochs=4, batch_size=32, verbose=False)
        took = time.monotonic() - t0
        assert took < 60, "typed error took {:.0f}s".format(took)
        error = info.value
        assert error.blackbox and os.path.exists(error.blackbox)
        blackbox = json.load(open(error.blackbox))
        assert blackbox["reason"] == "stall"
        # Two singles completed before the third call wedged.
        assert blackbox["last_step"] == 2
        (stuck,) = [th for th in blackbox["threads"] if th["stuck"]]
        assert any(f["function"] == "hung_step"
                   for f in stuck["stack"])
        # Scope teardown ran despite the stall.
        assert watch.current() is None