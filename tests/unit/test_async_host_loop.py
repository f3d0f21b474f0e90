"""The async host loop: coalesced single-fetch metrics, off-thread
readback, non-blocking checkpoints.

What these tests pin, in the tier-1 (fast, CPU) suite:

- `runtime.record_d2h`/`device_fetch` count device->host round trips
  (one per coalesced fetch CALL, not per leaf), so "one fetch per
  logging interval" is asserted from a counter instead of wall clock —
  the same doctrine the H2D side established in PR 1.
- A steady-state `fit` epoch performs EXACTLY one device->host fetch
  per logging interval (the tentpole's counted invariant), across the
  host-streaming, steps_per_execution, and device-resident loops; one
  more per epoch with validation (evaluate is itself one coalesced
  fetch).
- Metric values are BIT-IDENTICAL between the sync and async logging
  paths at a fixed seed (the device-side aggregation is shared; the
  paths differ only in who calls device_fetch and when).
- `MetricFuture` exception propagation: a failed background fetch
  re-raises on the training thread — on `result()`, at the next
  `submit()` boundary, and out of `fit` itself.
- `LazyLogs` semantics: host items and membership never force the
  fetch; callback writes win over late resolution; callback-added
  keys stay out of history (the Keras contract the eager path had).
- `Trainer.fit` drains async checkpoint writes on EVERY exit path
  (normal, EarlyStopping, raising callback) — the regression this PR
  fixes — and same-path async saves never interleave (in-flight
  guard + donation-safe host snapshots).
"""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from cloud_tpu.models import MLP
from cloud_tpu.parallel import runtime
from cloud_tpu.training import (AsyncMetricReader, Callback,
                                EarlyStopping, LazyLogs, MetricFuture,
                                ModelCheckpoint, TerminateOnNaN, Trainer)
from cloud_tpu.training import checkpoint as checkpoint_lib
from cloud_tpu.training import async_logs as async_logs_lib


@pytest.fixture(autouse=True)
def _reset_runtime():
    runtime.reset()
    runtime.reset_transfer_stats()
    yield
    runtime.reset()
    runtime.reset_transfer_stats()


def _data(n=64, d=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, classes, size=n).astype(np.int32)
    return x, y


def _trainer(**kwargs):
    return Trainer(MLP(hidden=16, num_classes=4,
                       compute_dtype=jnp.float32),
                   optimizer=optax.adam(1e-2),
                   loss="sparse_categorical_crossentropy",
                   metrics=("accuracy",), seed=0, **kwargs)


class TestD2hCounter:

    def test_one_fetch_regardless_of_leaves(self):
        """The unit is the round trip: a coalesced tree of N device
        scalars is ONE fetch."""
        tree = {"loss": jnp.asarray(1.0), "acc": jnp.asarray(0.5),
                "lr": jnp.asarray(0.1)}
        recorded = runtime.record_d2h(tree)
        stats = runtime.transfer_stats()
        assert stats["d2h_fetches"] == 1
        assert recorded == sum(v.nbytes for v in tree.values())
        assert stats["d2h_bytes"] == recorded

    def test_host_only_tree_records_nothing(self):
        """No device leaf -> no round trip to count."""
        runtime.record_d2h({"a": 1.0, "b": np.zeros(4)})
        assert runtime.transfer_stats()["d2h_fetches"] == 0

    def test_device_fetch_returns_host_values(self):
        out = runtime.device_fetch({"x": jnp.asarray(3.0), "y": 2.0})
        assert float(out["x"]) == 3.0
        assert out["y"] == 2.0
        assert not isinstance(out["x"], jax.Array)
        assert runtime.transfer_stats()["d2h_fetches"] == 1


class TestOneFetchPerInterval:
    """THE tentpole invariant, from the counter: a steady-state fit
    epoch performs exactly one device->host fetch."""

    def test_async_fit_one_fetch_per_epoch(self):
        x, y = _data()
        trainer = _trainer()
        runtime.reset_transfer_stats()
        history = trainer.fit(x, y, epochs=3, batch_size=16,
                              verbose=False)
        assert runtime.transfer_stats()["d2h_fetches"] == 3
        assert len(history["loss"]) == 3

    def test_sync_fit_also_coalesces(self):
        """async_logging=False still fetches ONCE per epoch — the
        coalescing is shared; only the thread differs."""
        x, y = _data()
        trainer = _trainer()
        runtime.reset_transfer_stats()
        trainer.fit(x, y, epochs=3, batch_size=16, verbose=False,
                    async_logging=False)
        assert runtime.transfer_stats()["d2h_fetches"] == 3

    def test_verbose_fit_still_one_fetch(self):
        """Progress logging resolves the future at the boundary — the
        SAME coalesced fetch, not extra per-metric round trips."""
        x, y = _data()
        trainer = _trainer()
        runtime.reset_transfer_stats()
        trainer.fit(x, y, epochs=2, batch_size=16, verbose=True)
        assert runtime.transfer_stats()["d2h_fetches"] == 2

    def test_multi_step_fit_one_fetch_per_epoch(self):
        x, y = _data()
        trainer = _trainer(steps_per_execution=2)
        runtime.reset_transfer_stats()
        trainer.fit(x, y, epochs=3, batch_size=16, verbose=False)
        assert runtime.transfer_stats()["d2h_fetches"] == 3

    def test_resident_fit_one_fetch_per_epoch(self):
        """cache="device" composes: zero steady-state H2D (PR 1) AND
        one D2H per epoch (this PR) — the loop touches the wire once
        per logging interval, total, in either direction."""
        x, y = _data()
        trainer = _trainer()
        runtime.reset_transfer_stats()
        trainer.fit(x, y, epochs=3, batch_size=16, verbose=False,
                    cache="device")
        stats = runtime.transfer_stats()
        assert stats["d2h_fetches"] == 3
        assert stats["h2d_bytes"] == x.nbytes + y.nbytes  # upload only

    def test_weighted_fit_one_fetch_per_epoch(self):
        x, y = _data()
        sw = np.linspace(0.5, 1.5, x.shape[0]).astype(np.float32)
        trainer = _trainer()
        runtime.reset_transfer_stats()
        trainer.fit(x, y, epochs=2, batch_size=16, verbose=False,
                    sample_weight=sw)
        assert runtime.transfer_stats()["d2h_fetches"] == 2

    def test_evaluate_is_one_fetch(self):
        """evaluate coalesces every metric total AND the weight into a
        single device_get (was N+1 float() round trips)."""
        x, y = _data()
        trainer = _trainer()
        trainer.fit(x, y, epochs=1, batch_size=16, verbose=False)
        runtime.reset_transfer_stats()
        trainer.evaluate(x, y, verbose=False)
        assert runtime.transfer_stats()["d2h_fetches"] == 1

    def test_validation_fit_two_fetches_per_epoch(self):
        """With validation: one train-metric fetch + one evaluate
        fetch per epoch — still O(1) per interval, never per-metric."""
        x, y = _data()
        trainer = _trainer()
        runtime.reset_transfer_stats()
        trainer.fit(x, y, epochs=2, batch_size=16, verbose=False,
                    validation_data=(x, y))
        assert runtime.transfer_stats()["d2h_fetches"] == 4


class TestBitIdenticalPaths:

    def test_sync_async_history_bit_identical(self):
        x, y = _data()
        h_async = _trainer().fit(x, y, epochs=3, batch_size=16,
                                 verbose=False, async_logging=True)
        h_sync = _trainer().fit(x, y, epochs=3, batch_size=16,
                                verbose=False, async_logging=False)
        for key in ("loss", "accuracy"):
            assert h_async[key] == h_sync[key]  # bitwise, no approx
        assert sorted(h_async) == sorted(h_sync)

    def test_history_values_are_plain_floats(self):
        x, y = _data()
        history = _trainer().fit(x, y, epochs=2, batch_size=16,
                                 verbose=False)
        for values in history.values():
            assert all(type(v) is float for v in values)


class TestMetricFuture:

    def test_result_blocks_until_set(self):
        f = MetricFuture()
        assert not f.done()
        f.set_result({"loss": 1.0})
        assert f.done()
        assert f.result() == {"loss": 1.0}

    def test_exception_propagates_to_result(self):
        f = MetricFuture()
        f.set_exception(RuntimeError("backend died"))
        with pytest.raises(RuntimeError, match="backend died"):
            f.result()

    def test_timeout(self):
        with pytest.raises(TimeoutError):
            MetricFuture().result(timeout=0.01)

    def test_reader_resolves_to_floats(self):
        reader = AsyncMetricReader()
        try:
            f = reader.submit({"loss": jnp.asarray(2.5)})
            assert f.result(timeout=10) == {"loss": 2.5}
            assert type(f.result()["loss"]) is float
        finally:
            reader.close()

    def test_reader_error_reaches_caller(self, monkeypatch):
        """(c) of the test satellite: a failed background fetch
        re-raises on result() AND at the next submit boundary."""
        def boom(tree):
            raise RuntimeError("fetch exploded")

        monkeypatch.setattr(async_logs_lib.runtime, "device_fetch", boom)
        reader = AsyncMetricReader()
        try:
            f = reader.submit({"loss": jnp.asarray(1.0)})
            with pytest.raises(RuntimeError, match="fetch exploded"):
                f.result(timeout=10)
            monkeypatch.undo()
            with pytest.raises(RuntimeError, match="fetch exploded"):
                reader.submit({"loss": jnp.asarray(1.0)})
            # The boundary raise cleared the pending error: the reader
            # is usable again (a retry loop must not re-see it).
            f2 = reader.submit({"loss": jnp.asarray(1.0)})
            assert f2.result(timeout=10) == {"loss": 1.0}
        finally:
            reader.close()

    def test_fetch_error_propagates_out_of_fit(self, monkeypatch):
        """End-to-end: the train loop never reads the metrics itself
        (verbose=False, no callbacks), so the poisoned fetch surfaces
        at fit's exit barrier — but it DOES surface."""
        def boom(tree):
            raise RuntimeError("fetch exploded")

        x, y = _data()
        trainer = _trainer()
        monkeypatch.setattr(
            "cloud_tpu.parallel.runtime.device_fetch", boom)
        with pytest.raises(RuntimeError, match="fetch exploded"):
            trainer.fit(x, y, epochs=2, batch_size=16, verbose=False)

    def test_drain_waits_for_all(self):
        reader = AsyncMetricReader()
        try:
            futures = [reader.submit({"v": jnp.asarray(float(i))})
                       for i in range(3)]
            reader.drain()
            assert [f.result()["v"] for f in futures] == [0.0, 1.0, 2.0]
            assert all(f.done() for f in futures)
        finally:
            reader.close()


class TestLazyLogs:

    def _pending(self, values, host=None):
        f = MetricFuture()
        f.set_result(values)
        return f, LazyLogs(f, device_keys=tuple(values),
                           host_items=host or {})

    def test_host_items_never_force_fetch(self):
        f = MetricFuture()  # never resolved
        logs = LazyLogs(f, device_keys=("loss",),
                        host_items={"steps_per_sec": 10.0})
        assert logs["steps_per_sec"] == 10.0
        assert "loss" in logs          # membership from device_keys
        assert len(logs) == 2
        assert "pending" in repr(logs)  # repr doesn't resolve either

    def test_read_resolves(self):
        _, logs = self._pending({"loss": 1.5, "accuracy": 0.5})
        assert logs["loss"] == 1.5
        assert logs.get("accuracy") == 0.5
        assert dict(logs.items()) == {"loss": 1.5, "accuracy": 0.5}

    def test_callback_write_wins_over_resolution(self):
        """A callback that overwrites a pending key before anything
        read it wins — later callbacks see the mutation (Keras
        contract: callbacks share one logs dict)."""
        _, logs = self._pending({"loss": 1.5})
        logs["loss"] = 99.0
        assert logs["loss"] == 99.0
        assert dict(logs.items())["loss"] == 99.0

    def test_missing_key_raises(self):
        _, logs = self._pending({"loss": 1.5})
        with pytest.raises(KeyError):
            logs["nope"]
        assert logs.get("nope", "dflt") == "dflt"

    def test_callback_added_keys_not_in_history(self):
        """The deferred history append snapshots BEFORE callbacks run:
        keys a callback adds to logs must stay out of history (the
        contract the eager path always had)."""
        class Adds(Callback):
            def on_epoch_end(self, epoch, logs):
                logs["fake"] = 123.0

        x, y = _data()
        history = _trainer().fit(x, y, epochs=2, batch_size=16,
                                 verbose=False, callbacks=(Adds(),))
        assert "fake" not in history
        assert len(history["loss"]) == 2

    def test_callback_chain_sees_mutation(self):
        """Callback order still composes under LazyLogs: an earlier
        callback's write is visible to a later EarlyStopping monitor."""
        schedule = iter([1.0, 2.0, 3.0, 4.0])

        class FakeMetric(Callback):
            def on_epoch_end(self, epoch, logs):
                logs["fake"] = next(schedule)

        x, y = _data()
        stopper = EarlyStopping(monitor="fake", mode="min", patience=0)
        history = _trainer().fit(
            x, y, epochs=4, batch_size=16, verbose=False,
            callbacks=(FakeMetric(), stopper))
        # fake worsens (mode=min) from epoch 1 -> stops after epoch 2.
        assert len(history["loss"]) == 2


class TestTerminateOnNaN:

    def test_stops_on_nan_loss(self):
        x, y = _data()
        trainer = Trainer(
            MLP(hidden=16, num_classes=4, compute_dtype=jnp.float32),
            optimizer=optax.adam(1e-2),
            loss=lambda logits, labels: jnp.full(
                (labels.shape[0],), jnp.nan),
            metrics=(), seed=0)
        history = trainer.fit(x, y, epochs=5, batch_size=16,
                              verbose=False,
                              callbacks=(TerminateOnNaN(),))
        assert len(history["loss"]) == 1
        assert math.isnan(history["loss"][0])

    def test_finite_loss_trains_through(self):
        x, y = _data()
        history = _trainer().fit(x, y, epochs=2, batch_size=16,
                                 verbose=False,
                                 callbacks=(TerminateOnNaN(),))
        assert len(history["loss"]) == 2


class TestCheckpointDrain:
    """The satellite bugfix: fit never returns (or raises) with an
    async checkpoint write still in flight."""

    def _spy(self, monkeypatch):
        calls = []
        original = checkpoint_lib.wait_until_finished

        def spy():
            calls.append(True)
            original()

        monkeypatch.setattr(checkpoint_lib, "wait_until_finished", spy)
        return calls

    def test_normal_exit_drains(self, tmp_path, monkeypatch):
        calls = self._spy(monkeypatch)
        x, y = _data()
        ckpt = os.path.join(str(tmp_path), "ckpt")
        _trainer().fit(x, y, epochs=2, batch_size=16, verbose=False,
                       callbacks=(ModelCheckpoint(ckpt,
                                                  use_async=True),))
        assert calls  # drained before fit returned
        assert checkpoint_lib.pending_saves() == frozenset()
        assert checkpoint_lib.latest_step(ckpt) == 8

    def test_early_stopping_exit_drains(self, tmp_path, monkeypatch):
        calls = self._spy(monkeypatch)

        class StopNow(Callback):
            def on_epoch_end(self, epoch, logs):
                self.trainer.stop_training = True

        x, y = _data()
        ckpt = os.path.join(str(tmp_path), "ckpt")
        _trainer().fit(x, y, epochs=5, batch_size=16, verbose=False,
                       callbacks=(ModelCheckpoint(ckpt, use_async=True),
                                  StopNow()))
        assert calls
        assert checkpoint_lib.latest_step(ckpt) == 4

    def test_raising_exit_drains(self, tmp_path, monkeypatch):
        """A train-time exception still drains in-flight writes on the
        way out — the crash window can't leave a torn checkpoint."""
        calls = self._spy(monkeypatch)

        class Boom(Callback):
            def on_epoch_end(self, epoch, logs):
                if epoch == 1:
                    raise RuntimeError("mid-train crash")

        x, y = _data()
        ckpt = os.path.join(str(tmp_path), "ckpt")
        with pytest.raises(RuntimeError, match="mid-train crash"):
            _trainer().fit(
                x, y, epochs=5, batch_size=16, verbose=False,
                callbacks=(ModelCheckpoint(ckpt, use_async=True),
                           Boom()))
        assert calls
        # Both epochs' saves committed whole: restorable.
        assert checkpoint_lib.latest_step(ckpt) == 8

    def test_async_save_restores_identically(self, tmp_path):
        """Donation-safe host snapshot: the async write must capture
        the state AS OF the save call, immune to the next step's
        donation rewriting the buffers."""
        x, y = _data()
        trainer = _trainer()
        ckpt = os.path.join(str(tmp_path), "ckpt")
        trainer.fit(x, y, epochs=1, batch_size=16, verbose=False,
                    callbacks=(ModelCheckpoint(ckpt, use_async=True),))
        restored = checkpoint_lib.restore(ckpt, trainer.state)
        for a, b in zip(jax.tree_util.tree_leaves(trainer.state),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestInFlightGuard:

    def test_pending_saves_bookkeeping(self, tmp_path):
        x, y = _data()
        trainer = _trainer()
        trainer.fit(x, y, epochs=1, batch_size=16, verbose=False,
                    async_logging=False)
        path = trainer.save_checkpoint(str(tmp_path / "ckpt"),
                                       use_async=True)
        assert path in checkpoint_lib.pending_saves()
        checkpoint_lib.wait_until_finished()
        assert checkpoint_lib.pending_saves() == frozenset()

    def test_same_path_resave_completes_whole(self, tmp_path):
        """Two async saves racing to one <dir>/<step> serialize
        (wait-then-write): the survivor is a complete checkpoint."""
        x, y = _data()
        trainer = _trainer()
        trainer.fit(x, y, epochs=1, batch_size=16, verbose=False)
        directory = str(tmp_path / "ckpt")
        trainer.save_checkpoint(directory, use_async=True)
        trainer.save_checkpoint(directory, use_async=True)  # same step
        checkpoint_lib.wait_until_finished()
        restored = checkpoint_lib.restore(directory, trainer.state)
        for a, b in zip(jax.tree_util.tree_leaves(trainer.state),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_host_snapshot_detaches_from_device(self):
        x, y = _data()
        trainer = _trainer()
        trainer.fit(x, y, epochs=1, batch_size=16, verbose=False)
        runtime.reset_transfer_stats()
        snap = checkpoint_lib._host_snapshot(trainer.state)
        assert not any(isinstance(l, jax.Array)
                       for l in jax.tree_util.tree_leaves(snap))
        # The snapshot is itself ONE coalesced, counted fetch.
        assert runtime.transfer_stats()["d2h_fetches"] == 1


class TestLogsConsumersUnderAsync:
    """The stock log consumers work against LazyLogs end-to-end."""

    def test_metrics_logger_jsonl(self, tmp_path):
        from cloud_tpu.training import MetricsLogger, read_metrics_log

        x, y = _data()
        path = str(tmp_path / "metrics.jsonl")
        _trainer().fit(x, y, epochs=2, batch_size=16, verbose=False,
                       callbacks=(MetricsLogger(path),))
        records = read_metrics_log(path)
        assert len(records) == 2
        assert all("loss" in r and "epoch" in r for r in records)

    def test_early_stopping_on_train_metric(self):
        x, y = _data()
        stopper = EarlyStopping(monitor="loss", mode="min",
                                patience=10)
        history = _trainer().fit(x, y, epochs=3, batch_size=16,
                                 verbose=False, callbacks=(stopper,))
        assert len(history["loss"]) == 3
        assert stopper.best == min(history["loss"])
