"""Profiler subsystem: traces are captured and land on disk."""

import glob
import os

import jax.numpy as jnp
import numpy as np

from cloud_tpu.monitoring import profiler


class TestTrace:
    def test_trace_writes_profile_artifacts(self, tmp_path):
        log_dir = str(tmp_path / "prof")
        with profiler.trace(log_dir):
            x = jnp.ones((64, 64))
            (x @ x).block_until_ready()
        found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        assert found, "no xplane trace written"

    def test_annotate_usable_as_context(self):
        with profiler.annotate("my_span"):
            jnp.ones((8,)).block_until_ready()

    def test_device_memory_profile_bytes(self, tmp_path):
        path = str(tmp_path / "mem.pprof")
        data = profiler.device_memory_profile(path)
        assert isinstance(data, bytes) and len(data) > 0
        assert os.path.getsize(path) == len(data)


class TestProfilerCallback:
    def test_profiles_selected_epoch_during_fit(self, tmp_path):
        import optax

        from cloud_tpu.models import MLP
        from cloud_tpu.parallel import runtime
        from cloud_tpu.training import Trainer

        runtime.reset()
        log_dir = str(tmp_path / "prof")
        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 8, 8)).astype(np.float32)
        y = rng.integers(0, 10, size=64).astype(np.int32)
        trainer = Trainer(MLP(hidden=16, compute_dtype=jnp.float32),
                          optimizer=optax.adam(1e-3),
                          loss="sparse_categorical_crossentropy",
                          metrics=())
        trainer.fit(x, y, epochs=2, batch_size=32, verbose=False,
                    callbacks=[profiler.ProfilerCallback(log_dir)])
        found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        assert found, "callback produced no trace"

    def test_single_epoch_fit_still_traces(self, tmp_path, caplog):
        """Default epochs=(1,) with fit(epochs=1): only epoch 0 runs —
        the callback must fall back to epoch 0 (with a warning) instead
        of silently producing no trace."""
        import optax

        from cloud_tpu.models import MLP
        from cloud_tpu.parallel import runtime
        from cloud_tpu.training import Trainer

        runtime.reset()
        log_dir = str(tmp_path / "prof1")
        rng = np.random.default_rng(0)
        x = rng.normal(size=(32, 8, 8)).astype(np.float32)
        y = rng.integers(0, 10, size=32).astype(np.int32)
        trainer = Trainer(MLP(hidden=16, compute_dtype=jnp.float32),
                          optimizer=optax.adam(1e-3),
                          loss="sparse_categorical_crossentropy",
                          metrics=())
        with caplog.at_level("WARNING", logger="cloud_tpu"):
            trainer.fit(x, y, epochs=1, batch_size=32, verbose=False,
                        callbacks=[profiler.ProfilerCallback(log_dir)])
        found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        assert found, "no trace despite epoch-0 fallback"
        assert any("profiling epoch 0 instead" in r.message
                   for r in caplog.records)
