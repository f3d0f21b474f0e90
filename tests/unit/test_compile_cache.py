"""Compile census, persistent cache, and zero-retrace invariants.

What these tests pin, in the tier-1 (fast, CPU) suite:

- `runtime.instrumented_jit` counts traces and compiles from inside
  the traced body, so compile behavior is asserted from a counter
  instead of inferred from wall clock (the `transfer_stats` doctrine).
- THE tentpole invariant: a steady-state fit epoch performs ZERO new
  traces/compiles — for the single-step host loop (ragged tails
  included), the steps_per_execution loop, and the device-resident
  loop — enforced by the retrace sentinel (`on_retrace="raise"`).
- `Trainer.warmup()` AOT-compiles the step executables from
  ShapeDtypeStructs; `fit(warm_start=True)` over the same geometry
  then runs its FIRST step trace-free.
- Decode prefill bucketing: varied prompt lengths share power-of-two
  bucket executables instead of minting one each.
- The persistent compilation cache round-trips: a second "process"
  (simulated via `jax.clear_caches()`) re-compiles from disk and the
  hit is COUNTED via the monitoring listener.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from cloud_tpu.models import MLP
from cloud_tpu.parallel import compile_cache, runtime
from cloud_tpu.training import Trainer
from cloud_tpu.training.callbacks import Callback
from cloud_tpu.training.data import GeneratorDataset


@pytest.fixture(autouse=True)
def _reset_counters():
    runtime.reset_compile_stats()
    compile_cache.reset_stats()
    yield
    runtime.reset_compile_stats()
    compile_cache.reset_stats()
    compile_cache.disable()


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


class TestCompileCounters:

    def test_record_and_reset(self):
        runtime.record_compile(n_traces=2, n_compiles=1,
                               compile_seconds=0.5, cache_hits=3)
        stats = runtime.compile_stats()
        assert stats["n_traces"] == 2
        assert stats["n_compiles"] == 1
        assert stats["compile_seconds"] == pytest.approx(0.5)
        assert stats["cache_hits"] == 3
        runtime.reset_compile_stats()
        assert runtime.compile_stats() == {
            "n_traces": 0, "n_compiles": 0, "compile_seconds": 0.0,
            "cache_hits": 0}

    def test_instrumented_jit_counts_per_shape(self):
        f = runtime.instrumented_jit(lambda a: a * 2)
        f(jnp.ones((2, 2)))
        stats = runtime.compile_stats()
        assert stats["n_traces"] == 1
        assert stats["n_compiles"] == 1
        assert stats["compile_seconds"] > 0
        # Cached dispatch: the counter must NOT move.
        f(jnp.zeros((2, 2)))
        assert runtime.compile_stats()["n_traces"] == 1
        # A new shape legitimately retraces.
        f(jnp.ones((3,)))
        assert runtime.compile_stats()["n_traces"] == 2
        assert f.n_traces == 2

    def test_warm_dispatch_is_trace_free(self):
        f = runtime.instrumented_jit(lambda a: a + 1)
        f.warm(jax.ShapeDtypeStruct((3,), jnp.float32))
        assert len(f.warm_signatures()) == 1
        # Idempotent per signature: no second lower/compile.
        before = runtime.compile_stats()["n_compiles"]
        f.warm(jax.ShapeDtypeStruct((3,), jnp.float32))
        assert runtime.compile_stats()["n_compiles"] == before

        runtime.reset_compile_stats()
        out = f(jnp.zeros((3,), jnp.float32))
        np.testing.assert_allclose(np.asarray(out), 1.0)
        assert runtime.compile_stats() == {
            "n_traces": 0, "n_compiles": 0, "compile_seconds": 0.0,
            "cache_hits": 0}


class _RaggedStream:
    """Per-epoch batch stream with a ragged tail (8, 8, 3 rows)."""

    def __init__(self, x, y):
        self.x, self.y = x, y

    def __call__(self):
        for lo, hi in ((0, 8), (8, 16), (16, 19)):
            yield self.x[lo:hi], self.y[lo:hi]


class TestSteadyStateZeroCompile:
    """The counted invariant: ZERO new compiles after epoch 1, raised
    on (not just warned about) by `on_retrace="raise"`."""

    def test_host_loop_with_ragged_tail(self):
        x, y = _data(n=19)
        stream = GeneratorDataset(_RaggedStream(x, y),
                                  steps_per_epoch=3)
        trainer = _trainer()
        history = trainer.fit(stream, epochs=3, verbose=False,
                              on_retrace="raise")
        assert len(history["loss"]) == 3

    def test_steps_per_execution_loop(self):
        x, y = _data()
        trainer = _trainer(steps_per_execution=2)
        history = trainer.fit(x, y, epochs=3, batch_size=16,
                              verbose=False, on_retrace="raise")
        assert len(history["loss"]) == 3

    def test_resident_loop(self):
        x, y = _data()
        trainer = _trainer()
        history = trainer.fit(x, y, epochs=3, batch_size=16,
                              verbose=False, cache="device",
                              on_retrace="raise")
        assert len(history["loss"]) == 3

    def test_sentinel_fires_on_steady_state_compile(self):
        """A compile in epoch >= 2 must be reported (here: injected
        through the counter a callback bumps — the sentinel reads the
        census, so anything that compiles trips it)."""
        x, y = _data()

        class Retracer(Callback):
            def on_epoch_begin(self, epoch):
                if epoch >= 1:
                    runtime.record_compile(n_traces=1, n_compiles=1)

        trainer = _trainer()
        with pytest.warns(runtime.RetraceWarning):
            trainer.fit(x, y, epochs=3, batch_size=16, verbose=False,
                        callbacks=(Retracer(),), on_retrace="warn")

        trainer2 = _trainer()
        with pytest.raises(runtime.RetraceWarning):
            trainer2.fit(x, y, epochs=3, batch_size=16, verbose=False,
                         callbacks=(Retracer(),), on_retrace="raise")

    def test_env_policy_validated(self):
        x, y = _data()
        with pytest.raises(ValueError):
            _trainer().fit(x, y, epochs=1, batch_size=16,
                           verbose=False, on_retrace="explode")


class TestWarmStart:

    def test_fit_after_warmup_is_trace_free(self):
        """warmup() pays every compile; the fit itself adds none —
        including its first step (the warm table dispatches the AOT
        executable directly)."""
        x, y = _data()
        trainer = _trainer()
        stats = trainer.warmup(x, y, batch_size=16)
        assert stats["n_compiles"] >= 1
        runtime.reset_compile_stats()
        history = trainer.fit(x, y, epochs=2, batch_size=16,
                              shuffle=False, verbose=False,
                              warm_start=True, on_retrace="raise")
        assert len(history["loss"]) == 2
        after = runtime.compile_stats()
        assert after["n_traces"] == 0, after
        assert after["n_compiles"] == 0, after

    def test_warm_start_matches_cold_fit_exactly(self):
        x, y = _data()
        a, b = _trainer(), _trainer()
        ha = a.fit(x, y, epochs=2, batch_size=16, shuffle=True,
                   verbose=False)
        b.warmup(x, y, batch_size=16)
        hb = b.fit(x, y, epochs=2, batch_size=16, shuffle=True,
                   verbose=False, warm_start=True)
        np.testing.assert_allclose(ha["loss"], hb["loss"], rtol=1e-6)
        for la, lb in zip(jax.tree_util.tree_leaves(a.state.params),
                          jax.tree_util.tree_leaves(b.state.params)):
            np.testing.assert_array_equal(np.asarray(la),
                                          np.asarray(lb))

    def test_warmup_eval_and_predict(self):
        x, y = _data()
        trainer = _trainer()
        trainer.warmup(x, y, batch_size=16, include_eval=True,
                       include_predict=True)
        runtime.reset_compile_stats()
        trainer.evaluate(x[:16], y[:16], batch_size=16, verbose=False)
        trainer.predict(x[:16], batch_size=16)
        after = runtime.compile_stats()
        assert after["n_traces"] == 0, after


class TestDecodeBucketing:

    def test_bucket_length(self):
        from cloud_tpu.models.decoding import bucket_length
        assert [bucket_length(n) for n in (1, 2, 3, 5, 8, 9)] == [
            1, 2, 4, 8, 8, 16]
        assert bucket_length(9, cap=12) == 12   # clipped to budget
        assert bucket_length(13, cap=12) == 13  # over cap: unchanged
        with pytest.raises(ValueError):
            bucket_length(0)

    def test_varied_prompt_lengths_share_executables(self):
        """The bucket census cap: three prompt lengths in one bucket
        compile ONE prefill (+ one decode scan, + the cache pool's two
        one-time executables: the fresh cache and the re-zero), never
        a per-length prefill."""
        from cloud_tpu.models import TransformerLM, generate

        model = TransformerLM(vocab_size=17, num_layers=1, num_heads=2,
                              d_model=16, d_ff=32, max_seq_len=32,
                              compute_dtype=jnp.float32)
        prompt = jnp.asarray(
            np.random.default_rng(0).integers(0, 17, (1, 7)), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), prompt)["params"]

        runtime.reset_compile_stats()
        outs = {}
        for length in (5, 6):
            p = prompt[:, :length]
            outs[length] = generate(model, params, p, 4,
                                    temperature=0.0)
            assert outs[length].shape == (1, length + 4)
        # Call 1: the fresh cache (one program, not a dispatch a
        # leaf) + prefill + decode scan. Call 2: +1 for the in-place
        # zero of the reacquired pool cache (the executable that
        # replaced per-call HBM allocation) — and nothing else.
        stats = runtime.compile_stats()
        assert stats["n_traces"] == 4, stats
        # Every further length in the bucket rides entirely warm.
        runtime.reset_compile_stats()
        outs[7] = generate(model, params, prompt, 4, temperature=0.0)
        assert outs[7].shape == (1, 11)
        stats = runtime.compile_stats()
        assert stats["n_traces"] == 0, stats

        # Bucketing is output-invisible: same tokens as the unbucketed
        # exact-shape dispatch (the left-padded-mask parity contract).
        unbucketed = generate(model, params, prompt[:, :5], 4,
                              temperature=0.0, bucket_prompts=False)
        np.testing.assert_array_equal(np.asarray(outs[5]),
                                      np.asarray(unbucketed))


class TestDecodeCachePool:

    @staticmethod
    def _decoder():
        from cloud_tpu.models import TransformerLM
        return TransformerLM(vocab_size=17, num_layers=2, num_heads=2,
                             d_model=16, d_ff=32, max_seq_len=32,
                             compute_dtype=jnp.float32).clone(
                                 decode=True, dropout_rate=0.0)

    def test_fresh_cache_is_one_program(self):
        """A cache the pool cannot supply is made by ONE program,
        traced once a (decoder, batch), not a dispatch a leaf."""
        from cloud_tpu.models import decoding
        decoder = self._decoder()
        runtime.reset_compile_stats()
        cache = decoding.empty_cache(decoder, 1)
        assert runtime.compile_stats()["n_traces"] == 1
        again = decoding.empty_cache(decoder, 1)
        assert runtime.compile_stats()["n_traces"] == 1
        shapes = decoding._cache_shapes(decoder, 1)
        leaves, want = (jax.tree_util.tree_leaves(t)
                        for t in (cache, shapes))
        assert len(leaves) == len(want) > 4
        for leaf, shape in zip(leaves, want):
            assert (leaf.shape, leaf.dtype) == (shape.shape, shape.dtype)
            assert not np.asarray(leaf).any()
        # Two caches, not one handed out twice.
        assert all(a is not b for a, b in zip(
            leaves, jax.tree_util.tree_leaves(again)))

    def test_acquire_takes_the_cache_parked_longest(self, monkeypatch):
        """What consumed the cache parked last may still be reading it
        on the device; the one parked first is the one re-zeroed."""
        from cloud_tpu.models import decoding
        decoder = self._decoder()
        decoding.clear_cache_pool()
        monkeypatch.setattr(decoding, "_zero_in_place",
                            lambda: (lambda cache: cache))
        first, second = {"parked": 1}, {"parked": 2}
        decoding.release_cache(decoder, 1, first)
        decoding.release_cache(decoder, 1, second)
        decoding.release_cache(decoder, 1, {"parked": 3})  # pool full
        assert decoding.acquire_cache(decoder, 1) is first
        assert decoding.acquire_cache(decoder, 1) is second
        fresh = decoding.acquire_cache(decoder, 1)
        assert "parked" not in fresh
        decoding.clear_cache_pool()


class TestPersistentCache:
    """Where the cache lives is decided outside the code: JAX's own
    `JAX_COMPILATION_CACHE_DIR` when set, else one fixed git-ignored
    path in the checkout. The path is part of the cache key, so there
    is no version, pid or time component in it."""

    @pytest.fixture
    def config_updates(self, monkeypatch):
        """Every `jax.config.update` key `compile_cache` issues."""
        keys = []
        real = jax.config.update

        def spy(key, value):
            keys.append(key)
            return real(key, value)

        monkeypatch.setattr(jax.config, "update", spy)
        return keys

    def test_variable_set_leaves_the_directory_to_jax(
            self, tmp_path, monkeypatch, config_updates):
        # What JAX itself does at import when the variable is set.
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        del config_updates[:]
        try:
            assert compile_cache.enable() == str(tmp_path)
            assert compile_cache.is_enabled()
            compile_cache.disable()
            assert "jax_compilation_cache_dir" not in config_updates
            assert jax.config.jax_compilation_cache_dir == str(tmp_path)
            # It still lifts the floors that keep small executables out.
            assert ("jax_persistent_cache_min_entry_size_bytes"
                    in config_updates)
        finally:
            jax.config.update("jax_compilation_cache_dir", None)

    def test_variable_unset_uses_the_fixed_checkout_path(
            self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        assert compile_cache.CHECKOUT_DIR == os.path.join(
            repo, "benchmarks", ".jax_cache")
        assert compile_cache.ENV_VAR == "JAX_COMPILATION_CACHE_DIR"
        try:
            assert compile_cache.enable() == compile_cache.CHECKOUT_DIR
            assert (jax.config.jax_compilation_cache_dir
                    == compile_cache.CHECKOUT_DIR)
            assert compile_cache.cache_dir() == compile_cache.CHECKOUT_DIR
        finally:
            compile_cache.disable()
        assert jax.config.jax_compilation_cache_dir is None
        assert compile_cache.cache_dir() is None

    def test_hit_after_restart_round_trip(self, tmp_path, monkeypatch):
        """enable() -> compile (miss, persisted) -> clear_caches (the
        in-process stand-in for a restart) -> recompile reads the disk
        entry and the hit lands in BOTH stats surfaces."""
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        monkeypatch.setattr(compile_cache, "CHECKOUT_DIR",
                            str(tmp_path))
        resolved = compile_cache.enable()
        assert resolved == str(tmp_path)
        try:
            f = runtime.instrumented_jit(lambda a: a * 3 + 1)
            f(jnp.arange(8, dtype=jnp.float32))
            assert compile_cache.stats()["persistent_misses"] >= 1
            assert os.listdir(resolved), "no cache entry persisted"

            jax.clear_caches()
            compile_cache.reset_stats()
            runtime.reset_compile_stats()
            g = runtime.instrumented_jit(lambda a: a * 3 + 1)
            out = g(jnp.arange(8, dtype=jnp.float32))
            np.testing.assert_allclose(np.asarray(out),
                                       np.arange(8) * 3 + 1)
            assert compile_cache.stats()["persistent_hits"] >= 1
            assert runtime.compile_stats()["cache_hits"] >= 1
        finally:
            compile_cache.disable()
            assert not compile_cache.is_enabled()

    def test_second_process_hits(self, tmp_path):
        """Two real processes sharing the directory through JAX's
        variable: the first compiles and persists, the second hits —
        and neither had a directory set by code."""
        import json
        import subprocess
        import sys

        script = (
            "import json, jax, jax.numpy as jnp\n"
            "from cloud_tpu.parallel import compile_cache\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "compile_cache.enable()\n"
            "jax.jit(lambda a: a * 5 + 2)(jnp.arange(16.0))"
            ".block_until_ready()\n"
            "print(json.dumps(dict(compile_cache.stats(), before=before,"
            " after=jax.config.jax_compilation_cache_dir)))\n")
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path))

        def run():
            proc = subprocess.run([sys.executable, "-c", script],
                                  capture_output=True, text=True,
                                  env=env, timeout=300)
            assert proc.returncode == 0, proc.stderr[-2000:]
            return json.loads(proc.stdout.strip().splitlines()[-1])

        first, second = run(), run()
        for stats in (first, second):
            assert stats["before"] == stats["after"] == str(tmp_path)
        assert first["persistent_misses"] >= 1
        assert first["persistent_hits"] == 0
        assert second["persistent_hits"] >= 1

    def test_serialize_round_trip_where_backend_allows(self):
        """The executable reloads on the devices it was compiled for
        (by id), not on every device of the backend."""
        f = runtime.instrumented_jit(lambda a: a + 2)
        compiled = f.lower(
            jax.ShapeDtypeStruct((4,), jnp.float32)).compile()
        triple = compile_cache.serialize_executable(compiled)
        assert len(triple) == 3 and isinstance(triple[0], bytes)
        loaded = compile_cache.deserialize_executable(triple)
        out = loaded(jnp.zeros((4,), jnp.float32))
        np.testing.assert_allclose(np.asarray(out), 2.0)

    def test_save_and_load_executable(self, tmp_path):
        f = runtime.instrumented_jit(lambda a: a * 2)
        compiled = f.lower(
            jax.ShapeDtypeStruct((4,), jnp.float32)).compile()
        path = compile_cache.save_executable(
            str(tmp_path / "step.bin"), compiled)
        out = compile_cache.load_executable(path)(
            jnp.ones((4,), jnp.float32))
        np.testing.assert_allclose(np.asarray(out), 2.0)
