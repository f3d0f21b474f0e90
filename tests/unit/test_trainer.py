"""Trainer tests on the 8-device virtual CPU mesh.

The TPU-native analogue of the reference's remote-fit unit tests (which
run `model.fit` in-process under a fabricated cluster, reference
cloud_fit/tests/unit/remote_test.py:80-127): real training steps, real
sharding, no hardware.
"""

import jax
import numpy as np
import optax
import pytest

pytestmark = pytest.mark.slow  # numeric-heavy: excluded from the fast tier

from cloud_tpu.models import MLP, ConvNet, TransformerLM, ResNet18
from cloud_tpu.models import tensor_parallel_rules
from cloud_tpu.parallel import runtime
from cloud_tpu.parallel import sharding as sharding_lib
from cloud_tpu.training import (ArrayDataset, EarlyStopping, MetricsLogger,
                                ModelCheckpoint, Trainer, read_metrics_log)
from cloud_tpu.training import checkpoint as checkpoint_lib


@pytest.fixture(autouse=True)
def _reset_runtime():
    runtime.reset()
    yield
    runtime.reset()


def _toy_classification(n=256, d=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d, classes))
    y = np.argmax(x @ w, axis=-1).astype(np.int32)
    return x, y


class TestFit:

    def test_loss_decreases_single_device(self):
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=32, num_classes=4),
                          optimizer=optax.adam(1e-2))
        history = trainer.fit(x, y, epochs=5, batch_size=64, verbose=False)
        assert history["loss"][-1] < history["loss"][0]
        assert history["accuracy"][-1] > 0.5

    def test_fit_on_dp_mesh(self):
        runtime.initialize(strategy="tpu_slice")
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=32, num_classes=4),
                          optimizer=optax.adam(1e-2))
        history = trainer.fit(x, y, epochs=3, batch_size=64, verbose=False)
        assert history["loss"][-1] < history["loss"][0]
        # Params live replicated on the mesh.
        leaf = next(iter(
            trainer.state.params["Dense_0"]["kernel"].addressable_shards))
        assert leaf is not None

    def test_evaluate_and_predict(self):
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=32, num_classes=4))
        trainer.fit(x, y, epochs=1, batch_size=64, verbose=False)
        logs = trainer.evaluate(x, y, batch_size=64, verbose=False)
        assert set(logs) == {"loss", "accuracy"}
        preds = trainer.predict(x[:100], batch_size=64)
        assert preds.shape == (100, 4)

    def test_evaluate_exact_example_weighted(self):
        """A dataset of batch_size+1 examples: the wrapped tail padding
        must not shift metrics — evaluate matches the hand-computed
        example mean exactly."""
        import jax
        import jax.numpy as jnp

        x, y = _toy_classification(n=33)
        # f32 compute: the check is weighting exactness, not bf16 noise
        # between the jitted eval step and the unjitted predict pass.
        trainer = Trainer(MLP(hidden=16, num_classes=4,
                              compute_dtype=jnp.float32))
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        logs = trainer.evaluate(x, y, batch_size=32, verbose=False)

        logits = trainer.predict(x, batch_size=32)
        per_ex_loss = np.asarray(
            optax.softmax_cross_entropy_with_integer_labels(
                jnp.asarray(logits), jnp.asarray(y)))
        expected_loss = float(per_ex_loss.mean())
        expected_acc = float(
            (np.argmax(logits, axis=-1) == y).mean())
        assert logs["loss"] == pytest.approx(expected_loss, rel=1e-5)
        assert logs["accuracy"] == pytest.approx(expected_acc, rel=1e-6)
        del jax

    def test_evaluate_exact_on_mesh(self):
        """Same exactness through the sharded eval step (mask rides the
        batch sharding)."""
        import jax.numpy as jnp

        runtime.initialize(strategy="tpu_slice")
        x, y = _toy_classification(n=40)
        trainer = Trainer(MLP(hidden=16, num_classes=4,
                              compute_dtype=jnp.float32))
        trainer.fit(x, y, epochs=1, batch_size=16, verbose=False)
        logs = trainer.evaluate(x, y, batch_size=16, verbose=False)
        logits = trainer.predict(x, batch_size=16)
        per_ex_loss = np.asarray(
            optax.softmax_cross_entropy_with_integer_labels(
                jnp.asarray(logits), jnp.asarray(y)))
        assert logs["loss"] == pytest.approx(float(per_ex_loss.mean()),
                                             rel=1e-5)

    def test_evaluate_list_shaped_batches(self):
        """Re-iterables may yield [x, y] lists; evaluate must unpack
        them like the train step does, not treat them as unlabeled."""
        x, y = _toy_classification(n=64)
        trainer = Trainer(MLP(hidden=16, num_classes=4))
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        batches = [[x[:32], y[:32]], [x[32:], y[32:]]]
        logs = trainer.evaluate(batches, verbose=False)
        assert np.isfinite(logs["loss"])

    def test_evaluate_caps_streaming_dataset(self):
        """evaluate() must honor a dataset-level steps_per_epoch the way
        fit() does — otherwise an unbounded GeneratorDataset loops
        forever."""
        from cloud_tpu.training.data import GeneratorDataset

        x, y = _toy_classification(n=64)
        trainer = Trainer(MLP(hidden=16, num_classes=4))
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)

        def unbounded():
            while True:
                yield x[:32], y[:32]

        dataset = GeneratorDataset(unbounded, steps_per_epoch=3)
        logs = trainer.evaluate(dataset, verbose=False)
        assert np.isfinite(logs["loss"])

    def test_mask_aware_custom_metric_exact_under_padding(self):
        """A custom metric that takes mask= sees the valid-mask and can
        return an exact scalar even on padded tail batches."""
        import jax.numpy as jnp

        x, y = _toy_classification(n=33)

        def frac_class0(outputs, y, mask=None):
            hit = (jnp.argmax(outputs, axis=-1) == 0).astype(jnp.float32)
            return jnp.sum(hit * mask) / jnp.maximum(jnp.sum(mask), 1.0)

        trainer = Trainer(MLP(hidden=16, num_classes=4,
                              compute_dtype=jnp.float32),
                          metrics=(frac_class0,))
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        logs = trainer.evaluate(x, y, batch_size=32, verbose=False)
        logits = trainer.predict(x, batch_size=32)
        expected = float((np.argmax(logits, axis=-1) == 0).mean())
        assert logs["frac_class0"] == pytest.approx(expected, rel=1e-5)

    def test_scalar_unmasked_metric_raises_on_padded_batch(self):
        """A scalar custom metric with no mask= signature cannot be
        corrected for padded duplicates: evaluate fails loudly instead
        of silently averaging them in."""
        import jax.numpy as jnp

        x, y = _toy_classification(n=33)

        def scalar_metric(outputs, y):
            return jnp.mean(jnp.argmax(outputs, axis=-1) == y)

        trainer = Trainer(MLP(hidden=16, num_classes=4),
                          metrics=(scalar_metric,))
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        with pytest.raises(ValueError, match="scalar_metric"):
            trainer.evaluate(x, y, batch_size=32, verbose=False)
        # Unpadded eval still works fine with the same metric.
        logs = trainer.evaluate(x[:32], y[:32], batch_size=32,
                                verbose=False)
        assert np.isfinite(logs["scalar_metric"])

    def test_scalar_metric_ok_on_short_unpadded_batch(self):
        """A dataset that yields a genuinely SHORT final batch (no
        wrapping, mask all-ones) is exact for any metric — the padded
        guard must not fire (it conflated short with padded once)."""
        import jax.numpy as jnp

        x, y = _toy_classification(n=42)

        def scalar_metric(outputs, y):
            return jnp.mean(jnp.argmax(outputs, -1) == y)

        trainer = Trainer(MLP(hidden=16, num_classes=4),
                          metrics=(scalar_metric,))
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        batches = [(x[:32], y[:32]), (x[32:], y[32:])]  # short tail
        logs = trainer.evaluate(batches, verbose=False)
        assert np.isfinite(logs["scalar_metric"])

    def test_validation_data(self):
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=16, num_classes=4))
        history = trainer.fit(x, y, epochs=2, batch_size=64,
                              validation_data=(x[:64], y[:64]),
                              verbose=False)
        assert "val_loss" in history
        assert "val_accuracy" in history

    def test_convnet_images(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 12, 12, 1)).astype(np.float32)
        y = rng.integers(0, 10, size=64).astype(np.int32)
        trainer = Trainer(ConvNet(num_classes=10))
        history = trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        assert np.isfinite(history["loss"][0])


class TestBatchNormModels:

    def test_resnet_trains_with_batch_stats(self):
        runtime.initialize(strategy="tpu_slice")
        rng = np.random.default_rng(0)
        x = rng.normal(size=(16, 16, 16, 3)).astype(np.float32)
        y = rng.integers(0, 5, size=16).astype(np.int32)
        trainer = Trainer(ResNet18(num_classes=5, num_filters=8),
                          optimizer=optax.sgd(1e-2),
                          train_kwargs={"train": True},
                          eval_kwargs={"train": False})
        history = trainer.fit(x, y, epochs=1, batch_size=8, verbose=False)
        assert np.isfinite(history["loss"][0])
        assert "batch_stats" in trainer.state.extra_vars
        # Running stats moved away from init.
        stats = trainer.state.extra_vars["batch_stats"]
        mean = np.asarray(stats["bn_init"]["mean"])
        assert np.abs(mean).sum() > 0


class TestResNetVariants:

    def test_resnet18_is_basic_block(self):
        """ResNet18 must match the canonical basic-block architecture
        (11,689,512 params at 1000 classes), not a bottleneck stand-in."""
        import jax
        import jax.numpy as jnp

        from cloud_tpu.models import ResNet18

        model = ResNet18(num_classes=1000)
        shapes = jax.eval_shape(
            lambda k: model.init(k, jnp.ones((1, 224, 224, 3)),
                                 train=False),
            jax.random.PRNGKey(0))
        n = sum(int(np.prod(p.shape))
                for p in jax.tree_util.tree_leaves(shapes["params"]))
        assert n == 11_689_512


class TestTensorParallel:

    def test_transformer_tp_sharding(self):
        ctx = runtime.initialize(strategy="tpu_slice",
                                 axis_names=("dp", "tp"),
                                 mesh_shape=(2, 4))
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 64, size=(8, 16)).astype(np.int32)
        targets = rng.integers(0, 64, size=(8, 16)).astype(np.int32)

        def lm_loss(logits, labels):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean(axis=-1)

        model = TransformerLM(vocab_size=64, num_layers=2, num_heads=4,
                              d_model=32, d_ff=64, max_seq_len=16)
        trainer = Trainer(model, optimizer=optax.adam(1e-2), loss=lm_loss,
                          metrics=(),
                          param_sharding_rules=tensor_parallel_rules("tp"))
        history = trainer.fit(tokens, targets, epochs=2, batch_size=8,
                              shuffle=False, verbose=False)
        assert history["loss"][-1] < history["loss"][0]

        # mlp_in kernel must actually be column-sharded over tp=4.
        kernel = trainer.state.params["block_0"]["mlp_in"]["kernel"]
        spec = kernel.sharding.spec
        assert spec == (None, "tp") or tuple(spec) == (None, "tp")
        shard = next(iter(kernel.addressable_shards))
        assert shard.data.shape == (32, 64 // 4)


class TestReviewRegressions:

    def test_generator_dataset_trains_all_epochs(self):
        x, y = _toy_classification(n=128)

        def gen():
            for i in range(4):
                yield x[i * 32:(i + 1) * 32], y[i * 32:(i + 1) * 32]

        trainer = Trainer(MLP(hidden=16, num_classes=4))
        history = trainer.fit(gen(), epochs=3, verbose=False)
        assert len(history["loss"]) == 3
        # Every epoch actually ran 4 steps (non-zero, finite loss).
        assert all(np.isfinite(v) for v in history["loss"])

    def test_small_validation_set_still_evaluated(self):
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=16, num_classes=4))
        history = trainer.fit(x, y, epochs=1, batch_size=64,
                              validation_data=(x[:10], y[:10]),
                              verbose=False)
        assert "val_loss" in history and np.isfinite(history["val_loss"][0])

    def test_predict_smaller_than_batch(self):
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=16, num_classes=4))
        trainer.fit(x, y, epochs=1, batch_size=64, verbose=False)
        preds = trainer.predict(x[:5], batch_size=64)
        assert preds.shape == (5, 4)

    def test_predict_pytree_outputs(self):
        """A tuple/dict-returning model (e.g. MoE's (out, aux)) must
        round-trip through predict() with its structure intact and
        every leaf concatenated/truncated per batch dim (np.asarray
        over a tuple would crash or mis-stack)."""
        import flax.linen as nn
        import jax
        import jax.numpy as jnp

        class TupleOut(nn.Module):
            @nn.compact
            def __call__(self, x):
                h = nn.Dense(4)(x)
                # `aux` is a 0-d per-batch scalar, the MoEMLP
                # (out, aux_loss) shape: predict must stack it
                # per batch, not concatenate per example.
                return {"logits": h, "pooled": jnp.mean(h, axis=-1),
                        "aux": jnp.mean(h)}

        x, y = _toy_classification(n=80)

        def loss_fn(outputs, yb):
            logits = outputs["logits"]
            one_hot = jax.nn.one_hot(yb, logits.shape[-1])
            return -jnp.mean(
                jnp.sum(jax.nn.log_softmax(logits) * one_hot, -1))

        trainer = Trainer(TupleOut(), loss=loss_fn, metrics=())
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        # 80 rows / batch 32 -> 3 batches with a ragged 16-row tail:
        # leaves must concatenate across batches and truncate to n.
        preds = trainer.predict(x, batch_size=32)
        assert set(preds) == {"logits", "pooled", "aux"}
        assert preds["logits"].shape == (80, 4)
        assert preds["pooled"].shape == (80,)
        assert preds["aux"].shape == (3,)  # one scalar per batch
        np.testing.assert_allclose(
            preds["pooled"], preds["logits"].mean(-1), rtol=1e-5)

    def test_dict_pytree_input(self):
        rng = np.random.default_rng(0)
        x = {"a": rng.normal(size=(64, 4)).astype(np.float32),
             "b": rng.normal(size=(64, 4)).astype(np.float32)}
        y = rng.integers(0, 3, size=64).astype(np.int32)

        import flax.linen as nn

        class TwoInput(nn.Module):
            @nn.compact
            def __call__(self, inputs):
                h = jnp_concat([inputs["a"], inputs["b"]])
                return nn.Dense(3)(h)

        import jax.numpy as jnp

        def jnp_concat(parts):
            return jnp.concatenate(parts, axis=-1)

        trainer = Trainer(TwoInput())
        history = trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        assert np.isfinite(history["loss"][0])

    def test_tp_optimizer_state_inherits_param_sharding(self):
        runtime.initialize(strategy="tpu_slice", axis_names=("dp", "tp"),
                           mesh_shape=(2, 4))
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 64, size=(8, 16)).astype(np.int32)
        model = TransformerLM(vocab_size=64, num_layers=1, num_heads=4,
                              d_model=32, d_ff=64, max_seq_len=16)
        trainer = Trainer(model, optimizer=optax.adam(1e-2),
                          loss=lambda o, t: o.mean(axis=(-1, -2)),
                          metrics=(),
                          param_sharding_rules=tensor_parallel_rules("tp"))
        trainer.build(tokens)
        # Adam's first moment for the tp-sharded mlp_in kernel must be
        # tp-sharded too (not replicated).
        mu = trainer.state.opt_state[0].mu
        kernel_mu = mu["block_0"]["mlp_in"]["kernel"]
        shard = next(iter(kernel_mu.addressable_shards))
        assert shard.data.shape == (32, 64 // 4)


class TestCallbacks:

    def test_early_stopping(self):
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=16, num_classes=4),
                          optimizer=optax.sgd(0.0))  # loss frozen
        history = trainer.fit(
            x, y, epochs=10, batch_size=64, verbose=False,
            callbacks=[EarlyStopping(monitor="loss", patience=1)])
        assert len(history["loss"]) < 10

    def test_metrics_logger_jsonl(self, tmp_path):
        x, y = _toy_classification()
        path = str(tmp_path / "logs" / "metrics.jsonl")
        trainer = Trainer(MLP(hidden=16, num_classes=4))
        trainer.fit(x, y, epochs=3, batch_size=64, verbose=False,
                    callbacks=[MetricsLogger(path)])
        records = read_metrics_log(path)
        assert [r["epoch"] for r in records] == [0, 1, 2]
        assert all("loss" in r and "accuracy" in r for r in records)

    def test_model_checkpoint_and_restore(self, tmp_path):
        x, y = _toy_classification()
        ckpt_dir = str(tmp_path / "ckpt")
        trainer = Trainer(MLP(hidden=16, num_classes=4))
        trainer.fit(x, y, epochs=2, batch_size=64, verbose=False,
                    callbacks=[ModelCheckpoint(ckpt_dir)])
        step = checkpoint_lib.latest_step(ckpt_dir)
        assert step == 8  # 2 epochs x 4 steps

        restored = checkpoint_lib.restore(ckpt_dir, trainer.state)
        np.testing.assert_allclose(
            np.asarray(restored.params["Dense_0"]["kernel"]),
            np.asarray(trainer.state.params["Dense_0"]["kernel"]))


class TestArrayDataset:

    def test_batching_and_shuffle_determinism(self):
        x = np.arange(100, dtype=np.float32)[:, None]
        y = np.arange(100, dtype=np.int32)
        ds1 = ArrayDataset(x, y, batch_size=32, shuffle=True, seed=7)
        ds2 = ArrayDataset(x, y, batch_size=32, shuffle=True, seed=7)
        b1 = next(iter(ds1))
        b2 = next(iter(ds2))
        np.testing.assert_array_equal(b1[0], b2[0])
        assert ds1.steps_per_epoch == 3  # drop_remainder

    def test_epochs_reshuffle(self):
        x = np.arange(64, dtype=np.float32)[:, None]
        ds = ArrayDataset(x, None, batch_size=64, shuffle=True, seed=0)
        e1 = next(iter(ds))
        e2 = next(iter(ds))
        assert not np.array_equal(e1, e2)

    def test_process_local_view(self):
        x = np.arange(32, dtype=np.float32)[:, None]
        y = np.arange(32, dtype=np.int32)
        ds = ArrayDataset(x, y, batch_size=8)
        shards = list(ds.process_local_view(process_index=1,
                                            process_count=4))
        assert len(shards) == 4
        xb, yb = shards[0]
        assert xb.shape == (2, 1)
        np.testing.assert_array_equal(yb, [2, 3])

    def test_pad_tail(self):
        x = np.arange(10, dtype=np.float32)[:, None]
        ds = ArrayDataset(x, None, batch_size=4, drop_remainder=False)
        batches = list(ds)
        assert len(batches) == 3
        assert all(b.shape == (4, 1) for b in batches)


class TestResume:
    def test_fit_resumes_from_checkpoint(self, tmp_path):
        """Preemption recovery: a second Trainer resumes exactly where
        the checkpointed run stopped (step counter and params)."""
        import jax.numpy as jnp
        import optax

        from cloud_tpu.models import MLP
        from cloud_tpu.parallel import runtime
        from cloud_tpu.training import Trainer
        from cloud_tpu.training.callbacks import ModelCheckpoint

        runtime.reset()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 8, 8)).astype(np.float32)
        y = rng.integers(0, 10, size=64).astype(np.int32)
        ckpt_dir = str(tmp_path / "ckpt")

        def make():
            return Trainer(MLP(hidden=16, compute_dtype=jnp.float32),
                           optimizer=optax.adam(1e-3),
                           loss="sparse_categorical_crossentropy",
                           metrics=(), seed=0)

        first = make()
        first.fit(x, y, epochs=2, batch_size=32, shuffle=False,
                  verbose=False,
                  callbacks=[ModelCheckpoint(ckpt_dir)])
        steps_done = int(first.state.step)
        assert steps_done == 4  # 2 epochs x 2 steps

        resumed = make()
        resumed.fit(x, y, epochs=1, batch_size=32, shuffle=False,
                    verbose=False, resume_from=ckpt_dir)
        assert int(resumed.state.step) == steps_done + 2
        # Fresh run (no resume) would be at 2 steps with different params.
        fresh = make()
        fresh.fit(x, y, epochs=1, batch_size=32, shuffle=False,
                  verbose=False)
        assert int(fresh.state.step) == 2

    def test_resume_from_empty_dir_is_noop(self, tmp_path):
        import jax.numpy as jnp
        import optax

        from cloud_tpu.models import MLP
        from cloud_tpu.parallel import runtime
        from cloud_tpu.training import Trainer

        runtime.reset()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(32, 8, 8)).astype(np.float32)
        y = rng.integers(0, 10, size=32).astype(np.int32)
        trainer = Trainer(MLP(hidden=16, compute_dtype=jnp.float32),
                          optimizer=optax.adam(1e-3),
                          loss="sparse_categorical_crossentropy",
                          metrics=(), seed=0)
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False,
                    resume_from=str(tmp_path / "missing"))
        assert int(trainer.state.step) == 1


class TestAccumulationAndRemat:
    def _data(self):
        import jax  # noqa: F401 (used by tests below)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 8, 8)).astype(np.float32)
        y = rng.integers(0, 10, size=64).astype(np.int32)
        return x, y

    def test_gradient_accumulation_matches_large_batch(self):
        """SGD with N accumulation steps over batch B == one step over
        batch N*B (identical data, mean losses)."""
        import jax.numpy as jnp
        import optax

        from cloud_tpu.models import MLP
        from cloud_tpu.parallel import runtime
        from cloud_tpu.training import Trainer

        runtime.reset()
        x, y = self._data()

        def make(accum):
            return Trainer(MLP(hidden=16, compute_dtype=jnp.float32),
                           optimizer=optax.sgd(0.1),
                           loss="sparse_categorical_crossentropy",
                           metrics=(), seed=0,
                           gradient_accumulation_steps=accum)

        import jax

        accum = make(2)
        accum.fit(x, y, epochs=1, batch_size=32, shuffle=False,
                  verbose=False)
        big = make(1)
        big.fit(x, y, epochs=1, batch_size=64, shuffle=False,
                verbose=False)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5),
            accum.state.params, big.state.params)

    def test_remat_matches_plain(self):
        import jax.numpy as jnp
        import optax

        from cloud_tpu.models import MLP
        from cloud_tpu.parallel import runtime
        from cloud_tpu.training import Trainer

        runtime.reset()
        x, y = self._data()

        def make(remat):
            return Trainer(MLP(hidden=16, compute_dtype=jnp.float32),
                           optimizer=optax.sgd(0.1),
                           loss="sparse_categorical_crossentropy",
                           metrics=(), seed=0, remat=remat)

        import jax

        a = make(True)
        a.fit(x, y, epochs=1, batch_size=32, shuffle=False, verbose=False)
        b = make(False)
        b.fit(x, y, epochs=1, batch_size=32, shuffle=False, verbose=False)
        jax.tree_util.tree_map(
            lambda p, q: np.testing.assert_allclose(
                np.asarray(p), np.asarray(q), atol=1e-5, rtol=1e-5),
            a.state.params, b.state.params)


class TestSpaceToDepthResNet:
    def test_s2d_stem_trains_and_matches_shapes(self):
        """s2d stem: same logits shape and downstream feature geometry
        as the standard 7x7/s2 stem, and the model trains."""
        import jax.numpy as jnp
        import optax

        from cloud_tpu.models import ResNet
        from cloud_tpu.parallel import runtime
        from cloud_tpu.training import Trainer

        runtime.reset()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 64, 64, 3)).astype(np.float32)
        y = rng.integers(0, 10, size=8).astype(np.int32)
        model = ResNet(stage_sizes=(1, 1), num_classes=10,
                       num_filters=16, compute_dtype=jnp.float32,
                       conv0_space_to_depth=True)
        trainer = Trainer(model, optimizer=optax.adam(1e-3),
                          loss="sparse_categorical_crossentropy",
                          metrics=(), train_kwargs={"train": True},
                          eval_kwargs={"train": False})
        history = trainer.fit(x, y, epochs=2, batch_size=8,
                              verbose=False)
        assert history["loss"][-1] < history["loss"][0]

        # Shape equivalence with the standard stem: identical logits
        # shape, and the stems produce identical spatial dims.
        import jax

        std = ResNet(stage_sizes=(1, 1), num_classes=10, num_filters=16,
                     compute_dtype=jnp.float32)
        std_vars = std.init(jax.random.PRNGKey(0), x[:1], train=False)
        std_out = std.apply(std_vars, x[:1], train=False)
        s2d_out = model.apply(trainer.state.as_variables()
                              if hasattr(trainer.state, "as_variables")
                              else {"params": trainer.state.params,
                                    **trainer.state.extra_vars},
                              x[:1], train=False)
        assert std_out.shape == s2d_out.shape == (1, 10)

    def test_s2d_rejects_odd_spatial(self):
        import jax
        import jax.numpy as jnp

        from cloud_tpu.models import ResNet

        model = ResNet(stage_sizes=(1,), num_classes=10, num_filters=8,
                       compute_dtype=jnp.float32,
                       conv0_space_to_depth=True)
        x = jnp.ones((1, 65, 65, 3))
        with pytest.raises(ValueError, match="even spatial"):
            model.init(jax.random.PRNGKey(0), x, train=False)


class TestZero1:
    """ZeRO-1 optimizer-state sharding over the dp axis."""

    def test_moments_dp_sharded_and_training_matches(self):
        runtime.initialize(strategy="tpu_slice")  # 8-device dp mesh
        x, y = _toy_classification()

        def build(zero1):
            return Trainer(MLP(hidden=32, num_classes=4),
                           optimizer=optax.adam(1e-2), seed=0,
                           zero1=zero1)

        base = build(False)
        z1 = build(True)
        hb = base.fit(x, y, epochs=2, batch_size=64, shuffle=False,
                      verbose=False)
        hz = z1.fit(x, y, epochs=2, batch_size=64, shuffle=False,
                    verbose=False)
        # Same math, different layout.
        np.testing.assert_allclose(hb["loss"], hz["loss"], rtol=1e-4)

        # Adam mu for the hidden kernel: [8, 32] — dim 0 divides 8, so
        # the moment is dp-sharded while the param stays replicated.
        mu = z1.state.opt_state[0].mu["Dense_0"]["kernel"]
        spec = mu.sharding.spec
        assert "dp" in tuple(spec), spec
        param = z1.state.params["Dense_0"]["kernel"]
        assert tuple(param.sharding.spec) in ((), (None,), (None, None))
        # 8x memory saving: each device holds 1/8 of the moment.
        shard = next(iter(mu.addressable_shards))
        assert shard.data.shape[0] == mu.shape[0] // 8

    def test_zero1_noop_without_mesh(self):
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=32, num_classes=4),
                          optimizer=optax.adam(1e-2), zero1=True)
        history = trainer.fit(x, y, epochs=1, batch_size=64, verbose=False)
        assert history["loss"][-1] > 0

    def test_zero1_composes_with_tp(self):
        """tp-sharded params keep tp in the moment spec; dp lands on a
        free dimension."""
        runtime.initialize(strategy="tpu_slice", axis_names=("dp", "tp"),
                           mesh_shape=(4, 2))
        model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                              d_model=16, d_ff=64, max_seq_len=16)
        trainer = Trainer(model, optimizer=optax.adam(1e-3),
                          loss=lambda o, y: optax.
                          softmax_cross_entropy_with_integer_labels(o, y)
                          .mean(axis=-1),
                          param_sharding_rules=tensor_parallel_rules(),
                          zero1=True)
        toks = np.random.default_rng(0).integers(
            0, 64, size=(16, 16)).astype(np.int32)
        trainer.fit(toks, np.roll(toks, -1, 1), epochs=1, batch_size=8,
                    verbose=False)
        # Find a tp-sharded moment leaf and check both axes appear.
        import jax
        leaves = jax.tree_util.tree_leaves(trainer.state.opt_state[0].mu)
        specs = [tuple(l.sharding.spec) for l in leaves]
        assert any("tp" in s and "dp" in s for s in specs), specs

    def test_zero1_param_already_dp_sharded(self):
        """Params sharded on dp (FSDP-style rules) must not produce a
        double-dp moment spec (NamedSharding rejects axis reuse)."""
        from jax.sharding import PartitionSpec as P

        runtime.initialize(strategy="tpu_slice")  # 8-device dp mesh
        x, y = _toy_classification()
        trainer = Trainer(
            MLP(hidden=32, num_classes=4), optimizer=optax.adam(1e-2),
            param_sharding_rules=[(r".*Dense_0/kernel", P("dp", None))],
            zero1=True)
        history = trainer.fit(x, y, epochs=1, batch_size=64,
                              verbose=False)
        assert history["loss"][-1] > 0
        mu = trainer.state.opt_state[0].mu["Dense_0"]["kernel"]
        assert tuple(mu.sharding.spec).count("dp") == 1


class TestFSDP:
    """Fully-sharded parameters (ZeRO-3 style) over the dp axis."""

    def test_params_and_moments_dp_sharded_training_matches(self):
        runtime.initialize(strategy="tpu_slice")  # 8-device dp mesh
        x, y = _toy_classification()

        def build(fsdp):
            return Trainer(MLP(hidden=32, num_classes=4),
                           optimizer=optax.adam(1e-2), seed=0, fsdp=fsdp)

        hb = build(False).fit(x, y, epochs=2, batch_size=64,
                              shuffle=False, verbose=False)
        tz = build(True)
        hz = tz.fit(x, y, epochs=2, batch_size=64, shuffle=False,
                    verbose=False)
        np.testing.assert_allclose(hb["loss"], hz["loss"], rtol=1e-4)

        # Hidden kernel [8, 32]: dim 0 divides 8 -> dp-sharded weights
        # AND moments (each device holds 1/8 of both).
        kern = tz.state.params["Dense_0"]["kernel"]
        assert "dp" in tuple(kern.sharding.spec)
        mu = tz.state.opt_state[0].mu["Dense_0"]["kernel"]
        assert "dp" in tuple(mu.sharding.spec)
        shard = next(iter(kern.addressable_shards))
        assert shard.data.shape[0] == kern.shape[0] // 8

    def test_fsdp_composes_with_tp(self):
        runtime.initialize(strategy="tpu_slice", axis_names=("dp", "tp"),
                           mesh_shape=(4, 2))
        model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                              d_model=16, d_ff=64, max_seq_len=16)
        trainer = Trainer(model, optimizer=optax.adam(1e-3),
                          loss=lambda o, y: optax.
                          softmax_cross_entropy_with_integer_labels(o, y)
                          .mean(axis=-1),
                          param_sharding_rules=tensor_parallel_rules(),
                          fsdp=True)
        toks = np.random.default_rng(0).integers(
            0, 64, size=(16, 16)).astype(np.int32)
        h = trainer.fit(toks, np.roll(toks, -1, 1), epochs=1,
                        batch_size=8, verbose=False)
        assert np.isfinite(h["loss"][-1])
        import jax
        leaves = jax.tree_util.tree_leaves(trainer.state.params)
        specs = [tuple(l.sharding.spec) for l in leaves]
        assert any("tp" in str(s) and "dp" in str(s) for s in specs), specs

    def test_fsdp_checkpoint_roundtrip(self, tmp_path):
        runtime.initialize(strategy="tpu_slice")
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=32, num_classes=4),
                          optimizer=optax.adam(1e-2), seed=0, fsdp=True)
        trainer.fit(x, y, epochs=1, batch_size=64, verbose=False)
        trainer.save_checkpoint(str(tmp_path / "ckpt"))
        restored = Trainer(MLP(hidden=32, num_classes=4),
                           optimizer=optax.adam(1e-2), seed=0, fsdp=True)
        restored.restore_checkpoint(str(tmp_path / "ckpt"), x)
        import jax
        a = np.asarray(jax.device_get(
            trainer.state.params["Dense_0"]["kernel"]))
        b = np.asarray(jax.device_get(
            restored.state.params["Dense_0"]["kernel"]))
        np.testing.assert_allclose(a, b)


class TestOptimizerRegistry:

    def test_all_names_build_and_step(self):
        from cloud_tpu.training.trainer import OPTIMIZERS

        x, y = _toy_classification(n=64)
        for name in OPTIMIZERS:
            trainer = Trainer(MLP(hidden=16, num_classes=4),
                              optimizer=name, metrics=())
            h = trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
            assert np.isfinite(h["loss"][-1]), name


class TestAsyncCheckpoint:

    def test_async_save_roundtrips(self, tmp_path):
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=16, num_classes=4),
                          optimizer=optax.adam(1e-2), seed=0)
        cb = ModelCheckpoint(str(tmp_path / "ckpt"), use_async=True)
        trainer.fit(x, y, epochs=2, batch_size=64, verbose=False,
                    callbacks=[cb])
        # on_train_end waited; the latest step is the final one and the
        # state restores bit-exact.
        assert checkpoint_lib.latest_step(str(tmp_path / "ckpt")) == \
            int(trainer.state.step)
        restored = Trainer(MLP(hidden=16, num_classes=4),
                           optimizer=optax.adam(1e-2), seed=0)
        restored.restore_checkpoint(str(tmp_path / "ckpt"), x)
        import jax
        a = jax.device_get(trainer.state.params)
        b = jax.device_get(restored.state.params)
        for la, lb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    def test_restore_waits_for_inflight_async_save(self, tmp_path,
                                                   monkeypatch):
        x, _ = _toy_classification()
        trainer = Trainer(MLP(hidden=16, num_classes=4),
                          optimizer=optax.adam(1e-2), seed=0)
        trainer.build(x)
        checkpoint_lib.save(str(tmp_path / "c"), trainer.state, step=7,
                            use_async=True)
        # No explicit wait: restore/latest_step must block internally.
        # Timing alone can't prove that for a tiny local write, so spy
        # on the barrier: every read path must hit it.
        real = checkpoint_lib._async_checkpointer
        assert real is not None
        waits = []

        class Spy:
            def wait_until_finished(self):
                waits.append(True)
                real.wait_until_finished()

        monkeypatch.setattr(checkpoint_lib, "_async_checkpointer", Spy())
        assert checkpoint_lib.latest_step(str(tmp_path / "c")) == 7
        assert waits  # latest_step blocked on the async barrier
        waits.clear()
        restored = checkpoint_lib.restore(str(tmp_path / "c"),
                                          trainer.state, step=7)
        assert waits  # explicit-step restore blocked too
        import jax
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(restored.step)),
            np.asarray(jax.device_get(trainer.state.step)))

    def test_failing_teardown_does_not_skip_other_callbacks(self):
        from cloud_tpu.training import LambdaCallback

        x, y = _toy_classification()
        ran = []

        class Exploding(LambdaCallback):
            def on_train_end(self, history):
                raise RuntimeError("commit failed")

        ok = LambdaCallback(
            on_train_end=lambda history: ran.append("ok"))
        trainer = Trainer(MLP(hidden=16, num_classes=4),
                          optimizer=optax.adam(1e-2))
        with pytest.raises(RuntimeError, match="commit failed"):
            trainer.fit(x, y, epochs=1, batch_size=64, verbose=False,
                        callbacks=[Exploding(), ok])
        assert ran == ["ok"]


class TestEMA:

    def test_shadow_tracks_and_eval_uses_it(self):
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=16, num_classes=4),
                          optimizer=optax.adam(5e-2), seed=0,
                          ema_decay=0.9)
        trainer.fit(x, y, epochs=2, batch_size=64, verbose=False)
        import jax
        ema = jax.device_get(trainer.ema_params)
        live = jax.device_get(trainer.state.params)
        # Shadow lags the live params (high LR makes them differ).
        diffs = [float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                 for a, b in zip(jax.tree_util.tree_leaves(ema),
                                 jax.tree_util.tree_leaves(live))]
        assert max(diffs) > 1e-5
        # use_ema evaluates/predicts on the shadow: results differ from
        # the live-params run, and the plumbing is exercised.
        a = trainer.evaluate(x, y, batch_size=64, verbose=False)
        b = trainer.evaluate(x, y, batch_size=64, verbose=False,
                             use_ema=True)
        assert a["loss"] != b["loss"]
        pa = trainer.predict(x[:8], batch_size=8)
        pb = trainer.predict(x[:8], batch_size=8, use_ema=True)
        assert not np.allclose(pa, pb)

    def test_ema_manual_recurrence(self):
        """One step, SGD: shadow == decay*init + (1-decay)*updated."""
        import jax

        x, y = _toy_classification(n=32)
        d = 0.5
        trainer = Trainer(MLP(hidden=8, num_classes=4),
                          optimizer=optax.sgd(0.1), seed=0, ema_decay=d)
        trainer.build(x)
        init = jax.device_get(trainer.state.params)
        trainer.fit(x, y, epochs=1, batch_size=32, shuffle=False,
                    verbose=False)
        after = jax.device_get(trainer.state.params)
        ema = jax.device_get(trainer.ema_params)
        want = jax.tree_util.tree_map(
            lambda i, a: d * np.asarray(i) + (1 - d) * np.asarray(a),
            init, after)
        for w, e in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(ema)):
            np.testing.assert_allclose(np.asarray(w), np.asarray(e),
                                       rtol=1e-5)

    def test_ema_with_accumulation_and_checkpoint(self, tmp_path):
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=16, num_classes=4),
                          optimizer=optax.adam(1e-2), seed=0,
                          ema_decay=0.99, gradient_accumulation_steps=2)
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        _ = trainer.ema_params  # reaches through MultiSteps state
        trainer.save_checkpoint(str(tmp_path / "c"))
        restored = Trainer(MLP(hidden=16, num_classes=4),
                           optimizer=optax.adam(1e-2), seed=0,
                           ema_decay=0.99, gradient_accumulation_steps=2)
        restored.restore_checkpoint(str(tmp_path / "c"), x)
        import jax
        for a, b in zip(jax.tree_util.tree_leaves(
                jax.device_get(trainer.ema_params)),
                jax.tree_util.tree_leaves(
                jax.device_get(restored.ema_params))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_guards(self):
        x, _ = _toy_classification()
        with pytest.raises(ValueError, match="ema_decay"):
            Trainer(MLP(hidden=8, num_classes=4), ema_decay=1.5)
        t = Trainer(MLP(hidden=8, num_classes=4))
        t.build(x)
        with pytest.raises(RuntimeError, match="EMA"):
            _ = t.ema_params

    def test_ema_eval_composes_with_zero1(self):
        runtime.initialize(strategy="tpu_slice")
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=32, num_classes=4),
                          optimizer=optax.adam(1e-2), seed=0,
                          zero1=True, ema_decay=0.9)
        trainer.fit(x, y, epochs=1, batch_size=64, verbose=False)
        # The shadow keeps the PARAM layout (not the zero1 moment
        # layout), so substituting it into the params slot works.
        logs = trainer.evaluate(x, y, batch_size=64, verbose=False,
                                use_ema=True)
        assert np.isfinite(logs["loss"])
        preds = trainer.predict(x[:8], batch_size=8, use_ema=True)
        assert preds.shape == (8, 4)


class TestSampleWeight:
    """Keras `sample_weight` parity: weighted loss in fit, weighted
    means in evaluate, (x, y, w) validation_data."""

    def test_zero_weight_excludes_examples(self):
        """Examples with weight 0 must not influence training: corrupt
        half the labels, zero-weight them, and the model still learns
        the clean mapping."""
        import jax.numpy as jnp

        x, y = _toy_classification(n=256)
        y_corrupt = y.copy()
        y_corrupt[128:] = (y[128:] + 1) % 4  # wrong labels
        w = np.ones(256, np.float32)
        w[128:] = 0.0
        trainer = Trainer(MLP(hidden=32, num_classes=4,
                              compute_dtype=jnp.float32),
                          optimizer=optax.adam(1e-2))
        trainer.fit(x, y_corrupt, epochs=8, batch_size=64,
                    sample_weight=w, verbose=False)
        # Accuracy against the CLEAN labels on the corrupted half must
        # beat chance comfortably (the zero-weighted wrong labels never
        # pulled the model away), and accuracy against the CORRUPTED
        # labels there must stay near chance (they were never trained).
        clean = trainer.evaluate(x[128:], y[128:], batch_size=64,
                                 verbose=False)
        corrupt = trainer.evaluate(x[128:], y_corrupt[128:],
                                   batch_size=64, verbose=False)
        assert clean["accuracy"] > 0.6
        assert clean["accuracy"] > corrupt["accuracy"] + 0.2

    def test_evaluate_weighted_mean_exact(self):
        import jax.numpy as jnp

        x, y = _toy_classification(n=96)
        rng = np.random.default_rng(1)
        w = rng.uniform(0.1, 2.0, size=96).astype(np.float32)
        trainer = Trainer(MLP(hidden=16, num_classes=4,
                              compute_dtype=jnp.float32))
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        logs = trainer.evaluate(x, y, batch_size=32, sample_weight=w,
                                verbose=False)
        logits = trainer.predict(x, batch_size=32)
        per_ex = np.asarray(
            optax.softmax_cross_entropy_with_integer_labels(
                jnp.asarray(logits), jnp.asarray(y)))
        expected_loss = float((per_ex * w).sum() / w.sum())
        hits = (np.argmax(logits, -1) == y).astype(np.float32)
        expected_acc = float((hits * w).sum() / w.sum())
        assert logs["loss"] == pytest.approx(expected_loss, rel=1e-5)
        assert logs["accuracy"] == pytest.approx(expected_acc, rel=1e-5)

    def test_weighted_eval_exact_with_padded_tail(self):
        """Weights compose with the tail-padding mask: 33 examples at
        batch 32 still give the exact weighted mean."""
        import jax.numpy as jnp

        x, y = _toy_classification(n=33)
        w = np.linspace(0.5, 1.5, 33).astype(np.float32)
        trainer = Trainer(MLP(hidden=16, num_classes=4,
                              compute_dtype=jnp.float32))
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        logs = trainer.evaluate(x, y, batch_size=32, sample_weight=w,
                                verbose=False)
        logits = trainer.predict(x, batch_size=32)
        per_ex = np.asarray(
            optax.softmax_cross_entropy_with_integer_labels(
                jnp.asarray(logits), jnp.asarray(y)))
        assert logs["loss"] == pytest.approx(
            float((per_ex * w).sum() / w.sum()), rel=1e-5)

    def test_validation_data_triple(self):
        x, y = _toy_classification(n=128)
        w = np.ones(64, np.float32)
        trainer = Trainer(MLP(hidden=16, num_classes=4))
        history = trainer.fit(x[:64], y[:64], epochs=1, batch_size=32,
                              validation_data=(x[64:], y[64:], w),
                              verbose=False)
        assert "val_loss" in history

    def test_weights_on_dp_mesh(self):
        runtime.initialize(strategy="tpu_slice")
        x, y = _toy_classification()
        w = np.ones(256, np.float32)
        trainer = Trainer(MLP(hidden=16, num_classes=4),
                          optimizer=optax.adam(1e-2))
        history = trainer.fit(x, y, epochs=2, batch_size=64,
                              sample_weight=w, verbose=False)
        assert history["loss"][-1] < history["loss"][0]

    def test_sample_weight_needs_arrays(self):
        trainer = Trainer(MLP(hidden=8, num_classes=4))
        batches = [(np.zeros((4, 8), np.float32),
                    np.zeros(4, np.int32))]
        with pytest.raises(ValueError, match="sample_weight"):
            trainer.fit(batches, epochs=1, verbose=False,
                        sample_weight=np.ones(4, np.float32))


class TestMetricRegistry:
    def test_top5_and_regression_metrics(self):
        import jax.numpy as jnp

        from cloud_tpu.training.trainer import METRICS

        logits = jnp.asarray([[0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 9.0],
                              [9.0, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8]])
        labels = jnp.asarray([3, 1])
        top5 = np.asarray(METRICS["top5_accuracy"](logits, labels))
        # label 3 is in row 0's top-5 (indices 7,6,5,4,3); label 1 is
        # NOT in row 1's top-5 (indices 0,7,6,5,4).
        np.testing.assert_array_equal(top5, [1.0, 0.0])

        pred = jnp.asarray([[1.0, 2.0], [3.0, 5.0]])
        target = jnp.asarray([[1.0, 4.0], [3.0, 1.0]])
        np.testing.assert_allclose(
            np.asarray(METRICS["mae"](pred, target)), [1.0, 2.0])
        np.testing.assert_allclose(
            np.asarray(METRICS["mse"](pred, target)), [2.0, 8.0])


class TestSampleWeightGuards:
    def test_prebuilt_dataset_with_sample_weight_rejected(self):
        from cloud_tpu.training import ArrayDataset

        x, y = _toy_classification(n=64)
        ds = ArrayDataset(x, y, batch_size=32)
        trainer = Trainer(MLP(hidden=8, num_classes=4))
        with pytest.raises(ValueError, match="pre-built"):
            trainer.fit(ds, epochs=1, verbose=False,
                        sample_weight=np.ones(64, np.float32))
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        with pytest.raises(ValueError, match="pre-built"):
            trainer.evaluate(ds, sample_weight=np.ones(64, np.float32),
                             verbose=False)

    def test_scalar_metric_raises_under_weighted_fit(self):
        import jax.numpy as jnp

        def scalar_m(outputs, y):
            return jnp.mean(jnp.argmax(outputs, -1) == y)

        x, y = _toy_classification(n=64)
        trainer = Trainer(MLP(hidden=8, num_classes=4),
                          metrics=(scalar_m,))
        with pytest.raises(ValueError, match="scalar_m"):
            trainer.fit(x, y, epochs=1, batch_size=32, verbose=False,
                        sample_weight=np.ones(64, np.float32))

    def test_tiny_weights_stay_exact(self):
        """Batch weight sums below 1.0 must not scale the result (the
        aggregation identity weighted_mean * sum(w) == sum(v*w))."""
        import jax.numpy as jnp

        x, y = _toy_classification(n=64)
        w = np.full(64, 1.0 / 128.0, np.float32)  # batch sum = 0.25
        trainer = Trainer(MLP(hidden=16, num_classes=4,
                              compute_dtype=jnp.float32))
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        logs = trainer.evaluate(x, y, batch_size=32, sample_weight=w,
                                verbose=False)
        unweighted = trainer.evaluate(x, y, batch_size=32,
                                      verbose=False)
        # Uniform weights, however tiny, must equal the unweighted mean.
        assert logs["loss"] == pytest.approx(unweighted["loss"],
                                             rel=1e-4)


class TestWeightedEpochAggregation:
    def test_epoch_metrics_weight_exact_across_batches(self):
        """Per-batch weighted means re-weight by batch weight sums: a
        heavy batch dominates the epoch metric, a near-zero-weight
        batch barely moves it (a plain mean of ratios would say 0.5)."""
        import jax.numpy as jnp

        x, y = _toy_classification(n=64)
        w = np.ones(64, np.float32)
        w[32:] = 1e-3  # second batch nearly weightless
        trainer = Trainer(MLP(hidden=16, num_classes=4,
                              compute_dtype=jnp.float32),
                          optimizer=optax.sgd(0.0))  # frozen params
        history = trainer.fit(x, y, epochs=1, batch_size=32,
                              shuffle=False, sample_weight=w,
                              verbose=False)
        logs = trainer.evaluate(x, y, batch_size=32, sample_weight=w,
                                verbose=False)
        # Frozen params: the epoch train accuracy must equal evaluate's
        # exact weighted mean over the same data/weights.
        assert history["accuracy"][0] == pytest.approx(
            logs["accuracy"], rel=1e-4)


class TestClassWeight:
    def test_class_weight_matches_equivalent_sample_weight(self):
        import jax.numpy as jnp

        x, y = _toy_classification(n=128)
        cw = {0: 2.0, 2: 0.5}
        sw = np.ones(128, np.float32)
        sw[y == 0] = 2.0
        sw[y == 2] = 0.5
        a = Trainer(MLP(hidden=16, num_classes=4,
                        compute_dtype=jnp.float32),
                    optimizer=optax.adam(1e-2), seed=0)
        b = Trainer(MLP(hidden=16, num_classes=4,
                        compute_dtype=jnp.float32),
                    optimizer=optax.adam(1e-2), seed=0)
        ha = a.fit(x, y, epochs=2, batch_size=32, shuffle=False,
                   class_weight=cw, verbose=False)
        hb = b.fit(x, y, epochs=2, batch_size=32, shuffle=False,
                   sample_weight=sw, verbose=False)
        np.testing.assert_allclose(ha["loss"], hb["loss"], rtol=1e-6)

    def test_class_weight_composes_with_sample_weight(self):
        x, y = _toy_classification(n=64)
        sw = np.full(64, 0.5, np.float32)
        trainer = Trainer(MLP(hidden=8, num_classes=4))
        history = trainer.fit(x, y, epochs=1, batch_size=32,
                              class_weight={1: 3.0}, sample_weight=sw,
                              verbose=False)
        assert np.isfinite(history["loss"][0])

    def test_class_weight_needs_labels(self):
        x, _ = _toy_classification(n=32)
        trainer = Trainer(MLP(hidden=8, num_classes=4))
        with pytest.raises(ValueError, match="class_weight"):
            trainer.fit(x, None, epochs=1, verbose=False,
                        class_weight={0: 2.0})


class TestWeightedFitReviewRegressions:
    def test_alternating_weighted_unweighted_fits(self):
        """Both train-step variants cache; a weighted fit after an
        unweighted one (and back) works and the scalar guard doesn't
        leak across variants."""
        x, y = _toy_classification(n=64)
        w = np.ones(64, np.float32)
        trainer = Trainer(MLP(hidden=8, num_classes=4),
                          optimizer=optax.adam(1e-2))
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        trainer.fit(x, y, epochs=1, batch_size=32, sample_weight=w,
                    verbose=False)
        h = trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        assert np.isfinite(h["loss"][0])
        assert set(trainer._train_step_cache) == {False, True}

    def test_top5_clamps_to_class_count(self):
        import jax.numpy as jnp

        from cloud_tpu.training.trainer import METRICS

        logits = jnp.asarray([[0.1, 0.9], [0.9, 0.1]])
        labels = jnp.asarray([0, 1])
        # 2 classes < 5: every example is a top-k hit by definition.
        np.testing.assert_array_equal(
            np.asarray(METRICS["top5_accuracy"](logits, labels)),
            [1.0, 1.0])

    def test_zero_total_weight_message(self):
        x, y = _toy_classification(n=32)
        trainer = Trainer(MLP(hidden=8, num_classes=4))
        trainer.fit(x, y, epochs=1, batch_size=32, verbose=False)
        with pytest.raises(ValueError, match="sample_weight is zero"):
            trainer.evaluate(x, y, batch_size=32, verbose=False,
                             sample_weight=np.zeros(32, np.float32))

    def test_cloud_fit_ships_validation_weights(self, tmp_path):
        from cloud_tpu.cloud_fit import client, remote

        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 8)).astype(np.float32)
        y = rng.integers(0, 4, size=64).astype(np.int32)
        vw = np.ones(32, np.float32)
        trainer = Trainer(MLP(hidden=8, num_classes=4),
                          optimizer="adam",
                          loss="sparse_categorical_crossentropy",
                          metrics=("accuracy",))
        client.serialize_assets(
            str(tmp_path), trainer, x, y,
            validation_data=(x[:32], y[:32], vw), epochs=1,
            batch_size=32)
        history = remote.run(str(tmp_path), "one_device")
        assert "val_loss" in history

    def test_class_weight_accepts_list_labels(self):
        x, _ = _toy_classification(n=32)
        y_list = [int(v) for v in np.random.default_rng(0).integers(
            0, 4, size=32)]
        trainer = Trainer(MLP(hidden=8, num_classes=4))
        history = trainer.fit(x, y_list, epochs=1, batch_size=32,
                              class_weight={0: 2.0}, verbose=False)
        assert np.isfinite(history["loss"][0])


class TestStepsPerExecution:
    """Keras steps_per_execution: N optimizer steps per XLA dispatch
    via lax.scan over stacked batches."""

    def test_matches_single_step_exactly(self):
        import jax.numpy as jnp

        x, y = _toy_classification(n=192)
        a = Trainer(MLP(hidden=16, num_classes=4,
                        compute_dtype=jnp.float32),
                    optimizer=optax.adam(1e-2), seed=0,
                    steps_per_execution=3)
        b = Trainer(MLP(hidden=16, num_classes=4,
                        compute_dtype=jnp.float32),
                    optimizer=optax.adam(1e-2), seed=0)
        ha = a.fit(x, y, epochs=3, batch_size=32, shuffle=False,
                   verbose=False)
        hb = b.fit(x, y, epochs=3, batch_size=32, shuffle=False,
                   verbose=False)
        np.testing.assert_allclose(ha["loss"], hb["loss"], rtol=1e-5)
        assert int(a.state.step) == int(b.state.step) == 18

    def test_leftover_batches_run_singly(self):
        # 5 batches/epoch with spe=2: two groups + one single.
        x, y = _toy_classification(n=160)
        trainer = Trainer(MLP(hidden=8, num_classes=4),
                          optimizer=optax.adam(1e-2),
                          steps_per_execution=2)
        trainer.fit(x, y, epochs=1, batch_size=32, shuffle=False,
                    verbose=False)
        assert int(trainer.state.step) == 5

    def test_on_dp_mesh(self):
        runtime.initialize(strategy="tpu_slice")
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=16, num_classes=4),
                          optimizer=optax.adam(1e-2),
                          steps_per_execution=2)
        history = trainer.fit(x, y, epochs=2, batch_size=64,
                              verbose=False)
        assert history["loss"][-1] < history["loss"][0]

    def test_with_sample_weight(self):
        import jax.numpy as jnp

        x, y = _toy_classification(n=128)
        w = np.linspace(0.5, 1.5, 128).astype(np.float32)
        a = Trainer(MLP(hidden=16, num_classes=4,
                        compute_dtype=jnp.float32),
                    optimizer=optax.adam(1e-2), seed=0,
                    steps_per_execution=2)
        b = Trainer(MLP(hidden=16, num_classes=4,
                        compute_dtype=jnp.float32),
                    optimizer=optax.adam(1e-2), seed=0)
        ha = a.fit(x, y, epochs=2, batch_size=32, shuffle=False,
                   sample_weight=w, verbose=False)
        hb = b.fit(x, y, epochs=2, batch_size=32, shuffle=False,
                   sample_weight=w, verbose=False)
        np.testing.assert_allclose(ha["loss"], hb["loss"], rtol=1e-5)
        np.testing.assert_allclose(ha["accuracy"], hb["accuracy"],
                                   rtol=1e-5)

    def test_invalid_value_rejected(self):
        with pytest.raises(ValueError, match="steps_per_execution"):
            Trainer(MLP(hidden=8, num_classes=4),
                    steps_per_execution=0)

    def test_weighted_spe_with_leftover_exact(self):
        """Group + leftover single under sample_weight: frozen params
        make the epoch metric comparable to evaluate's exact weighted
        mean (group weights must not double-count)."""
        import jax.numpy as jnp

        x, y = _toy_classification(n=96)  # 3 batches: 1 group + 1 single
        w = np.linspace(0.2, 2.0, 96).astype(np.float32)
        trainer = Trainer(MLP(hidden=16, num_classes=4,
                              compute_dtype=jnp.float32),
                          optimizer=optax.sgd(0.0),  # frozen
                          steps_per_execution=2, seed=0)
        history = trainer.fit(x, y, epochs=1, batch_size=32,
                              shuffle=False, sample_weight=w,
                              verbose=False)
        logs = trainer.evaluate(x, y, batch_size=32, sample_weight=w,
                                verbose=False)
        assert history["accuracy"][0] == pytest.approx(
            logs["accuracy"], rel=1e-4)
        # The epoch LOSS is a per-step mean: the spe=2 group entry must
        # count as two steps against the leftover single batch, so the
        # grouped run must match an identical spe=1 run exactly (same
        # frozen params, same batches).
        single = Trainer(MLP(hidden=16, num_classes=4,
                             compute_dtype=jnp.float32),
                         optimizer=optax.sgd(0.0), seed=0)
        h1 = single.fit(x, y, epochs=1, batch_size=32, shuffle=False,
                        sample_weight=w, verbose=False)
        assert history["loss"][0] == pytest.approx(h1["loss"][0],
                                                   rel=1e-5)

    def test_ragged_tail_inside_group_runs_singly(self):
        """A custom iterable yielding batches 32,32,32,16 with spe=2:
        the ragged 16-row batch can't stack into a group — it (and any
        group-in-progress) must run through the single-step path
        instead of crashing np.stack."""
        x, y = _toy_classification(n=112)
        batches = [(x[i:i + 32], y[i:i + 32]) for i in (0, 32, 64)]
        batches.append((x[96:], y[96:]))  # ragged 16-row tail
        trainer = Trainer(MLP(hidden=8, num_classes=4),
                          optimizer=optax.adam(1e-2),
                          steps_per_execution=2)
        trainer.fit(batches, epochs=1, verbose=False)
        assert int(trainer.state.step) == 4

    def test_scalar_metric_raises_under_weighted_spe(self):
        import jax.numpy as jnp

        def scalar_m(outputs, y):
            return jnp.mean(jnp.argmax(outputs, -1) == y)

        x, y = _toy_classification(n=128)
        trainer = Trainer(MLP(hidden=8, num_classes=4),
                          metrics=(scalar_m,), steps_per_execution=2)
        with pytest.raises(ValueError, match="scalar_m"):
            trainer.fit(x, y, epochs=1, batch_size=32, verbose=False,
                        sample_weight=np.ones(128, np.float32))


class TestEarlyStoppingRestore:
    def test_restore_best_weights(self):
        """Params revert to the best-epoch snapshot when a later epoch
        is worse (deterministically forced via a metric schedule)."""
        import jax
        import jax.numpy as jnp

        from cloud_tpu.training import EarlyStopping

        x, y = _toy_classification(n=64)
        trainer = Trainer(MLP(hidden=8, num_classes=4,
                              compute_dtype=jnp.float32),
                          optimizer=optax.adam(5e-2))
        from cloud_tpu.training import Callback

        es = EarlyStopping(monitor="fake", patience=0,
                           restore_best_weights=True)
        schedule = iter([1.0, 5.0, 5.0])  # best at epoch 0, then worse

        class FakeMetric(Callback):
            def on_epoch_end(self, epoch, logs):
                logs["fake"] = next(schedule)

        fake = FakeMetric()
        snapshots = {}

        class Snap(Callback):
            def on_epoch_end(self, epoch, logs):
                snapshots[epoch] = jax.tree_util.tree_map(
                    lambda p: np.asarray(p),
                    self.trainer.state.params)

        # Order: snapshot -> fake metric -> early stopping.
        trainer.fit(x, y, epochs=3, batch_size=32, verbose=False,
                    callbacks=[Snap(), fake, es])
        # Stopped after epoch 1 (patience 0, epoch1 worse than epoch0)
        # and restored epoch-0 params.
        final = jax.tree_util.tree_map(lambda p: np.asarray(p),
                                       trainer.state.params)
        flat_final = jax.tree_util.tree_leaves(final)
        flat_best = jax.tree_util.tree_leaves(snapshots[0])
        flat_last = jax.tree_util.tree_leaves(snapshots[max(snapshots)])
        for a, b in zip(flat_final, flat_best):
            np.testing.assert_array_equal(a, b)
        # And they differ from the last epoch's (training moved them).
        assert any(not np.array_equal(a, b)
                   for a, b in zip(flat_final, flat_last))

    def test_no_restore_keeps_last_weights(self):
        import jax

        from cloud_tpu.training import Callback, EarlyStopping

        x, y = _toy_classification(n=64)
        trainer = Trainer(MLP(hidden=8, num_classes=4),
                          optimizer=optax.adam(5e-2))
        es = EarlyStopping(monitor="loss", patience=0)
        last = {}

        class Snap(Callback):
            def on_epoch_end(self, epoch, logs):
                last["params"] = jax.tree_util.tree_map(
                    lambda p: np.asarray(p),
                    self.trainer.state.params)

        trainer.fit(x, y, epochs=2, batch_size=32, verbose=False,
                    callbacks=[Snap(), es])
        assert es._best_state is None
        # Without restore_best_weights the final state IS the last
        # epoch's state, untouched by on_train_end.
        for a, b in zip(
                jax.tree_util.tree_leaves(last["params"]),
                jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                    lambda p: np.asarray(p), trainer.state.params))):
            np.testing.assert_array_equal(a, b)

    def test_restores_batch_stats_with_weights(self):
        """BatchNorm statistics (extra_vars) revert with the weights —
        best-epoch params against last-epoch BN stats would be tensors
        from two different models."""
        import jax

        from cloud_tpu.models import ResNet
        from cloud_tpu.models.resnet import BasicBlock
        from cloud_tpu.training import Callback, EarlyStopping

        rng = np.random.default_rng(0)
        x = rng.normal(size=(32, 16, 16, 3)).astype(np.float32)
        y = rng.integers(0, 4, size=32).astype(np.int32)
        import jax.numpy as jnp
        trainer = Trainer(ResNet(stage_sizes=(1,), block=BasicBlock,
                                 num_filters=8, num_classes=4,
                                 compute_dtype=jnp.float32),
                          optimizer=optax.sgd(1e-1),
                          train_kwargs={"train": True},
                          eval_kwargs={"train": False}, metrics=())
        es = EarlyStopping(monitor="fake", patience=0,
                           restore_best_weights=True)
        schedule = iter([1.0, 5.0, 5.0])
        stats = {}

        class Fake(Callback):
            def on_epoch_end(self, epoch, logs):
                stats[epoch] = jax.tree_util.tree_map(
                    lambda p: np.asarray(p),
                    self.trainer.state.extra_vars)
                logs["fake"] = next(schedule)

        trainer.fit(x, y, epochs=3, batch_size=16, verbose=False,
                    callbacks=[Fake(), es])
        final = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda p: np.asarray(p), trainer.state.extra_vars))
        best = jax.tree_util.tree_leaves(stats[0])
        last = jax.tree_util.tree_leaves(stats[max(stats)])
        for a, b in zip(final, best):
            np.testing.assert_array_equal(a, b)
        assert any(not np.array_equal(a, b)
                   for a, b in zip(final, last))


class TestSummary:
    def test_summary_counts_params(self):
        x, y = _toy_classification(n=64)
        trainer = Trainer(MLP(hidden=8, num_classes=4))
        with pytest.raises(RuntimeError, match="not built"):
            trainer.summary()
        trainer.build(x)
        out = []
        text = trainer.summary(print_fn=out.append)
        assert out and out[0] == text
        # MLP(hidden=8, num_classes=4) on 8-dim input:
        # Dense_0: 8*8+8 = 72; Dense_1: 8*4+4 = 36 -> 108 total.
        assert "Total params" in text
        assert "108" in text

    def test_summary_reports_extra_vars(self):
        from cloud_tpu.models import ResNet
        from cloud_tpu.models.resnet import BasicBlock

        import jax.numpy as jnp
        rng = np.random.default_rng(0)
        x = rng.normal(size=(2, 16, 16, 3)).astype(np.float32)
        trainer = Trainer(ResNet(stage_sizes=(1,), block=BasicBlock,
                                 num_filters=8, num_classes=4,
                                 compute_dtype=jnp.float32),
                          train_kwargs={"train": True},
                          eval_kwargs={"train": False}, metrics=())
        trainer.build(x)
        text = trainer.summary(print_fn=lambda t: None)
        assert "Extra vars" in text


class TestRequestStop:
    def test_stops_at_step_boundary_mid_epoch(self):
        """request_stop() from another thread (the signal-handler
        calling convention) breaks the epoch at the next step, the
        partial epoch still reaches on_epoch_end, and fit returns."""
        import threading

        from cloud_tpu.training import LambdaCallback

        x, y = _toy_classification(n=4096)
        trainer = Trainer(MLP(hidden=16, num_classes=4),
                          optimizer=optax.sgd(0.1))
        epoch_ends = []

        # Fire from a LambdaCallback at first epoch begin via a timer
        # thread, so the stop lands while the step loop is running.
        def arm(epoch):
            if epoch == 0:
                threading.Timer(0.3, trainer.request_stop).start()

        history = trainer.fit(
            x, y, epochs=50, batch_size=32, verbose=False,
            callbacks=(LambdaCallback(
                on_epoch_begin=arm,
                on_epoch_end=lambda e, logs: epoch_ends.append(e)),))
        total_steps = int(trainer.state.step)
        # Stopped long before the 50-epoch budget (128 steps/epoch).
        assert total_steps < 50 * 128
        assert len(history["loss"]) == len(epoch_ends)
        assert epoch_ends, "epoch-end callbacks must still fire"

    def test_request_stop_before_fit_is_reset(self):
        x, y = _toy_classification(n=64)
        trainer = Trainer(MLP(hidden=8, num_classes=4))
        trainer.request_stop()  # stale flag from a previous life
        history = trainer.fit(x, y, epochs=2, batch_size=32,
                              verbose=False)
        assert len(history["loss"]) == 2  # fit() resets the flags


class TestValidationSplit:
    def test_matches_manual_split(self):
        """validation_split holds out the LAST fraction (pre-shuffle),
        matching an explicit validation_data split exactly."""
        import jax.numpy as jnp

        x, y = _toy_classification(n=128)
        a = Trainer(MLP(hidden=16, num_classes=4,
                        compute_dtype=jnp.float32),
                    optimizer=optax.sgd(0.0), seed=0)  # frozen
        b = Trainer(MLP(hidden=16, num_classes=4,
                        compute_dtype=jnp.float32),
                    optimizer=optax.sgd(0.0), seed=0)
        ha = a.fit(x, y, epochs=1, batch_size=32, shuffle=False,
                   validation_split=0.25, verbose=False)
        hb = b.fit(x[:96], y[:96], epochs=1, batch_size=32,
                   shuffle=False, validation_data=(x[96:], y[96:]),
                   verbose=False)
        assert ha["loss"][0] == pytest.approx(hb["loss"][0], rel=1e-6)
        assert ha["val_loss"][0] == pytest.approx(hb["val_loss"][0],
                                                  rel=1e-6)

    def test_split_carries_sample_weights(self):
        x, y = _toy_classification(n=96)
        w = np.linspace(0.2, 2.0, 96).astype(np.float32)
        trainer = Trainer(MLP(hidden=8, num_classes=4),
                          optimizer=optax.sgd(0.1))
        h = trainer.fit(x, y, epochs=1, batch_size=32, shuffle=False,
                        sample_weight=w, validation_split=1 / 3,
                        verbose=False)
        assert "val_loss" in h
        assert int(trainer.state.step) == 2  # 64 train rows / 32

    def test_rejections(self):
        x, y = _toy_classification(n=64)
        t = Trainer(MLP(hidden=8, num_classes=4))
        with pytest.raises(ValueError, match="not both"):
            t.fit(x, y, epochs=1, validation_split=0.5,
                  validation_data=(x, y), verbose=False)
        with pytest.raises(ValueError, match="in \\(0, 1\\)"):
            t.fit(x, y, epochs=1, validation_split=1.5, verbose=False)
        with pytest.raises(ValueError, match="array inputs"):
            t.fit([(x[:32], y[:32])], epochs=1, validation_split=0.5,
                  verbose=False)
        with pytest.raises(ValueError, match="empty"):
            t.fit(x[:3], y[:3], epochs=1, validation_split=0.9,
                  verbose=False)


class TestInitialEpoch:
    def test_resumes_epoch_numbering(self):
        x, y = _toy_classification(n=64)
        trainer = Trainer(MLP(hidden=8, num_classes=4),
                          optimizer=optax.adam(1e-2))
        seen = []
        from cloud_tpu.training import LambdaCallback
        trainer.fit(x, y, epochs=5, initial_epoch=3, batch_size=32,
                    verbose=False,
                    callbacks=(LambdaCallback(
                        on_epoch_begin=seen.append),))
        assert seen == [3, 4]
        assert int(trainer.state.step) == 4  # 2 epochs x 2 steps


class TestInitialEpochGuards:
    def test_scalar_weighted_guard_fires_on_resumed_fit(self):
        """The loud scalar-metric-with-weights failure must fire on the
        FIRST epoch of a resumed fit (initial_epoch > 0), not only on
        epoch index 0 (review r4 regression)."""
        import jax.numpy as jnp

        def scalar_m(outputs, y):
            return jnp.mean(jnp.argmax(outputs, -1) == y)

        x, y = _toy_classification(n=64)
        trainer = Trainer(MLP(hidden=8, num_classes=4),
                          metrics=(scalar_m,))
        with pytest.raises(ValueError, match="scalar_m"):
            trainer.fit(x, y, epochs=5, initial_epoch=3, batch_size=32,
                        verbose=False,
                        sample_weight=np.ones(64, np.float32))

    def test_profiler_fallback_uses_start_epoch(self, tmp_path):
        """ProfilerCallback's will-it-run check accounts for
        initial_epoch: requested epoch 1 never runs in a fit over
        epochs [3, 5), so the fallback must target epoch 3 (which
        runs), not epoch 0 (which doesn't)."""
        from cloud_tpu.monitoring.profiler import ProfilerCallback

        x, y = _toy_classification(n=64)
        trainer = Trainer(MLP(hidden=8, num_classes=4),
                          optimizer=optax.adam(1e-2))
        cb = ProfilerCallback(str(tmp_path), epochs=(1,))
        trainer.fit(x, y, epochs=5, initial_epoch=3, batch_size=32,
                    verbose=False, callbacks=(cb,))
        assert cb._run_epochs == {3}
        # A trace directory was actually produced for the traced epoch.
        import os as os_lib
        assert any(os_lib.scandir(str(tmp_path)))


class TestTrainableFreeze:
    """Trainer(trainable=...): regex-selected params update, the rest
    stay frozen, and frozen params allocate no optimizer moments."""

    def test_frozen_params_unchanged_trainable_learn(self):
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=32, num_classes=4),
                          optimizer=optax.adam(1e-2),
                          trainable=r"Dense_1")
        trainer.build(x[:4])
        before = jax.tree_util.tree_map(np.asarray,
                                        trainer.state.params)
        history = trainer.fit(x, y, epochs=3, batch_size=64,
                              verbose=False)
        after = trainer.state.params
        np.testing.assert_array_equal(
            before["Dense_0"]["kernel"],
            np.asarray(after["Dense_0"]["kernel"]))
        np.testing.assert_array_equal(
            before["Dense_0"]["bias"],
            np.asarray(after["Dense_0"]["bias"]))
        assert not np.allclose(before["Dense_1"]["kernel"],
                               np.asarray(after["Dense_1"]["kernel"]))
        # The head alone can still fit the linear toy problem.
        assert history["loss"][-1] < history["loss"][0]

    def test_frozen_params_allocate_no_moments(self):
        """optax.multi_transform masking: Adam moments exist only for
        the trainable subset."""
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=32, num_classes=4),
                          optimizer=optax.adam(1e-2),
                          trainable=r"Dense_1")
        trainer.build(x[:4])
        moment_paths = {
            sharding_lib.path_string(path)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                trainer.state.opt_state)[0]}
        assert any("Dense_1" in p for p in moment_paths)
        assert not any("Dense_0" in p for p in moment_paths)

    def test_callable_predicate(self):
        x, y = _toy_classification()
        trainer = Trainer(
            MLP(hidden=32, num_classes=4), optimizer=optax.adam(1e-2),
            trainable=lambda path: path.endswith("bias"))
        trainer.build(x[:4])
        before = jax.tree_util.tree_map(np.asarray,
                                        trainer.state.params)
        trainer.fit(x, y, epochs=1, batch_size=64, verbose=False)
        after = trainer.state.params
        np.testing.assert_array_equal(
            before["Dense_0"]["kernel"],
            np.asarray(after["Dense_0"]["kernel"]))
        assert not np.allclose(before["Dense_1"]["bias"],
                               np.asarray(after["Dense_1"]["bias"]))

    def test_composes_with_zero1_moment_sharding(self):
        """Masked moments (MaskedNode at frozen leaves) must still get
        the ZeRO-1 dp layout — not fall into the replicated fallback."""
        runtime.initialize(strategy="tpu_slice")  # 8-device dp mesh
        try:
            x, y = _toy_classification()
            trainer = Trainer(MLP(hidden=32, num_classes=4),
                              optimizer=optax.adam(1e-2), seed=0,
                              zero1=True, trainable=r"Dense_0")
            history = trainer.fit(x, y, epochs=1, batch_size=64,
                                  verbose=False)
            assert np.isfinite(history["loss"][-1])
            moments = {
                sharding_lib.path_string(path): leaf
                for path, leaf in jax.tree_util.tree_flatten_with_path(
                    trainer.state.opt_state)[0]
                if hasattr(leaf, "sharding")}
            mu = [v for k, v in moments.items()
                  if "Dense_0" in k and "kernel" in k and "/mu/" in k]
            assert mu, sorted(moments)
            # [8, 32] kernel moment: dim 0 divides the 8-wide dp axis.
            assert "dp" in tuple(mu[0].sharding.spec), mu[0].sharding
            assert not any("Dense_1" in k for k in moments)
        finally:
            runtime.reset()


class TestBuildFromVariables:
    """build(variables=): the fine-tuning entry point — start from
    imported/pretrained weights instead of random init."""

    def test_provided_params_are_used(self):
        x, y = _toy_classification()
        ref = Trainer(MLP(hidden=16, num_classes=4), seed=0)
        ref.build(x[:4])
        pretrained = jax.tree_util.tree_map(
            lambda a: np.asarray(a) + 1.0, ref.state.params)

        trainer = Trainer(MLP(hidden=16, num_classes=4), seed=1)
        trainer.build(x[:4], variables={"params": pretrained})
        for path_got, path_want in zip(
                jax.tree_util.tree_leaves(trainer.state.params),
                jax.tree_util.tree_leaves(pretrained)):
            np.testing.assert_array_equal(np.asarray(path_got),
                                          path_want)
        history = trainer.fit(x, y, epochs=1, batch_size=64,
                              verbose=False)
        assert np.isfinite(history["loss"][-1])

    def test_shape_mismatch_is_loud(self):
        x, _ = _toy_classification()
        donor = Trainer(MLP(hidden=32, num_classes=4), seed=0)
        donor.build(x[:4])
        trainer = Trainer(MLP(hidden=16, num_classes=4), seed=1)
        with pytest.raises(ValueError, match="structure/shapes"):
            trainer.build(x[:4],
                          variables={"params": donor.state.params})

    def test_missing_params_collection_is_loud(self):
        x, _ = _toy_classification()
        trainer = Trainer(MLP(hidden=16, num_classes=4))
        with pytest.raises(ValueError, match="params"):
            trainer.build(x[:4], variables={"batch_stats": {}})

    def test_partial_collections_keep_fresh_extras(self):
        """Providing only params keeps freshly initialized batch_stats
        (ResNet): the per-collection override contract."""
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 32, 32, 3)).astype(np.float32)
        y = rng.integers(0, 4, size=8).astype(np.int32)
        ref = Trainer(ResNet18(num_classes=4), seed=0,
                      train_kwargs={"train": True},
                      eval_kwargs={"train": False})
        ref.build(x[:2])
        pretrained = jax.tree_util.tree_map(np.asarray,
                                            ref.state.params)
        trainer = Trainer(ResNet18(num_classes=4), seed=1,
                          train_kwargs={"train": True},
                          eval_kwargs={"train": False})
        trainer.build(x[:2], variables={"params": pretrained})
        assert "batch_stats" in trainer.state.extra_vars
        history = trainer.fit(x, y, epochs=1, batch_size=4,
                              verbose=False)
        assert np.isfinite(history["loss"][-1])

    def test_variables_on_built_trainer_is_loud(self):
        """Loading weights after a lazy build must raise, not silently
        keep the random init."""
        x, y = _toy_classification()
        trainer = Trainer(MLP(hidden=16, num_classes=4))
        trainer.fit(x, y, epochs=1, batch_size=64, verbose=False)
        with pytest.raises(RuntimeError, match="already-built"):
            trainer.build(x[:4],
                          variables={"params": trainer.state.params})
