"""cloud_fit tests: serialize -> re-hydrate -> fit round trips.

Mirrors reference cloud_fit unit tests: asset round-trip through real
files in tmp dirs (client_test.py:144-217), job-spec/submit verification
with a mocked API (110-142), and the in-process remote-run
"fake-cluster" test asserting outputs + callbacks fire
(remote_test.py:80-127) — here on the 8-device CPU mesh.
"""

import json
import os
import pickle
from unittest import mock

import numpy as np
import pytest

from cloud_tpu.cloud_fit import client, remote
from cloud_tpu.models import MLP
from cloud_tpu.parallel import runtime
from cloud_tpu.training import LambdaCallback, Trainer
from cloud_tpu.utils import storage


@pytest.fixture(autouse=True)
def _reset_runtime():
    runtime.reset()
    yield
    runtime.reset()


def _toy_data(n=128, d=8, classes=4):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d, classes))
    y = np.argmax(x @ w, axis=-1).astype(np.int32)
    return x, y


def _trainer():
    return Trainer(MLP(hidden=16, num_classes=4), optimizer="adam",
                   loss="sparse_categorical_crossentropy",
                   metrics=("accuracy",))


class EpochRecorder(LambdaCallback):
    """Picklable callback (lambdas can't cross the wire — same constraint
    as the reference's pickled Keras callbacks, client.py:73-75)."""

    def __init__(self, path):
        super().__init__()
        self.path = path

    def on_epoch_end(self, epoch, logs):
        with open(self.path, "a") as f:
            f.write("%d\n" % epoch)


class TestSerialization:

    def test_assets_round_trip(self, tmp_path):
        x, y = _toy_data()
        remote_dir = str(tmp_path / "assets")
        client.serialize_assets(remote_dir, _trainer(), x, y,
                                epochs=2, batch_size=32)

        spec = pickle.loads(
            storage.read_bytes(storage.join(remote_dir, client.SPEC_FILE)))
        assert spec["optimizer"] == {"kind": "name", "value": "adam"}
        assert spec["loss"] == {"kind": "name",
                                "value": "sparse_categorical_crossentropy"}
        assert isinstance(spec["model"], MLP)

        fit_kwargs = pickle.loads(storage.read_bytes(
            storage.join(remote_dir, client.FIT_KWARGS_FILE)))
        assert fit_kwargs == {"epochs": 2, "batch_size": 32}

    def test_unpicklable_optimizer_rejected(self, tmp_path):
        import optax

        x, y = _toy_data()
        trainer = Trainer(MLP(hidden=16, num_classes=4),
                          optimizer=optax.adam(1e-3))
        with pytest.raises(ValueError, match="cannot be shipped"):
            client.serialize_assets(str(tmp_path), trainer, x, y)

    def test_module_level_loss_ships_as_path(self, tmp_path):
        from cloud_tpu.training import trainer as trainer_lib

        x, y = _toy_data()
        trainer = Trainer(MLP(hidden=16, num_classes=4),
                          loss=trainer_lib._mse, metrics=())
        client.serialize_assets(str(tmp_path), trainer, x, y)
        spec = pickle.loads(storage.read_bytes(
            storage.join(str(tmp_path), client.SPEC_FILE)))
        assert spec["loss"]["kind"] == "path"
        assert client.resolve_dotted(spec["loss"]["value"]) \
            is trainer_lib._mse


class TestCloudFitSubmit:

    def test_submit_payload(self, tmp_path, monkeypatch):
        monkeypatch.setenv("GOOGLE_CLOUD_PROJECT", "my-project")
        x, y = _toy_data()
        api = mock.MagicMock()
        job_id = client.cloud_fit(
            _trainer(), str(tmp_path), image_uri="gcr.io/p/img:tag",
            x=x, y=y, epochs=1, api_client=api)
        assert job_id.startswith("cloud_fit_")
        body = (api.projects.return_value.jobs.return_value
                .create.call_args.kwargs["body"])
        assert body["jobId"] == job_id
        ti = body["trainingInput"]
        assert ti["masterType"] == "tpu-vm"
        assert ti["masterConfig"]["acceleratorConfig"]["type"] == \
            "v5litepod-8"
        assert ti["args"] == ["--remote_dir", str(tmp_path),
                              "--distribution_strategy", "tpu_slice"]

    def test_invalid_strategy_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="not supported"):
            client.cloud_fit(_trainer(), str(tmp_path),
                             distribution_strategy="parameter_server",
                             x=np.zeros((4, 2), np.float32))


class TestRemoteRun:

    def test_end_to_end_fit_on_mesh(self, tmp_path):
        """Fake-cluster analogue: serialize, then run the remote worker
        in-process on the 8-device CPU mesh."""
        x, y = _toy_data()
        remote_dir = str(tmp_path / "job")
        fired_log = str(tmp_path / "fired.txt")
        client.serialize_assets(
            remote_dir, _trainer(), x, y,
            validation_data=(x[:32], y[:32]),
            epochs=2, batch_size=32,
            callbacks=[EpochRecorder(fired_log)])

        history = remote.run(remote_dir, "tpu_slice")

        assert len(history["loss"]) == 2
        assert "val_loss" in history
        # Pickled callbacks fire remotely.
        assert open(fired_log).read().split() == ["0", "1"]
        # Outputs: final state checkpoint + chief-written history.
        from cloud_tpu.training import checkpoint as checkpoint_lib
        out = storage.join(remote_dir, remote.OUTPUT_DIR)
        assert checkpoint_lib.latest_step(out) == 8  # 2 epochs x 4 steps
        saved_history = json.loads(storage.read_bytes(
            storage.join(out, remote.HISTORY_FILE)))
        assert saved_history["loss"] == history["loss"]

    def test_state_round_trips_through_output_dir(self, tmp_path):
        """The saved checkpoint must restore into a fresh trainer —
        the remote worker's product is the trained state, not just
        history.json."""
        import jax

        from cloud_tpu.training import checkpoint as checkpoint_lib

        x, y = _toy_data(n=64)
        remote_dir = str(tmp_path / "job")
        client.serialize_assets(remote_dir, _trainer(), x, y, epochs=1,
                                batch_size=32)
        remote.run(remote_dir, "tpu_slice")

        fresh = _trainer()
        fresh.build(x)
        restored = checkpoint_lib.restore(
            storage.join(remote_dir, remote.OUTPUT_DIR), fresh.state)
        assert int(restored.step) == 2  # 1 epoch x 2 steps
        for leaf in jax.tree_util.tree_leaves(restored.params):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_gcs_output_dir_still_saves_state(self, monkeypatch):
        """Regression: the production path (remote worker writing to a
        bucket) must save the model state, not only history.json.
        Reference always saves (remote.py:130-145); orbax/tensorstore
        handles gs:// natively, so there is no reason to skip."""
        import jax

        from cloud_tpu.training import checkpoint as checkpoint_lib

        saved = {}
        monkeypatch.setattr(
            checkpoint_lib, "save",
            lambda directory, state, step=0, **kw: saved.update(
                {"dir": directory, "step": step}))
        written = {}
        monkeypatch.setattr(
            storage, "write_bytes",
            lambda path, data: written.update({"path": path}))

        state = mock.MagicMock()
        state.step = 7
        trainer = mock.MagicMock()
        trainer.state = state
        remote._save_outputs("gs://bucket/job", trainer, {"loss": [1.0]})

        assert saved["dir"] == "gs://bucket/job/output"
        assert saved["step"] == 7
        if jax.process_index() == 0:
            assert written["path"] == "gs://bucket/job/output/history.json"

    def test_main_flags(self, tmp_path):
        x, y = _toy_data(n=32)
        remote_dir = str(tmp_path / "job")
        client.serialize_assets(remote_dir, _trainer(), x, y, epochs=1,
                                batch_size=16)
        remote.main(["--remote_dir", remote_dir,
                     "--distribution_strategy", "one_device"])
        assert storage.exists(
            storage.join(remote_dir, remote.OUTPUT_DIR,
                         remote.HISTORY_FILE))


class TestStorage:

    def test_local_paths(self, tmp_path):
        path = str(tmp_path / "a" / "b.bin")
        storage.write_bytes(path, b"hello")
        assert storage.read_bytes(path) == b"hello"
        assert storage.exists(path)
        assert not storage.exists(str(tmp_path / "missing"))

    def test_join(self):
        assert storage.join("gs://bucket/dir", "x", "y") == \
            "gs://bucket/dir/x/y"

    def test_gcs_requires_sdk(self, monkeypatch):
        monkeypatch.setattr(storage, "gcs", None)
        with pytest.raises(RuntimeError, match="google-cloud-storage"):
            storage.read_bytes("gs://bucket/blob")

    def test_append_bytes_local(self, tmp_path):
        path = str(tmp_path / "log.jsonl")
        storage.append_bytes(path, b"a\n")
        storage.append_bytes(path, b"b\n")
        assert storage.read_bytes(path) == b"a\nb\n"

    def test_append_bytes_gcs_composes(self, monkeypatch):
        """GCS appends must extend the object server-side (compose), not
        re-upload the accumulated stream — O(total) bytes per run."""
        bucket = mock.MagicMock()
        dest = mock.MagicMock()
        part = mock.MagicMock()
        dest.exists.return_value = True
        part_names = []

        def _blob(name):
            if ".part." in name:
                part_names.append(name)
                return part
            return dest

        bucket.blob.side_effect = _blob
        fake_client = mock.MagicMock()
        fake_client.bucket.return_value = bucket
        monkeypatch.setattr(storage, "_client", lambda: fake_client)

        storage.append_bytes("gs://b/log.jsonl", b"line\n")

        part.upload_from_string.assert_called_once_with(b"line\n")
        # Unique staging name per append (no cross-writer clobbering).
        assert len(part_names) == 1
        assert part_names[0].startswith("log.jsonl.part.")
        # Compose guarded by a generation precondition.
        dest.compose.assert_called_once_with(
            [dest, part], if_generation_match=dest.generation)
        part.delete.assert_called_once()
        dest.upload_from_string.assert_not_called()

    def test_gcs_listdir_uses_delimiter(self, monkeypatch):
        """listdir must aggregate children server-side (delimiter='/'),
        not enumerate every blob under the prefix — an orbax checkpoint
        tree holds thousands of shard files."""

        class FakeListing(list):
            prefixes = {"ckpt/0/", "ckpt/1/"}

        blob = mock.MagicMock()
        blob.name = "ckpt/manifest.json"
        listing = FakeListing([blob])
        bucket = mock.MagicMock()
        bucket.list_blobs.return_value = listing
        fake_client = mock.MagicMock()
        fake_client.bucket.return_value = bucket
        monkeypatch.setattr(storage, "_client", lambda: fake_client)

        names = storage.listdir("gs://b/ckpt")

        assert names == ["0", "1", "manifest.json"]
        assert bucket.list_blobs.call_args.kwargs["delimiter"] == "/"


def make_toy_batches(seed=0, steps=4, batch=32):
    """Module-level generator factory (ships by dotted path)."""
    rng = np.random.default_rng(seed)

    def batches():
        for _ in range(steps):
            x = rng.normal(size=(batch, 8)).astype(np.float32)
            y = rng.integers(0, 4, size=batch).astype(np.int32)
            yield x, y
    return batches()


class TestDatasetTransport:
    """Datasets ship as references — a dotted
    factory path + kwargs, or an npz shard manifest — with NO data
    bytes in the serialized assets (reference ships live tf.data
    datasets, client.py:151-189)."""

    def test_generator_round_trip_without_data_in_assets(self, tmp_path):
        from cloud_tpu.training import GeneratorDataset

        remote_dir = str(tmp_path / "job")
        ds = GeneratorDataset(
            make_toy_batches,
            steps_per_epoch=4,
            factory_kwargs={"seed": 3, "steps": 4, "batch": 32})
        client.serialize_assets(remote_dir, _trainer(), ds, epochs=2)

        # The data never crossed: no data.npz, and the JSON spec holds
        # only the factory reference.
        assert not os.path.exists(os.path.join(remote_dir,
                                               client.DATA_FILE))
        spec = json.loads(storage.read_bytes(
            storage.join(remote_dir, client.DATASET_SPEC_FILE)))
        assert spec["kind"] == "generator"
        assert spec["factory"].endswith(":make_toy_batches")
        assert spec["factory_kwargs"] == {"seed": 3, "steps": 4,
                                          "batch": 32}

        history = remote.run(remote_dir, "one_device")
        assert len(history["loss"]) == 2
        assert np.isfinite(history["loss"][-1])

    def test_threaded_generator_round_trip(self, tmp_path):
        from cloud_tpu.training import GeneratorDataset, ThreadedDataset

        remote_dir = str(tmp_path / "job")
        ds = ThreadedDataset(
            GeneratorDataset(make_toy_batches, steps_per_epoch=4),
            buffer_size=2)
        client.serialize_assets(remote_dir, _trainer(), ds, epochs=1)
        spec = json.loads(storage.read_bytes(
            storage.join(remote_dir, client.DATASET_SPEC_FILE)))
        assert spec["threaded"] is True
        assert spec["buffer_size"] == 2
        history = remote.run(remote_dir, "one_device")
        assert np.isfinite(history["loss"][0])

    def test_shard_manifest_round_trip(self, tmp_path):
        """Arrays already on storage cross as a path manifest."""
        import io as _io

        from cloud_tpu.training import NpzShardDataset

        shard_paths = []
        x_all, y_all = _toy_data(n=96)
        for i in range(3):
            buf = _io.BytesIO()
            np.savez(buf, x=x_all[i * 32:(i + 1) * 32],
                     y=y_all[i * 32:(i + 1) * 32])
            p = str(tmp_path / "shard-{}.npz".format(i))
            storage.write_bytes(p, buf.getvalue())
            shard_paths.append(p)

        remote_dir = str(tmp_path / "job")
        ds = NpzShardDataset(shard_paths, batch_size=16)
        client.serialize_assets(remote_dir, _trainer(), ds, epochs=2)
        spec = json.loads(storage.read_bytes(
            storage.join(remote_dir, client.DATASET_SPEC_FILE)))
        assert spec["kind"] == "npz_shards"
        assert spec["paths"] == shard_paths
        history = remote.run(remote_dir, "one_device")
        assert len(history["loss"]) == 2
        assert np.isfinite(history["loss"][-1])

    def test_closure_factory_rejected(self, tmp_path):
        from cloud_tpu.training import GeneratorDataset

        x, y = _toy_data()

        def local_factory():
            return iter([(x[:32], y[:32])])

        ds = GeneratorDataset(local_factory)
        with pytest.raises(ValueError, match="module-level"):
            client.serialize_assets(str(tmp_path / "j"), _trainer(), ds)

    def test_dataset_with_y_rejected(self, tmp_path):
        from cloud_tpu.training import GeneratorDataset

        ds = GeneratorDataset(make_toy_batches)
        with pytest.raises(ValueError, match="y must be None"):
            client.serialize_assets(str(tmp_path / "j"), _trainer(), ds,
                                    y=np.zeros(4, np.int32))
