"""Tuner tests.

Mirrors the reference's tuner unit tests (tuner/tests/unit/tuner_test.py
and optimizer_client_test.py): trial lifecycle against a faked Vizier
service with pinned REST bodies, converter round-trips (utils_test.py),
and the distributed-tuner remote flow with mocked cloud_fit + job status
— plus a REAL end-to-end local search loop training tiny models.
"""

from unittest import mock

import numpy as np
import pytest

from cloud_tpu.tuner import optimizer_client
from cloud_tpu.tuner import utils as tuner_utils
from cloud_tpu.tuner.hyperparameters import HyperParameters, Objective
from cloud_tpu.tuner.tuner import (CloudOracle, CloudTuner,
                                   DistributingCloudTuner, TrialStatus)


# ---------------------------------------------------------------------
# Fake Vizier service: answers the googleapiclient-style fluent calls.
# ---------------------------------------------------------------------

class FakeVizier:
    """Suggests each parameter's default; records all request bodies."""

    def __init__(self, max_suggestions=3):
        self.max_suggestions = max_suggestions
        self.suggested = 0
        self.trials = {}
        self.created_studies = []
        self.measurements = []
        self.stopped = []
        self.service = self._build()

    def _execute(self, result):
        call = mock.MagicMock()
        call.execute.side_effect = result
        return call

    def _build(self):
        service = mock.MagicMock()
        studies = service.projects.return_value.locations.return_value \
            .studies.return_value
        trials = studies.trials.return_value
        operations = service.projects.return_value.locations.return_value \
            .operations.return_value

        def create_study(body=None, parent=None, studyId=None):
            self.created_studies.append((studyId, body))
            return self._execute(lambda: {"name": studyId})

        def get_study(name=None):
            return self._execute(lambda: {"name": name})

        def suggest(parent=None, body=None):
            def run():
                self.suggested += 1
                trial_id = str(self.suggested)
                if self.suggested > self.max_suggestions:
                    return {"name": "operations/op%s" % trial_id,
                            "done_payload": {"trials": []}}
                name = "{}/trials/{}".format(parent, trial_id)
                self.trials[trial_id] = {
                    "name": name,
                    "state": "ACTIVE",
                    "parameters": [
                        {"parameter": "units", "floatValue": 32.0},
                        {"parameter": "lr", "floatValue": 0.01},
                    ],
                }
                return {"name": "operations/op%s" % trial_id,
                        "done_payload": {
                            "trials": [self.trials[trial_id]]}}
            return self._execute(run)

        def op_get(name=None):
            # Operations complete immediately; the payload was stashed by
            # the producing call via a closure trick below.
            return self._execute(
                lambda: {"done": True, "response": self._last_op_payload})

        def add_measurement(name=None, body=None):
            def run():
                self.measurements.append((name, body))
                return {}
            return self._execute(run)

        def check_early_stopping(name=None):
            def run():
                return {"name": "operations/early",
                        "done_payload": {"shouldStop": False}}
            return self._execute(run)

        def stop(name=None):
            def run():
                self.stopped.append(name)
                return {}
            return self._execute(run)

        def complete(name=None, body=None):
            def run():
                trial_id = name.split("/")[-1]
                trial = self.trials[trial_id]
                trial["state"] = ("INFEASIBLE" if body["trial_infeasible"]
                                  else "COMPLETED")
                if not body["trial_infeasible"]:
                    value = 0.1 * float(trial_id)
                    trial["finalMeasurement"] = {
                        "stepCount": 1,
                        "metrics": [{"value": value}],
                    }
                return trial
            return self._execute(run)

        def list_trials(parent=None):
            return self._execute(
                lambda: {"trials": list(self.trials.values())})

        studies.create.side_effect = create_study
        studies.get.side_effect = get_study
        trials.suggest.side_effect = self._wrap_op(suggest)
        trials.addMeasurement.side_effect = add_measurement
        trials.checkEarlyStoppingState.side_effect = self._wrap_op(
            check_early_stopping)
        trials.stop.side_effect = stop
        trials.complete.side_effect = complete
        trials.list.side_effect = list_trials
        operations.get.side_effect = op_get
        return service

    def _wrap_op(self, factory):
        def wrapped(**kwargs):
            call = factory(**kwargs)
            orig = call.execute.side_effect

            def run():
                resp = orig()
                self._last_op_payload = resp.pop("done_payload")
                return resp
            call.execute.side_effect = run
            return call
        return wrapped


def _toy_xy(n=64, d=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, classes, size=n).astype(np.int32)
    return x, y


def _mlp_hypermodel(hp):
    from cloud_tpu.models import MLP
    from cloud_tpu.training import Trainer

    return Trainer(MLP(hidden=hp.get("units"), num_classes=4),
                   optimizer="adam")


def _search_space():
    hps = HyperParameters()
    hps.Int("units", 16, 64, step=16)
    hps.Float("lr", 1e-4, 1e-1, sampling="log")
    return hps


def _oracle(fake, max_trials=3):
    return CloudOracle(
        project_id="p", region="us-central1",
        objective=Objective("accuracy", "max"),
        hyperparameters=_search_space(),
        max_trials=max_trials, study_id="study1",
        service_client=fake.service)


class TestConverters:

    def test_study_config_round_trip(self):
        hps = _search_space()
        config = tuner_utils.make_study_config(
            Objective("accuracy", "max"), hps)
        assert config["metrics"] == [
            {"metric": "accuracy", "goal": "MAXIMIZE"}]
        params = {p["parameter"]: p for p in config["parameters"]}
        assert params["units"]["type"] == "DISCRETE"
        assert params["units"]["discrete_value_spec"]["values"] == \
            [16.0, 32.0, 48.0, 64.0]
        assert params["lr"]["type"] == "DOUBLE"
        assert params["lr"]["scale_type"] == "UNIT_LOG_SCALE"

        back = tuner_utils.convert_study_config_to_hps(config)
        assert set(back.space) == {"units", "lr"}
        objectives = tuner_utils.convert_study_config_to_objective(config)
        assert objectives == [Objective("accuracy", "max")]

    def test_boolean_and_fixed(self):
        hps = HyperParameters()
        hps.Boolean("use_bias")
        hps.Fixed("layers", 3)
        hps.Choice("act", ["relu", "gelu"])
        config = tuner_utils.make_study_config(Objective("loss"), hps)
        params = {p["parameter"]: p for p in config["parameters"]}
        assert params["use_bias"]["categorical_value_spec"]["values"] == \
            ["True", "False"]
        assert params["layers"]["discrete_value_spec"]["values"] == [3.0]
        assert params["act"]["type"] == "CATEGORICAL"

    def test_trial_to_hps(self):
        hps = _search_space()
        trial = {"name": "studies/s/trials/7",
                 "parameters": [
                     {"parameter": "units", "floatValue": 48.0},
                     {"parameter": "lr", "floatValue": 0.004},
                 ]}
        assert tuner_utils.get_trial_id(trial) == "7"
        out = tuner_utils.convert_optimizer_trial_to_hps(hps, trial)
        assert out.get("units") == 48  # int restored
        assert out.get("lr") == pytest.approx(0.004)


class TestHyperParameters:

    def test_defaults_and_get(self):
        hps = _search_space()
        assert hps.get("units") == 16
        with pytest.raises(KeyError):
            hps.get("nope")

    def test_random_sample_within_bounds(self):
        hps = _search_space()
        sample = hps.random_sample(seed=3)
        assert sample.get("units") in (16, 32, 48, 64)
        assert 1e-4 <= sample.get("lr") <= 1e-1


class TestCloudOracle:

    def test_trial_lifecycle(self):
        fake = FakeVizier()
        oracle = _oracle(fake)

        trial = oracle.create_trial("tuner0")
        assert trial.status == TrialStatus.RUNNING
        assert trial.hyperparameters.get("units") == 32

        status = oracle.update_trial(trial.trial_id, {"accuracy": 0.8},
                                     step=0)
        assert status == TrialStatus.RUNNING
        name, body = fake.measurements[0]
        assert name.endswith("trials/1")
        assert body["measurement"]["metrics"] == [
            {"metric": "accuracy", "value": 0.8}]

        done = oracle.end_trial(trial.trial_id)
        assert done.status == TrialStatus.COMPLETED
        assert done.score == pytest.approx(0.1)

    def test_stops_at_max_trials(self):
        fake = FakeVizier(max_suggestions=10)
        oracle = _oracle(fake, max_trials=2)
        for _ in range(2):
            trial = oracle.create_trial("tuner0")
            oracle.end_trial(trial.trial_id)
        assert oracle.create_trial("tuner0").status == TrialStatus.STOPPED

    def test_stops_when_suggestions_exhausted(self):
        fake = FakeVizier(max_suggestions=1)
        oracle = _oracle(fake, max_trials=None)
        assert oracle.create_trial("t").status == TrialStatus.RUNNING
        assert oracle.create_trial("t").status == TrialStatus.STOPPED

    def test_get_best_trials_ordering(self):
        fake = FakeVizier()
        oracle = _oracle(fake)
        for _ in range(3):
            trial = oracle.create_trial("tuner0")
            oracle.end_trial(trial.trial_id)
        best = oracle.get_best_trials(2)
        # Scores are 0.1 * trial_id and objective is max.
        assert [t.score for t in best] == [
            pytest.approx(0.3), pytest.approx(0.2)]

    def test_study_config_bootstrap(self):
        fake = FakeVizier()
        _oracle(fake)
        study_id, body = fake.created_studies[0]
        assert study_id == "study1"
        assert body["study_config"]["metrics"][0]["metric"] == "accuracy"


class TestCloudTunerSearch:

    def test_local_search_trains_real_models(self, tmp_path):
        x, y = _toy_xy()
        hypermodel = _mlp_hypermodel

        fake = FakeVizier(max_suggestions=2)
        tuner = CloudTuner(
            hypermodel, directory=str(tmp_path),
            project_id="p", region="us-central1",
            objective=Objective("accuracy", "max"),
            hyperparameters=_search_space(),
            max_trials=2, study_id="study_local",
            service_client=fake.service)
        tuner.search(x=x, y=y, epochs=1, batch_size=32, verbose=False)

        # Two trials ran, measured, completed; per-trial artifacts exist.
        assert len(fake.measurements) == 2
        assert (tmp_path / "1" / "logs" / "metrics.jsonl").exists()
        assert (tmp_path / "1" / "checkpoint").exists()
        best = tuner.get_best_hyperparameters(1)
        assert best[0].get("units") == 32

    def test_failed_trial_marked_invalid(self, tmp_path):
        def hypermodel(hp):
            raise RuntimeError("bad build")

        fake = FakeVizier(max_suggestions=1)
        tuner = CloudTuner(
            hypermodel, directory=str(tmp_path),
            project_id="p", region="us-central1",
            objective=Objective("accuracy", "max"),
            hyperparameters=_search_space(),
            max_trials=2, study_id="s",
            service_client=fake.service)
        tuner.search(x=np.zeros((4, 2), np.float32),
                     y=np.zeros(4, np.int32))
        assert fake.trials["1"]["state"] == "INFEASIBLE"


class TestDistributingCloudTuner:

    def test_remote_trial_flow(self, tmp_path, monkeypatch):
        monkeypatch.setenv("GOOGLE_CLOUD_PROJECT", "p")
        from cloud_tpu.models import MLP
        from cloud_tpu.training import Trainer
        from cloud_tpu.tuner import tuner as tuner_module

        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 8)).astype(np.float32)
        y = rng.integers(0, 4, size=64).astype(np.int32)

        def hypermodel(hp):
            return Trainer(MLP(hidden=hp.get("units"), num_classes=4),
                           optimizer="adam")

        fake = FakeVizier(max_suggestions=1)

        # cloud_fit serializes for real into the trial dir; the "remote"
        # job is simulated by running the worker in-process when the
        # tuner polls for success.
        from cloud_tpu.cloud_fit import remote as cloud_fit_remote

        submitted = {}
        real_cloud_fit = tuner_module.cloud_fit_client.cloud_fit

        def fake_cloud_fit(trainer, remote_dir, **kwargs):
            kwargs["api_client"] = mock.MagicMock()
            job_id = real_cloud_fit(trainer, remote_dir, **kwargs)
            submitted["dir"] = remote_dir
            submitted["job_id"] = job_id
            return job_id

        def fake_wait(job_id, project_id, api_client=None, **kw):
            cloud_fit_remote.run(submitted["dir"], "one_device")
            return True

        monkeypatch.setattr(tuner_module.cloud_fit_client, "cloud_fit",
                            fake_cloud_fit)
        monkeypatch.setattr(tuner_module.google_api_client,
                            "wait_for_api_training_job_success", fake_wait)

        tuner = DistributingCloudTuner(
            hypermodel, remote_dir=str(tmp_path),
            project_id="p", region="us-central1",
            objective=Objective("accuracy", "max"),
            hyperparameters=_search_space(),
            max_trials=1, study_id="s_remote",
            service_client=fake.service)
        tuner.search(x=x, y=y, epochs=2, batch_size=32)

        assert submitted["job_id"] == "s_remote_1"
        # Metrics were read back from the remote history and reported
        # per epoch.
        assert len(fake.measurements) == 2
        # load_trainer restores the remote-trained state.
        trial = tuner.oracle.trials["1"]
        trainer = tuner.load_trainer(trial, x[:1])
        assert int(trainer.state.step) == 4  # 2 epochs x 2 steps


class TestPinnedDiscovery:
    """Offline fallback parity with the reference's bundled discovery
    document (reference tuner/constants.py:20-22,
    optimizer_client.py:404-411)."""

    def _methods(self, doc):
        """Flattens resource tree -> {'studies.create': method, ...}."""
        flat = {}

        def walk(resources, prefix):
            for name, res in resources.items():
                for mname, meth in res.get("methods", {}).items():
                    flat[prefix + name + "." + mname] = meth
                walk(res.get("resources", {}), prefix + name + ".")

        walk(doc["resources"], "")
        return flat

    def test_doc_covers_every_client_method(self):
        doc = optimizer_client.load_pinned_discovery_doc(
            "https://us-central1-ml.googleapis.com")
        flat = self._methods(doc)
        base = "projects.locations.studies."
        needed = [
            base + m for m in ("create", "get", "list", "delete")
        ] + [
            base + "trials." + m
            for m in ("suggest", "addMeasurement", "complete",
                      "checkEarlyStoppingState", "stop", "get", "list")
        ] + ["projects.locations.operations.get"]
        for method in needed:
            assert method in flat, method
        # POST methods that the client passes a body to must declare a
        # request schema (googleapiclient rejects unexpected `body`).
        for m in ("suggest", "addMeasurement", "complete"):
            meth = flat[base + "trials." + m]
            assert meth["httpMethod"] == "POST"
            assert "request" in meth
        assert "create" in flat[base + "create"]["id"]
        # Schemas referenced by methods must exist.
        for meth in flat.values():
            for key in ("request", "response"):
                if key in meth:
                    assert meth[key]["$ref"] in doc["schemas"]

    def test_load_patches_regional_endpoint(self):
        doc = optimizer_client.load_pinned_discovery_doc(
            "https://europe-west4-ml.googleapis.com")
        assert doc["rootUrl"] == "https://europe-west4-ml.googleapis.com/"
        assert doc["baseUrl"] == doc["rootUrl"]

    def test_build_falls_back_to_pinned_doc(self, monkeypatch):
        captured = {}

        class FakeDiscovery:
            @staticmethod
            def build(*a, **k):
                captured["live_tried"] = True
                raise OSError("no egress")

            @staticmethod
            def build_from_document(doc, requestBuilder=None):
                captured["doc"] = doc
                return "offline-service"

        monkeypatch.setattr(optimizer_client, "discovery", FakeDiscovery)
        monkeypatch.delenv("CLOUD_TPU_PINNED_DISCOVERY", raising=False)
        svc = optimizer_client.build_service_client("us-central1")
        assert svc == "offline-service"
        assert captured["live_tried"]
        assert captured["doc"]["rootUrl"] == (
            "https://us-central1-ml.googleapis.com/")

    def test_env_var_skips_live_discovery(self, monkeypatch):
        class FakeDiscovery:
            @staticmethod
            def build(*a, **k):
                raise AssertionError("live discovery must not be tried")

            @staticmethod
            def build_from_document(doc, requestBuilder=None):
                return "offline-service"

        monkeypatch.setattr(optimizer_client, "discovery", FakeDiscovery)
        monkeypatch.setenv("CLOUD_TPU_PINNED_DISCOVERY", "1")
        assert optimizer_client.build_service_client(
            "us-central1") == "offline-service"


class TestSharedStudy:
    """Concurrent-tuner semantics: one Vizier study shared by several
    workers (the reference exercises this with multiprocessing.Pool(4)
    sharing one study id, tuner_integration_test.py:283-296; hermetic
    analogue here — two tuner processes' worth of clients against one
    fake service)."""

    def test_create_or_load_study_409_falls_back_to_load(self):
        class Conflict(Exception):
            def __init__(self):
                self.resp = mock.MagicMock(status=409)

        fake = FakeVizier()
        studies = (fake.service.projects.return_value.locations
                   .return_value.studies.return_value)

        def conflicted_create(body=None, parent=None, studyId=None):
            call = mock.MagicMock()
            call.execute.side_effect = Conflict()
            return call

        studies.create.side_effect = conflicted_create
        client = optimizer_client.create_or_load_study(
            "p", "us-central1", "shared", {"metrics": []},
            service_client=fake.service)
        # Lost the creation race -> loaded the existing study and is
        # fully usable.
        assert client.study_id == "shared"
        studies.get.assert_called_with(
            name="projects/p/locations/us-central1/studies/shared")

    def test_two_tuners_share_one_study(self, tmp_path):
        x, y = _toy_xy()
        hypermodel = _mlp_hypermodel

        # One study (one fake service), two workers with max_trials=3.
        # The suggestion budget (10) is deliberately ABOVE max_trials:
        # only the client-side study-wide completed-trial count can stop
        # worker 1, so the cross-worker accounting is load-bearing.
        fake = FakeVizier(max_suggestions=10)

        def worker(name):
            tuner = CloudTuner(
                hypermodel, directory=str(tmp_path / name),
                project_id="p", region="us-central1",
                objective=Objective("accuracy", "max"),
                hyperparameters=_search_space(),
                max_trials=3, study_id="shared_study",
                service_client=fake.service)
            tuner.search(x=x, y=y, epochs=1, batch_size=32,
                         verbose=False)
            return tuner

        worker("w0")
        t2 = worker("w1")

        # Worker 0 consumed the study's max_trials; worker 1 saw the
        # study-wide history and stopped WITHOUT requesting another
        # suggestion (per-worker accounting would have asked for a 4th).
        assert fake.suggested == 3
        states = {tid: t["state"] for tid, t in fake.trials.items()}
        assert states == {"1": "COMPLETED", "2": "COMPLETED",
                          "3": "COMPLETED"}
        # The late worker still sees the full study history.
        best = t2.get_best_hyperparameters(1)
        assert best[0].get("units") == 32


class TestLoadTrainerGCS:
    """load_trainer must accept the gs:// layout DistributingCloudTuner
    itself writes (round-2 gap: a NotImplementedError guard broke the
    tuner's only model-recovery path for real trials). orbax restores
    gs:// natively via tensorstore, so the wiring — spec read through
    the storage seam, the UNchanged gs:// URI handed to
    checkpoint.restore — is what this pins."""

    def test_gs_path_reaches_checkpoint_restore(self, monkeypatch):
        import pickle

        from cloud_tpu.models import MLP
        from cloud_tpu.training import Trainer
        from cloud_tpu.tuner import tuner as tuner_module

        def hypermodel(hp):
            return Trainer(MLP(hidden=hp.get("units"), num_classes=4),
                           optimizer="adam")

        fake = FakeVizier(max_suggestions=1)
        monkeypatch.setenv("GOOGLE_CLOUD_PROJECT", "p")
        tuner = DistributingCloudTuner(
            hypermodel, remote_dir="gs://bkt/tuning",
            project_id="p", region="us-central1",
            objective=Objective("accuracy", "max"),
            hyperparameters=_search_space(),
            max_trials=1, study_id="s_gcs",
            service_client=fake.service)

        # The spec the remote worker would have written for the trial.
        spec_trainer = hypermodel(_search_space())
        spec = tuner_module.cloud_fit_client.make_spec(spec_trainer)

        reads, restores = [], []

        def fake_read_bytes(path):
            reads.append(path)
            return pickle.dumps(spec)

        def fake_restore(directory, target, step=None):
            restores.append(directory)
            return target

        monkeypatch.setattr(tuner_module.storage, "read_bytes",
                            fake_read_bytes)
        monkeypatch.setattr(
            "cloud_tpu.training.checkpoint.restore", fake_restore)

        trial = mock.MagicMock()
        trial.trial_id = "7"
        trainer = tuner.load_trainer(
            trial, np.zeros((1, 8), np.float32))
        assert trainer.state is not None
        assert reads == ["gs://bkt/tuning/7/{}".format(
            tuner_module.cloud_fit_client.SPEC_FILE)]
        assert restores == ["gs://bkt/tuning/7/{}".format(
            tuner_module.cloud_fit_remote.OUTPUT_DIR)]


class TestResultsSummary:
    def test_results_summary_lists_best_trials(self, tmp_path):
        fake = FakeVizier(max_suggestions=2)

        def hypermodel(hp):
            from cloud_tpu.models import MLP
            from cloud_tpu.training import Trainer

            return Trainer(MLP(hidden=hp.get("units"), num_classes=4),
                           optimizer="adam")

        tuner = CloudTuner(hypermodel, directory=str(tmp_path),
                           objective=Objective("accuracy", "max"),
                           hyperparameters=_search_space(),
                           max_trials=2, study_id="s_summary",
                           project_id="p", region="r",
                           service_client=fake.service)
        x = np.random.default_rng(0).normal(
            size=(64, 8)).astype(np.float32)
        y = np.random.default_rng(0).integers(
            0, 4, size=64).astype(np.int32)
        tuner.search(x=x, y=y, epochs=1, batch_size=32)
        text = tuner.results_summary(num_trials=2)
        assert "Results summary" in text
        assert "accuracy" in text
        assert "units" in text


# ---------------------------------------------------------------------
# Offline (pinned) Vizier surface: every REST call the client can make
# must exist in the bundled discovery document (the
# fallback guarantee in build_service_client silently rots otherwise;
# reference bar: the full bundled doc, tuner/constants.py:20-22).
# ---------------------------------------------------------------------

class _RecordingService:
    """Chainable googleapiclient-shaped fake that records every
    (resource_path, method) pair the client traverses."""

    _RESOURCE_NAMES = frozenset(
        {"projects", "locations", "studies", "trials", "operations"})

    # Canned responses so the client's control flow actually runs all
    # the way through LRO polling / early-stop / completion branches.
    _RESPONSES = {
        "suggest": {"name": "projects/p/locations/r/operations/op1"},
        "checkEarlyStoppingState": {
            "name": "projects/p/locations/r/operations/op2"},
        # One op response serves both LRO consumers: `trials` for
        # get_suggestions, `shouldStop` True so should_trial_stop
        # proceeds to call trials.stop as well.
        "get": {"done": True,
                "response": {"trials": [], "shouldStop": True}},
        "list": {"trials": [], "studies": []},
    }

    def __init__(self, calls, path=()):
        self._calls = calls
        self._path = path

    def __getattr__(self, name):
        def chain(**kwargs):
            if name in self._RESOURCE_NAMES:
                return _RecordingService(self._calls,
                                         self._path + (name,))
            self._calls.add((self._path, name))
            response = dict(self._RESPONSES.get(name, {}))
            request = mock.MagicMock()
            request.execute.return_value = response
            return request
        return chain


def _pinned_doc_methods():
    import json

    with open(optimizer_client.PINNED_DISCOVERY_PATH) as f:
        doc = json.load(f)
    methods = {}

    def walk(resources, path):
        for rname, resource in resources.items():
            for mname, m in resource.get("methods", {}).items():
                methods[(path + (rname,), mname)] = m
            walk(resource.get("resources", {}), path + (rname,))

    walk(doc["resources"], ())
    return doc, methods


class TestPinnedDiscoverySurface:
    def _exercise_client(self):
        """Runs EVERY public OptimizerClient entry point against the
        recording service; returns the set of REST calls made."""
        calls = set()
        service = _RecordingService(calls)
        # create path (studies.create) and load path (studies.get).
        client = optimizer_client.create_or_load_study(
            "proj", "region", "study", study_config={"metrics": []},
            service_client=service)
        optimizer_client.create_or_load_study(
            "proj", "region", "study", study_config=None,
            service_client=service)
        exercised = {"get_suggestions", "report_intermediate_objective_value",
                     "should_trial_stop", "complete_trial", "get_trial",
                     "list_trials", "list_studies", "delete_study"}
        client.get_suggestions("client0")
        client.report_intermediate_objective_value(
            1, 2.0, [{"metric": "accuracy", "value": 0.5}], "1")
        assert client.should_trial_stop("1") is True  # exercises stop too
        client.complete_trial("1")
        client.get_trial("1")
        client.list_trials()
        client.list_studies()
        client.delete_study()
        # Reflection guard: a NEW public method must be added here (and
        # thereby have its REST calls checked) before it can ship.
        public = {name for name in dir(optimizer_client.OptimizerClient)
                  if not name.startswith("_")
                  and callable(getattr(optimizer_client.OptimizerClient,
                                       name))}
        assert public == exercised, (
            "public OptimizerClient methods changed; exercise the new "
            "method(s) in this test: {}".format(
                sorted(public.symmetric_difference(exercised))))
        return calls

    def test_every_client_call_is_in_pinned_doc(self):
        calls = self._exercise_client()
        _, doc_methods = _pinned_doc_methods()
        missing = {c for c in calls if c not in doc_methods}
        assert not missing, (
            "OptimizerClient calls missing from the pinned discovery "
            "doc (offline fallback would break): {}".format(
                sorted(missing)))
        # Sanity: the recorder actually saw the full expected surface.
        assert (("projects", "locations", "studies", "trials"),
                "suggest") in calls
        assert (("projects", "locations", "operations"), "get") in calls
        assert (("projects", "locations", "studies", "trials"),
                "stop") in calls

    def test_pinned_doc_is_structurally_sound(self):
        doc, methods = _pinned_doc_methods()
        assert methods, "pinned doc defines no methods"
        for (path, name), m in methods.items():
            ident = "ml." + ".".join(path) + "." + name
            assert m.get("id") == ident, m.get("id")
            assert m.get("httpMethod") in {"GET", "POST", "DELETE",
                                           "PATCH", "PUT"}
            # Every {+param} template var must be declared as a
            # required path parameter (googleapiclient build_from_
            # document fails on undeclared template vars).
            import re
            for var in re.findall(r"{\+(\w+)}", m.get("path", "")):
                param = m.get("parameters", {}).get(var)
                assert param and param.get("location") == "path", (
                    ident, var)
        for ref in ("JsonBody", "JsonResponse"):
            assert ref in doc["schemas"]

    def test_load_pinned_doc_patches_endpoint(self):
        doc = optimizer_client.load_pinned_discovery_doc(
            "https://us-central1-ml.googleapis.com")
        assert doc["rootUrl"] == "https://us-central1-ml.googleapis.com/"
        assert doc["baseUrl"] == doc["rootUrl"]
