"""Two-process tpu_pod correctness: real processes, real collectives.

The reference tests multi-node behavior with a fabricated TF_CONFIG and
an in-process strategy (cloud_fit/tests/unit/remote_test.py:80-127).
The JAX analogue needs real processes: jax.distributed.initialize over a
local coordinator, the CLOUD_TPU_* env contract, per-process local data
views assembled into global arrays. This is the one test where
`jax.process_count() > 1` branches (runtime._maybe_init_distributed,
data.process_local_view, sharding.make_global_batch) actually execute.

Hermetic: CPU-only (4 virtual devices per process), localhost
coordinator, no hardware or network beyond 127.0.0.1.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "pod_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(process_id, port, num_processes=2, local_devices=None):
    env = dict(os.environ)
    env.update({
        "CLOUD_TPU_COORDINATOR_ADDRESS": "127.0.0.1:{}".format(port),
        "CLOUD_TPU_NUM_PROCESSES": str(num_processes),
        "CLOUD_TPU_PROCESS_ID": str(process_id),
    })
    if local_devices is not None:
        env["CLOUD_TPU_TEST_LOCAL_DEVICES"] = str(local_devices)
    else:  # same leak-scrub as CLOUD_TPU_MESH below
        env.pop("CLOUD_TPU_TEST_LOCAL_DEVICES", None)
    # Virtual CPU devices come from the launcher's environment; the
    # worker sets its own device count, so the suite's XLA_FLAGS count
    # must not ride along. Scrub mesh-layout leftovers so the pod
    # defaults apply.
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("CLOUD_TPU_MESH", None)
    return subprocess.Popen(
        [sys.executable, WORKER], env=env, cwd=REPO_ROOT,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _run_pod(num_processes, local_devices=None, timeout=300):
    port = _free_port()
    procs = [_launch(i, port, num_processes, local_devices)
             for i in range(num_processes)]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            assert p.returncode == 0, "worker failed:\n{}\n{}".format(
                out, err[-3000:])
            line = [ln for ln in out.splitlines()
                    if ln.startswith("{")][-1]
            outs.append(json.loads(line))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return outs


_REFERENCE = {}


def _single_process_reference():
    """Single-process histories on the same 8-device mesh, computed
    once and shared by the 2- and 4-process parity tests (the pod runs
    use bit-identical global batches, so losses must match to float32
    noise)."""
    if _REFERENCE:
        return _REFERENCE

    from cloud_tpu.models import MLP
    from cloud_tpu.parallel import runtime
    from cloud_tpu.training import Trainer

    import jax.numpy as jnp
    import optax

    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 8)).astype(np.float32)
    w = rng.normal(size=(8, 4))
    y = np.argmax(x @ w, axis=-1).astype(np.int32)

    runtime.reset()
    runtime.initialize(strategy="tpu_slice")
    try:
        trainer = Trainer(MLP(hidden=16, num_classes=4,
                              compute_dtype=jnp.float32),
                          optimizer=optax.sgd(0.1))
        history = trainer.fit(x, y, epochs=2, batch_size=32,
                              shuffle=False, verbose=False)
    finally:
        runtime.reset()

    # Weighted (x, y, w) validation + weighted evaluate with a padded
    # validation tail (90/32).
    runtime.reset()
    runtime.initialize(strategy="tpu_slice")
    try:
        sw = np.linspace(0.2, 2.0, 128).astype(np.float32)
        val_n = 90
        wv_trainer = Trainer(MLP(hidden=16, num_classes=4,
                                 compute_dtype=jnp.float32),
                             optimizer=optax.sgd(0.1))
        wv_history = wv_trainer.fit(
            x, y, epochs=2, batch_size=32, shuffle=False, verbose=False,
            sample_weight=sw,
            validation_data=(x[:val_n], y[:val_n], sw[:val_n]))
        weighted_eval = wv_trainer.evaluate(
            x, y, batch_size=32, sample_weight=sw, verbose=False)
    finally:
        runtime.reset()

    _REFERENCE.update(history=history, wv_history=wv_history,
                      weighted_eval=weighted_eval)
    return _REFERENCE


def _assert_pod_parity(outs, num_processes):
    # Every process saw the full 8-device pod.
    for rec in outs:
        assert rec["process_count"] == num_processes
        assert rec["num_devices"] == 8
    assert ({rec["process_index"] for rec in outs}
            == set(range(num_processes)))

    # Replicated training state: all processes report identical losses.
    for rec in outs[1:]:
        np.testing.assert_allclose(outs[0]["loss"], rec["loss"],
                                   rtol=1e-6)
        np.testing.assert_allclose(outs[0]["spe_loss"],
                                   rec["spe_loss"], rtol=1e-6)
        np.testing.assert_allclose(outs[0]["es_eval_loss"],
                                   rec["es_eval_loss"], rtol=1e-6)

    ref = _single_process_reference()
    np.testing.assert_allclose(outs[0]["loss"], ref["history"]["loss"],
                               rtol=1e-5)
    # steps_per_execution on the pod (local groups -> global stacked
    # arrays) must match the single-step pod run exactly.
    np.testing.assert_allclose(outs[0]["spe_loss"], outs[0]["loss"],
                               rtol=1e-5)

    for rec in outs:
        np.testing.assert_allclose(rec["wv_loss"],
                                   ref["wv_history"]["loss"], rtol=1e-5)
        np.testing.assert_allclose(rec["wv_val_loss"],
                                   ref["wv_history"]["val_loss"],
                                   rtol=1e-5)
        np.testing.assert_allclose(rec["wv_val_accuracy"],
                                   ref["wv_history"]["val_accuracy"],
                                   rtol=1e-5)
        assert rec["weighted_eval_loss"] == pytest.approx(
            ref["weighted_eval"]["loss"], rel=1e-5)
        assert rec["weighted_eval_accuracy"] == pytest.approx(
            ref["weighted_eval"]["accuracy"], rel=1e-5)
        # EarlyStopping restore ran multi-host (sharding-preserving
        # snapshot over FSDP shards) and all processes agree.
        assert rec["es_epochs"] >= 1


def test_two_process_pod_matches_single_process():
    _assert_pod_parity(_run_pod(2), 2)


def test_four_process_pod_matches_single_process():
    """The same parity surface over a 4-process grid (4 x 2 virtual
    devices = the same 8-device mesh): process_local_view quarters,
    make_array_from_process_local_data over four disjoint device sets,
    and FSDP shards where each process can address only a quarter of
    the parameter axis — grid math a 2-way split cannot distinguish
    (a wrong chunk order or transposed process mapping degenerates to
    the identity at 2 processes more often than at 4)."""
    _assert_pod_parity(_run_pod(4, local_devices=2, timeout=420), 4)


@pytest.mark.parametrize("bad_id", [0])
def test_worker_requires_peer(bad_id):
    """A lone worker with num_processes=2 must not silently run
    single-process: the distributed handshake blocks until killed."""
    port = _free_port()
    proc = _launch(bad_id, port)
    try:
        proc.communicate(timeout=15)
        timed_out = False
    except subprocess.TimeoutExpired:
        timed_out = True
    finally:
        proc.kill()
        proc.communicate()
    assert timed_out, "worker completed without its peer"
