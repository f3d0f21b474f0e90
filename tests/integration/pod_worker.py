"""Worker process for the two-process tpu_pod correctness test.

Launched by test_multiprocess.py with the CLOUD_TPU_* env contract set
(the analogue of the reference's fabricated-TF_CONFIG fake-cluster trick,
reference cloud_fit/tests/unit/remote_test.py:80-127 — but with real
processes and a real jax.distributed handshake, not a mocked cluster).

Runs a deterministic 2-epoch fit on the pod mesh and prints one JSON
line with the per-epoch losses.
"""

import json
import os
import sys

import jax

# Each process contributes CLOUD_TPU_TEST_LOCAL_DEVICES virtual CPU
# devices (default 4 -> the 2-process x 4 = 8-device pod; the 4-process
# test runs 4 x 2 = same 8-device global mesh over twice the process
# grid). The launcher's environment holds JAX to the CPU
# (JAX_PLATFORMS=cpu).
_local_devices = int(os.environ.get("CLOUD_TPU_TEST_LOCAL_DEVICES", "4"))
jax.config.update("jax_num_cpu_devices", _local_devices)
# Cross-process collectives on the CPU backend.
jax.config.update("jax_cpu_collectives_implementation", "gloo")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def main():
    import numpy as np
    import optax

    from cloud_tpu.models import MLP
    from cloud_tpu.parallel import runtime
    from cloud_tpu.training import Trainer

    # runtime.initialize picks up CLOUD_TPU_COORDINATOR_ADDRESS /
    # CLOUD_TPU_NUM_PROCESSES / CLOUD_TPU_PROCESS_ID from the env.
    runtime.initialize(strategy="tpu_pod")

    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 8)).astype(np.float32)
    w = rng.normal(size=(8, 4))
    y = np.argmax(x @ w, axis=-1).astype(np.int32)

    import jax.numpy as jnp
    trainer = Trainer(MLP(hidden=16, num_classes=4,
                          compute_dtype=jnp.float32),
                      optimizer=optax.sgd(0.1))
    history = trainer.fit(x, y, epochs=2, batch_size=32, shuffle=False,
                          verbose=False)

    # steps_per_execution on the pod: local groups assemble into
    # global stacked arrays; the loss trajectory must match exactly.
    # spe=3 over 4 batches/epoch: one full group + one LEFTOVER single
    # step, so the mixed multi/single dispatch runs multi-host too.
    spe_trainer = Trainer(MLP(hidden=16, num_classes=4,
                              compute_dtype=jnp.float32),
                          optimizer=optax.sgd(0.1),
                          steps_per_execution=3)
    spe_history = spe_trainer.fit(x, y, epochs=2, batch_size=32,
                                  shuffle=False, verbose=False)

    # Weighted evaluate + weighted (x, y, w) validation on the pod:
    # per-batch weights are summed in-graph over the GLOBAL mask, so
    # the values must match the single-process run exactly (round-3
    # gap: both paths raised NotImplementedError multi-process). 90
    # examples / batch 32 leaves a padded tail batch, exercising
    # weights x padding-mask composition across processes.
    sw = np.linspace(0.2, 2.0, 128).astype(np.float32)
    val_n = 90
    wv_trainer = Trainer(MLP(hidden=16, num_classes=4,
                             compute_dtype=jnp.float32),
                         optimizer=optax.sgd(0.1))
    wv_history = wv_trainer.fit(
        x, y, epochs=2, batch_size=32, shuffle=False, verbose=False,
        sample_weight=sw,
        validation_data=(x[:val_n], y[:val_n], sw[:val_n]))
    weighted_eval = wv_trainer.evaluate(x, y, batch_size=32,
                                        sample_weight=sw, verbose=False)

    # EarlyStopping restore_best_weights on the pod with FSDP-sharded
    # params: each process holds only its own shards, so the best-epoch
    # snapshot MUST be a sharding-preserving device copy — a host-side
    # materializing copy fails on the non-addressable shards this
    # config creates (the exact regression the jitted _device_copy in
    # callbacks.py guards against). Frozen optimizer (lr=0.0) makes
    # every epoch identical, so restore is a no-op on VALUES while
    # still exercising the snapshot/restore machinery.
    from cloud_tpu.training import EarlyStopping
    es_trainer = Trainer(MLP(hidden=16, num_classes=4,
                             compute_dtype=jnp.float32),
                         optimizer=optax.sgd(0.0), fsdp=True)
    es = EarlyStopping(monitor="loss", patience=0,
                       restore_best_weights=True)
    es_history = es_trainer.fit(x, y, epochs=3, batch_size=32,
                                shuffle=False, verbose=False,
                                callbacks=(es,))
    es_eval = es_trainer.evaluate(x, y, batch_size=32, verbose=False)

    print(json.dumps({
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "num_devices": len(jax.devices()),
        "loss": history["loss"],
        "spe_loss": spe_history["loss"],
        "wv_loss": wv_history["loss"],
        "wv_val_loss": wv_history["val_loss"],
        "wv_val_accuracy": wv_history["val_accuracy"],
        "weighted_eval_loss": weighted_eval["loss"],
        "weighted_eval_accuracy": weighted_eval["accuracy"],
        "es_epochs": len(es_history["loss"]),
        "es_eval_loss": es_eval["loss"],
    }))


if __name__ == "__main__":
    main()
