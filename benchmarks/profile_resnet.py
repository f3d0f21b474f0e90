"""Capture an XLA profiler trace of the flagship ResNet50 train step.

The roofline-evidence tool for PERF.md: runs the same jitted Trainer
step bench.py measures, under `monitoring.profiler.trace`, and writes
the trace to --log-dir (default benchmarks/prof/<ts>) for TensorBoard's
trace/op/memory viewers. Use on the real chip to attribute the gap
between measured img/s and v5e peak (HBM-bound conv stem vs MXU-bound
body vs host overhead). Only the process that holds the chip can trace
it, so the step runs right here.

Usage: python benchmarks/profile_resnet.py [--steps 10] [--log-dir DIR]
       (BENCH_BATCH / BENCH_S2D env as in bench.py)
"""

import argparse
import os
import sys
import time

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--log-dir", default=None)
    args = parser.parse_args(argv)

    import jax
    import optax

    from cloud_tpu.models import ResNet50
    from cloud_tpu.monitoring import profiler
    from cloud_tpu.training import Trainer

    batch = int(os.environ.get("BENCH_BATCH", 256))
    image = int(os.environ.get("BENCH_IMAGE", 224))
    s2d = os.environ.get("BENCH_S2D", "0") == "1"
    log_dir = args.log_dir or os.path.join(
        _REPO_ROOT, "benchmarks", "prof",
        time.strftime("%Y%m%d_%H%M%S"))

    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, image, image, 3)).astype(np.float32)
    y = rng.integers(0, 1000, size=batch).astype(np.int32)
    trainer = Trainer(ResNet50(num_classes=1000,
                               conv0_space_to_depth=s2d),
                      optimizer=optax.sgd(0.1, momentum=0.9),
                      train_kwargs={"train": True},
                      eval_kwargs={"train": False}, metrics=())
    trainer.build(x)
    step_fn = trainer._make_train_step()
    fed = trainer._feed((x, y))
    state = trainer.state

    # Compile + settle outside the trace window.
    for _ in range(3):
        state, logs = step_fn(state, fed)
    jax.block_until_ready(logs["loss"])

    with profiler.trace(log_dir):
        for i in range(args.steps):
            with profiler.annotate("train_step_%d" % i):
                state, logs = step_fn(state, fed)
        jax.block_until_ready(logs["loss"])  # barrier inside the trace

    print("trace written to {} ({} steps, batch {}, platform {})".format(
        log_dir, args.steps, batch, jax.default_backend()))
    return log_dir


if __name__ == "__main__":
    main()
