"""Test configuration: force an 8-device virtual CPU mesh.

Tests exercise multi-chip sharding logic without TPU hardware by running
JAX on 8 virtual CPU devices — the TPU-native analogue of the reference's
fake-cluster trick (reference cloud_fit/tests/unit/remote_test.py:80-127,
which fabricates TF_CONFIG with bogus worker addresses). Must run before
jax initializes its backends, hence the env mutation at import time.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# XLA:CPU aborts the process when a collective's participants have not
# all arrived within 40 s. Eight virtual devices' threads on a machine
# whose cores six xdist workers share can be starved that long (a
# training step under the `tpu_slice` mesh in tests/cellbench, with the
# suite's heavier files beside it): a slow rendezvous is then a slow
# test, not "Fatal Python error: Aborted" and a lost worker.
if "xla_cpu_collective_call_terminate_timeout_seconds" not in _flags:
    os.environ["XLA_FLAGS"] += (
        " --xla_cpu_collective_call_terminate_timeout_seconds=900"
        " --xla_cpu_collective_call_warn_stuck_timeout_seconds=300"
        " --xla_cpu_collective_timeout_seconds=900")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

