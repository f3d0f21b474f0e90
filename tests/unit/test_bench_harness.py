"""bench.py's contract around the measurement (no accelerator).

The measurement itself needs the chip; what is pinned here is what
surrounds it: the run happens in this one process, every record is
stamped with the device JAX reports, a backend that is not a TPU is a
non-zero exit and not a fallback, and utilization is a share of the
peak published for that `device_kind` or nothing at all.
"""

import importlib.util
import os
import subprocess
import sys

import pytest

_BENCH_PATH = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "bench.py"))


@pytest.fixture()
def bench():
    """Fresh bench module per test (import-time env expansion)."""
    spec = importlib.util.spec_from_file_location("bench_under_test",
                                                  _BENCH_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TPU = {"platform": "tpu", "device_kind": "TPU v5 lite",
       "device_count": 1}
CPU = {"platform": "cpu", "device_kind": "cpu", "device_count": 1}


class TestDeviceGate:
    def test_default_backend_not_a_tpu_exits_nonzero(self, bench,
                                                     monkeypatch):
        """No TPU and nobody asked for the CPU: refuse, with the
        device in the message, before any worker starts."""
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(bench, "_device_stamp", lambda: dict(CPU))
        monkeypatch.setattr(bench, "worker", lambda stamp: pytest.fail(
            "worker ran without a TPU"))
        with pytest.raises(SystemExit) as info:
            bench.main()
        assert info.value.code not in (0, None)
        assert "'cpu'" in str(info.value.code)

    def test_explicit_cpu_run_is_stamped_cpu(self, bench, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.setattr(bench, "_device_stamp", lambda: dict(CPU))
        seen = []
        monkeypatch.setattr(bench, "worker", seen.append)
        bench.main()
        assert seen == [CPU]

    def test_tpu_runs_in_this_process(self, bench, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(bench, "_device_stamp", lambda: dict(TPU))
        seen = []
        monkeypatch.setattr(bench, "worker", seen.append)
        bench.main()
        assert seen == [TPU]

    def test_stamp_is_what_jax_reports(self, bench):
        import jax

        assert bench._device_stamp() == {
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices())}

    def test_script_exits_nonzero_on_a_non_tpu_default_backend(self):
        """The whole script, with the backend left to JAX: whatever it
        picks on a machine without a chip, the exit code is not 0 and
        no record is printed."""
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME")}
        proc = subprocess.run([sys.executable, _BENCH_PATH],
                              capture_output=True, text=True, env=env,
                              timeout=300)
        assert proc.returncode != 0
        assert not any(line.startswith("{")
                       for line in proc.stdout.splitlines())

    def test_no_parent_child_machinery_left(self, bench):
        """One process for each chip: nothing in bench.py may start
        another process or re-serve an old record."""
        src = open(_BENCH_PATH).read()
        for gone in ("subprocess", "--worker", "last_green", "best_pin",
                     "os._exit", "SIGTERM"):
            assert gone not in src, gone


class TestPeakTable:
    def test_pct_peak_uses_the_device_kinds_published_peak(self, bench):
        assert bench._pct_peak(53.6, TPU) == pytest.approx(27.2)

    def test_unknown_device_kind_raises(self, bench):
        with pytest.raises(ValueError, match="No published peak"):
            bench._pct_peak(
                53.6, dict(TPU, device_kind="TPU v99"))

    def test_cpu_run_has_no_utilization(self, bench):
        assert bench._pct_peak(0.02, CPU) is None


class TestSelfDescribingConfig:
    """Every record carries the configuration it was asked for."""

    def test_malformed_env_degrades_to_defaults(self, bench,
                                                monkeypatch):
        monkeypatch.setenv("BENCH_SPE", "garbage")
        cfg = bench._requested_config()
        assert cfg["steps_per_execution"] == 1
        assert cfg["batch"] == bench.BATCH

    def test_named_config_expands_and_is_recorded(self, monkeypatch):
        monkeypatch.setenv("BENCH_CONFIG", "bf16_s2d")
        monkeypatch.delenv("BENCH_BF16_INPUT", raising=False)
        monkeypatch.delenv("BENCH_S2D", raising=False)
        spec = importlib.util.spec_from_file_location(
            "bench_named", _BENCH_PATH)
        mod = importlib.util.module_from_spec(spec)
        try:
            spec.loader.exec_module(mod)
            cfg = mod._requested_config()
            assert cfg["bf16_input"] and cfg["space_to_depth"]
            assert cfg["named_config"] == "bf16_s2d"
            assert mod._metric_name().endswith("_s2d_bf16in")
        finally:
            # The import-time expansion writes os.environ directly.
            os.environ.pop("BENCH_BF16_INPUT", None)
            os.environ.pop("BENCH_S2D", None)
