"""The driver-facing entry point (`__graft_entry__`).

`dryrun_multichip(n)` runs on the first n devices `jax.devices()`
reports and has no fallback: too few devices is an error, and virtual
CPU devices exist only because the caller's environment asked for them
(tests/conftest.py does, for the whole suite).
"""

import os
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO_ROOT)

import __graft_entry__ as graft_entry  # noqa: E402


def test_dryrun_raises_when_fewer_devices_than_asked():
    import jax

    have = len(jax.devices())
    with pytest.raises(RuntimeError,
                       match="only {} are available".format(have)):
        graft_entry.dryrun_multichip(have + 1)
    # Still the devices the environment gave, not substitutes.
    assert len(jax.devices()) == have


def test_no_backend_selection_left():
    """The entry point touches `jax.devices()` in this process and
    nothing else decides where it runs."""
    for gone in ("_probe_default_backend", "_select_backend",
                 "_force_cpu_backend", "_cpu_forced_by_env"):
        assert not hasattr(graft_entry, gone), gone
    src = open(graft_entry.__file__).read()
    assert "subprocess" not in src
    assert "jax_platforms" not in src


@pytest.mark.slow
def test_dryrun_multichip_on_the_suites_virtual_devices(capsys):
    graft_entry.dryrun_multichip(4)
    assert "dryrun_multichip OK: 4 devices" in capsys.readouterr().out
