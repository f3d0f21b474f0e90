"""chip_smoke.py's phase functions at toy widths on the CPU, and the
guard that the script itself cannot pass without a TPU."""

import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                    ".."))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TRAIN_TOY = dict(vocab_size=97, num_layers=2, num_heads=4,
                 num_kv_heads=2, d_model=32, d_ff=48, max_seq_len=32,
                 rope_theta=1e4, rope_style="rotate_half",
                 qkv_bias=True, norm_eps=1e-6)
SERVE_TOY = dict(vocab_size=61, num_layers=2, num_heads=2, d_model=32,
                 d_ff=64, max_seq_len=32, norm_eps=1e-5)


def test_kernels_phase_interpreted():
    errs = chip_smoke.kernels_phase(TRAIN_TOY, SERVE_TOY, seq=32,
                                    slots=2, page_size=8,
                                    interpret=True)
    assert set(errs) == {"flash_causal_gqa2", "flash_masked",
                         "fused_rmsnorm", "fused_swiglu", "paged_bf16",
                         "paged_int8", "paged_bf16_seq4",
                         "paged_int8_seq4"}


def test_kernels_phase_fails_on_a_wrong_kernel(monkeypatch):
    """A phase that cannot fail checks nothing: a reference that is off
    by the whole magnitude must sink the run."""
    from cloud_tpu import ops

    monkeypatch.setattr(ops, "swiglu_reference",
                        lambda *a, **k: -ops.fused_swiglu(
                            *a, impl="reference", **k))
    with pytest.raises(AssertionError, match="fused_swiglu"):
        chip_smoke.kernels_phase(TRAIN_TOY, SERVE_TOY, seq=32, slots=2,
                                 page_size=8, interpret=True)


def test_train_phase_toy():
    out = chip_smoke.train_phase(TRAIN_TOY, batch_per_chip=2, seq=16,
                                 steps=4, learning_rate=1e-2)
    assert out["losses"][-1] < out["losses"][0]
    assert out["mesh"] == {"dp": len(__import__("jax").devices())}


def test_serve_phase_toy():
    out = chip_smoke.serve_phase(SERVE_TOY, slots=2, page_size=8,
                                 prompt_lengths=(5, 12, 3),
                                 new_tokens=4)
    assert out["ties"] <= 3


def test_script_exits_nonzero_without_a_tpu():
    """`python chip_smoke.py` has no switch that lets it pass off-TPU:
    under JAX_PLATFORMS=cpu it dies in the device phase and prints no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable,
                           os.path.join(REPO, "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=300)
    assert proc.returncode != 0
    assert "chip_smoke needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
