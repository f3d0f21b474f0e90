"""Notebook example end-to-end: convert + execute through the launch
pipeline.

The reference ships runnable notebooks
(core/tests/examples/call_run_within_nb_on_colab.ipynb,
dogs_classification.ipynb) and an example test that pushes one through
the preprocessor (core/tests/examples/call_run_on_notebook_with_keras_fit
.py); BASELINE.md config 5 names a notebook entry point explicitly. This
is the TPU-native analogue: `examples/mnist_notebook_fit.ipynb` is
nbconvert-ed by `get_preprocessed_entry_point`, the generated runner is
executed on the 8-device virtual CPU mesh, and the training output is
asserted on.
"""

import os
import subprocess
import sys

from cloud_tpu.core import preprocess
from cloud_tpu.core.machine_config import COMMON_MACHINE_CONFIGS
from cloud_tpu.parallel import compile_cache

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
NOTEBOOK = os.path.join(REPO_ROOT, "examples", "mnist_notebook_fit.ipynb")
IMAGE_NOTEBOOK = os.path.join(REPO_ROOT, "examples",
                              "image_classification_notebook.ipynb")
LLM_NOTEBOOK = os.path.join(REPO_ROOT, "examples",
                            "llm_finetune_notebook.ipynb")


def _collective_timeout_flags():
    """Raised collective-call timeouts: under full-suite parallel load
    the CPU all-reduce rendezvous threads can be starved past the 20s
    default, SIGABRTing the subprocess (round-3 flake)."""
    return (
        " --xla_cpu_collective_call_warn_stuck_timeout_seconds=60"
        " --xla_cpu_collective_call_terminate_timeout_seconds=240"
    )


def _mesh_env(**extra):
    """Subprocess env for running converted notebooks on a virtual CPU
    mesh (4 devices, not 8)."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(
            "--xla_force_host_platform_device_count=4"
            + _collective_timeout_flags()
        ),
        PYTHONPATH=REPO_ROOT,
        # Persistent compile cache: repeated runs (CI retries, the 10x
        # flake loop) skip the multi-minute model compile, taking the
        # whole compile-starvation timeout class off the table.
        JAX_COMPILATION_CACHE_DIR=compile_cache.CHECKOUT_DIR,
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="2",
    )
    env.pop("CLOUD_TPU_EXAMPLE_LAUNCH", None)
    env.update(extra)
    return env


class TestNotebookExample:

    def test_notebook_converts_and_trains_on_mesh(self, tmp_path,
                                                  monkeypatch):
        monkeypatch.chdir(REPO_ROOT)
        artifact = preprocess.get_preprocessed_entry_point(
            os.path.relpath(NOTEBOOK, REPO_ROOT),
            COMMON_MACHINE_CONFIGS["TPU_V5E_8"], None, 0, "auto")
        content = open(artifact).read()
        # Notebook magics must not survive into the shipped artifact.
        assert "pip list" not in content
        assert "%config" not in content
        # The training cells are inlined (not exec'd from a file).
        assert "load_synthetic_mnist" in content
        assert 'runtime.initialize(strategy="tpu_slice")' in content

        result = subprocess.run(
            [sys.executable, artifact], capture_output=True, text=True,
            env=_mesh_env(), cwd=tmp_path, timeout=420)
        assert result.returncode == 0, result.stderr
        assert "final loss:" in result.stdout
        assert "eval accuracy:" in result.stdout

    def test_image_classification_notebook(self, tmp_path, monkeypatch):
        """The image-classification-scale notebook (the reference's
        dogs_classification.ipynb analogue): ResNet18 + augmentation +
        validation + predict, converted and executed on the mesh in
        smoke mode."""
        monkeypatch.chdir(REPO_ROOT)
        artifact = preprocess.get_preprocessed_entry_point(
            os.path.relpath(IMAGE_NOTEBOOK, REPO_ROOT),
            COMMON_MACHINE_CONFIGS["TPU_V5E_8"], None, 0, "auto")
        content = open(artifact).read()
        assert "nvidia-smi" not in content  # magics stripped
        assert "%config" not in content
        assert "load_synthetic_pets" in content
        assert 'runtime.initialize(strategy="tpu_slice")' in content

        result = subprocess.run(
            [sys.executable, artifact], capture_output=True, text=True,
            env=_mesh_env(CLOUD_TPU_EXAMPLE_SMOKE="1"), cwd=tmp_path,
            timeout=420)
        assert result.returncode == 0, result.stderr
        assert "final loss:" in result.stdout
        assert "eval accuracy:" in result.stdout
        assert "predicted classes:" in result.stdout

    def test_llm_finetune_notebook(self, tmp_path, monkeypatch):
        """The LLM-scale notebook: import a (tiny random) GPT-2
        checkpoint, fine-tune head+last-block with trainable=, sample
        with top-p — converted and executed on the mesh in smoke
        mode."""
        monkeypatch.chdir(REPO_ROOT)
        artifact = preprocess.get_preprocessed_entry_point(
            os.path.relpath(LLM_NOTEBOOK, REPO_ROOT),
            COMMON_MACHINE_CONFIGS["TPU_V5E_8"], None, 0, "auto")
        content = open(artifact).read()
        assert "pip list" not in content  # magics stripped
        assert "%config" not in content
        assert "load_checkpoint" in content
        assert 'runtime.initialize(strategy="tpu_slice")' in content

        result = subprocess.run(
            [sys.executable, artifact], capture_output=True, text=True,
            env=_mesh_env(CLOUD_TPU_EXAMPLE_SMOKE="1"), cwd=tmp_path,
            timeout=420)
        assert result.returncode == 0, result.stderr
        assert "final loss:" in result.stdout
        assert "generated:" in result.stdout
