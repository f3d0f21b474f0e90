"""Dependency specification for the cloud-tpu framework.

Mirrors the reference's standalone dependency module
(reference src/python/dependencies.py:19-29) with the TPU-native stack:
jax/flax/optax replace `tensorflow>=1.15.0,<3.0`, orbax replaces the
SavedModel checkpoint path, and the GCP client libraries are optional
extras because every cloud boundary in the framework takes an injectable
transport (the library imports and unit-tests cleanly without them).
"""


def make_required_install_packages():
    return [
        "absl-py",
        # jax.shard_map with varying-axes types, jax.lax.pcast.
        "jax>=0.9",
        "flax",
        "optax",
        "numpy",
    ]


def make_required_extra_packages():
    return {
        "gcp": [
            "google-api-python-client",
            "google-auth",
            "google-cloud-storage",
        ],
        "docker": ["docker"],
        "checkpoint": ["orbax-checkpoint"],
        "tests": ["pytest"],
    }
