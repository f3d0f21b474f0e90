"""Weights from the seed, made on the device in one jitted call, in the type
they are trained or served in. The benchmark makes them, not the program, so
the program and the plain reference start from the same numbers and neither
takes anything from the other.

Rule per leaf, by the last key of its path: `scale` -> 1 + 0.1 n, `bias` ->
0.02 n, anything else (kernels, embeddings) -> 0.02 n, n standard normal.
Small random biases and scales keep every term of every layer in play.
"""

import jax
import jax.numpy as jnp

from cellbench import harness

STD = 0.02


def _leaf(key, path, struct):
    last = str(getattr(path[-1], "key", path[-1]))
    noise = jax.random.normal(key, struct.shape, jnp.float32)
    if last == "scale":
        value = 1.0 + 0.1 * noise
    else:
        value = STD * noise
    return value.astype(struct.dtype)


def fill(shapes, key):
    """Pure: the tree of `shapes` filled from a raw uint32[2] key. Traceable,
    so another jitted function can make the initial weights again instead of
    keeping a copy of them."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = [_leaf(jax.random.fold_in(key, i), path, struct)
           for i, (path, struct) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def make_params(shapes, seed):
    """A tree like `shapes` (ShapeDtypeStructs, as `jax.eval_shape` of the
    model's init gives them) filled from `seed`, in one jitted call."""
    return jax.jit(lambda key: fill(shapes, key))(seed_key(seed))


def seed_key(seed):
    return jnp.asarray(harness.seed_words(seed, 2), jnp.uint32)


def param_shapes(model, sample_len=8):
    """The program's parameter tree as shapes only (nothing is initialised)."""
    return jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, sample_len), jnp.int32))["params"]


def build_model(config, **extra):
    """The program's model class for a configuration file, with the file's
    numbers under the program's argument names."""
    from cloud_tpu import models

    spec = config["model"]
    numbers = dict(config)
    numbers.update(config.get("assumed", {}))
    kwargs = {arg: numbers[key] for arg, key in spec["kwargs"].items()}
    if "max_seq_len" in spec:
        kwargs["max_seq_len"] = numbers[spec["max_seq_len"]]
    kwargs.update(spec.get("fixed", {}))
    kwargs.update(extra)
    dtype = jnp.dtype(numbers.get("compute_dtype", "bfloat16"))
    return getattr(models, spec["class"])(compute_dtype=dtype, **kwargs)
