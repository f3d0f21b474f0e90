"""Sharding helpers: how arrays lay out over the ambient device mesh.

The TPU-native replacement for the implicit placement decisions inside
`tf.distribute` strategies (reference core/preprocess.py:124-149 selects a
strategy; the strategy owns variable/batch placement). Here placement is
explicit and compiler-visible: `jax.sharding.NamedSharding` specs over the
ambient `Mesh`, with XLA inserting the collectives (psum for gradient
reduction rides ICI automatically when the batch is sharded on the "dp"
axis and parameters are replicated).
"""

import re

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from cloud_tpu.parallel import runtime

DATA_AXIS = "dp"
MODEL_AXIS = "tp"
SEQUENCE_AXIS = "sp"


def _active_context_mesh():
    """The mesh of an enclosing `with Mesh(...)` block, if any. jax
    keeps it in a thread-local it does not export
    (tests/unit/test_runtime.py pins the lookup)."""
    from jax._src import mesh as mesh_lib

    m = mesh_lib.thread_resources.env.physical_mesh
    return None if m.empty else m


def ambient_mesh():
    """Enclosing `with Mesh(...)` context > ambient runtime mesh, or
    None when neither exists — most-local wins, like variable
    scoping."""
    mesh = _active_context_mesh()
    return runtime.global_mesh() if mesh is None else mesh


def _resolve_mesh(mesh=None):
    """Explicit arg > `ambient_mesh()`; raises when there is none."""
    if mesh is None:
        mesh = ambient_mesh()
    if mesh is None:
        raise RuntimeError(
            "No mesh: pass `mesh=`, enter a `with Mesh(...)` block, or "
            "initialize the ambient runtime "
            "(cloud_tpu.parallel.runtime.initialize).")
    return mesh


def batch_sharding(mesh=None, axis=DATA_AXIS):
    """Sharding for a batch: leading dim split over the data axis."""
    mesh = _resolve_mesh(mesh)
    if axis not in mesh.axis_names:
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(axis))


def replicated(mesh=None):
    """Fully-replicated sharding (default for parameters under pure DP)."""
    return NamedSharding(_resolve_mesh(mesh), P())


def shard_batch(batch, mesh=None, axis=DATA_AXIS):
    """Device-puts a (possibly nested) batch with the leading dim sharded
    over the data axis. Works for single-process use; multi-host feeding
    goes through `make_global_batch`."""
    sharding = batch_sharding(mesh, axis)
    runtime.record_h2d(batch)
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), batch)


def make_global_batch(local_batch, mesh=None, axis=DATA_AXIS,
                      sharding=None):
    """Assembles a global array from per-process local batches.

    On multi-host pods each process holds 1/num_processes of the global
    batch (the analogue of `tf.distribute` per-worker dataset sharding,
    reference cloud_fit/remote.py:84-88 delegates this to the strategy).
    `sharding` overrides the default batch layout (e.g. the
    steps_per_execution path assembles [spe, B, ...] stacks under
    P(None, dp)).
    """
    if sharding is None:
        mesh = _resolve_mesh(mesh)
        sharding = batch_sharding(mesh, axis)
    runtime.record_h2d(local_batch)
    return jax.tree_util.tree_map(
        lambda x: jax.make_array_from_process_local_data(sharding, x),
        local_batch)


def param_sharding(params, rules=None, mesh=None):
    """Returns a sharding pytree for `params`.

    Args:
        params: Parameter pytree (or its shape-struct).
        rules: Optional list of (path_regex, PartitionSpec) pairs, first
            match wins — e.g. [(r".*attention.*kernel", P(None, "tp"))].
            Unmatched params are replicated. None means replicate all
            (pure data parallelism).
        mesh: Mesh override; default ambient.

    Returns:
        Pytree of `NamedSharding` congruent with `params`.
    """
    mesh = _resolve_mesh(mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    shardings = []
    for path, _ in flat:
        spec = P()
        if rules:
            path_str = path_string(path)
            for pattern, rule_spec in rules:
                if re.search(pattern, path_str):
                    spec = rule_spec
                    break
        shardings.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, shardings)


def add_axis_sharding(params, shardings, mesh=None, axis=DATA_AXIS):
    """Adds `axis` to each leaf's spec on the first eligible dimension.

    Eligible = not already sharded and divisible by the axis size;
    leaves already sharded on `axis` (anywhere) or with no eligible
    dimension keep their layout. The generic building block for
    weight/moment sharding over the data axis (ZeRO / FSDP layouts).
    """
    mesh = _resolve_mesh(mesh)
    if axis not in mesh.axis_names:
        return shardings
    n = mesh.shape[axis]
    if n <= 1:
        return shardings

    def _mentions(spec_entry, name):
        if spec_entry is None:
            return False
        if isinstance(spec_entry, (tuple, list)):
            return name in spec_entry
        return spec_entry == name

    def leaf(p, s):
        spec = list(s.spec) + [None] * (p.ndim - len(s.spec))
        if any(_mentions(e, axis) for e in spec):
            return s  # already sharded on the data axis somewhere
        for i, dim in enumerate(p.shape):
            if spec[i] is None and dim % n == 0 and dim >= n:
                spec[i] = axis
                return NamedSharding(mesh, P(*spec))
        return s

    return jax.tree_util.tree_map(leaf, params, shardings)


def zero1_opt_sharding(params, param_shardings, mesh=None, axis=DATA_AXIS):
    """ZeRO-1 layout for params-shaped optimizer subtrees (moments).

    Each leaf's spec is its parameter's spec with the data axis added
    (see `add_axis_sharding`). Under pjit this makes XLA compute the
    optimizer update on 1/|dp| shards and all-gather the updates —
    optimizer memory drops to O(1/|dp|) per device (the ZeRO-1 trade:
    one all-gather per step for an |dp|-fold moment-memory saving)
    while parameters themselves stay in their data-parallel (replicated
    or tp-sharded) layout.
    """
    return add_axis_sharding(params, param_shardings, mesh, axis)


def fsdp_sharding(params, mesh=None, axis=DATA_AXIS, rules=None):
    """Fully-sharded (ZeRO-3 style) parameter layout.

    Every parameter is sharded over the data axis on its first eligible
    dimension, on top of any model-parallel `rules` (tp rules apply
    first; dp lands on a free dimension). XLA's SPMD partitioner then
    all-gathers weights where layers consume them and reduce-scatters
    gradients — per-device weight+grad+moment memory drops to
    O(1/|dp|), the pjit form of FSDP (How-to-Scale-Your-Model recipe:
    annotate shardings, let XLA insert the collectives).
    """
    base = param_sharding(params, rules=rules, mesh=mesh)
    return add_axis_sharding(params, base, mesh, axis)


def path_string(path):
    """Key path -> slash-separated string, e.g. "block_0/mlp_in/kernel"."""
    parts = []
    for entry in path:
        if hasattr(entry, "key"):
            parts.append(str(entry.key))
        elif hasattr(entry, "idx"):
            parts.append(str(entry.idx))
        elif hasattr(entry, "name"):
            parts.append(str(entry.name))
        else:
            parts.append(str(entry))
    return "/".join(parts)


def local_batch_size(global_batch_size, mesh=None, axis=DATA_AXIS):
    """Per-process batch size for a global batch sharded on `axis`."""
    mesh = _resolve_mesh(mesh)
    num_processes = jax.process_count()
    if global_batch_size % num_processes:
        raise ValueError(
            "global_batch_size={} is not divisible by the process count "
            "{}.".format(global_batch_size, num_processes))
    return global_batch_size // num_processes
