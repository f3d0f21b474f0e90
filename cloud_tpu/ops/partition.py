"""Running the Pallas kernels under a device mesh.

XLA's SPMD partitioner cannot split a Mosaic kernel ("Mosaic kernels
cannot be automatically partitioned. Please wrap the call in a
shard_map"), so a bare `pallas_call` inside a jit over more than one
device fails at lowering. Every dispatcher in this package therefore
runs its kernel through `per_shard`: under the ambient mesh the op
becomes a `shard_map` whose body sees one device's block — batch split
over the data axis, heads (or the MLP's hidden dim) over the model
axis, everything else replicated — and with no mesh, one device, or an
enclosing `shard_map` (ulysses, the pipeline schedule) it is a plain
call.

The ambient mesh is the one the surrounding jit runs on: an enclosing
`with Mesh(...)` block, else `runtime.initialize()`'s (the Trainer's
default). Kernels type their outputs with the operands' varying-axes
set (`vma_of`), so `check_vma=True` shard_maps (ours and callers')
accept them.
"""

import jax
from jax.sharding import PartitionSpec as P

from cloud_tpu.parallel import sharding as sharding_lib


def _partition_mesh():
    """The mesh to split over, or None for a plain call: no ambient
    mesh, a single device, or already inside a shard_map (its body is
    per-device code; the axes are manual and cannot be mapped again)."""
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    mesh = sharding_lib.ambient_mesh()
    if mesh is None or mesh.size == 1:
        return None
    return mesh


def _axis_for(mesh, name, *dims):
    """`name` when the mesh has that axis and it divides every dim."""
    if name not in mesh.axis_names:
        return None
    size = mesh.shape[name]
    if size == 1 or any(d % size for d in dims):
        return None
    return name


def data_axis(mesh, *dims):
    return _axis_for(mesh, sharding_lib.DATA_AXIS, *dims)


def model_axis(mesh, *dims):
    return _axis_for(mesh, sharding_lib.MODEL_AXIS, *dims)


def rows_spec(mesh, x):
    """Spec of a `[rows..., features]` activation: the leading dim over
    the data axis when there is one to split."""
    return P(data_axis(mesh, x.shape[0]) if x.ndim > 1 else None)


def vma_of(*arrays):
    """Union of the arrays' varying-axes sets (None entries skipped) —
    the `vma=` of a kernel's `out_shape`. Empty outside shard_map."""
    return frozenset().union(
        *(jax.typeof(a).vma for a in arrays if a is not None))


def common_vma(*arrays):
    """The arrays, each cast up to `vma_of(*arrays)`: a Pallas call
    takes operands of one type. The identity outside shard_map."""
    vma = vma_of(*arrays)

    def cast(a):
        missing = tuple(sorted(vma - jax.typeof(a).vma))
        return jax.lax.pcast(a, missing, to="varying") if missing else a

    return [cast(a) for a in arrays]


def per_shard(fn, args, plan, interpret=False):
    """Runs `fn(*args)` per device of the ambient mesh.

    `plan(mesh)` returns `(in_specs, out_specs, reduce_axis)` for the
    given mesh; it is only called when there is one to split over, so
    the single-device path never builds a spec. With `reduce_axis` the
    per-device results are partial sums over that axis (a contraction
    dim was split) and are `psum`med to the full value.

    `interpret` says the kernels inside run in Pallas interpret mode
    (tests): the interpreter's own ops are not typed for varying axes,
    so the map then runs unchecked — replicated inputs' gradients are
    summed by the map's transpose instead of by `common_vma`'s casts,
    to the same values.
    """
    mesh = _partition_mesh()
    if mesh is None:
        return fn(*args)
    in_specs, out_specs, reduce_axis = plan(mesh)

    def body(*shard_args):
        out = fn(*shard_args)
        if reduce_axis is not None:
            out = jax.lax.psum(out, reduce_axis)
        return out

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         check_vma=not interpret)(*args)


__all__ = ["common_vma", "data_axis", "model_axis", "per_shard",
           "rows_spec", "vma_of"]
