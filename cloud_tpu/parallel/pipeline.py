"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

Another capability absent from the reference (SURVEY §2.3: "tensor
parallelism, pipeline parallelism ... Nothing in the tree implements or
references them") built here as a first-class mesh axis. The design is
the shard_map pipelining pattern from the public scaling playbook: each
device along the "pp" axis holds ONE stage's parameters, activations hop
stage-to-stage with `jax.lax.ppermute` (one neighbor transfer per tick,
riding ICI), and a `lax.scan` over ticks runs the M-microbatch / n-stage
schedule in M + n - 1 ticks — device utilization M / (M + n - 1), the
standard GPipe bubble.

Everything is lax-traceable, so `jax.grad` differentiates through the
whole schedule (ppermute transposes to the reverse hop; the scan body is
`jax.checkpoint`ed so backward recomputes a tick instead of storing
every intermediate).

Usage inside shard_map (see `pipeline_apply` for the global-array entry
point):

    def stage_fn(stage_params, x):          # one pipeline stage
        ...
    y = pipeline(stage_fn, stage_params, x_microbatches, axis_name="pp")
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline(stage_fn, stage_params, microbatches, axis_name):
    """Runs the GPipe schedule inside `shard_map`.

    Args:
        stage_fn: `(stage_params, x) -> y` applying one stage; input and
            output must have the same shape/dtype (the classic pipeline
            contract — embed/head belong to stages themselves).
        stage_params: This device's stage parameters (pytree; under
            shard_map, shard the stacked [n_stages, ...] params on
            `axis_name` so each device sees its own stage's slice with
            the leading stage axis collapsed... see `pipeline_apply`).
        microbatches: [M, mb, ...] microbatched input, resident on every
            device (replicated over `axis_name`).
        axis_name: The pipeline mesh axis.

    Returns:
        [M, mb, ...] outputs of the final stage, replicated over
        `axis_name`.
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage_index = jax.lax.axis_index(axis_name)
    num_micro = microbatches.shape[0]
    total_ticks = num_micro + n_stages - 1

    # i -> i+1 activation hop; the wrap-around edge (last -> 0) carries
    # garbage that stage 0 always overwrites with a fresh microbatch.
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    # The scan carry must be typed device-varying over the pp axis from
    # tick 0 (stage outputs are varying), hence the pvary casts.
    vary = lambda v: jax.lax.pcast(v, (axis_name,), to="varying")
    carry0 = vary(jnp.zeros_like(microbatches[0]))
    outputs0 = vary(jnp.zeros_like(microbatches))

    @jax.checkpoint
    def tick(state, t):
        carry, outputs = state
        # Stage 0 ingests microbatch t (clamped; ticks >= M feed dummy
        # work that never reaches the output buffer).
        feed = microbatches[jnp.minimum(t, num_micro - 1)]
        x = jnp.where(stage_index == 0, feed, carry)
        y = stage_fn(stage_params, x)
        # The last stage finished microbatch t - (n-1) at tick t.
        mb_done = t - (n_stages - 1)
        is_last = stage_index == n_stages - 1
        outputs = jax.lax.cond(
            jnp.logical_and(is_last, mb_done >= 0),
            lambda o: o.at[jnp.maximum(mb_done, 0)].set(y),
            lambda o: o,
            outputs)
        carry = jax.lax.ppermute(y, axis_name, perm)
        return (carry, outputs), None

    (carry, outputs), _ = jax.lax.scan(
        tick, (carry0, outputs0), jnp.arange(total_ticks))
    # Only the last stage holds real outputs; broadcast them to every
    # stage so the result is replicated over the pp axis (psum of
    # one-hot contributions — a single all-reduce at the end).
    is_last = (stage_index == n_stages - 1).astype(outputs.dtype)
    return jax.lax.psum(outputs * is_last, axis_name)


def pipeline_apply(stage_fn, stacked_params, x, num_microbatches,
                   mesh=None, axis="pp", batch_axis="auto"):
    """Pipeline-parallel apply over global arrays.

    Args:
        stage_fn: `(stage_params, x) -> y`, one stage (same-shape in/out).
        stacked_params: Pytree whose leaves are stacked along a leading
            [n_stages] axis — stage i's params at index i. Sharded over
            `axis` so each device keeps only its stage.
        x: [B, ...] global input batch.
        num_microbatches: M; B must divide by it.
        mesh: Mesh override; default ambient.
        axis: Pipeline mesh axis name.
        batch_axis: Mesh axis the microbatch dim is sharded over —
            "auto" picks the ambient data axis ("dp") when the mesh has
            one and the per-microbatch size divides it, so pp composes
            with dp in ONE mesh: each dp group runs the full schedule
            on its batch shard, stage params replicated across dp (the
            dp gradient psum over stage grads is inserted by shard_map's
            transpose). None forces replication (pure pp).

    Returns:
        [B, ...] output of the last stage.
    """
    from jax import shard_map

    from cloud_tpu.parallel import sharding as sharding_lib

    mesh = sharding_lib._resolve_mesh(mesh)
    if axis not in mesh.axis_names:
        raise ValueError(
            "Mesh axes {} have no {!r} axis for pipeline parallelism."
            .format(tuple(mesh.axis_names), axis))
    n_stages = mesh.shape[axis]
    batch = x.shape[0]
    if num_microbatches < 1 or batch % num_microbatches:
        raise ValueError(
            "Batch size {} is not divisible by num_microbatches {}."
            .format(batch, num_microbatches))
    micro_b = batch // num_microbatches

    if batch_axis == "auto":
        batch_axis = (sharding_lib.DATA_AXIS
                      if sharding_lib.DATA_AXIS in mesh.axis_names
                      else None)
        if batch_axis is not None and micro_b % mesh.shape[batch_axis]:
            # Falling back to replication is correct but duplicates the
            # whole schedule on every dp group — say so instead of
            # silently burning dp-fold compute.
            import logging
            logging.getLogger("cloud_tpu").warning(
                "pipeline_apply: microbatch size %d does not divide the "
                "'%s' axis (size %d); running the pipeline REPLICATED "
                "across it. Raise the batch or lower num_microbatches "
                "to restore data parallelism.",
                micro_b, batch_axis, mesh.shape[batch_axis])
            batch_axis = None
    elif batch_axis is not None:
        if batch_axis not in mesh.axis_names:
            raise ValueError(
                "Mesh axes {} have no {!r} batch axis.".format(
                    tuple(mesh.axis_names), batch_axis))
        if micro_b % mesh.shape[batch_axis]:
            raise ValueError(
                "Microbatch size {} is not divisible by the {!r} axis "
                "size {}.".format(micro_b, batch_axis,
                                  mesh.shape[batch_axis]))

    def check_leading(leaf):
        if leaf.shape[0] != n_stages:
            raise ValueError(
                "stacked_params leaves must have leading dim n_stages={}"
                "; got shape {}.".format(n_stages, leaf.shape))
        return leaf

    jax.tree_util.tree_map(check_leading, stacked_params)

    micro = x.reshape((num_microbatches, micro_b) + x.shape[1:])

    def local_fn(stage_params, microbatches):
        # shard_map keeps the sharded leading stage axis as size 1;
        # collapse it so stage_fn sees this stage's params directly.
        own = jax.tree_util.tree_map(lambda l: l[0], stage_params)
        return pipeline(stage_fn, own, microbatches, axis_name=axis)

    params_spec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    micro_spec = P(None, batch_axis)
    out = shard_map(
        local_fn, mesh=mesh,
        in_specs=(params_spec, micro_spec),
        out_specs=micro_spec)(stacked_params, micro)
    return out.reshape((batch,) + out.shape[2:])
