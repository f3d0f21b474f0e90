"""Ulysses-style sequence parallelism: all-to-all head/sequence exchange.

The second long-context strategy next to `ring_attention` (SURVEY §5
"Long-context / sequence parallelism: Absent" — the reference scales by
DP replica count only; both strategies here are new surface). The
DeepSpeed-Ulysses recipe, re-expressed as XLA collectives:

    [B, S/n, H, D]  --all_to_all-->  [B, S, H/n, D]
         attention over the FULL sequence, local head subset
    [B, S, H/n, D]  --all_to_all-->  [B, S/n, H, D]

versus ring attention's n-step `ppermute` rotation. The trade:

- **Ulysses** does O(1) collective rounds (three tiled all-to-alls in,
  one out) and then runs the *unmodified* flash kernel over the full
  sequence — the attention inner loop is the single-device fast path,
  no per-chunk online-softmax merge. Per-device attention memory is
  O(S · H/n), i.e. it scales sequence length at fixed memory only while
  heads outnumber devices: n is capped at the head count.
- **Ring** needs only neighbor exchanges (perfect for the ICI torus),
  caps at much larger n (any divisor of S), and keeps K/V memory at
  O(S/n) — but pays n-1 rotation steps and does its softmax merge in
  HLO rather than inside the Pallas kernel.

Rule of thumb on a TPU slice: Ulysses for moderate sp degrees
(sp <= heads, e.g. one v5e-8 slice), ring for pod-scale context where
sp must exceed the head count or memory must stay strictly O(S/n).

All-to-all volume rides ICI: with the sequence sharded on "sp" and
batch on "dp", each exchange moves (n-1)/n of the local Q/K/V block
between the sp peers, the same links ring's ppermute uses.

Like `ring_attention`, everything is differentiable lax code —
`all_to_all`'s transpose is the inverse all-to-all, so `jax.grad`
flows through with the identical communication pattern reversed.
"""

import functools
import math

import jax
from jax.sharding import PartitionSpec as P


def ulysses_local(q, k, v, axis_name, causal=True, sm_scale=None,
                  impl="auto", mask=None):
    """Ulysses attention on per-device shards inside `shard_map`.

    Args:
        q, k, v: Local chunks [B, S_local, H, D]; the sequence dim is
            sharded over `axis_name`, heads are full.
        axis_name: Mesh axis of the sequence sharding. H must divide by
            the axis size (heads are scattered across it).
        causal / sm_scale: As in `cloud_tpu.ops.attention`.
        impl: Attention implementation for the full-sequence local
            compute ("auto" = flash kernel on TPU).
        mask: Optional [B, S_local] boolean key mask for this device's
            sequence chunk (True = attend). The local attention after
            the head/sequence exchange covers the FULL sequence, so the
            mask chunks are all-gathered along `axis_name` — [B, S]
            bools, a negligible fraction of the q/k/v all-to-all bytes
            — and handed to the kernel's native masked path.

    Returns:
        Local output chunk [B, S_local, H, D], same dtype as q.
    """
    from cloud_tpu import ops
    from cloud_tpu.ops.attention import repeat_kv

    n = jax.lax.psum(1, axis_name)
    heads = q.shape[2]
    h_kv = k.shape[2]
    if heads % n:
        raise ValueError(
            "Ulysses needs head count {} divisible by the {!r} axis "
            "size {} (use ring attention beyond that).".format(
                heads, axis_name, n))
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])

    # GQA: keep K/V at H_kv width through the exchange when the kv
    # heads split over the axis too — the all-to-all then moves
    # H_kv/H as many K/V bytes and the local flash kernel takes the
    # grouped layout natively. Otherwise (h_kv < n) expand first.
    if h_kv != heads and h_kv % n:
        k = repeat_kv(k, heads)
        v = repeat_kv(v, heads)

    def scatter_heads(x):  # [B, S/n, H', D] -> [B, S, H'/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    def scatter_seq(x):  # [B, S, H/n, D] -> [B, S/n, H, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    full_mask = None
    if mask is not None:
        full_mask = jax.lax.all_gather(mask.astype(bool), axis_name,
                                       axis=1, tiled=True)
    out = ops.attention(scatter_heads(q), scatter_heads(k),
                        scatter_heads(v), causal=causal,
                        sm_scale=sm_scale, impl=impl, mask=full_mask)
    return scatter_seq(out)


def ulysses_attention(q, k, v, mesh=None, axis="sp", causal=True,
                      sm_scale=None, batch_axis="auto", impl="auto",
                      mask=None):
    """Ulysses sequence-parallel attention over global [B, S, H, D].

    The standalone entry point, API-compatible with
    `sequence_parallel_attention` (ring): shards the sequence dim over
    `axis` with `shard_map`, all-to-alls into head-sharded
    full-sequence layout, runs the flash/reference kernel, and
    all-to-alls back. S and H must both divide by the axis size.
    `mask` is the global [B, S] boolean key mask (True = attend); it is
    sharded over `axis` and re-gathered inside the shard for the
    full-sequence local kernel.

    batch_axis: Mesh axis the batch dim is sharded over — "auto" picks
    the ambient data axis ("dp") when present, so Ulysses (sp) and data
    (dp) parallelism compose without replicated compute. (No head_axis
    knob: the sp all-to-all owns the head dim; combine tp with ring
    instead when heads must stay tp-sharded.)
    """
    from jax import shard_map

    from cloud_tpu.parallel import sharding as _sharding

    mesh = _sharding._resolve_mesh(mesh)
    if axis not in mesh.axis_names:
        raise ValueError(
            "Mesh axes {} have no {!r} axis for sequence parallelism; "
            "initialize the runtime with e.g. axis_names=('dp', 'sp')."
            .format(tuple(mesh.axis_names), axis))
    axis_size = mesh.shape[axis]
    batch, seq, heads = q.shape[:3]
    if seq % axis_size:
        raise ValueError(
            "Sequence length {} must divide the {!r} axis size {}."
            .format(seq, axis, axis_size))
    if heads % axis_size:
        raise ValueError(
            "Ulysses needs head count {} divisible by the {!r} axis "
            "size {} (use ring attention beyond that).".format(
                heads, axis, axis_size))

    if batch_axis == "auto":
        batch_axis = (_sharding.DATA_AXIS
                      if _sharding.DATA_AXIS in mesh.axis_names else None)
        if batch_axis is not None and batch % mesh.shape[batch_axis]:
            batch_axis = None
    elif batch_axis is not None:
        if batch_axis not in mesh.axis_names:
            raise ValueError(
                "Mesh axes {} have no {!r} batch axis.".format(
                    tuple(mesh.axis_names), batch_axis))
        if batch % mesh.shape[batch_axis]:
            raise ValueError(
                "Batch size {} is not divisible by the {!r} axis size "
                "{}.".format(batch, batch_axis, mesh.shape[batch_axis]))

    from cloud_tpu.parallel.ring_attention import sharded_sp_call

    spec = P(batch_axis, axis, None, None)
    fn = functools.partial(ulysses_local, axis_name=axis, causal=causal,
                           sm_scale=sm_scale, impl=impl)
    return sharded_sp_call(shard_map, fn, mesh, spec, axis, q, k, v,
                           mask)
