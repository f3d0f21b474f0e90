"""Ring attention: sequence/context parallelism over the device mesh.

Long-context support the reference never had (SURVEY §5 "Long-context /
sequence parallelism: Absent"): sequence length there is never a concept
and scaling is DP-only. Here long context is first-class — the sequence
axis of Q/K/V is sharded over a mesh axis ("sp"), each device keeps its
local Q chunk resident, and K/V chunks rotate around the ring via
`jax.lax.ppermute` (neighbor exchange rides the ICI torus links; no
all-gather, so per-device memory is O(S/n) instead of O(S)).

Per ring step each device computes blockwise attention of its Q chunk
against the visiting K/V chunk and folds the result into a running
(output, logsumexp) pair with the numerically-stable online-softmax
merge — the same recurrence the Pallas flash kernel uses across k-blocks
(cloud_tpu/ops/attention.py), lifted one level up to mesh shards. The
per-chunk einsums are plain XLA matmuls (MXU-tiled by the compiler);
chunks strictly above the causal diagonal skip the compute via
`lax.cond`.

Everything is pure lax (scan + ppermute), so `jax.grad` differentiates
straight through it — ppermute's transpose is the reverse permute, which
XLA again schedules on ICI. The scan body is `jax.checkpoint`ed: the
backward pass recomputes per-chunk attention instead of keeping
O(steps) residuals, the standard flash/ring memory trade.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


def _chunk_attention(q, k, v, row_offset, col_offset, kv_len, causal,
                     sm_scale, key_mask=None):
    """Attention of a Q chunk against one K/V chunk, with logsumexp.

    Args:
        q: [B, Sq, H, D] local query chunk.
        k, v: [B, Sk, H, D] visiting key/value chunk.
        row_offset / col_offset: Global positions of element 0 of the
            chunks (traced values; the ring rotates col_offset).
        kv_len: True global K/V length (masks ring padding).
        causal / sm_scale: As in `ring_attention`.
        key_mask: Optional [B, Sk] per-example key validity for THIS
            visiting chunk (True = attend); rotates with k/v.

    Returns:
        (out, lse): normalized chunk output [B, Sq, H, D] and its
        logsumexp [B, Sq, H] (−inf rows ⇒ fully-masked chunk).
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    rows = row_offset + jnp.arange(q.shape[1])
    cols = col_offset + jnp.arange(k.shape[1])
    mask = (cols < kv_len)[None, :]
    if causal:
        mask = mask & (cols[None, :] <= rows[:, None])
    mask = mask[None, None]                 # [1, 1, {1|Sq}, Sk]
    if key_mask is not None:
        mask = mask & key_mask[:, None, None, :]  # [B, 1, {1|Sq}, Sk]
    logits = jnp.where(mask, logits, _NEG_INF)

    m = jnp.max(logits, axis=-1)                      # [B, H, Sq]
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)                           # [B, H, Sq]
    masked = m <= _NEG_INF / 2
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out / safe_l.transpose(0, 2, 1)[..., None]
    lse = jnp.where(masked, -jnp.inf, m + jnp.log(safe_l))
    return out, lse.transpose(0, 2, 1)                # [B, Sq, H]


def _merge(o1, lse1, o2, lse2):
    """Online-softmax merge of two normalized partial attentions."""
    m = jnp.maximum(lse1, lse2)
    m = jnp.where(jnp.isneginf(m), 0.0, m)            # both empty: avoid nan
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    total = w1 + w2
    safe = jnp.where(total == 0.0, 1.0, total)
    out = (o1 * w1[..., None] + o2 * w2[..., None]) / safe[..., None]
    lse = m + jnp.log(safe)
    lse = jnp.where(total == 0.0, -jnp.inf, lse)
    return out, lse


def ring_attention(q, k, v, axis_name, causal=True, sm_scale=None,
                   kv_len=None, mask=None):
    """Sequence-parallel attention inside `shard_map`.

    Call this from a `shard_map`-ed function whose inputs shard the
    sequence dim of q/k/v over `axis_name`. Each device holds
    [B, S/n, H, D] of each operand; K/V rotate n steps around the ring.

    Args:
        q, k, v: Local chunks, [B, S_local, H, D].
        axis_name: Mesh axis the sequence is sharded over.
        causal: Autoregressive masking in *global* positions.
        sm_scale: Softmax scale; default 1/sqrt(D).
        kv_len: True global sequence length when the padded global length
            (S_local * axis_size) exceeds it; default no padding.
        mask: Optional [B, S_local] boolean key mask for THIS device's
            local sequence chunk (True = attend) — the per-example
            padding contract of `flash_attention`, sharded with the
            sequence. The mask chunk rotates around the ring alongside
            its k/v chunk. Rows whose keys end up all masked output
            zeros (flash convention): although the finite _NEG_INF
            makes a fully-masked chunk's softmax a uniform average
            locally, `_chunk_attention` flags such rows with an lse of
            −inf, and `_merge` weighs an −inf-lse contribution to
            exactly zero — so the uniform average never reaches the
            output (pinned by
            tests/unit/test_ring_attention.py::test_fully_masked_rows).
            Any pattern is supported, not just contiguous prefixes.

    Returns:
        Local output chunk [B, S_local, H, D], same dtype as q.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    axis_size = jax.lax.psum(1, axis_name)
    my_index = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    if kv_len is None:
        kv_len = s_local * axis_size
    if mask is not None:
        mask = mask.astype(bool)

    row_offset = my_index * s_local
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def compute_chunk(out, lse, ck, cv, cm, chunk_index):
        """Folds one visiting chunk into (out, lse), skipping the
        attention compute entirely for chunks strictly above the causal
        diagonal (their mask is all-False; `lax.cond` makes that a real
        skip, not a masked full-price einsum). `cm` is the visiting
        chunk's key mask (None when no padding mask is in play — a
        static choice, so the no-mask path compiles identically to
        before)."""
        def visit(out, lse, ck, cv, cm):
            chunk_out, chunk_lse = _chunk_attention(
                q, ck, cv, row_offset, chunk_index * s_local, kv_len,
                causal, sm_scale, key_mask=cm)
            return _merge(out, lse, chunk_out, chunk_lse)

        if not causal:
            return visit(out, lse, ck, cv, cm)
        fully_masked = chunk_index * s_local > row_offset + s_local - 1
        return jax.lax.cond(fully_masked,
                            lambda out, lse, ck, cv, cm: (out, lse),
                            visit, out, lse, ck, cv, cm)

    # Derived from q (not fresh literals) so the carry is marked varying
    # over `axis_name` under shard_map's per-axis type system.
    out0 = (q * 0).astype(jnp.float32)
    lse0 = jnp.sum(out0, axis=-1) - jnp.inf           # [B, Sq, H]

    # Step 0: the locally-resident chunk, no rotation needed.
    out, lse = compute_chunk(out0, lse0, k, v, mask, my_index)

    @jax.checkpoint
    def body(carry, step):
        # `mask is None` is static: the carry simply has no mask leaf
        # on the unmasked path (None is an empty pytree).
        out, lse, ck, cv, cm = carry
        ck = jax.lax.ppermute(ck, axis_name, perm)
        cv = jax.lax.ppermute(cv, axis_name, perm)
        if cm is not None:
            cm = jax.lax.ppermute(cm, axis_name, perm)
        # After `step` forward rotations, this device holds the chunk
        # originally resident on (my_index - step) mod n.
        chunk_index = jax.lax.rem(my_index - step + axis_size, axis_size)
        out, lse = compute_chunk(out, lse, ck, cv, cm, chunk_index)
        return (out, lse, ck, cv, cm), None

    (out, _, _, _, _), _ = jax.lax.scan(
        body, (out, lse, k, v, mask), jnp.arange(1, axis_size))
    return out.astype(q.dtype)


def sharded_sp_call(shard_map_fn, fn, mesh, spec, seq_axis, q, k, v,
                     mask):
    """Shared masked/unmasked shard_map entry for the sp strategies.

    One place owns the mask leg of the entry contract (shape check,
    [B, S] spec over (batch, sequence) axes, bool cast) so ring and
    ulysses can't drift apart.
    """
    if mask is None:
        return shard_map_fn(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec)(q, k, v)
    expect = (q.shape[0], q.shape[1])
    if mask.shape != expect:
        raise ValueError(
            "mask must be [batch, seq] = {}; got {}.".format(
                expect, mask.shape))
    mask_spec = P(spec[0], seq_axis)
    masked = lambda q, k, v, m: fn(q, k, v, mask=m)
    return shard_map_fn(masked, mesh=mesh,
                        in_specs=(spec, spec, spec, mask_spec),
                        out_specs=spec)(q, k, v, mask.astype(bool))


def sequence_parallel_attention(q, k, v, mesh=None, axis="sp", causal=True,
                                sm_scale=None, batch_axis="auto",
                                head_axis="auto", mask=None):
    """Ring attention over global [B, S, H, D] arrays on a mesh.

    The standalone entry point: shards the sequence dim over `axis` with
    `shard_map` and runs `ring_attention` per shard. S must divide by the
    axis size (pad upstream; causal masking makes right-padding safe for
    all non-pad rows). `mask` is the global [B, S] boolean key mask
    (True = attend, the `flash_attention` padded-batch contract); it is
    sharded over `axis` with the sequence and rotates with k/v.

    batch_axis: Mesh axis the batch dim is sharded over — "auto" picks
    the ambient data axis ("dp") when the mesh has one, so ring (sp) and
    data (dp) parallelism compose without replicated compute.

    head_axis: Mesh axis the head dim is sharded over — "auto" picks the
    ambient model axis ("tp") when the mesh has one and the head count
    divides it. Heads are independent in attention, so this composes
    ring (sp) with Megatron-style tensor parallelism (tp-sharded qkv
    heads stay resident; no cross-tp gather).
    """
    from jax import shard_map

    from cloud_tpu.parallel import sharding as _sharding_resolve

    if k.shape[2] != q.shape[2]:
        # GQA: the ring rotates K/V at full q-head width (no native
        # grouped path yet — the per-chunk einsums assume matching
        # heads), so expand before sharding. Ulysses keeps H_kv width;
        # prefer it when kv heads divide the sp axis.
        from cloud_tpu.ops.attention import repeat_kv
        k = repeat_kv(k, q.shape[2])
        v = repeat_kv(v, q.shape[2])

    mesh = _sharding_resolve._resolve_mesh(mesh)
    if axis not in mesh.axis_names:
        raise ValueError(
            "Mesh axes {} have no {!r} axis for sequence parallelism; "
            "initialize the runtime with e.g. axis_names=('dp', 'sp').".format(
                tuple(mesh.axis_names), axis))
    axis_size = mesh.shape[axis]
    seq = q.shape[1]
    if seq % axis_size:
        raise ValueError(
            "Sequence length {} must divide the {!r} axis size {}.".format(
                seq, axis, axis_size))

    from cloud_tpu.parallel import sharding as _sharding

    def _resolve_axis(value, default_axis, dim, what):
        """auto -> default axis when present+divisible; explicit axes
        are validated, only the implicit path gets silent fallback."""
        if value == "auto":
            resolved = (default_axis
                        if default_axis in mesh.axis_names else None)
            if resolved is not None and dim % mesh.shape[resolved]:
                resolved = None
            return resolved
        if value is None:
            return None
        if value not in mesh.axis_names:
            raise ValueError(
                "Mesh axes {} have no {!r} {} axis.".format(
                    tuple(mesh.axis_names), value, what))
        if dim % mesh.shape[value]:
            raise ValueError(
                "{} size {} is not divisible by the {!r} axis size "
                "{}.".format(what.capitalize(), dim, value,
                             mesh.shape[value]))
        return value

    batch_axis = _resolve_axis(batch_axis, _sharding.DATA_AXIS,
                               q.shape[0], "batch")
    head_axis = _resolve_axis(head_axis, _sharding.MODEL_AXIS,
                              q.shape[2], "head")
    spec = P(batch_axis, axis, head_axis, None)
    fn = functools.partial(ring_attention, axis_name=axis, causal=causal,
                           sm_scale=sm_scale, kv_len=seq)
    return sharded_sp_call(shard_map, fn, mesh, spec, axis, q, k, v,
                           mask)
