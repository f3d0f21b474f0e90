"""Paged decode attention as a Pallas TPU kernel.

The serving tick's hot op: one (or `spec_k + 1`) query positions per
slot attending over that slot's logical KV cache, which lives scattered
across a physical page pool (`key_pages`/`value_pages`
`[num_pages, page_size, H*D]`, serving/kvpool.py) and is addressed
through a per-slot page table. A page row is one token's K (or V) for
every head, heads folded into the lane dim: the TPU stores an array
whose minor dim is under 128 with the *page* index minor-most (its
compact layout), and Mosaic would then need the whole pool copied
row-major before every call — at H*D wide the device layout is the
kernel's own. The lax path materializes a dense
`[slots, cache_len, H, D]` view by gathering the pool through the page
table every tick; this kernel never does — the page table rides as a
scalar-prefetch operand, so each grid step's K/V block is *indexed*
straight out of the pool in HBM (the gather becomes block addressing)
and streamed through VMEM with FlashAttention-style online softmax.

Grid and masking contract (see /opt/skills/guides/pallas_guide.md):
- Grid is (slots, pages_per_slot) with the page dimension innermost.
  Program (s, j) serves every head of slot s against logical page j;
  its K/V block is the whole physical page `page_table[s, j]`
  (`[P, H*D]`, the array's full trailing dims, which is what Mosaic's
  block rule asks of a 16-row page) — `PrefetchScalarGridSpec` places
  the table in SMEM before the kernel runs so the BlockSpec index maps
  can read it.
- Heads stay folded in lanes. Per query row the scores are
  `(k * q_row) @ seg`, with `seg` the `[H*D, H]` 0/1 matrix that sums
  each head's D lanes, giving `[P, H]` (keys on sublanes, heads on
  lanes); `p @ seg.T` spreads the probabilities back over the lanes to
  weight V. No per-head slicing or relayout; the matmuls are f32 at
  HIGHEST precision, so the sums are the reference's f32 accumulation.
- VMEM scratch (acc `[seq, H*D]`, m/l `[seq, H]`) carries the
  online-softmax state across page steps; the output block is written
  on the last page step.
- Masking is purely the caller's `allowed [slots, seq, cache_len]`
  (from `decoding.paged_slot_update`): it already encodes per-query
  causality over *logical* key slots plus slot validity, so freed /
  never-written / scratch-page-0 entries carry exact-zero weight — the
  kernel zeroes masked probabilities explicitly (`p = where(mask, ...)`)
  rather than relying on exp underflow, so a fully-masked row (e.g. a
  padded query row or an evicted slot) outputs zeros, never a uniform
  average over pool garbage.
- `seq` (1 for the plain tick, spec_k + 1 for the speculative verify
  window) is a static unrolled loop over query rows.

The gathered-lax reference below is bitwise the math
`models/transformer.py::_paged_decode_attention` shipped before this
kernel (gather -> f32 einsum -> -1e30 mask -> softmax -> cast ->
einsum), so engine-vs-solo bit-identity pins keep holding wherever the
reference is selected. Off-TPU the kernel path executes as
`_paged_walk_lax` — the same page-block walk and online-softmax update
order, vectorized in lax (Mosaic can't compile there, and Pallas
interpret mode is two orders of magnitude too slow for a serving
tick) — which is what the `CLOUD_TPU_PAGED_KERNEL=1` smoke measures;
the parity suite additionally forces `interpret=True` to pin the true
interpreted kernel against both the walk and the reference.

Quantized pages (graftpack): with `key_scales`/`value_scales` given
(`[num_pages, heads]` f32, per-page per-head symmetric scales), the
K/V pages are int8 and every impl dequantizes INSIDE its block load —
the dequant contract, identical across kernel/walk/reference:

    k_f32 = k_int8.astype(f32) * scale[page, head]

and both the QK and PV dots run in f32 (int8 quantization already
costs ~0.4% relative error, so bf16 intermediate rounding would
dominate it). In the kernel the page's `[1, H]` scale row is one more
VMEM block indexed through the page table (not SMEM, whose size would
cap the pool); the dequant folds into the `[P, H]` scores and
probabilities as a per-head multiply, and nothing dequantized is ever
materialized in HBM. The walk and reference grow the same math,
so the parity suite covers all three impls in int8 mode too. A zero
scale means an all-zero (never-written) page and dequantizes to exact
zeros.

Forward only: decode never differentiates through the cache.
"""

import functools
import math
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from cloud_tpu.ops import partition

#: The kernel's declared name (table in monitoring/spans.py): the
#: trace's op text carries it, whatever module calls the kernel.
PAGED_DECODE = "paged_decode"

#: `pl.pallas_call(name=)` is the innermost scope, and XLA:TPU names
#: the custom call by it (`%<name>.N`). The benchmark's accepted
#: `paged_attn_roofline` finds this kernel as a custom call whose name
#: holds `_paged_decode_attention` (the flax method it used to be named
#: by), so the call passes the declared name behind that prefix until
#: a `benchmark` PR moves the reader to the declared name.
_CALL_PREFIX = "attention._paged_decode_attention."

_NEG_INF = -1e30
_HIGHEST = jax.lax.Precision.HIGHEST


class _PagedConfig(NamedTuple):
    sm_scale: float
    heads: int
    seq: int
    page_size: int
    interpret: bool
    quantized: bool = False


def _check_scales(key_pages, key_scales, value_scales, heads):
    """Validates the int8-page calling convention: both scale arrays or
    neither; int8 pages; [num_pages, heads] f32 scales."""
    if (key_scales is None) != (value_scales is None):
        raise ValueError(
            "key_scales and value_scales must be given together.")
    if key_scales is None:
        return False
    num_pages = key_pages.shape[0]
    if key_pages.dtype != jnp.int8:
        raise ValueError(
            "scales imply int8 pages; got page dtype {}.".format(
                key_pages.dtype))
    for name, s in (("key_scales", key_scales),
                    ("value_scales", value_scales)):
        if s.shape != (num_pages, heads):
            raise ValueError(
                "{} must be [num_pages, heads] = {}; got {}.".format(
                    name, (num_pages, heads), s.shape))
    return True


def paged_attention_reference(q, key_pages, value_pages, page_table,
                              allowed, sm_scale=None, key_scales=None,
                              value_scales=None):
    """Gathered-lax paged decode attention (the correctness oracle).

    q: [slots, seq, H, D]; key_pages/value_pages: [N, P, H*D];
    page_table: [slots, pages_per_slot] int32; allowed:
    [slots, seq, cache_len] bool (True = attend) ->
    [slots, seq, H, D] in the page dtype (q's dtype for int8 pages).

    Logical per-slot [cache_len] views, one gather per call — bitwise
    the pre-kernel serving-tick math, kept verbatim so the kernel-off
    engine stays bit-identical to solo `generate()` decodes. With
    `key_scales`/`value_scales` the int8 pages are dequantized into
    the gathered f32 view (the module-level dequant contract) and the
    whole computation stays f32.
    """
    page_size = key_pages.shape[1]
    heads, head_dim = q.shape[2:]
    slots, pages_per_slot = page_table.shape
    cache_len = pages_per_slot * page_size
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    quantized = _check_scales(key_pages, key_scales, value_scales,
                              heads)

    def view(pages, scales):
        """[slots, cache_len, H, D] logical view of the slots' pages."""
        g = pages[page_table].reshape(slots, pages_per_slot, page_size,
                                      heads, head_dim)
        if quantized:
            g = g.astype(jnp.float32) * scales[page_table][
                :, :, None, :, None]
        return g.reshape(slots, cache_len, heads, head_dim)

    k_view = view(key_pages, key_scales)
    v_view = view(value_pages, value_scales)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_view,
                        preferred_element_type=jnp.float32) * sm_scale
    logits = jnp.where(allowed[:, None], logits, _NEG_INF)
    out_dtype = q.dtype if quantized else value_pages.dtype
    weights = jax.nn.softmax(logits, axis=-1).astype(
        jnp.float32 if quantized else value_pages.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights,
                      v_view).astype(out_dtype)


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------


def _paged_kernel(pt_ref, q_ref, k_ref, v_ref, a_ref, seg_ref,
                  segt_ref, *rest, config, num_pages):
    """One (slot, logical page) step for every head. Int8 pages bring
    two more inputs, the page's `[1, H]` K and V scale rows:
    `s = ((k_i8 * q) @ seg) * (ks * sm_scale)` and
    `acc += sum_p (p * vs) @ seg.T * v_i8` are exactly the pre-dot
    dequant contract because a scale is constant over its head's
    lanes."""
    del pt_ref  # consumed by the BlockSpec index maps
    if config.quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    ji = pl.program_id(1)
    page = config.page_size

    @pl.when(ji == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def dot(a, b):
        return jnp.dot(a, b, precision=_HIGHEST,
                       preferred_element_type=jnp.float32)

    k = k_ref[0].astype(jnp.float32)     # [P, H*D], page pt[slot, ji]
    v = v_ref[0].astype(jnp.float32)
    seg = seg_ref[...]                   # [H*D, H]
    segt = segt_ref[...]                 # [H, H*D]
    scale = config.sm_scale
    if config.quantized:
        scale = ks_ref[0] * config.sm_scale          # [1, H]
    for i in range(config.seq):
        row = slice(i, i + 1)
        q = q_ref[0, row, :].astype(jnp.float32)     # [1, H*D]
        s = dot(k * q, seg) * scale                  # [P, H]
        mask = a_ref[0, 0, i * page:(i + 1) * page, :] != 0  # [P, 1]
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[row, :]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)             # [1, H]
        # Explicit zero where masked: exp(s - m) underflows to 0 for
        # normal rows, but a fully-masked row (evicted slot, scratch
        # page) has m == s == -inf and exp(0) == 1 would leak pool
        # garbage.
        p = jnp.where(mask, jnp.exp(s - m_next), 0.0)
        l_ref[row, :] = alpha * l_ref[row, :] + jnp.sum(
            p, axis=0, keepdims=True)
        if config.quantized:
            p = p * vs_ref[0]
        pv = jnp.sum(dot(p, segt) * v, axis=0, keepdims=True)
        acc_ref[row, :] = acc_ref[row, :] * dot(alpha, segt) + pv
        m_ref[row, :] = m_next

    @pl.when(ji == num_pages - 1)
    def _finalize():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / dot(safe_l, segt)).astype(
            o_ref.dtype)


def _paged_forward(config, q, key_pages, value_pages, page_table,
                   allowed, key_scales=None, value_scales=None):
    """q: [S, seq, H*D]; allowed: [S, pages_per_slot, seq*P, 1] int32;
    scales (int8 mode): [N, 1, H] -> out [S, seq, H*D].

    The page table is the scalar-prefetch operand: index maps read
    `pt[s, j]` to address each program's physical K/V page (and, in
    int8 mode, its scale rows), so the pool is only ever touched at the
    pages a slot actually owns.
    """
    slots, seq, width = q.shape
    heads = config.heads
    page_size = config.page_size
    pages_per_slot = page_table.shape[1]
    kernel = functools.partial(_paged_kernel, config=config,
                               num_pages=pages_per_slot)
    # seg[c, h] = 1 where lane c belongs to head h.
    seg = (jnp.arange(width)[:, None] // (width // heads)
           == jnp.arange(heads)[None, :]).astype(jnp.float32)
    operands = [page_table, q, key_pages, value_pages, allowed, seg,
                seg.T]
    if config.quantized:
        operands += [key_scales, value_scales]
    operands = partition.common_vma(*operands)

    whole = lambda shape: pl.BlockSpec(shape, lambda s, j, pt: (0, 0))
    slot_block = pl.BlockSpec((1, seq, width),
                              lambda s, j, pt: (s, 0, 0))
    # K/V blocks are single physical pages, gathered by block
    # *indexing* through the prefetched table — never an HBM
    # materialization of the dense [S, cache_len, H, D] view.
    page_block = pl.BlockSpec((1, page_size, width),
                              lambda s, j, pt: (pt[s, j], 0, 0))
    in_specs = [
        slot_block, page_block, page_block,
        pl.BlockSpec((1, 1, seq * page_size, 1),
                     lambda s, j, pt: (s, j, 0, 0)),
        whole((width, heads)), whole((heads, width)),
    ]
    if config.quantized:
        scale_block = pl.BlockSpec((1, 1, heads),
                                   lambda s, j, pt: (pt[s, j], 0, 0))
        in_specs += [scale_block, scale_block]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(slots, pages_per_slot),
        in_specs=in_specs,
        out_specs=slot_block,
        scratch_shapes=[
            pltpu.VMEM((seq, width), jnp.float32),
            pltpu.VMEM((seq, heads), jnp.float32),
            pltpu.VMEM((seq, heads), jnp.float32),
        ],
    )
    out_dtype = q.dtype if config.quantized else value_pages.dtype
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            q.shape, out_dtype, vma=partition.vma_of(*operands)),
        interpret=config.interpret,
        name=_CALL_PREFIX + PAGED_DECODE,
    )(*operands)


def _paged_walk_lax(q, key_pages, value_pages, page_table, allowed,
                    sm_scale, key_scales=None, value_scales=None):
    """The kernel's defining math as vectorized lax: walk the page
    blocks in grid order, gathering ONLY the slots' own pages (one
    [slots, P, H*D] take per logical page — never the dense
    [slots, cache_len] view), with the exact online-softmax update
    sequence `_paged_kernel` runs per step. This is the off-TPU
    execution of the kernel path: Mosaic can't compile there and
    Pallas interpret mode is ~100x too slow for a serving tick, so the
    `CLOUD_TPU_PAGED_KERNEL=1` smoke runs this form while the parity
    suite pins it against the true interpreted kernel
    (`interpret=True`) and the gathered reference. Int8 pages are
    dequantized per page block in f32 (the module dequant contract)."""
    page_size = key_pages.shape[1]
    slots, seq, heads, head_dim = q.shape
    pages_per_slot = page_table.shape[1]
    quantized = key_scales is not None
    am = allowed.reshape(slots, seq, pages_per_slot, page_size)
    m = jnp.full((slots, heads, seq, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((slots, heads, seq, 1), jnp.float32)
    acc = jnp.zeros((slots, heads, seq, head_dim), jnp.float32)
    for j in range(pages_per_slot):
        pages = page_table[:, j]
        k = key_pages[pages].reshape(slots, page_size, heads,
                                     head_dim)
        v = value_pages[pages].reshape(k.shape)
        if quantized:
            k = k.astype(jnp.float32) * key_scales[pages][:, None, :,
                                                          None]
            v = v.astype(jnp.float32) * value_scales[pages][:, None, :,
                                                            None]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * sm_scale
        mask = am[:, :, j, :][:, None]       # [slots, 1, seq, P]
        s = jnp.where(mask, s, _NEG_INF)
        m_curr = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m, m_curr)
        alpha = jnp.exp(m - m_next)
        p = jnp.where(mask, jnp.exp(s - m_next), 0.0)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhqk,bkhd->bhqd",
                                       p.astype(v.dtype), v)
        m = m_next
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out_dtype = q.dtype if quantized else value_pages.dtype
    out = (acc / safe_l).astype(out_dtype)
    return jnp.transpose(out, (0, 2, 1, 3))


def paged_decode_attention(q, key_pages, value_pages, page_table,
                           allowed, sm_scale=None,
                           interpret: Optional[bool] = None,
                           key_scales=None, value_scales=None):
    """Pallas paged decode attention; layouts as the reference.

    Handles both the seq=1 plain tick and the seq=spec_k+1 speculative
    verify window. Output matches
    `paged_attention_reference` to online-softmax accumulation order —
    tolerance-level, not bitwise; fully-masked rows (evicted slots)
    output exact zeros. With scales given the pages
    are int8 and the kernel dequantizes in its block loads (module
    docstring).

    interpret: None (default) compiles the kernel on TPU and runs the
    lax page-walk form of the same math elsewhere; True forces Pallas
    interpret mode (the parity suite's same-code-path check — far too
    slow for a serving tick).
    """
    page_size = key_pages.shape[1]
    slots, seq, heads, head_dim = q.shape
    pages_per_slot = page_table.shape[1]
    cache_len = pages_per_slot * page_size
    if key_pages.ndim != 3 or key_pages.shape[2] != heads * head_dim:
        raise ValueError(
            "key_pages must be [num_pages, page_size, heads * head_dim"
            " = {}] — the paged decode cache stores full-width heads; "
            "got {}.".format(heads * head_dim, key_pages.shape))
    if value_pages.shape != key_pages.shape:
        raise ValueError(
            "key_pages and value_pages must have identical shapes; "
            "got {} vs {}.".format(key_pages.shape, value_pages.shape))
    if allowed.shape != (slots, seq, cache_len):
        raise ValueError(
            "allowed must be [slots, seq, cache_len] = {}; got "
            "{}.".format((slots, seq, cache_len), allowed.shape))
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    quantized = _check_scales(key_pages, key_scales, value_scales,
                              heads)
    if interpret is None:
        if jax.default_backend() != "tpu":
            return _paged_walk_lax(q, key_pages, value_pages,
                                   page_table, allowed,
                                   float(sm_scale),
                                   key_scales=key_scales,
                                   value_scales=value_scales)
        interpret = False

    args = [q, key_pages, value_pages, page_table.astype(jnp.int32),
            allowed]
    if quantized:
        args += [key_scales, value_scales]

    def kernel(q, key_pages, value_pages, page_table, allowed,
               *scales):
        """[slots, seq, H', D] over one device's H' heads."""
        local_heads = q.shape[2]
        config = _PagedConfig(sm_scale=float(sm_scale),
                              heads=local_heads, seq=seq,
                              page_size=page_size,
                              interpret=bool(interpret),
                              quantized=quantized)
        # [slots, pages, seq * P, 1]: per page, each query row's P key
        # flags as a sublane column.
        amask = jnp.transpose(
            allowed.astype(jnp.int32).reshape(
                slots, seq, pages_per_slot, page_size),
            (0, 2, 1, 3)).reshape(slots, pages_per_slot,
                                  seq * page_size, 1)
        out = _paged_forward(
            config, q.reshape(slots, seq, local_heads * head_dim),
            key_pages, value_pages, page_table, amask,
            *(sc[:, None, :] for sc in scales))
        return out.reshape(q.shape)

    def plan(mesh):
        """Heads over the model axis; slots share the pool, so every
        other axis sees the whole call."""
        tp = partition.model_axis(mesh, heads)
        by_head = P(None, None, tp, None)
        pages = P(None, None, tp)
        specs = [by_head, pages, pages, P(), P()]
        if quantized:
            specs += [P(None, tp)] * 2
        return tuple(specs), by_head, None

    return partition.per_shard(kernel, args, plan, interpret)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def paged_attention(q, key_pages, value_pages, page_table, allowed,
                    sm_scale=None, impl="auto",
                    interpret: Optional[bool] = None,
                    key_scales=None, value_scales=None):
    """Dispatching paged decode attention: Pallas kernel or gathered lax.

    impl: "paged" forces the kernel, "reference" forces the gathered
    lax path; "auto" (and any training-side impl name such as "flash",
    which has no paged analogue) picks the kernel on TPU and the
    reference elsewhere. The `CLOUD_TPU_PAGED_KERNEL` env var is the
    deployment/A-B override and beats `impl`: "1" forces the kernel
    (interpret mode off-TPU, so CPU CI drives the kernel code path),
    "0" forces the reference, unset/empty defers to `impl`.
    key_scales/value_scales select int8-page mode on whichever impl is
    picked (the dequant contract in the module docstring).
    """
    env = os.environ.get("CLOUD_TPU_PAGED_KERNEL", "").strip()
    if env == "1":
        use_kernel = True
    elif env == "0":
        use_kernel = False
    elif impl == "paged":
        use_kernel = True
    elif impl == "reference":
        use_kernel = False
    else:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        return paged_decode_attention(q, key_pages, value_pages,
                                      page_table, allowed,
                                      sm_scale=sm_scale,
                                      interpret=interpret,
                                      key_scales=key_scales,
                                      value_scales=value_scales)
    return paged_attention_reference(q, key_pages, value_pages,
                                     page_table, allowed,
                                     sm_scale=sm_scale,
                                     key_scales=key_scales,
                                     value_scales=value_scales)


def paged_attention_cost(slots, seq, heads, head_dim, page_size,
                         pages_per_slot, dtype=jnp.bfloat16,
                         kv_dtype=None):
    """Per-call flops / bytes-moved row for the telemetry gauges.

    flops come from the jit cost-analysis hook (the PR 6 idiom —
    `lower().cost_analysis()`, exception-swallowed) on
    the gathered reference at these shapes; bytes_moved is the kernel's
    HBM traffic (q + out + the slot's own K/V pages + table + mask),
    i.e. what the fused path touches — NOT the dense gather the
    reference materializes. kv_dtype (default: `dtype`) sizes the K/V
    page traffic separately so int8 pages report their real, smaller
    byte movement (plus the per-page f32 scale reads). Returns
    {"flops", "bytes_moved"}; never raises (falls back to the analytic
    flop count).
    """
    cache_len = page_size * pages_per_slot
    num_pages = slots * pages_per_slot + 1
    itemsize = jnp.dtype(dtype).itemsize
    kv_itemsize = jnp.dtype(kv_dtype or dtype).itemsize
    quantized = kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8
    # 2 matmuls (qk^T, pv), 2 flops per MAC.
    flops = 4.0 * slots * seq * cache_len * heads * head_dim
    try:
        shapes = (
            jax.ShapeDtypeStruct((slots, seq, heads, head_dim), dtype),
            jax.ShapeDtypeStruct((num_pages, page_size,
                                  heads * head_dim), dtype),
            jax.ShapeDtypeStruct((num_pages, page_size,
                                  heads * head_dim), dtype),
            jax.ShapeDtypeStruct((slots, pages_per_slot), jnp.int32),
            jax.ShapeDtypeStruct((slots, seq, cache_len), jnp.bool_),
        )
        analysis = jax.jit(paged_attention_reference).lower(
            *shapes).cost_analysis()
        flops = float(analysis.get("flops", flops) or flops)
    except Exception:
        pass
    bytes_moved = float(
        2 * slots * cache_len * heads * head_dim * kv_itemsize  # K/V
        + 2 * slots * seq * heads * head_dim * itemsize       # q + out
        + slots * pages_per_slot * 4                          # table
        + slots * seq * cache_len)                            # mask
    if quantized:
        # Per-page per-head f32 K and V scale rows.
        bytes_moved += float(2 * slots * pages_per_slot * heads * 4)
    return {"flops": flops, "bytes_moved": bytes_moved}
