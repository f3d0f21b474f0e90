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
scalar-prefetch operand, so each grid step's K/V blocks are *indexed*
straight out of the pool in HBM (the gather becomes block addressing)
and streamed through VMEM with FlashAttention-style online softmax.

Grid and masking contract (see /opt/skills/guides/pallas_guide.md):
- The walk goes a *group* of G logical pages at a time and ends at
  the slot's last live page. `live_pages[s]` is 1 + the last page on
  which `allowed` lets any query row of slot s attend (0 for an
  evicted slot), taken from the mask the caller already gives; a slot
  takes `live_groups(live_pages[s], G)` steps and no more, so a slot
  500 tokens deep costs 4 steps of 128 keys, not its table's 64 pages.
- The grid has one dimension, the steps of every slot in turn, and
  its size is a value of the call (a dynamic grid bound): `_schedule`
  lists per step its slot and group, and the lists, the page table and
  `live_pages` are the scalar-prefetch operands
  (`PrefetchScalarGridSpec`, placed in SMEM before the kernel runs so
  the BlockSpec index maps can read them). A grid of
  (slots, groups) with the dead steps skipped inside cost 1.5 us a
  dead step on the v5e, most of a chat tick; so the dead steps are not
  in the grid. An evicted slot keeps one step, which computes nothing
  and writes its zeros.
- Step t's K (and V) arrive as G blocks, each the whole physical page
  `page_table[slot[t], group[t] * G + g]` (`[P, H*D]`, the array's
  full trailing dims, which is what Mosaic's block rule asks of a
  16-row page): the pool is passed G times and Pallas's own pipeline
  double-buffers the 2G copies of the next step behind this one. The
  table is padded to whole groups with the scratch page and the mask
  with False, so `pages_per_slot` need not be a multiple of G. (The
  other form, the pool left in HBM and the kernel issuing its own
  `make_async_copy`s for live pages only, does not compile for a
  1600-wide pool: this Mosaic refuses an HBM slice whose minor extent
  is not a multiple of 128. PERF.md section 6, PR 27.)
- G comes from the shapes (`group_pages`): the most pages whose
  blocks fit `_VMEM_BUDGET`, a whole number of 128-key lane tiles — 8
  for the serve cells' 16-token bf16 pages 1600 wide, 16 for int8
  pages, more under a tp mesh where the local width is a share.
- Heads stay folded in lanes and keys go on lanes too. The query
  is laid block-diagonal once a slot, `qbd[(i, h), c] = q[i, c]` on
  head h's lanes and 0 elsewhere (heads padded to a multiple of 8
  sublanes), so one product `qbd @ k.T` gives `[seq * H8, G * P]`
  scores for every head and query row, and `p @ v` gives
  `[seq * H8, H*D]` of which row (i, h) is kept on head h's lanes at
  the end. bf16 (and int8) pages meet a bf16 query in one native MXU
  pass with f32 accumulation — exact products, the reference's own
  `einsum(..., preferred_element_type=f32)` — and the probabilities
  are cast as the reference casts them (bf16 for bf16 pages); f32
  operands run at HIGHEST. No per-head slicing or relayout.
- VMEM scratch (qbd and acc `[seq * H8, H*D]`, m/l `[seq * H8, 128]`)
  carries the online-softmax state across a slot's steps; the output
  block is written on the slot's last step.
- Masking is purely the caller's `allowed [slots, seq, cache_len]`
  (from `decoding.paged_slot_update`): it already encodes per-query
  causality over *logical* key slots plus slot validity, so freed /
  never-written / scratch-page-0 entries carry exact-zero weight — the
  kernel zeroes masked probabilities explicitly (`p = where(mask, ...)`)
  rather than relying on exp underflow, so a fully-masked row (e.g. a
  padded query row or an evicted slot) outputs zeros, never a uniform
  average over pool garbage. Holes inside the live range are the
  mask's, as before: the live bound only ends the walk.
- `seq` (1 for the plain tick, spec_k + 1 for the speculative verify
  window) rides in the rows of the one product a step.

The gathered-lax reference below is bitwise the math
`models/transformer.py::_paged_decode_attention` shipped before this
kernel (gather -> f32 einsum -> -1e30 mask -> softmax -> cast ->
einsum), so engine-vs-solo bit-identity pins keep holding wherever the
reference is selected. Off-TPU the kernel path executes as
`_paged_walk_lax` — the same walk by groups and online-softmax update
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

and the PV dot runs in f32 (int8 quantization already costs ~0.4%
relative error, so bf16 rounding of the probabilities would dominate
it); the QK dot takes the int8 values as bf16, which holds them
exactly. In the kernel a group's scales are one more VMEM block,
`[H8, G]` (heads on sublanes like the scores' rows), gathered through
the page table before the call — a few KB, not the pool; the dequant
folds into the `[rows, keys]` scores and probabilities as a multiply
by the page's scale over its keys, and nothing dequantized is ever
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
#: The same walk over a window layer's band (`window=`): it starts at
#: the slot's first live page as well as ending at its last.
PAGED_DECODE_WINDOW = "paged_decode_window"

#: `pl.pallas_call(name=)` is the innermost scope, and XLA:TPU names
#: the custom call by it (`%<name>.N`). The benchmark's accepted
#: `paged_attn_roofline` finds this kernel as a custom call whose name
#: holds `_paged_decode_attention` (the flax method it used to be named
#: by), so the call passes the declared name behind that prefix until
#: a `benchmark` PR moves the reader to the declared name.
_CALL_PREFIX = "attention._paged_decode_attention."

_NEG_INF = -1e30
_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
_SUBLANES = 8

#: VMEM a grid step's group of pages may take (`group_pages`). Fixed
#: on the v5e at the serve cells' shape (16-token bf16 pages 1600
#: wide, seq 1), where it gives G = 8: PERF.md section 6, PR 27.
_VMEM_BUDGET = 6 * 1024 * 1024


class _PagedConfig(NamedTuple):
    sm_scale: float
    heads: int
    seq: int
    page_size: int
    group: int
    interpret: bool
    quantized: bool = False
    kv_heads: int = 0     # 0 = heads (MHA); fewer = grouped queries
    banded: bool = False  # the mask has a lower edge (a window layer)

    @property
    def q_group(self):
        """Query heads a key/value head serves."""
        return self.heads // (self.kv_heads or self.heads)


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


def _kv_heads(q, key_pages):
    """Key/value heads of a pool `[N, P, H_kv * D]` under `q`
    `[slots, seq, H, D]`: H for full multi-head attention, a divisor
    of H where each key/value head serves H / H_kv query heads."""
    heads, head_dim = q.shape[2:]
    kv_heads, rest = divmod(key_pages.shape[-1], head_dim)
    if key_pages.ndim != 3 or rest or not kv_heads or heads % kv_heads:
        raise ValueError(
            "key_pages must be [num_pages, page_size, kv_heads * "
            "head_dim] with kv_heads dividing the {} query heads of "
            "{}; got {}.".format(heads, head_dim, key_pages.shape))
    return kv_heads


def paged_attention_reference(q, key_pages, value_pages, page_table,
                              allowed, sm_scale=None, key_scales=None,
                              value_scales=None):
    """Gathered-lax paged decode attention (the correctness oracle).

    q: [slots, seq, H, D]; key_pages/value_pages: [N, P, H_kv*D]
    (H_kv = H, or a divisor of it under grouped-query attention);
    page_table: [slots, pages_per_slot] int32; allowed:
    [slots, seq, cache_len] bool (True = attend) ->
    [slots, seq, H, D] in the page dtype (q's dtype for int8 pages).

    Logical per-slot [cache_len] views, one gather per call — bitwise
    the pre-kernel serving-tick math, kept verbatim so the kernel-off
    engine stays bit-identical to solo `generate()` decodes: the
    dense cache's einsums of `TransformerLM` for H_kv = H, and of
    `LlamaLM`'s grouped form otherwise. With
    `key_scales`/`value_scales` the int8 pages are dequantized into
    the gathered f32 view (the module-level dequant contract) and the
    whole computation stays f32.
    """
    page_size = key_pages.shape[1]
    heads, head_dim = q.shape[2:]
    kv_heads = _kv_heads(q, key_pages)
    slots, pages_per_slot = page_table.shape
    cache_len = pages_per_slot * page_size
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    quantized = _check_scales(key_pages, key_scales, value_scales,
                              kv_heads)

    def view(pages, scales):
        """[slots, cache_len, H_kv, D] logical view of the slots'
        pages."""
        g = pages[page_table].reshape(slots, pages_per_slot, page_size,
                                      kv_heads, head_dim)
        if quantized:
            g = g.astype(jnp.float32) * scales[page_table][
                :, :, None, :, None]
        return g.reshape(slots, cache_len, kv_heads, head_dim)

    k_view = view(key_pages, key_scales)
    v_view = view(value_pages, value_scales)
    out_dtype = q.dtype if quantized else value_pages.dtype
    weight_dtype = jnp.float32 if quantized else value_pages.dtype
    if kv_heads == heads:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_view,
                            preferred_element_type=jnp.float32) * sm_scale
        logits = jnp.where(allowed[:, None], logits, _NEG_INF)
        weights = jax.nn.softmax(logits, axis=-1).astype(weight_dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", weights,
                          v_view).astype(out_dtype)
    # Grouped queries: `GQAttention._decode_attention`'s own einsums,
    # each key/value head against its group of query heads.
    seq = q.shape[1]
    qg = q.reshape(slots, seq, kv_heads, heads // kv_heads, head_dim)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_view,
                        preferred_element_type=jnp.float32) * sm_scale
    logits = jnp.where(allowed[:, None, None], logits, _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1).astype(weight_dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", weights, v_view)
    return out.reshape(q.shape).astype(out_dtype)


# ---------------------------------------------------------------------------
# The walk's geometry
# ---------------------------------------------------------------------------


def _round_up(x, multiple):
    return -(-x // multiple) * multiple


def group_pages(page_size, heads, width, itemsize, seq, pages_per_slot):
    """G, the logical pages one grid step serves.

    The most pages whose VMEM fits `_VMEM_BUDGET`: per page a K and a
    V block, each double-buffered by the pipeline at the page itemsize
    and joined once into the step's f32-or-narrower operand; per call
    the `[seq * H8, width]` block-diagonal query, accumulator and
    step product. Where a slot has more pages than that, G is a whole
    number of 128-key lane tiles (the scores are `[rows, G * page_size]`
    with keys on lanes, and the mask block must be lane-aligned), and
    never less than one.
    """
    lanes = _round_up(width, _LANES)
    rows = seq * _round_up(heads, _SUBLANES)
    per_page = 2 * page_size * lanes * (2 * itemsize + 4)
    fixed = 3 * rows * lanes * 4
    tile = math.lcm(page_size, _LANES) // page_size
    fit = max(_VMEM_BUDGET - fixed, 0) // per_page
    group = max(fit // tile, 1) * tile
    return pages_per_slot if group >= pages_per_slot else group


def live_groups(live_pages, group):
    """Groups the walk serves for a slot whose last live page is
    `live_pages - 1`: the grid steps that compute, and (times
    `group`) the pages it fetches. Python ints or traced scalars."""
    return (live_pages + group - 1) // group


def walked_tokens(depth, page_size, group):
    """Keys the kernel's walk fetches for a slot `depth` tokens deep:
    the depth rounded up to whole groups (the scheduler's
    `kv_walked_tokens`)."""
    pages = -(-depth // page_size)
    return live_groups(pages, group) * group * page_size


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------


def _operand_dtypes(q_dtype, page_dtype):
    """(QK operand dtype, PV operand dtype). bf16 pages (and int8
    pages, whose values bf16 holds exactly) meet a bf16 query in one
    native MXU pass — exact products, f32 accumulation: the
    reference's `einsum(..., preferred_element_type=f32)`. The
    probabilities are cast as the reference casts them: to bf16 for
    bf16 pages, else f32 (f32 and int8 pages; f32 operands run at
    HIGHEST)."""
    narrow = (q_dtype == jnp.bfloat16
              and page_dtype in (jnp.bfloat16, jnp.int8))
    qk = jnp.bfloat16 if narrow else jnp.float32
    pv = jnp.bfloat16 if page_dtype == jnp.bfloat16 else jnp.float32
    return qk, pv


def _dot(a, b, contract_b):
    """a [m, k] with b [k, n] (contract_b 0) or b [n, k] (1) -> f32."""
    precision = _HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        a, b, (((1,), (contract_b,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)


def _paged_kernel(slot_ref, group_ref, pt_ref, live_ref, *rest, config):
    """One step of the walk: every head of one slot against one group
    of G logical pages. The grid runs over the live groups alone;
    step t serves group `group_ref[t]` of slot `slot_ref[t]`.

    rest: (a window layer: the slots' first live group), the query
    block, the mask block, `segt`, G key-page blocks, G value-page
    blocks, (int8: the group's `[H8, G]` K and V scale blocks), the
    output block, then scratch: the block-diagonal query
    `[seq * H8, width]`, acc (same shape, f32), m and l
    `[seq * H8, 128]`, and for full multi-head attention the
    `[seq, width]` f32 output rows."""
    del pt_ref  # consumed by the BlockSpec index maps
    group, seq, page = config.group, config.seq, config.page_size
    if config.banded:
        first_ref, *rest = rest
    q_ref, a_ref, segt_ref, *rest = rest
    k_refs, v_refs, rest = rest[:group], rest[group:2 * group], rest[
        2 * group:]
    if config.quantized:
        ks_ref, vs_ref, *rest = rest
    grouped = config.q_group > 1
    if grouped:
        o_ref, qbd_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, qbd_ref, acc_ref, m_ref, l_ref, rows_ref = rest
    ti = pl.program_id(0)
    ji = group_ref[ti]
    first = first_ref[slot_ref[ti]] if config.banded else 0
    steps = live_groups(live_ref[slot_ref[ti]], group)
    hp = qbd_ref.shape[0] // seq          # heads, padded to sublanes
    keys = group * page
    kv_heads = config.kv_heads or config.heads
    depth = qbd_ref.shape[1] // kv_heads  # head size
    qk_dtype, pv_dtype = _operand_dtypes(q_ref.dtype, k_refs[0].dtype)

    @pl.when(ji == first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # Row (i, h) of the block-diagonal query is query row i on
        # head h's lanes and zero elsewhere, so one [rows, width] x
        # [keys, width]^T product gives every head's scores. Under
        # grouped queries head h's lanes are those of the key/value
        # head it reads, and its own `depth` features are laid over
        # them.
        for i in range(seq):
            if grouped:
                q = q_ref[0, i * hp:(i + 1) * hp, :].astype(jnp.float32)
                q = jnp.concatenate([q] * kv_heads, axis=1)
            else:
                q = q_ref[0, i:i + 1, :].astype(jnp.float32)
            qbd_ref[i * hp:(i + 1) * hp, :] = (
                q * segt_ref[...]).astype(qk_dtype)

    def per_row(blocks):
        """`seq` blocks `[H8, keys]` or `[1, keys]`, one a query row
        -> `[seq * H8, keys]`."""
        tiles = [jnp.broadcast_to(b, (hp, keys)) for b in blocks]
        return tiles[0] if seq == 1 else jnp.concatenate(tiles, axis=0)

    def spread(scales_ref, lane_page):
        """The group's [H8, G] scale block -> [rows, keys]: page g's
        scale over its `page` key lanes, the same for every query
        row."""
        out = jnp.zeros((hp, keys), jnp.float32)
        for g in range(group):
            out = jnp.where(lane_page == g,
                            scales_ref[0, 0, :, g:g + 1], out)
        return per_row([out] * seq)

    @pl.when(ji < steps)        # false only on an evicted slot's step
    def _step():
        join = lambda refs, dtype: jnp.concatenate(
            [r[0].astype(dtype) for r in refs], axis=0)
        k = join(k_refs, qk_dtype)                   # [keys, width]
        v = join(v_refs, pv_dtype)
        s = _dot(qbd_ref[...], k, 1) * config.sm_scale  # [rows, keys]
        if config.quantized:
            lane_page = jax.lax.broadcasted_iota(
                jnp.int32, (hp, keys), 1) // page
            s = s * spread(ks_ref, lane_page)
        mask = per_row(
            [a_ref[0, i:i + 1, :] for i in range(seq)]) != 0
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)             # [rows, 1]
        # Explicit zero where masked: exp(s - m) underflows to 0 for
        # normal rows, but a fully-masked row (a padded head row, a
        # hole that covers the whole group) has m == s == -inf and
        # exp(0) == 1 would leak pool garbage.
        p = jnp.where(mask, jnp.exp(s - m_next), 0.0)
        l_next = alpha * l_ref[:, :1] + jnp.sum(p, axis=1,
                                                keepdims=True)
        if config.quantized:
            p = p * spread(vs_ref, lane_page)
        acc_ref[...] = acc_ref[...] * alpha + _dot(
            p.astype(pv_dtype), v, 0)
        m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_next, l_ref.shape)

    @pl.when(ji + 1 >= steps)
    def _finalize():
        l = l_ref[:, :1]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        # Row (i, h) holds head h's output on head h's lanes (and
        # other heads' keys against this head's weights elsewhere):
        # keep the diagonal blocks.
        for i in range(seq):
            kept = out[i * hp:(i + 1) * hp] * segt_ref[...]
            if grouped:
                # Head h's `depth` features, from the lanes of the
                # key/value head it read.
                o_ref[0, i * hp:(i + 1) * hp, :] = sum(
                    kept[:, g * depth:(g + 1) * depth]
                    for g in range(kv_heads)).astype(o_ref.dtype)
            else:
                rows_ref[i:i + 1, :] = jnp.sum(kept, axis=0,
                                               keepdims=True)
        if not grouped:
            o_ref[0] = rows_ref[...].astype(o_ref.dtype)


def _schedule(live_pages, group, most, first_groups=None):
    """The walk as a list of grid steps: per step its slot and its
    group, and the number of steps. A slot takes `live_groups` steps
    (less the groups before `first_groups[s]`, where a window layer's
    band starts), and an evicted slot one, which computes nothing and
    writes its zeros; entries past the count (to `most`) are never
    run. Sums over a [steps, slots] comparison: a few dozen integers,
    no scan and no gather."""
    steps = live_groups(live_pages, group)
    if first_groups is not None:
        steps = steps - first_groups
    steps = jnp.maximum(steps, 1)
    slots = steps.shape[0]
    upto = jnp.arange(slots)[:, None] >= jnp.arange(slots)[None, :]
    ends = jnp.sum(jnp.where(upto, steps[None, :], 0), axis=1)
    t = jnp.arange(most, dtype=jnp.int32)
    past = t[:, None] >= ends[None, :]      # step t is past slot s
    slot_of = jnp.minimum(jnp.sum(past, axis=1), slots - 1)
    group_of = t - jnp.sum(jnp.where(past, steps[None, :], 0), axis=1)
    if first_groups is not None:
        own = slot_of[:, None] == jnp.arange(slots)[None, :]
        group_of = group_of + jnp.sum(
            jnp.where(own, first_groups[None, :], 0), axis=1)
    return (slot_of.astype(jnp.int32), group_of.astype(jnp.int32),
            ends[-1].astype(jnp.int32))


def _paged_forward(config, q, key_pages, value_pages, page_table,
                   live_pages, allowed, first_groups=None,
                   key_scales=None, value_scales=None):
    """q: [S, seq, H*D] (grouped queries: [S, seq * H8, D], heads
    padded to sublanes); page_table: [S, groups * G] (padded with the
    scratch page); live_pages: [S]; allowed: [S, seq, groups * G * P]
    int32; first_groups (a window layer): [S]; scales (int8 mode):
    [S, groups, H8, G] -> out, shaped as q.

    The schedule (`_schedule`), the page table and the live bounds
    are the scalar-prefetch operands and the grid's one dimension is
    the schedule's length, a value of the call: block g of step t's K
    (and V) is physical page `pt[slot[t], group[t] * G + g]`, so the
    pool is only ever touched at the pages of groups a slot has live.
    """
    slots = q.shape[0]
    width = key_pages.shape[2]
    heads, group, seq = config.heads, config.group, config.seq
    page_size = config.page_size
    hp = _round_up(heads, _SUBLANES)
    rows = seq * hp
    qk_dtype, _ = _operand_dtypes(q.dtype, key_pages.dtype)
    kernel = functools.partial(_paged_kernel, config=config)
    # segt[h, c] = 1 where lane c belongs to the key/value head that
    # query head h reads; rows past `heads` are zero, so the padded
    # rows score 0 and are dropped at the end.
    kv_heads = config.kv_heads or heads
    row_head = jnp.arange(hp) // config.q_group
    segt = ((row_head[:, None]
             == jnp.arange(width)[None, :] // (width // kv_heads))
            & (jnp.arange(hp) < heads)[:, None]).astype(jnp.float32)
    slot_of, group_of, total = _schedule(
        live_pages, group, slots * (page_table.shape[1] // group),
        first_groups)
    scalars = [slot_of, group_of, page_table, live_pages]
    if config.banded:
        scalars.append(first_groups)
    operands = scalars + [q, allowed, segt]
    operands += [key_pages] * group + [value_pages] * group
    if config.quantized:
        operands += [key_scales, value_scales]
    operands = partition.common_vma(*operands)

    slot_block = pl.BlockSpec(
        (1,) + q.shape[1:], lambda t, slot, *_: (slot[t], 0, 0))
    # K/V blocks are single physical pages, gathered by block
    # *indexing* through the prefetched schedule — never an HBM
    # materialization of the dense [S, cache_len, H, D] view.
    page_blocks = [
        pl.BlockSpec(
            (1, page_size, width),
            lambda t, slot, grp, pt, *_, g=g: (
                pt[slot[t], grp[t] * group + g], 0, 0))
        for g in range(group)]
    in_specs = [
        slot_block,
        pl.BlockSpec((1, seq, group * page_size),
                     lambda t, slot, grp, *_: (slot[t], 0, grp[t])),
        pl.BlockSpec((hp, width), lambda t, *_: (0, 0)),
    ] + page_blocks * 2
    if config.quantized:
        in_specs += [pl.BlockSpec(
            (1, 1, hp, group), lambda t, slot, grp, *_: (
                slot[t], grp[t], 0, 0))] * 2
    scratch_shapes = [
        pltpu.VMEM((rows, width), qk_dtype),
        pltpu.VMEM((rows, width), jnp.float32),
        pltpu.VMEM((rows, _LANES), jnp.float32),
        pltpu.VMEM((rows, _LANES), jnp.float32),
    ]
    if config.q_group == 1:
        scratch_shapes.append(pltpu.VMEM((seq, width), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(total,),
        in_specs=in_specs,
        out_specs=slot_block,
        scratch_shapes=scratch_shapes,
    )
    out_dtype = q.dtype if config.quantized else value_pages.dtype
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            q.shape, out_dtype, vma=partition.vma_of(*operands)),
        interpret=config.interpret,
        name=_CALL_PREFIX + (PAGED_DECODE_WINDOW if config.banded
                             else PAGED_DECODE),
    )(*operands)


def _grouped(page_table, allowed, page_size, group):
    """The walk's view of a call: the table padded with the scratch
    page and the mask with False to whole groups, and per slot
    1 + the last page on which any query row may attend (0 for an
    evicted slot)."""
    pages_per_slot = page_table.shape[1]
    pad = _round_up(pages_per_slot, group) - pages_per_slot
    table = jnp.pad(page_table, ((0, 0), (0, pad)))
    mask = jnp.pad(allowed, ((0, 0), (0, 0), (0, pad * page_size)))
    live_keys = jnp.max(
        jnp.where(allowed, 1 + jnp.arange(allowed.shape[2]), 0),
        axis=(1, 2)).astype(jnp.int32)
    live_pages = (live_keys + page_size - 1) // page_size
    return table, mask, live_pages


def _first_groups(allowed, page_size, group):
    """Per slot the group that holds the first key any query row may
    attend (0 for an evicted slot): where a window layer's walk
    starts. Keys before the band inside that group are the mask's."""
    cache_len = allowed.shape[2]
    first_key = jnp.min(
        jnp.where(allowed, jnp.arange(cache_len), cache_len),
        axis=(1, 2))
    first_key = jnp.where(first_key == cache_len, 0, first_key)
    return (first_key // (page_size * group)).astype(jnp.int32)


def _paged_walk_lax(q, key_pages, value_pages, page_table, allowed,
                    sm_scale, key_scales=None, value_scales=None):
    """The kernel's defining math as vectorized lax: walk the table in
    grid order a group of G pages at a time, gathering ONLY the slots'
    own pages (one [slots, G * P, H_kv*D] take per group — never the
    dense [slots, cache_len] view), with the exact online-softmax
    update sequence `_paged_kernel` runs per step. It walks every
    group: one past a slot's last live page (or before a window
    layer's first) is wholly masked, which
    leaves m, l and acc bit for bit as they were (alpha = exp(0) = 1,
    p = 0), so the kernel's live bounds change no value. This is the
    off-TPU execution of the kernel path: Mosaic can't compile there
    and Pallas interpret mode is ~100x too slow for a serving tick,
    so the `CLOUD_TPU_PAGED_KERNEL=1` smoke runs this form while the
    parity suite pins it against the true interpreted kernel
    (`interpret=True`) and the gathered reference. Int8 pages are
    dequantized per group in f32 (the module dequant contract)."""
    page_size = key_pages.shape[1]
    slots, seq, heads, head_dim = q.shape
    kv_heads = _kv_heads(q, key_pages)
    quantized = key_scales is not None
    group = group_pages(page_size, heads, kv_heads * head_dim,
                        key_pages.dtype.itemsize, seq,
                        page_table.shape[1])
    keys = group * page_size
    table, mask, _ = _grouped(page_table, allowed, page_size, group)
    _, pv_dtype = _operand_dtypes(q.dtype, key_pages.dtype)
    # [slots, H_kv, G_q, seq, .]: each key/value head with the query
    # heads it serves (one a head for full multi-head attention).
    qg = q.reshape(slots, seq, kv_heads, heads // kv_heads, head_dim)
    stat = (slots, kv_heads, heads // kv_heads, seq)
    m = jnp.full(stat + (1,), _NEG_INF, jnp.float32)
    l = jnp.zeros(stat + (1,), jnp.float32)
    acc = jnp.zeros(stat + (head_dim,), jnp.float32)
    for j in range(table.shape[1] // group):
        pages = table[:, j * group:(j + 1) * group]

        def take(pool, scales):
            x = pool[pages].reshape(slots, group, page_size, kv_heads,
                                    head_dim)
            if quantized:
                x = x.astype(jnp.float32) * scales[pages][
                    :, :, None, :, None]
            return x.reshape(slots, keys, kv_heads, head_dim)

        k = take(key_pages, key_scales)
        v = take(value_pages, value_scales)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                       preferred_element_type=jnp.float32) * sm_scale
        live = mask[:, None, None, :, j * keys:(j + 1) * keys]
        s = jnp.where(live, s, _NEG_INF)   # [slots, H_kv, G_q, seq, keys]
        m_next = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_next)
        p = jnp.where(live, jnp.exp(s - m_next), 0.0)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(pv_dtype), v.astype(pv_dtype),
            preferred_element_type=jnp.float32)
        m = m_next
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out_dtype = q.dtype if quantized else value_pages.dtype
    out = (acc / safe_l).astype(out_dtype)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(q.shape)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "interpret", "banded"))
def _paged_call(q, key_pages, value_pages, page_table, allowed, *scales,
                sm_scale, interpret, banded=False):
    """One device's call, `[slots, seq, H', D]` over its H' heads: the
    walk's geometry from the shapes, then the kernel. Jitted because a
    model's layers all make this call with the same shapes, and a
    jitted callee is traced and lowered once a program and not once a
    layer (the serve cells' set-up traces the 24-layer tick several
    times: PERF.md section 6, PR 27)."""
    slots, seq, local_heads, head_dim = q.shape
    page_size = key_pages.shape[1]
    width = key_pages.shape[2]
    kv_heads = width // head_dim
    q_group = local_heads // kv_heads
    group = group_pages(page_size, local_heads, width,
                        key_pages.dtype.itemsize, seq,
                        page_table.shape[1])
    config = _PagedConfig(sm_scale=sm_scale, heads=local_heads, seq=seq,
                          page_size=page_size, group=group,
                          interpret=interpret, quantized=bool(scales),
                          kv_heads=kv_heads, banded=banded)
    table, mask, live_pages = _grouped(page_table, allowed, page_size,
                                       group)
    first = (_first_groups(allowed, page_size, group) if banded
             else None)
    hp = _round_up(local_heads, _SUBLANES)

    def by_group(sc):
        """[N, H_kv'] page scales -> [slots, groups, H8, G]: each
        group's rows through the table, a row a query head, heads on
        sublanes."""
        rows = jnp.repeat(sc[table], q_group, axis=-1)
        rows = jnp.pad(rows, ((0, 0), (0, 0), (0, hp - local_heads)))
        return jnp.swapaxes(
            rows.reshape(slots, -1, group, rows.shape[-1]), 2, 3)

    if q_group == 1:
        rows = q.reshape(slots, seq, width)
    else:
        # A row a query head, `head_dim` wide: the kernel lays each
        # over the lanes of the key/value head it reads.
        rows = jnp.pad(q, ((0, 0), (0, 0), (0, hp - local_heads),
                           (0, 0))).reshape(slots, seq * hp, head_dim)
    out = _paged_forward(
        config, rows, key_pages, value_pages, table, live_pages,
        mask.astype(jnp.int32), first, *(by_group(sc) for sc in scales))
    if q_group > 1:
        out = out.reshape(slots, seq, hp, head_dim)[:, :, :local_heads]
    return out.reshape(q.shape)


def paged_decode_attention(q, key_pages, value_pages, page_table,
                           allowed, sm_scale=None,
                           interpret: Optional[bool] = None,
                           key_scales=None, value_scales=None,
                           window: Optional[int] = None):
    """Pallas paged decode attention; layouts as the reference.

    Handles both the seq=1 plain tick and the seq=spec_k+1 speculative
    verify window. Output matches
    `paged_attention_reference` to online-softmax accumulation order —
    tolerance-level, not bitwise; fully-masked rows (evicted slots)
    output exact zeros. With scales given the pages
    are int8 and the kernel dequantizes in its block loads (module
    docstring). With `window` (a window layer: the caller's `allowed`
    already carries the band) the walk starts at the slot's first
    live page, so a tick reads at most
    `ceil(window / page_size) + 1` pages a slot, rounded out to
    groups, and the call goes under `PAGED_DECODE_WINDOW`.

    interpret: None (default) compiles the kernel on TPU and runs the
    lax page-walk form of the same math elsewhere; True forces Pallas
    interpret mode (the parity suite's same-code-path check — far too
    slow for a serving tick).
    """
    page_size = key_pages.shape[1]
    slots, seq, heads, head_dim = q.shape
    pages_per_slot = page_table.shape[1]
    cache_len = pages_per_slot * page_size
    kv_heads = _kv_heads(q, key_pages)
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
                              kv_heads)
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

    def plan(mesh):
        """Heads over the model axis; slots share the pool, so every
        other axis sees the whole call."""
        tp = partition.model_axis(mesh, heads, kv_heads)
        by_head = P(None, None, tp, None)
        pages = P(None, None, tp)
        specs = [by_head, pages, pages, P(), P()]
        if quantized:
            specs += [P(None, tp)] * 2
        return tuple(specs), by_head, None

    kernel = functools.partial(_paged_call, sm_scale=float(sm_scale),
                               interpret=bool(interpret),
                               banded=bool(window))
    return partition.per_shard(kernel, args, plan, interpret)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def paged_attention(q, key_pages, value_pages, page_table, allowed,
                    sm_scale=None, impl="auto",
                    interpret: Optional[bool] = None,
                    key_scales=None, value_scales=None,
                    window: Optional[int] = None):
    """Dispatching paged decode attention: Pallas kernel or gathered lax.

    impl: "paged" forces the kernel, "reference" forces the gathered
    lax path; "auto" (and any training-side impl name such as "flash",
    which has no paged analogue) picks the kernel on TPU and the
    reference elsewhere. The `CLOUD_TPU_PAGED_KERNEL` env var is the
    deployment/A-B override and beats `impl`: "1" forces the kernel
    (interpret mode off-TPU, so CPU CI drives the kernel code path),
    "0" forces the reference, unset/empty defers to `impl`.
    key_scales/value_scales select int8-page mode on whichever impl is
    picked (the dequant contract in the module docstring). `window`
    says that `allowed` is a window layer's band (the mask itself
    decides every weight): the kernel's walk then starts at the first
    live page.
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
                                      value_scales=value_scales,
                                      window=window)
    return paged_attention_reference(q, key_pages, value_pages,
                                     page_table, allowed,
                                     sm_scale=sm_scale,
                                     key_scales=key_scales,
                                     value_scales=value_scales)

