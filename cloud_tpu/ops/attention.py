"""Flash attention as a Pallas TPU kernel.

The compute-path counterpart the reference never had: its attention runs
wherever `tf.distribute` puts Keras layers (reference core/preprocess.py
picks a strategy, TF picks kernels). Here the hot op is a hand-written
TPU kernel: blockwise online-softmax attention that never materializes
the [S, S] score matrix in HBM, keeps the matmuls on the MXU in bf16/f32,
and walks K/V blocks that sit in VMEM.

Design notes (see /opt/skills/guides/pallas_guide.md):
- Two levels of tiling, both from the shapes (`flash_plan`). A GRID STEP
  holds one kv head's span of k/v rows and a span of q rows of every q
  head of its group: the whole padded sequence while that fits
  `_VMEM_BUDGET`, else the most whole tiles that do. Inside the step a
  `fori_loop` walks `[block_q, block_k]` score tiles over the spans. A
  turn of that loop has a fixed latency of 0.5-0.75 us on the v5e
  (product, row max, exp, row sum, product: a chain), the same a tile as
  a grid step of the old 128 x 128 grid cost (PERF.md section 6, PR 29),
  so a turn carries a fat tile and the group's heads side by side,
  independent chains the scheduler interleaves; and neither level
  visits a dead tile: the grid is the list of span pairs that hold a
  visible entry (the schedule rides in scalar prefetch, as in
  ops/paged_attention.py), and the in-kernel walk runs between the
  causal / band bounds of its rows (`_live_blocks`, the same arithmetic
  `flash_plan` counts with).
- Block-causal attention (`block=B`, the block-diffusion models'
  mask): key j is visible to query i iff j // B <= i // B, causal
  between blocks and bidirectional inside one. It is the causal walk
  with each row's edge moved to the end of its block (`_row_edge`), so
  the tiles, the schedule and the masks count it with the same
  arithmetic; B <= 1 is the causal mask and builds what it always did.
- The online-softmax state (acc, m, l) is carried as values along a
  row block's k walk and parked in VMEM scratch between span pairs; m/l
  scratch is (group, span, 128) lane-broadcast.
- The saved logsumexp and the backward's delta travel as ROWS,
  `[B*H_kv, G, S/block_q, 1, block_q]` f32 (4 bytes a query, not 512): the
  forward transposes its lane-broadcast column once a row block, dq
  transposes back once a row block, and dk/dv computes the transposed
  score tile `K Q^T`, where a query's statistic is a lane and broadcasts
  down the sublanes for nothing (and dV = P^T dO, dK = dS^T Q become
  plain products).
- Backward = two kernels (dq walks k blocks; dk/dv walks q blocks of
  every q head of the kv head's group, summing the group in its
  accumulator), the standard FlashAttention-2 recomputation split,
  wired through `jax.custom_vjp`.
- Sequences are padded to a tile multiple outside the custom_vjp, so
  autodiff of pad/slice handles the edges; padded keys are masked inside
  the kernel, padded dO rows are zero so they contribute nothing.

On non-TPU backends the kernels run in Pallas interpret mode (tests), so
the same code path is exercised everywhere. Under a device mesh the
kernels run per shard (ops/partition.py).
"""

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from cloud_tpu.ops import partition

#: The kernels' declared names (table in monitoring/spans.py): the
#: trace's op text carries them, whatever module calls the kernel.
FLASH_FWD = "flash_fwd"
FLASH_BWD_DQ = "flash_bwd_dq"
FLASH_BWD_DKV = "flash_bwd_dkv"

#: `pl.pallas_call(name=)` is the innermost scope, and XLA:TPU names
#: the custom call by it (`%<name>.N`). The benchmark's accepted
#: `flash_roofline` finds these kernels as custom calls whose name
#: starts `attention.` (the flax scope they used to be named by), so
#: the calls pass the declared name behind that prefix until a
#: `benchmark` PR moves the reader to the declared names.
_CALL_PREFIX = "attention."

_NEG_INF = -1e30
_LANES = 128


class _Config(NamedTuple):
    causal: bool
    sm_scale: float
    tiles: "FlashPlan"  # the pass's tiles, spans and schedule
    kv_len: int  # true (unpadded) sequence length
    heads: int   # q heads of the call (a key mask row serves them all)
    has_mask: bool  # per-example key mask, a (1, block_k) row a k block
    interpret: bool
    kv_group: int = 1  # q heads per kv head (grouped-query attention)
    window: int = 0  # sliding-window width; 0 = full causal
    softcap: float = 0.0  # Gemma2-style tanh logit cap; 0 = off
    backward: Optional["FlashPlan"] = None  # the backward's own tiles
    block: int = 0  # block-causal: key j seen by row i iff j//B <= i//B

    block_q = property(lambda self: self.tiles.block_q)
    block_k = property(lambda self: self.tiles.block_k)
    span_q = property(lambda self: self.tiles.span_q)
    span_k = property(lambda self: self.tiles.span_k)
    seq_pad = property(lambda self: self.tiles.seq_pad)
    pairs = property(lambda self: self.tiles.pairs)

    def for_backward(self):
        """This call's config with the backward passes' plan in place
        (same `block_q`: the saved logsumexp's rows are laid out by
        it)."""
        return self._replace(tiles=self.backward, backward=None)


def repeat_kv(k, num_heads):
    """Broadcast [B, S, H_kv, D] key/value heads to num_heads groups.

    GQA's compute-side expansion: each kv head serves
    num_heads // H_kv query heads. Prefer passing H_kv-width k/v
    straight to `flash_attention`/`mha_reference` (both take the
    grouped layout natively); this helper is for paths that need the
    materialized expansion (e.g. sharding heads across a mesh axis).
    """
    h_kv = k.shape[2]
    if num_heads == h_kv:
        return k
    if num_heads % h_kv:
        raise ValueError(
            "num_heads=%d must be a multiple of num_kv_heads=%d."
            % (num_heads, h_kv))
    return jnp.repeat(k, num_heads // h_kv, axis=2)


def mha_reference(q, k, v, causal=True, sm_scale=None, mask=None,
                  window=None, logit_softcap=None, block=None):
    """Pure-jnp multi-head attention, layout [B, S, H, D].

    The correctness oracle for the kernel and the fallback path for
    shapes/backends the kernel does not cover. Grouped-query attention:
    k/v may carry H_kv < H heads (H divisible by H_kv); they are
    broadcast to the q-head grouping here. window: sliding-window
    (Mistral-style) attention — row i attends keys (i-window, i];
    requires causal=True. logit_softcap: Gemma2-style tanh capping,
    logits -> cap * tanh(logits / cap), applied after the softmax scale
    and before any masking (the HF Gemma2 order). block: block-causal
    attention — row i attends keys j with j // block <= i // block;
    requires causal=True and no window.
    """
    head_dim = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    if v.shape != k.shape:
        raise ValueError("k and v must have identical shapes; got "
                         "{} vs {}.".format(k.shape, v.shape))
    _check_mask_kind(causal, window, block)
    if k.shape[2] != q.shape[2]:
        heads, h_kv = q.shape[2], k.shape[2]
        if heads % h_kv:
            raise ValueError(
                "q heads {} must be a multiple of kv heads {}.".format(
                    heads, h_kv))
        k = jnp.repeat(k, heads // h_kv, axis=2)
        v = jnp.repeat(v, heads // h_kv, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * sm_scale
    logits = logits.astype(jnp.float32)
    if logit_softcap:
        cap = float(logit_softcap)
        logits = cap * jnp.tanh(logits / cap)
    seq_q, seq_k = q.shape[1], k.shape[1]
    if causal and (block or 0) > 1:
        row = jnp.arange(seq_q)[:, None]
        col = jnp.arange(seq_k)[None, :]
        logits = jnp.where(col // int(block) <= row // int(block), logits,
                           _NEG_INF)
    elif causal:
        allowed = jnp.tril(jnp.ones((seq_q, seq_k), dtype=bool))
        if window is not None:
            # Band: col in (row - window, row]. HF Mistral's convention
            # (sliding_window keys INCLUDING self are visible).
            row = jnp.arange(seq_q)[:, None]
            col = jnp.arange(seq_k)[None, :]
            allowed = allowed & (col > row - int(window))
        logits = jnp.where(allowed, logits, _NEG_INF)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :], logits, _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    if causal or mask is not None:
        # Fully-masked rows output ZEROS (and zero grads) — the flash
        # convention, unified here (round 4) so the oracle and kernel
        # agree on every row and the sp strategies (ring zeros via its
        # lse sentinel; ulysses delegates to whichever local kernel the
        # backend picked) behave identically on any backend. Without
        # this, softmax over all-(-1e30) logits is a uniform average.
        all_masked = jnp.max(logits, axis=-1,
                             keepdims=True) <= _NEG_INF / 2
        weights = jnp.where(all_masked, 0.0, weights)
    weights = weights.astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


# ---------------------------------------------------------------------------
# Tiles and schedule
# ---------------------------------------------------------------------------


class FlashPlan(NamedTuple):
    """What `flash_plan` chose for a shape; the kernels' grids and
    walks are built from it."""
    block_q: int  # rows of a score tile
    block_k: int  # columns of a score tile
    span_q: int   # q rows (of every head of a group) a grid step holds
    span_k: int   # k/v rows a grid step holds
    seq_pad: int  # the sequence padded to whole spans
    pairs: Tuple[Tuple[int, int], ...]  # live (q span, k span), q-major

    def grid_steps(self, batch=1, kv_heads=1):
        """Grid steps of each pass for a call over `batch * kv_heads`
        key/value heads: every pass runs one step a kv head and live
        span pair (its group's q heads ride in the step)."""
        steps = batch * kv_heads * len(self.pairs)
        return {FLASH_FWD: steps, FLASH_BWD_DQ: steps,
                FLASH_BWD_DKV: steps}

    def tiles(self, causal=True, window=0, block=0):
        """Score tiles a q head's walk visits in one pass: `walked` by
        the bounds the kernels loop between, `live` by asking every
        tile of the square whether it holds a visible entry, `dense`
        the square itself. `pairs` / `pairs_live` count the grid's
        span pairs the same two ways."""
        per_q = self.span_q // self.block_q
        per_k = self.span_k // self.block_k
        walked = 0
        for qi, kj in self.pairs:
            for s in range(per_q):
                lo, hi = _live_blocks(
                    qi * self.span_q + s * self.block_q, self.block_q,
                    self.block_k, kj * per_k, (kj + 1) * per_k, causal,
                    window, attn_block=block)
                walked += max(hi - lo, 0)
        rows = range(0, self.seq_pad, self.block_q)
        cols = range(0, self.seq_pad, self.block_k)
        live = sum(_live(r0, self.block_q, c0, self.block_k, causal,
                         window, block) for r0 in rows for c0 in cols)
        pairs_live = sum(
            _live(r0, self.span_q, c0, self.span_k, causal, window, block)
            for r0 in range(0, self.seq_pad, self.span_q)
            for c0 in range(0, self.seq_pad, self.span_k))
        return {"walked": walked, "live": live,
                "dense": len(rows) * len(cols),
                "pairs": len(self.pairs), "pairs_live": pairs_live}


def _check_mask_kind(causal, window, block):
    if window is not None and not causal:
        raise ValueError("window (sliding-window attention) requires "
                         "causal=True.")
    if (block or 0) > 1 and (not causal or window is not None):
        raise ValueError("block (block-causal attention) requires "
                         "causal=True and no window.")


def _row_edge(row, block):
    """The last column row `row` sees: itself, or under block-causal
    attention the last column of its block."""
    if block > 1:
        return row // block * block + block - 1
    return row


def _live(r0, rows, c0, cols, causal, window, block=0):
    """Whether rows [r0, r0 + rows) see any of columns [c0, c0 + cols):
    at or below the diagonal (under `block`, the end of the last row's
    block) and, under a window, not wholly left of the band
    (col > row - window)."""
    if not causal:
        return True
    seen = c0 <= _row_edge(r0 + rows - 1, block)
    if window:
        seen = seen and c0 + cols - 1 > r0 - window
    return seen


def _live_blocks(start, size, block, lo, hi, causal, window,
                 transposed=False, attn_block=0):
    """[lo', hi') within [lo, hi): the blocks of `block` columns that
    rows [start, start + size) see — or, `transposed`, the blocks of
    `block` rows that see columns [start, start + size). Python ints
    (`flash_plan`) or traced scalars (the kernels' loop bounds).
    `attn_block`: block-causal attention's block length (0 = causal)."""
    if not causal:
        return lo, hi
    traced = not isinstance(start, int)
    most = jnp.maximum if traced else max
    least = jnp.minimum if traced else min
    if transposed:
        # The first row that sees column `start`: the first of the
        # column's attention block.
        first = (start // attn_block * attn_block if attn_block > 1
                 else start)
        lo = most(lo, first // block)
        if window:
            hi = least(hi, (start + size - 2 + window) // block + 1)
        return lo, hi
    hi = least(hi, _row_edge(start + size - 1, attn_block) // block + 1)
    if window:
        lo = most(lo, most(start - window + 1, 0) // block)
    return lo, hi


#: VMEM the Pallas calls may take (`vmem_limit_bytes`; the v5e has
#: 128 MiB). `flash_plan` gives a grid step's resident blocks and
#: scratch half of it and leaves the rest to the walk's score tiles.
_VMEM_BUDGET = 64 * 1024 * 1024

#: The f32 score tile a walk aims at, `[_BLOCK_Q, bytes / 4 /
#: _BLOCK_Q]`, from the v5e's sweep at the train cell's shape (PERF.md
#: section 6, PR 29). A turn of the forward's walk rescales the running
#: max, sum and accumulator, work that costs as much for a narrow k
#: block as for a wide one: 256 x 1024. The backward has no such
#: per-turn work, holds two tiles a head (P and dS) and is bound by its
#: products, so the less of the causal diagonal's dead half a tile
#: drags in the better: 256 x 256.
_BLOCK_Q = 256
_SCORE_TILE_BYTES = 1024 * 1024
_SCORE_TILE_BYTES_BACKWARD = 256 * 1024


def _pow2_tile(target, cap):
    """The largest 128 * 2^n within both `target` and `cap`."""
    size = _LANES
    while size * 2 <= min(target, cap):
        size *= 2
    return size


def flash_plan(seq, head_dim, group=1, itemsize=2, causal=True, window=0,
               block_q=None, block_k=None, backward=False, block=0):
    """Tiles, spans and schedule for `seq` tokens at `head_dim`, `group`
    q heads a kv head, operands of `itemsize` bytes.

    Tiles. The score tile is `[block_q, block_k]` f32 of
    `_SCORE_TILE_BYTES` (256 x 1024; `backward`, over the sequence as
    the forward padded it: `_SCORE_TILE_BYTES_BACKWARD`, 256 x 256).
    Each side is a 128 * 2^n of at most the sequence padded to 128 and,
    under a window, the window padded to 128 (a wider tile would
    compute columns the band never shows its rows), halved while
    padding the sequence to it would add over an eighth. Explicit
    `block_q` / `block_k` replace the rule (they must divide one
    another).

    Spans. A grid step's resident set gets half of `_VMEM_BUDGET`,
    minor dims padded to 128 lanes. The k side comes first, a row of it
    at the cost of the widest pass (dk/dv: k, v, dk, dv double-buffered
    and two f32 accumulators): the whole padded sequence when that is
    within half of the share. Then the q side, a row of it costing
    every head of the group its q, dO and dq (or o) double-buffered, an
    f32 accumulator and the lane-broadcast m and l. A side that does
    not fit whole gets the most whole tiles that do and that divide the
    padded sequence evenly, and the grid walks the span pairs that hold
    a visible entry. `block`: block-causal attention's block length
    (0 = causal), whose live tiles are counted by the kernels' own
    arithmetic (`_live`).
    """
    window = int(window or 0)
    lane_seq = -(-seq // _LANES) * _LANES
    auto = block_q is None or block_k is None
    cap = min(lane_seq, -(-window // _LANES) * _LANES) if window \
        else lane_seq
    if block_q is None:
        block_q = _pow2_tile(_BLOCK_Q, cap)
    if block_k is None:
        block_k = _pow2_tile(
            (_SCORE_TILE_BYTES_BACKWARD if backward
             else _SCORE_TILE_BYTES) // (4 * block_q), cap)
    small, large = sorted((block_q, block_k))
    if small <= 0 or large % small:
        raise ValueError(
            "block_q={} and block_k={} must divide one another.".format(
                block_q, block_k))

    def pads_too_far(tile):
        padded = -(-seq // tile) * tile
        return padded > (seq if backward else lane_seq + lane_seq // 8)

    while auto and large > _LANES and pads_too_far(large):
        # Padding to the larger tile would add over an eighth (the
        # backward: anything to what the forward padded): halve it.
        if block_k == large:
            block_k //= 2
        else:
            block_q //= 2
        small, large = sorted((block_q, block_k))
    seq_pad = -(-seq // large) * large

    lanes = -(-head_dim // _LANES) * _LANES
    per_k_row = 8 * lanes * itemsize + 8 * lanes
    per_q_row = group * (6 * lanes * itemsize + 4 * lanes
                         + 2 * 4 * _LANES)
    block = int(block or 0)
    share = _VMEM_BUDGET // 2

    def span(tile, fit):
        """The most whole tiles within `fit` rows that divide the
        padded sequence evenly (never less than one)."""
        tiles = seq_pad // tile
        return tile * max(n for n in range(1, tiles + 1)
                          if tiles % n == 0 and n <= max(fit // tile, 1))

    span_k = span(block_k, share // 2 // per_k_row)
    span_q = span(block_q, (share - span_k * per_k_row) // per_q_row)
    pairs = tuple(
        (qi, kj) for qi in range(seq_pad // span_q)
        for kj in range(seq_pad // span_k)
        if _live(qi * span_q, span_q, kj * span_k, span_k, causal,
                 window, block))
    return FlashPlan(block_q, block_k, span_q, span_k, seq_pad, pairs)


def _schedule(pairs, k_major=False):
    """The grid's span pairs as the two int32 arrays (qi, kj) the index
    maps read from scalar prefetch, q-major or (dk/dv) k-major."""
    if k_major:
        pairs = sorted(pairs, key=lambda p: (p[1], p[0]))
    return tuple(jnp.asarray([pair[side] for pair in pairs], jnp.int32)
                 for side in (0, 1))


def _pair_ends(config, at, transposed=False):
    """(first, last) k span a q span at row `at` meets in the schedule
    — transposed, the q spans a k span at column `at` meets: where its
    accumulators start and where they are written out."""
    size, block = config.span_q, config.span_k
    if transposed:
        size, block = block, size
    lo, hi = _live_blocks(at, size, block, 0, config.seq_pad // block,
                          config.causal, config.window, transposed,
                          config.block)
    return lo, hi - 1


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _column(row, size):
    """A `(1, size)` row of per-query (or per-key) numbers as the
    `(size, 1)` column a score tile with them on sublanes wants: the
    row broadcast down 128 sublanes, transposed on the XLU."""
    return jnp.broadcast_to(row, (_LANES, size)).T[:, :1]


def _scores(config, a, b):
    """Scaled (and soft-capped) logits of a tile, f32, with the
    softcap's chain-rule factor d(cap * tanh(s / cap))/ds =
    1 - tanh^2(s / cap) (None when capping is off). `a @ b.T`: q
    against k for a `[block_q, block_k]` tile, k against q for the
    transposed one."""
    s = jax.lax.dot_general(
        a, b, _NT, preferred_element_type=jnp.float32) * config.sm_scale
    if not config.softcap:
        return s, None
    # Gemma2 logit soft-capping, before masking (the HF order; masked
    # entries go to -inf either way, so the capped value never leaks).
    t = jnp.tanh(s / config.softcap)
    return config.softcap * t, 1.0 - t * t


def _tile_mask(config, r0, c0, key_valid, transposed=False):
    """Validity of a score tile whose first query is `r0` and first
    key `c0`: kv padding, causal structure, window band and (when
    present) the key mask, `(1, block_k)` — `(block_k, 1)` transposed.
    Queries index sublanes and keys lanes, the other way round when
    `transposed`. One mask serves every head of the group; None where
    the call masks nothing (not causal, no padding, no key mask)."""
    shape = ((config.block_k, config.block_q) if transposed
             else (config.block_q, config.block_k))
    key_axis = 0 if transposed else 1
    key = jax.lax.broadcasted_iota(jnp.int32, shape, key_axis)
    terms = []
    if config.kv_len < config.seq_pad:
        terms.append(key < config.kv_len - c0)
    if config.causal and config.block > 1:
        # col // B <= row // B: col up to the end of the row's block.
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - key_axis)
        terms.append(c0 + key <= _row_edge(r0 + row, config.block))
    elif config.causal:
        # col <= row, as an offset between the two iotas.
        ahead = key - jax.lax.broadcasted_iota(jnp.int32, shape,
                                               1 - key_axis)
        terms.append(ahead <= r0 - c0)
        if config.window:
            # Sliding-window band: col in (row - window, row] — the HF
            # Mistral convention (window keys visible including self).
            terms.append(ahead > r0 - c0 - config.window)
    if key_valid is not None:
        terms.append(key_valid != 0)
    return functools.reduce(jnp.logical_and, terms) if terms else None


def _masked(logits, mask):
    return logits if mask is None else jnp.where(mask, logits, _NEG_INF)


def _unmasked_floor(stat):
    """A row's running max or logsumexp, kept above the mask value: a
    masked logit is -1e30, so `exp(logit - stat)` is an exact 0 where
    masked — also in a row with no visible key yet, whose own statistic
    IS the mask value and would make it exp(0) = 1 (such rows output
    zeros)."""
    return jnp.maximum(stat, _NEG_INF / 2)


def _unpack(config, refs):
    """(q, k, v, mask or None, rest...) from a kernel's refs after the
    two schedule arrays."""
    if config.has_mask:
        return refs
    return refs[:3] + (None,) + refs[3:]


def _fwd_kernel(qi_ref, kj_ref, *refs, config):
    """One kv head, one live (q span, k span): for every row block of
    the span, the group's q heads walk the row block's live k blocks
    side by side — independent online-softmax chains in one loop body,
    so one head's softmax fills the slots another's products leave."""
    (q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
     acc_ref, m_ref, l_ref) = _unpack(config, refs)
    block_q, block_k = config.block_q, config.block_k
    heads = range(config.kv_group)
    step = pl.program_id(1)
    q0 = qi_ref[step] * config.span_q
    kj = kj_ref[step]
    k_lo = kj * (config.span_k // block_k)
    first, last = _pair_ends(config, q0)

    @pl.when(kj == first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def q_walk(s, _):
        rows = pl.ds(pl.multiple_of(s * block_q, block_q), block_q)
        r0 = q0 + s * block_q
        q = [q_ref[0, g, rows, :] for g in heads]

        def k_walk(j, carry):
            cols = pl.ds(pl.multiple_of((j - k_lo) * block_k, block_k),
                         block_k)
            k = k_ref[0, cols, :]
            v = v_ref[0, cols, :]
            mask = _tile_mask(
                config, r0, j * block_k,
                None if mask_ref is None else mask_ref[0, j - k_lo])
            out = []
            for g in heads:
                m_prev, l_prev, acc = carry[g]
                logits, _ = _scores(config, q[g], k)
                logits = _masked(logits, mask)
                m_next = jnp.maximum(
                    m_prev, jnp.max(logits, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_next)
                p = jnp.exp(logits - _unmasked_floor(m_next))
                l_next = alpha * l_prev + jnp.sum(p, axis=-1,
                                                  keepdims=True)
                acc = acc * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v, _NN,
                    preferred_element_type=jnp.float32)
                out.append((m_next, l_next, acc))
            return tuple(out)

        lo, hi = _live_blocks(r0, block_q, block_k, k_lo,
                              k_lo + config.span_k // block_k,
                              config.causal, config.window,
                              attn_block=config.block)
        state = jax.lax.fori_loop(lo, hi, k_walk, tuple(
            (m_ref[g, rows, :1], l_ref[g, rows, :1], acc_ref[g, rows, :])
            for g in heads))
        for g in heads:
            m, l, acc = state[g]
            m_ref[g, rows, :] = jnp.broadcast_to(m, (block_q, _LANES))
            l_ref[g, rows, :] = jnp.broadcast_to(l, (block_q, _LANES))
            acc_ref[g, rows, :] = acc

        @pl.when(kj == last)
        def _finalize():
            for g in heads:
                m, l, acc = state[g]
                safe_l = jnp.where(l == 0.0, 1.0, l)
                o_ref[0, g, rows, :] = (acc / safe_l).astype(o_ref.dtype)
                lse = jnp.broadcast_to(m + jnp.log(safe_l),
                                       (block_q, _LANES))
                lse_ref[0, g, s] = lse.T[:1, :]
        return 0

    jax.lax.fori_loop(0, config.span_q // block_q, q_walk, 0)


def _specs(config, head_dim):
    """BlockSpecs of a grid `(B*H_kv, span pairs)` whose index maps
    read the schedule's (qi, kj): (the group's q-side span, its
    per-query rows, the k/v span, the key mask's blocks)."""
    heads_kv = config.heads // config.kv_group
    at = lambda index: lambda b, t, qi, kj: index(b, qi[t], kj[t])
    q_spec = pl.BlockSpec(
        (1, config.kv_group, config.span_q, head_dim),
        at(lambda b, i, j: (b, 0, i, 0)))
    row_spec = pl.BlockSpec(
        (1, config.kv_group, config.span_q // config.block_q, 1,
         config.block_q), at(lambda b, i, j: (b, 0, i, 0, 0)))
    k_spec = pl.BlockSpec((1, config.span_k, head_dim),
                          at(lambda b, i, j: (b, j, 0)))
    mask_spec = pl.BlockSpec(
        (1, config.span_k // config.block_k, 1, config.block_k),
        at(lambda b, i, j: (b // heads_kv, j, 0, 0)))
    return q_spec, row_spec, k_spec, mask_spec


def _compiler_params(config):
    if config.interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_BUDGET)


def _row_shape(config, q):
    """Per-query f32 statistics as rows: one `(1, block_q)` slab a row
    block (a block's last two dims equal the array's, which Mosaic
    accepts at any `block_q`)."""
    return q.shape[:2] + (config.seq_pad // config.block_q, 1,
                          config.block_q)


@functools.partial(jax.jit, static_argnums=0)
def _flash_forward(config, q, k, v, kmask):
    """q: [B*H_kv, G, S_pad, D] (G = kv_group q heads a kv head); k/v:
    [B*H_kv, S_pad, D]; kmask: [B, S_pad/block_k, 1, block_k] int32 or
    None -> (out like q, lse [B*H_kv, G, S_pad/block_q, 1, block_q]).

    GQA: a step holds one kv head's span and its whole group's q rows —
    the H-wide expansion is never materialized in HBM. Jitted, so a
    model's layers trace and lower the kernel once a program (PERF.md
    section 6, PR 27)."""
    vma = partition.vma_of(q, k, v, kmask)
    head_dim = q.shape[-1]
    group, span_q = config.kv_group, config.span_q
    q_spec, row_spec, k_spec, mask_spec = _specs(config, head_dim)
    mask_in = [kmask] if config.has_mask else []
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, config=config),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(q.shape[0], len(config.pairs)),
            in_specs=[q_spec, k_spec, k_spec]
            + [mask_spec] * len(mask_in),
            out_specs=[q_spec, row_spec],
            scratch_shapes=[
                pltpu.VMEM((group, span_q, head_dim), jnp.float32),
                pltpu.VMEM((group, span_q, _LANES), jnp.float32),
                pltpu.VMEM((group, span_q, _LANES), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
            jax.ShapeDtypeStruct(_row_shape(config, q), jnp.float32,
                                 vma=vma),
        ],
        compiler_params=_compiler_params(config),
        interpret=config.interpret,
        name=_CALL_PREFIX + FLASH_FWD,
    )(*_schedule(config.pairs), q, k, v, *mask_in)
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _probs(logits, mask, lse):
    """The probability tile recomputed from its logits and the saved
    logsumexp; exactly 0 where masked (`_unmasked_floor`)."""
    return jnp.exp(_masked(logits, mask) - _unmasked_floor(lse))


def _dq_kernel(qi_ref, kj_ref, *refs, config):
    (q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
     dq_ref, dq_acc) = _unpack(config, refs)
    block_q, block_k = config.block_q, config.block_k
    heads = range(config.kv_group)
    step = pl.program_id(1)
    q0 = qi_ref[step] * config.span_q
    kj = kj_ref[step]
    k_lo = kj * (config.span_k // block_k)
    first, last = _pair_ends(config, q0)

    @pl.when(kj == first)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def q_walk(s, _):
        rows = pl.ds(pl.multiple_of(s * block_q, block_q), block_q)
        r0 = q0 + s * block_q
        q = [q_ref[0, g, rows, :] for g in heads]
        do = [do_ref[0, g, rows, :] for g in heads]
        lse = [_column(lse_ref[0, g, s], block_q) for g in heads]
        delta = [_column(delta_ref[0, g, s], block_q) for g in heads]

        def k_walk(j, carry):
            cols = pl.ds(pl.multiple_of((j - k_lo) * block_k, block_k),
                         block_k)
            k = k_ref[0, cols, :]
            v = v_ref[0, cols, :]
            mask = _tile_mask(
                config, r0, j * block_k,
                None if mask_ref is None else mask_ref[0, j - k_lo])
            out = []
            for g in heads:
                logits, dcap = _scores(config, q[g], k)
                p = _probs(logits, mask, lse[g])
                dp = jax.lax.dot_general(
                    do[g], v, _NT, preferred_element_type=jnp.float32)
                ds = p * (dp - delta[g]) * config.sm_scale
                if dcap is not None:
                    ds = ds * dcap
                out.append(carry[g] + jax.lax.dot_general(
                    ds.astype(k.dtype), k, _NN,
                    preferred_element_type=jnp.float32))
            return tuple(out)

        lo, hi = _live_blocks(r0, block_q, block_k, k_lo,
                              k_lo + config.span_k // block_k,
                              config.causal, config.window,
                              attn_block=config.block)
        dq = jax.lax.fori_loop(
            lo, hi, k_walk, tuple(dq_acc[g, rows, :] for g in heads))
        for g in heads:
            dq_acc[g, rows, :] = dq[g]

        @pl.when(kj == last)
        def _finalize():
            for g in heads:
                dq_ref[0, g, rows, :] = dq[g].astype(dq_ref.dtype)
        return 0

    jax.lax.fori_loop(0, config.span_q // block_q, q_walk, 0)


def _dkdv_kernel(qi_ref, kj_ref, *refs, config):
    """Grid (B*H_kv, span pairs), k-major: a kv head's dk/dv span
    accumulates over every live q span — and, inside, over every q
    head of its group: the GQA sum happens in the same accumulator
    that sums over row blocks. Score tiles are transposed, `[block_k,
    block_q]`: keys on sublanes, queries on lanes."""
    (q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
     dk_ref, dv_ref, dk_acc, dv_acc) = _unpack(config, refs)
    block_q, block_k = config.block_q, config.block_k
    heads = range(config.kv_group)
    step = pl.program_id(1)
    k0 = kj_ref[step] * config.span_k
    qi = qi_ref[step]
    q_lo = qi * (config.span_q // block_q)
    first, last = _pair_ends(config, k0, transposed=True)

    @pl.when(qi == first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def k_walk(s, _):
        cols = pl.ds(pl.multiple_of(s * block_k, block_k), block_k)
        c0 = k0 + s * block_k
        k = k_ref[0, cols, :]
        v = v_ref[0, cols, :]
        key_valid = (None if mask_ref is None else
                     _column(mask_ref[0, s].astype(jnp.float32), block_k))

        def q_walk(i, carry):
            dk, dv = carry
            rows = pl.ds(pl.multiple_of((i - q_lo) * block_q, block_q),
                         block_q)
            mask = _tile_mask(config, i * block_q, c0, key_valid,
                              transposed=True)
            for g in heads:
                q = q_ref[0, g, rows, :]
                do = do_ref[0, g, rows, :]
                logits, dcap = _scores(config, k, q)
                p = _probs(logits, mask, lse_ref[0, g, i - q_lo])
                # dV += P^T dO
                dv = dv + jax.lax.dot_general(
                    p.astype(do.dtype), do, _NN,
                    preferred_element_type=jnp.float32)
                dp = jax.lax.dot_general(
                    v, do, _NT, preferred_element_type=jnp.float32)
                ds = (p * (dp - delta_ref[0, g, i - q_lo])
                      * config.sm_scale)
                if dcap is not None:
                    ds = ds * dcap
                # dK += dS^T Q
                dk = dk + jax.lax.dot_general(
                    ds.astype(q.dtype), q, _NN,
                    preferred_element_type=jnp.float32)
            return dk, dv

        lo, hi = _live_blocks(c0, block_k, block_q, q_lo,
                              q_lo + config.span_q // block_q,
                              config.causal, config.window,
                              transposed=True, attn_block=config.block)
        dk, dv = jax.lax.fori_loop(
            lo, hi, q_walk, (dk_acc[cols, :], dv_acc[cols, :]))
        dk_acc[cols, :] = dk
        dv_acc[cols, :] = dv

        @pl.when(qi == last)
        def _finalize():
            dk_ref[0, cols, :] = dk.astype(dk_ref.dtype)
            dv_ref[0, cols, :] = dv.astype(dv_ref.dtype)
        return 0

    jax.lax.fori_loop(0, config.span_k // block_k, k_walk, 0)


def _flash_dq(config, q, k, v, kmask, g, lse, delta):
    vma = partition.vma_of(q, k, v, g)
    head_dim = q.shape[-1]
    mask_in = [kmask] if config.has_mask else []
    q_spec, row_spec, k_spec, mask_spec = _specs(config, head_dim)
    return pl.pallas_call(
        functools.partial(_dq_kernel, config=config),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(q.shape[0], len(config.pairs)),
            in_specs=[q_spec, k_spec, k_spec]
            + [mask_spec] * len(mask_in) + [q_spec, row_spec, row_spec],
            out_specs=[q_spec],
            scratch_shapes=[pltpu.VMEM(
                (config.kv_group, config.span_q, head_dim),
                jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma)],
        compiler_params=_compiler_params(config),
        interpret=config.interpret,
        name=_CALL_PREFIX + FLASH_BWD_DQ,
    )(*_schedule(config.pairs), q, k, v, *mask_in, g, lse, delta)[0]


def _flash_dkdv(config, q, k, v, kmask, g, lse, delta):
    vma = partition.vma_of(q, k, v, g)
    head_dim = q.shape[-1]
    mask_in = [kmask] if config.has_mask else []
    q_spec, row_spec, k_spec, mask_spec = _specs(config, head_dim)
    return pl.pallas_call(
        functools.partial(_dkdv_kernel, config=config),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(q.shape[0], len(config.pairs)),
            in_specs=[q_spec, k_spec, k_spec]
            + [mask_spec] * len(mask_in) + [q_spec, row_spec, row_spec],
            out_specs=[k_spec, k_spec],
            scratch_shapes=[
                pltpu.VMEM((config.span_k, head_dim), jnp.float32),
                pltpu.VMEM((config.span_k, head_dim), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
            jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma),
        ],
        compiler_params=_compiler_params(config),
        interpret=config.interpret,
        name=_CALL_PREFIX + FLASH_BWD_DKV,
    )(*_schedule(config.pairs, k_major=True), q, k, v, *mask_in, g, lse,
      delta)


@functools.partial(jax.jit, static_argnums=0)
def _flash_backward(config, q, k, v, kmask, out, lse, g):
    config = config.for_backward()
    if kmask is not None:  # the same keys, a row a backward k block
        kmask = kmask.reshape(kmask.shape[0], -1, 1, config.block_k)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(_row_shape(config, q))
    dq = _flash_dq(config, q, k, v, kmask, g, lse, delta)
    dk, dv = _flash_dkdv(config, q, k, v, kmask, g, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_attention(config, q, k, v):
    out, _ = _flash_forward(config, q, k, v, None)
    return out


def _flash_attention_fwd(config, q, k, v):
    out, lse = _flash_forward(config, q, k, v, None)
    return out, (q, k, v, out, lse)


def _flash_attention_bwd(config, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_backward(config, q, k, v, None, out, lse, g)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_attention_masked(config, q, k, v, kmask):
    out, _ = _flash_forward(config, q, k, v, kmask)
    return out


def _flash_attention_masked_fwd(config, q, k, v, kmask):
    out, lse = _flash_forward(config, q, k, v, kmask)
    return out, (q, k, v, kmask, out, lse)


def _flash_attention_masked_bwd(config, residuals, g):
    q, k, v, kmask, out, lse = residuals
    dq, dk, dv = _flash_backward(config, q, k, v, kmask, out, lse, g)
    # Integer mask: the cotangent is the symbolic zero, float0.
    return dq, dk, dv, np.zeros(kmask.shape, jax.dtypes.float0)


_flash_attention_masked.defvjp(_flash_attention_masked_fwd,
                               _flash_attention_masked_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, causal=True, sm_scale=None, mask=None,
                    window=None, logit_softcap=None, block_q=None,
                    block_k=None, interpret: Optional[bool] = None,
                    block=None):
    """Blockwise flash attention, layout [batch, seq, heads, head_dim].

    Args:
        q, k, v: [B, S, H, D] arrays (any float dtype; compute is f32 on
            the MXU, output in the input dtype). Grouped-query
            attention: k/v may carry H_kv < H heads (H divisible by
            H_kv) — each kv head serves H/H_kv consecutive q heads, and
            the kernel streams kv blocks per group instead of
            materializing the H-wide expansion in HBM.
        causal: Apply a causal (autoregressive) mask.
        window: Sliding-window (Mistral-style) attention — row i
            attends keys in (i-window, i]; requires causal=True. Tiles
            entirely outside the band are never visited
            (_live_blocks), so long-sequence cost scales with S*window,
            not S^2.
        sm_scale: Softmax temperature; default 1/sqrt(D).
        logit_softcap: Gemma2-style tanh logit capping — logits become
            cap * tanh(logits / cap) after the softmax scale and before
            masking (the HF Gemma2 order); the backward kernels fold
            the tanh derivative into dS. None/0 = off.
        mask: Optional [B, S] boolean key mask (True = attend). The
            padded-batch fast path: masked keys are excluded inside the
            kernel, so Keras-parity workloads with per-example padding
            never leave the flash path. Any pattern is supported, not
            just contiguous prefixes. Rows whose keys are ALL masked
            output zeros — and since round 4 `mha_reference` adopts the
            same convention, kernel and oracle agree on every row.
        block_q / block_k: Rows and columns of the score tile the
            kernels walk. Default (None): from the shapes, by
            `flash_plan`'s rule. S is padded up to a multiple
            internally.
        interpret: Force Pallas interpret mode. Default: interpret
            everywhere except on real TPU backends.
        block: Block-causal attention (block-diffusion models): row i
            attends keys j with j // block <= i // block — causal
            between blocks, bidirectional inside one. Requires
            causal=True and no window; None/0/1 = the causal mask.

    Returns:
        [B, S, H, D] attention output, differentiable w.r.t. q/k/v.
    """
    batch, seq, heads, head_dim = q.shape
    h_kv = k.shape[2]
    if v.shape != k.shape:
        raise ValueError("k and v must have identical shapes; got "
                         "{} vs {}.".format(k.shape, v.shape))
    if heads % h_kv:
        raise ValueError(
            "q heads {} must be a multiple of kv heads {}.".format(
                heads, h_kv))
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    _check_mask_kind(causal, window, block)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    shape = (head_dim, heads // h_kv, q.dtype.itemsize, causal, window)
    extra = {"block": int(block)} if (block or 0) > 1 else {}
    tiles = flash_plan(seq, *shape, block_q, block_k, **extra)
    seq_pad = tiles.seq_pad
    backward = flash_plan(seq_pad, *shape, tiles.block_q, block_k,
                          backward=True, **extra)

    if mask is not None and mask.shape != (batch, seq):
        raise ValueError(
            "mask must be [batch, seq] = {}; got {}.".format(
                (batch, seq), mask.shape))

    def kernel(q, k, v, *kmask):
        """One device's [B', S, H', D] block."""
        batch, _, heads, _ = q.shape
        config = _Config(causal=bool(causal), sm_scale=float(sm_scale),
                         tiles=tiles, kv_len=seq, heads=heads,
                         has_mask=bool(kmask), interpret=bool(interpret),
                         kv_group=heads // k.shape[2],
                         window=int(window or 0),
                         softcap=float(logit_softcap or 0.0),
                         backward=backward, **extra)

        def fold(x):
            n_heads = x.shape[2]
            x = jnp.transpose(x, (0, 2, 1, 3)).reshape(
                batch * n_heads, seq, head_dim)
            if seq_pad != seq:
                x = jnp.pad(x, ((0, 0), (0, seq_pad - seq), (0, 0)))
            return x

        def fold_q(x):
            # A kv head's group of q heads is contiguous: a free view.
            return fold(x).reshape(-1, config.kv_group, seq_pad, head_dim)

        operands = [fold_q(q), fold(k), fold(v)]
        if kmask:
            # [B, S_pad / block_k, 1, block_k]: a (1, block_k) row a k
            # block, legal under Mosaic's sublane rule at any block_k
            # (_row_shape).
            operands.append(jnp.pad(
                kmask[0].astype(jnp.int32),
                ((0, 0), (0, seq_pad - seq))).reshape(
                    batch, seq_pad // tiles.block_k, 1, tiles.block_k))
        attend = _flash_attention_masked if kmask else _flash_attention
        out = attend(config, *partition.common_vma(*operands))
        out = out[:, :, :seq].reshape(batch, heads, seq, head_dim)
        return jnp.transpose(out, (0, 2, 1, 3))

    def plan(mesh):
        """Batch over the data axis, heads over the model axis."""
        dp = partition.data_axis(mesh, batch)
        tp = partition.model_axis(mesh, heads, h_kv)
        spec = P(dp, None, tp, None)
        in_specs = (spec,) * 3 + ((P(dp, None),) if mask is not None
                                  else ())
        return in_specs, spec, None

    args = (q, k, v) + ((mask,) if mask is not None else ())
    return partition.per_shard(kernel, args, plan, interpret)


def attention(q, k, v, causal=True, sm_scale=None, mask=None,
              window=None, logit_softcap=None, impl="auto", block=None):
    """Dispatching attention: pallas flash kernel or jnp reference.

    impl: "auto" picks the flash kernel on TPU (with or without a key
    mask — padded batches stay on the fast path), the jnp reference
    elsewhere; "flash"/"reference" force a path. window: sliding-window
    width; logit_softcap: Gemma2 tanh capping; block: block-causal
    attention's block length (every path honors all three).
    """
    kwargs = dict(causal=causal, sm_scale=sm_scale, mask=mask,
                  window=window, logit_softcap=logit_softcap)
    if (block or 0) > 1:
        kwargs["block"] = int(block)
    if impl == "flash":
        return flash_attention(q, k, v, **kwargs)
    if impl == "reference":
        return mha_reference(q, k, v, **kwargs)
    if impl != "auto":
        raise ValueError("Unknown attention impl: {!r}".format(impl))
    if jax.default_backend() == "tpu":
        return flash_attention(q, k, v, **kwargs)
    return mha_reference(q, k, v, **kwargs)


# ---------------------------------------------------------------------------
# Latent prefill: a suffix's queries over a longer dense cache
# ---------------------------------------------------------------------------

#: The latent prefill's declared name (table in monitoring/spans.py).
MLA_PREFILL = "mla_prefill"

#: Query and key rows of a grid step of `mla_prefill`: a `[512, 1024]`
#: f32 score tile, twice the rows of the flash forward's walk tile (the
#: heads here are 192 wide, so a turn's products outweigh its softmax
#: the more).
_PREFILL_BLOCK_Q = 512
_PREFILL_BLOCK_K = 1024


class _PrefillConfig(NamedTuple):
    sm_scale: float
    block_q: int
    block_k: int
    cache_len: int


def _prefill_last_block(config, start, qi):
    """The last key block that query block `qi` sees: its last row sits
    at cache row `start + (qi + 1) * block_q - 1`."""
    return jnp.minimum(start + (qi + 1) * config.block_q - 1,
                       config.cache_len - 1) // config.block_k


def _mla_prefill_kernel(start_ref, full_ref, qn_ref, qr_ref, kn_ref, kr_ref,
                        v_ref, valid_ref, o_ref, acc_ref, m_ref, l_ref, *,
                        config):
    """One step: one head's block of queries against one block of its
    expanded keys and values and of the rope key all heads share. Steps
    past the query block's causal edge fetch nothing (their index maps
    repeat the last live block) and compute nothing; only the blocks
    that straddle the edge or hold an invalid key are masked."""
    import jax.lax as lax

    b, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    bq, bk = config.block_q, config.block_k
    start = start_ref[0]
    row0 = start + qi * bq
    last = _prefill_last_block(config, start, qi)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(masked):
        contract = (((1,), (1,)), ((), ()))
        s = (lax.dot_general(qn_ref[0, 0], kn_ref[0, 0], contract,
                             preferred_element_type=jnp.float32)
             + lax.dot_general(qr_ref[0, 0], kr_ref[0], contract,
                               preferred_element_type=jnp.float32)
             ) * config.sm_scale
        if masked:
            rows = row0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = kj * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = (cols <= rows) & (valid_ref[0, 0] != 0)
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        if masked:
            p = jnp.where(mask, p, 0.0)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0, 0],
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)

    # Some key of the block lies past some row's causal edge, or is not
    # valid: the masked update; else the plain one.
    edge = ((kj + 1) * bk - 1 > row0) | (full_ref[b, kj] == 0)

    @pl.when((kj <= last) & edge)
    def _masked():
        update(True)

    @pl.when((kj <= last) & jnp.logical_not(edge))
    def _plain():
        update(False)

    @pl.when(kj == last)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0, 0] = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _mla_prefill_call(q_nope, q_rope, k_nope, k_rope, v, start, key_valid,
                      *, sm_scale, interpret):
    batch, heads, seq, nope = q_nope.shape
    cache_len, width = k_nope.shape[2], v.shape[-1]
    block_q = min(_PREFILL_BLOCK_Q, -(-seq // 8) * 8)
    block_k = min(_PREFILL_BLOCK_K, cache_len)
    if cache_len % block_k:
        raise ValueError(
            "cache_len ({}) must be a multiple of {} (or below it)."
            .format(cache_len, block_k))
    seq_pad = -(-seq // block_q) * block_q
    if seq_pad != seq:
        rows = ((0, 0), (0, 0), (0, seq_pad - seq), (0, 0))
        q_nope, q_rope = jnp.pad(q_nope, rows), jnp.pad(q_rope, rows)
    config = _PrefillConfig(sm_scale=sm_scale, block_q=block_q,
                            block_k=block_k, cache_len=cache_len)
    n_k = cache_len // block_k
    valid = key_valid.astype(jnp.int32).reshape(batch, n_k, 1, block_k)
    full = jnp.min(valid, axis=(2, 3))
    start = jnp.reshape(start, (1,)).astype(jnp.int32)

    def key_block(b, h, i, j, start_ref, _):
        return jnp.minimum(j, _prefill_last_block(config, start_ref[0], i))

    q_map = lambda b, h, i, j, *_: (b, h, i, 0)
    k_map = lambda b, h, i, j, *refs: (b, h, key_block(b, h, i, j, *refs), 0)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, nope), q_map),
        pl.BlockSpec((1, 1, block_q, q_rope.shape[-1]), q_map),
        pl.BlockSpec((1, 1, block_k, nope), k_map),
        pl.BlockSpec((1, block_k, k_rope.shape[-1]),
                     lambda b, h, i, j, *refs: (
                         b, key_block(b, h, i, j, *refs), 0)),
        pl.BlockSpec((1, 1, block_k, width), k_map),
        pl.BlockSpec((1, 1, 1, block_k),
                     lambda b, h, i, j, *refs: (
                         b, key_block(b, h, i, j, *refs), 0, 0)),
    ]
    out = pl.pallas_call(
        functools.partial(_mla_prefill_kernel, config=config),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, heads, seq_pad // block_q, n_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, block_q, width), q_map),
            scratch_shapes=[pltpu.VMEM((block_q, width), jnp.float32),
                            pltpu.VMEM((block_q, _LANES), jnp.float32),
                            pltpu.VMEM((block_q, _LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((batch, heads, seq_pad, width),
                                       jnp.float32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET),
        interpret=interpret,
        name=MLA_PREFILL,
    )(start, full, q_nope, q_rope, k_nope, k_rope, v, valid)
    return out[:, :, :seq]


def mla_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, start,
                          key_valid, sm_scale,
                          interpret: Optional[bool] = None):
    """Causal attention of a prefill's queries over a dense cache, the
    expanded form of latent attention: the Pallas kernel `mla_prefill`.

    q_nope `[B, H, S, n]`, q_rope `[B, H, S, p]`; k_nope `[B, H, L, n]`
    and v `[B, H, L, w]` (`kv_b` over the cache's latents), k_rope
    `[B, L, p]` (the rotated rope key every head shares); `start` the
    cache row of query 0 (a traced scalar: the prefix a prefill runs
    behind); key_valid `[B, L]`. Query row i sees key j where
    `j <= start + i` and `key_valid[b, j]`: what
    `decoding.decode_slot_update`'s `allowed` says. -> `[B, H, S, w]`
    float32; a row that sees no key is zero. L is a multiple of 1024
    (or below it)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _mla_prefill_call(q_nope, q_rope, k_nope, k_rope, v, start,
                             key_valid, sm_scale=float(sm_scale),
                             interpret=bool(interpret))
