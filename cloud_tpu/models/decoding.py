"""Shared KV-cache slot bookkeeping for decode-mode attention.

Every decode attention (`CausalSelfAttention`, `GQAttention`,
`MLAttention`) appends incoming tokens at the cache write pointer and
attends over everything valid so far. The left-padded-prompt contract
(`generate(prompt_mask=)`) adds per-example bookkeeping on top: padded
slots must never be attended, and rotary angles / learned-position
lookups / sliding-window bands must count only REAL tokens. This
module holds that recipe ONCE so the three families cannot drift.

Cache variables created on the calling module ("cache" collection):
  cache_index  []       slot write pointer (shared across examples)
  slot_valid   [B, L]   True where a real token was written
  slot_pos     [B, L]   the slot's LOGICAL position (real tokens only)
  token_count  [B]      number of real tokens seen per example
"""

import functools
import re
import warnings

import jax
import jax.lax as lax
import jax.numpy as jnp


def decode_slot_update(module, mask, batch, seq, cache_len):
    """Advance the decode cache's slot bookkeeping for one call.

    module: the flax module (inside @nn.compact) owning the cache.
    mask: optional [B, S] marking REAL incoming tokens (None = all).

    Returns (idx, positions, allowed):
      idx        the write pointer BEFORE this call (callers write
                 their k/v tensors at slots [idx, idx+S));
      positions  [B, S] int32 logical position of each incoming token
                 (#real tokens before it, per example) — feed to RoPE
                 or a learned position table; padded entries carry a
                 harmless placeholder (their slots are invalid);
      allowed    [B, S, L] bool attention mask: slot-order causality
                 (append-only writes make slot index the causal order)
                 AND slot validity (padded + never-written slots
                 excluded).

    The sliding-window band is the caller's concern: compare the
    module's `slot_pos` cache variable (logical key positions) against
    `positions` — see `GQAttention._decode_attention`.
    """
    index = module.variable(
        "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
    slot_valid = module.variable(
        "cache", "slot_valid", jnp.zeros, (batch, cache_len), jnp.bool_)
    slot_pos = module.variable(
        "cache", "slot_pos", jnp.zeros, (batch, cache_len), jnp.int32)
    token_count = module.variable(
        "cache", "token_count", jnp.zeros, (batch,), jnp.int32)

    m = (jnp.ones((batch, seq), jnp.int32) if mask is None
         else mask.astype(jnp.int32))
    idx = index.value
    positions = token_count.value[:, None] + jnp.cumsum(m, 1) - m

    slot_valid.value = lax.dynamic_update_slice(
        slot_valid.value, m.astype(jnp.bool_), (0, idx))
    slot_pos.value = lax.dynamic_update_slice(
        slot_pos.value, positions.astype(jnp.int32), (0, idx))
    index.value = idx + seq
    token_count.value = token_count.value + m.sum(axis=1)

    key_slots = jnp.arange(cache_len)
    allowed = (slot_valid.value[:, None, :]
               & (key_slots[None, None, :]
                  <= idx + jnp.arange(seq)[None, :, None]))
    return idx, positions, allowed


def paged_slot_update(module, mask, slots, seq, cache_len):
    """The per-slot (continuous-batching) counterpart of
    `decode_slot_update`, for decode ticks over a paged pool.

    Where `decode_slot_update` advances ONE shared write pointer (all
    examples decode in lockstep), a serving tick advances each slot
    independently: slot s sits at its own depth `slot_steps[s]`, and an
    inactive slot (mask 0) must not move at all. Slot-order causality
    and validity masking are otherwise the recipe above, per row.

    `seq` may exceed 1: the speculative tick verifies a (k+1)-token
    window per slot in one call (serving/engine.py), writing each
    slot's tokens at consecutive positions from its own pointer. The
    single-token plain tick is the seq=1 specialization — the masks
    and pointer math reduce to exactly the PR 10 forms.

    Cache variables created on the calling module ("cache" collection):
      slot_steps  [S]      per-slot write pointer (tokens written)
      slot_valid  [S, L]   True where a real token was written
    (The page table itself is the attention module's variable — it owns
    the physical layout; this helper owns only the logical bookkeeping.)

    Returns (pos, allowed):
      pos      [S, seq] int32 per-token write positions — callers write
               token j of slot s at logical position pos[s, j] (the
               slot's pointer plus the real tokens before j);
      allowed  [S, seq, L] bool attention mask over each slot's LOGICAL
               cache view (validity AND slot-order causality up to each
               query's own write position), the exact mask
               `decode_slot_update` would produce for a solo decode at
               the same depth.
    """
    slot_steps = module.variable(
        "cache", "slot_steps", jnp.zeros, (slots,), jnp.int32)
    slot_valid = module.variable(
        "cache", "slot_valid", jnp.zeros, (slots, cache_len), jnp.bool_)

    m = (jnp.ones((slots, seq), jnp.int32) if mask is None
         else mask.reshape(slots, seq).astype(jnp.int32))
    idx = slot_steps.value
    pos = idx[:, None] + jnp.cumsum(m, 1) - m
    # Masked scatter: active slots validate their write positions; an
    # inactive slot OR-writes False at its (clamped) current position —
    # the identity, so it neither moves nor changes state.
    slot_valid.value = slot_valid.value.at[
        jnp.arange(slots)[:, None],
        jnp.clip(pos, 0, cache_len - 1)].max(m.astype(jnp.bool_))
    slot_steps.value = idx + m.sum(axis=1)

    key_slots = jnp.arange(cache_len)
    allowed = (slot_valid.value[:, None, :]
               & (key_slots[None, None, :] <= pos[:, :, None]))
    return pos, allowed


def paged_kv_attention(module, q, k, v, mask, *, cache_len, page_size,
                       num_pages, page_dtype, store_dtype, sm_scale,
                       impl, rotate=None, window=None):
    """Decode over the paged KV pool (continuous batching), for the
    attention module of either served class (`CausalSelfAttention`,
    `GQAttention`): writes this call's K/V rows into the pool and
    reads the slots' logical views through `ops.paged_attention`.

    The batch dimension is SLOTS, each at its own depth: physical K/V
    live in a shared page pool `[num_pages, page_size, H_kv*D]` (a row
    per token, key/value heads folded into lanes — the device layout
    ops/paged_attention.py's kernel reads without a copy), each
    slot's logical `[cache_len]` view is its page table's gather
    over the pool. Writes are per-slot scatters at `slot_steps[s]`;
    insertion/eviction are index updates on the page table and
    validity rows (serving/engine.py), so the tick executable never
    retraces.

    q: [slots, seq, H, D]; k, v: [slots, seq, H_kv, D]. `seq` is 1 for
    the plain tick and k+1 for the speculative verify window — each
    slot's tokens land at consecutive logical positions from its own
    pointer and every query attends exactly the keys a solo decode at
    its depth would (per-query causality from `paged_slot_update`).
    `rotate(x, positions)` (RoPE) is applied to q and k at those
    positions before the write, so the pool holds rotated keys as the
    dense cache does. `window`: a window layer — query i attends keys
    in (i - window, i], the band the dense cache masks on logical
    positions; a slot's logical position is its cache index here.
    (That holds for every layer that keeps a row a token. The one
    exception is an EVA layer, `models/evabyte.py`, which does not come
    through this function: its slot keeps a ring of window rows, token
    t at row `t mod window`, and a table of summary rows, and its mask
    follows from the slot's depth alone — `ops/eva.py` `EvaLayout`.)

    Per-slot math is EXACTLY the dense `_decode_attention`'s per-row
    math over the gathered logical view (same masking, same f32
    einsum), which is what makes engine tokens bit-identical to solo
    `generate()` — see tests/unit/test_serving.py.

    Pages may be SHARED between slots (radix prefix cache,
    serving/prefixcache.py): shared pages sit strictly below every
    holder's write pointer, so they are only ever gathered, never
    scattered to — copy-on-write happens at insert time by routing
    divergent content into fresh pages.

    The scratch page (physical page 0) is never handed out by the
    pool allocator: freed/empty page-table rows are all 0, so an
    inactive slot's write lands in scratch and its garbage is
    masked to exact-zero weight, never attended by anyone.
    """
    slots, seq = q.shape[:2]
    kv_heads, head_dim = k.shape[2:]
    if not cache_len or cache_len % page_size:
        raise ValueError(
            "cache_len ({}) must be a positive multiple of "
            "page_size ({}).".format(cache_len, page_size))
    if num_pages < 2:
        raise ValueError("num_pages must be >= 2 (page 0 is the "
                         "scratch page).")
    if page_dtype not in ("", "int8"):
        raise ValueError(
            "page_dtype must be '' or 'int8'; got {!r}.".format(
                page_dtype))
    quantized = page_dtype == "int8"
    pages_per_slot = cache_len // page_size
    page_store = jnp.int8 if quantized else store_dtype
    pool_shape = (num_pages, page_size, kv_heads * head_dim)
    key_pages = module.variable("cache", "key_pages", jnp.zeros,
                                pool_shape, page_store)
    value_pages = module.variable("cache", "value_pages", jnp.zeros,
                                  pool_shape, page_store)
    page_table = module.variable(
        "cache", "page_table", jnp.zeros, (slots, pages_per_slot),
        jnp.int32)
    if quantized:
        # Per-page per-head symmetric scales; 0 = never-written
        # page (dequantizes to exact zeros). They live in the same
        # attention cache subtree as the pages, so the engine's
        # _map_attention / paged_slot_rewind carry them for free.
        key_scales = module.variable(
            "cache", "key_scales", jnp.zeros,
            (num_pages, kv_heads), jnp.float32)
        value_scales = module.variable(
            "cache", "value_scales", jnp.zeros,
            (num_pages, kv_heads), jnp.float32)

    pos, allowed = paged_slot_update(module, mask, slots, seq, cache_len)
    if rotate is not None:
        q, k = rotate(q, pos), rotate(k, pos)
    if window:
        allowed = allowed & (jnp.arange(cache_len)[None, None, :]
                             > pos[:, :, None] - window)
    # Physical write targets: slot s's page for each token's
    # logical position pos[s, j]. Inactive/evicted slots resolve
    # to page 0 (scratch) via their zeroed page-table row.
    phys = jnp.take_along_axis(page_table.value, pos // page_size, 1)
    off = pos % page_size
    if quantized:
        if mask is not None:
            # Zero invalid tokens pre-quantize so pad garbage never
            # inflates a real page's amax scale (their positions are
            # masked from attention either way).
            m = mask.reshape(slots, seq).astype(k.dtype)
            k = k * m[:, :, None, None]
            v = v * m[:, :, None, None]
        key_pages.value, key_scales.value = _quantized_page_write(
            key_pages.value, key_scales.value, k, phys, off)
        value_pages.value, value_scales.value = _quantized_page_write(
            value_pages.value, value_scales.value, v, phys, off)
        scales_kw = dict(key_scales=key_scales.value,
                         value_scales=value_scales.value)
    else:
        rows = lambda x: x.astype(store_dtype).reshape(
            slots, seq, kv_heads * head_dim)
        key_pages.value = key_pages.value.at[phys, off].set(rows(k))
        value_pages.value = value_pages.value.at[phys, off].set(rows(v))
        scales_kw = {}

    # Impl selection (ops/paged_attention.py): "auto" runs the
    # Pallas paged kernel on TPU — the page table rides as a
    # scalar-prefetch operand, so the pool is block-indexed a
    # group of pages a grid step, as far as `allowed` has a slot
    # live and no further, with online softmax in VMEM, never
    # materialized as a dense [slots, cache_len, H, D] gather —
    # and the gathered-lax reference elsewhere, which is bitwise
    # the dense path's math (engine-vs-solo bit-identity).
    # CLOUD_TPU_PAGED_KERNEL=1/0 force-overrides. Every paged
    # decode — engine tick, speculative verify window, solo paged
    # decode, of either class — routes through this one call.
    from cloud_tpu.ops import paged_attention
    return paged_attention(
        q, key_pages.value, value_pages.value, page_table.value,
        allowed, sm_scale=sm_scale, impl=impl, window=window,
        **scales_kw)


def _quantized_page_write(pages, scales, x, phys, off):
    """Write [slots, seq, H, D] decode K/V into int8 pages with
    per-page per-head amax rescale.

    pages: [N, P, H*D] int8; scales: [N, H] f32; phys/off: [slots,
    seq] physical page / in-page offset per token. Returns the updated
    (pages, scales).

    Per position j (static python loop — seq is 1 for the plain tick,
    spec_k + 1 for the verify window): the page's scale grows
    monotonically to cover the new token's amax
    (`new = max(old, amax / 127)`), the page's existing block is
    rescaled by `old / new` and the token quantized at `new`. When the
    scale doesn't grow the rescale factor is exactly 1.0 and
    `round(x * 1.0) == x` for int8-range values in f32, so the rewrite
    is an exact no-op — steady-state decode never degrades earlier
    tokens. Duplicate physical targets across slots only happen at the
    scratch page (inactive slots' zeroed table rows); its undefined
    winner is never attended. Scales only *reset* at page-granular
    rewrites (the engine insert scatter / host-tier promote), which
    cover every recycled page before a decode write can touch it.
    """
    slots, seq, heads, head_dim = x.shape
    page_size = pages.shape[1]
    xf = x.astype(jnp.float32)
    rows = jnp.arange(slots)
    for j in range(seq):
        p = phys[:, j]                       # [slots]
        o = off[:, j]
        xj = xf[:, j]                        # [slots, H, D]
        amax = jnp.max(jnp.abs(xj), axis=-1)  # [slots, H]
        old = scales[p]
        new = jnp.maximum(old, amax / 127.0)
        safe = jnp.where(new > 0, new, 1.0)
        factor = (old / safe)[:, None, :, None]
        block = pages[p].astype(jnp.float32).reshape(
            slots, page_size, heads, head_dim)
        block = jnp.clip(jnp.round(block * factor), -127, 127)
        qx = jnp.clip(jnp.round(xj / safe[:, :, None]), -127, 127)
        block = block.at[rows, o].set(qx)
        pages = pages.at[p].set(block.astype(jnp.int8).reshape(
            slots, page_size, heads * head_dim))
        scales = scales.at[p].set(new)
    return pages, scales


def paged_slot_rewind(cache_tree, delta, cache_len):
    """Rolls per-slot paged bookkeeping back by `delta[s]` positions:
    the speculative tick writes a full (k+1)-token verify window, then
    keeps only the accepted prefix — rejected positions become invalid
    and the pointer retreats, exactly `speculative._rewind_cache`'s
    bookkeeping-only rollback per slot. Physical page contents are NOT
    touched: an invalidated slot is masked to exact-zero attention
    weight and overwritten by the next real write.

    `cache_tree` is a plain-dict paged cache; attention subtrees are
    detected by their `key_pages` variable. Returns the rolled-back
    tree (functional update).
    """
    def rewind(att):
        out = dict(att)
        steps = att["slot_steps"] - delta
        out["slot_steps"] = steps
        out["slot_valid"] = (att["slot_valid"]
                             & (jnp.arange(cache_len)[None, :]
                                < steps[:, None]))
        return out

    def walk(tree):
        if isinstance(tree, dict):
            if "key_pages" in tree:
                return rewind(tree)
            return {k: walk(v) for k, v in tree.items()}
        return tree

    return walk(cache_tree)


# The load-bearing fragment of the warning jax emits when donated
# buffers can't alias (a plain `warnings.warn`, so category
# UserWarning; jax/_src/interpreters/mlir.py). Matching a FRAGMENT
# rather than the whole sentence ("Some donated buffers were not
# usable: ...") leaves the text around it free to change. Only if the core phrase itself disappears does the filter
# degrade to a no-op: the warning becomes visible again (fail open),
# never wrongly silenced.
_DONATION_FRAGMENT = "donated buffers were not usable"
# `warnings.filterwarnings` anchors its regex at the start of the
# message, so a leading wildcard makes this a substring match; the
# escape is future-proofing for fragments with regex metacharacters.
_DONATION_PATTERN = r".*" + re.escape(_DONATION_FRAGMENT)


def _arm_donation_filter():
    """Ensure ONE ignore entry for jax's donation warning is in the
    warnings filter list; re-installs after pytest's per-test filter
    resets wipe it. The scan compares the compiled pattern the
    installed entry carries (filterwarnings compiled it once, at
    install — never per dispatch) so repeated arming is an O(filters)
    string compare, not a filter-list mutation."""
    for entry in warnings.filters:
        if (entry[0] == "ignore"
                and getattr(entry[1], "pattern", None) == _DONATION_PATTERN
                and entry[2] is UserWarning):
            return
    warnings.filterwarnings("ignore", message=_DONATION_PATTERN,
                            category=UserWarning)


def best_effort_donation(fn):
    """Wrap a jitted decode executable whose cache arguments are
    donated: donation is an optimization, not a contract — under a
    mesh the caller's (e.g. replicated) cache layout may not alias the
    GSPMD-partitioned layout the executable compiled to, and JAX warns
    'Some donated buffers were not usable' on every call. The callers
    never reuse the passed-in cache either way, so suppress exactly
    that message (category + compiled-once regex match).

    The filter is installed AT MOST ONCE per process and only
    re-checked (not re-installed) per dispatch — the previous per-call
    `catch_warnings` save/restore mutated the thread-GLOBAL filter
    list on every decode step, which races with concurrent decode
    threads and thrashes the warning registry. The accepted trade:
    the ignore is process-wide, so a USER jit emitting the identical
    donation message is silenced too; that message is advisory (an
    optimization that didn't apply), never a correctness signal.
    """
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        _arm_donation_filter()
        return fn(*args, **kwargs)
    return wrapped


def bucket_length(n, cap=None):
    """The decode prefill bucket for a prompt of length `n`: the next
    power of two >= n, clipped to `cap` (the caller's token budget,
    typically `max_seq_len - max_new_tokens`).

    Under static shapes every distinct prompt length mints its own
    prefill executable; padding to power-of-two buckets bounds the
    executable census at ~log2(max_seq_len) per sampling config. The
    clip keeps the padded prompt inside the cache budget: lengths in
    (previous_power_of_two, cap] share the cap-width bucket. When `n`
    already exceeds `cap` the length is returned unchanged — bucketing
    pads, never truncates (overflow is the caller's validation error).
    """
    if n < 1:
        raise ValueError(
            "bucket_length needs a positive length; got {}.".format(n))
    bucket = 1
    while bucket < n:
        bucket *= 2
    if cap is not None:
        if cap < n:
            return n
        bucket = min(bucket, cap)
    return bucket


def validate_prompt_mask(prompt_mask, batch, prompt_len, reader):
    """The left-padded variable-length prompt contract, checked ONCE
    for every decode entry point (`generate`, `generate_beam`):
    prompt_mask is [batch, prompt_len] with every row's LAST column
    real — the position whose logits/log-probs `reader` consumes."""
    import numpy as np

    pm = np.asarray(prompt_mask)
    if pm.shape != (batch, prompt_len):
        raise ValueError(
            "prompt_mask must be [batch, prompt_len] = {}; got "
            "{}.".format((batch, prompt_len), pm.shape))
    if not pm[:, -1].all():
        raise ValueError(
            "prompt_mask must be LEFT-padded (last column all real): "
            "{} reads the final prompt position.".format(reader))


def warp_logits(logits, temperature, top_k=None, top_p=None):
    """HF-warper-order logits processing: top-k (on raw logits) →
    temperature → top-p nucleus. Shared by `generate()`'s sampler and
    stochastic speculative decoding, so the speculative accept/reject
    math targets EXACTLY the distribution `generate()` samples from.

    temperature must be > 0 (greedy argmax is a separate path).
    Nucleus membership is decided in sorted order and scattered back
    through the inverse permutation — exact logit ties at the cutoff
    are split by descending-sort position (jnp.argsort is stable, so
    equal logits keep vocab-index order), matching HF's sorted-index
    scatter rather than a value threshold that would keep every tied
    token (reference semantics: transformers TopPLogitsWarper).
    """
    logits = logits.astype(jnp.float32)
    if top_k is not None:
        # O(V log k), not a full vocab sort per decode step.
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    scaled = logits / temperature
    if top_p is not None and top_p < 1.0:
        # Keep the smallest top-probability set whose cumulative mass
        # reaches top_p: `cum - probs < top_p` keeps every token whose
        # EXCLUSIVE prefix mass is below the threshold — the set up to
        # and including the first token that crosses it, so at least
        # one always survives.
        # Descending order as HF's ascending stable sort, flipped:
        # among EXACT logit ties the higher vocab index outranks the
        # lower (TopPLogitsWarper removes the ascending prefix, so the
        # low-index tie is dropped first) — verified identical keep
        # sets against the torch warper incl. forced ties.
        sort_idx = jnp.flip(jnp.argsort(scaled, axis=-1), -1)
        sorted_scaled = jnp.take_along_axis(scaled, sort_idx, axis=-1)
        probs = jax.nn.softmax(sorted_scaled, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = (cum - probs) < top_p
        # sort_idx is a permutation per row, so its inverse is a
        # scatter of arange — O(V), where argsort would be a third
        # O(V log V) sort (XLA CPU sorts are the decode hot spot).
        vocab = sort_idx.shape[-1]
        flat = sort_idx.reshape(-1, vocab)
        inv = jnp.zeros_like(flat).at[
            jnp.arange(flat.shape[0])[:, None], flat].set(
                jnp.broadcast_to(jnp.arange(vocab), flat.shape))
        keep = jnp.take_along_axis(keep_sorted,
                                   inv.reshape(sort_idx.shape), axis=-1)
        scaled = jnp.where(keep, scaled, -1e30)
    return scaled


@functools.lru_cache(maxsize=256)
def _cache_shapes(decoder, batch):
    """Abstract decode-cache shapes for (decoder, batch), computed once
    per config: `jax.eval_shape` re-traces the whole model every call,
    which showed up as pure-python overhead on every generate()."""
    return jax.eval_shape(
        lambda: decoder.init(jax.random.PRNGKey(0),
                             jnp.zeros((batch, 1), jnp.int32)))["cache"]


@functools.lru_cache(maxsize=256)
def _empty_cache_fn(decoder, batch):
    """The one program that makes (decoder, batch)'s zeroed cache. Leaf
    by leaf a 24-layer cache is a hundred eager dispatches: 100-140 ms
    on a serving host beside a running tick, 11 ms as one program
    (PERF.md section 6, PR 35)."""
    from cloud_tpu.parallel import runtime

    shapes = _cache_shapes(decoder, batch)

    @runtime.instrumented_jit
    def cache_zero():
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    return cache_zero


def empty_cache(decoder, batch):
    """Zero-initialized decode-cache pytree for a decode-mode module
    (shared by `generate` and `generate_speculative`): built from the
    abstract init so no second params copy is ever materialized."""
    return _empty_cache_fn(decoder, batch)()


# --------------------------------------------------------------------------
# Decode-cache reuse pool.
#
# `empty_cache` allocates a fresh HBM cache every call, so a serving loop
# of repeated generate() calls churns allocations the size of the whole
# KV cache at request rate. The pool below recycles them: release() parks
# a finished call's final cache, acquire() re-zeros a parked one IN PLACE
# (a donated jitted tree-zero, so XLA aliases the buffers instead of
# allocating) and hands it back. Keyed on (decoder, batch) — the pair
# that fixes every leaf shape. Bounded per key so a burst can't pin
# unbounded HBM; thread-safe for concurrent generate() callers.

#: The two programs that make a zeroed cache, a fresh one and a parked
#: one zeroed in place, are both jitted under this name (the trace's
#: program line reads `jit_cache_zero`; table in monitoring/spans.py).
CACHE_ZERO = "cache_zero"

_CACHE_POOL = {}
_CACHE_POOL_LOCK = None
_CACHE_POOL_DEPTH = 2  # parked caches per (decoder, batch) key


def _pool_lock():
    global _CACHE_POOL_LOCK
    if _CACHE_POOL_LOCK is None:
        import threading
        _CACHE_POOL_LOCK = threading.Lock()
    return _CACHE_POOL_LOCK


@functools.lru_cache(maxsize=None)
def _zero_in_place():
    from cloud_tpu.parallel import runtime

    @functools.partial(runtime.instrumented_jit, donate_argnums=0)
    def cache_zero(cache):
        return jax.tree_util.tree_map(jnp.zeros_like, cache)
    return best_effort_donation(cache_zero)


def acquire_cache(decoder, batch):
    """A zeroed decode cache for (decoder, batch): a recycled buffer
    when one is parked, a fresh `empty_cache` otherwise."""
    with _pool_lock():
        parked = _CACHE_POOL.get((decoder, batch))
        # The one parked longest: what consumed a cache parked just now
        # (a slot insert, queued behind a tick) may still be reading
        # it, and donating it to the zero program then waits on the
        # host for that read (10 ms a prefill; PERF.md section 6, PR 35).
        cache = parked.pop(0) if parked else None
    if cache is None:
        return empty_cache(decoder, batch)
    return _zero_in_place()(cache)


def release_cache(decoder, batch, cache):
    """Parks a finished decode's final cache for reuse. The caller must
    not touch `cache` afterwards (the next acquire donates it). Drops
    the cache on the floor (normal GC) when the pool is full."""
    if cache is None:
        return
    with _pool_lock():
        parked = _CACHE_POOL.setdefault((decoder, batch), [])
        if len(parked) < _CACHE_POOL_DEPTH:
            parked.append(cache)


def clear_cache_pool():
    """Empties the reuse pool (test isolation; frees the parked HBM)."""
    with _pool_lock():
        _CACHE_POOL.clear()


def decode_latency_start():
    """graftscope hook: monotonic-ns start handle for one generate()/
    beam/speculative call, or None when telemetry is off.

    Zero-cost discipline: `sys.modules.get` means the disabled path is
    one dict lookup — if the telemetry module was never imported, it is
    certainly not enabled, and no import happens here.
    """
    import sys

    telemetry = sys.modules.get("cloud_tpu.monitoring.telemetry")
    if telemetry is None or not telemetry.enabled():
        return None
    from cloud_tpu.monitoring import spans

    return spans.begin("decode")


def decode_latency_finish(start, n_tokens, result=None):
    """Completes a `decode_latency_start` handle: blocks on `result`'s
    device leaves (the tokens are only 'generated' once the dispatch
    retires — measuring dispatch alone would report async-dispatch
    latency, not token latency), records one "decode" span and feeds
    the per-token decode-latency histogram. No-op for a None handle.
    The deliberate block only happens when telemetry is on: the
    measurement cost is the measurement.
    """
    if start is None:
        return
    import sys

    from cloud_tpu.monitoring import spans

    telemetry = sys.modules.get("cloud_tpu.monitoring.telemetry")
    tele = telemetry.get() if telemetry is not None else None
    if tele is None or not tele.active:
        spans.end(start)
        return
    if result is not None:
        for leaf in jax.tree_util.tree_leaves(result):
            if isinstance(leaf, jax.Array):
                leaf.block_until_ready()
    tele.observe_decode(n_tokens, spans.end(start) / 1e9)


__all__ = ["acquire_cache", "best_effort_donation", "bucket_length",
           "clear_cache_pool", "decode_latency_finish",
           "decode_latency_start", "decode_slot_update", "empty_cache",
           "paged_slot_rewind", "paged_slot_update", "release_cache",
           "validate_prompt_mask", "warp_logits"]
