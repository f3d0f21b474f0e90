"""graftserve decode engine: slot-indexed continuous decode tick.

One persistent jitted executable (`tick`) advances every active slot
over the paged KV pool — one token per call in the plain engine, up to
`spec_k + 1` tokens per call when a draft model rides along (per-slot
draft/verify speculation). Requests enter mid-flight — a dense prefill
(compiled per pow2 suffix bucket, off the tick's critical path) is
scattered into a free slot's pages by the `insert` executable — and
leave mid-flight: the `evict` executable zeros the finished slots'
page-table/validity rows without stopping the tick. All executables are
`runtime.instrumented_jit` sites with fixed shapes, so after warm-up
the compile counters are a retrace sentinel the engine can enforce.

Canonical right-pad prefill (the prefix-sharing layout): prompt token i
is written at cache slot i, the pad tail is right of the real tokens
and invalid. Page content is therefore position-independent — the page
holding positions [16, 32) of a prompt is bitwise the page any OTHER
request with the same prefix would produce — which is what lets the
radix prefix cache (serving/prefixcache.py) map one physical page into
many slots' page tables. Pad slots carry exact-zero attention weight
(-1e30 mask -> softmax 0.0) and positions count only real tokens, so
right-pad output is bitwise the left-pad output generate() computes.

Prefix reuse: `prefill(prefix_len=, gather_vec=)` seeds the dense
prefill cache from already-resident pool pages (one gather + zeroed
invalid tail) and runs the model over the SUFFIX only — TTFT drops
from O(prompt) to O(suffix). At insert, `scatter_vec` routes chunk i
either to its fresh page (owned/divergent content — the copy-on-write
copy happens here, device-side, fixed shape) or to the scratch page
(shared content already resident; the slot's page table still points
at the shared page).

Block diffusion (a model whose class generates by denoising blocks,
`models.sdar.SdarLM`): the tick is a block tick. Every slot runs the B
rows of its open block: a denoising step, which commits 1 to B of the
block's masked rows, or the block's clean pass once none is left, which
leaves the block's final keys and values in the pool and opens the next
block. Slots at different steps of their blocks, with different
`denoising_steps`, closing and opening, ride one executable; the
out-array is the speculative tick's form (committed tokens, their
offsets in the block, the count). The prefill writes the prompt's whole
blocks and samples nothing. No prefix reuse, host tier or speculation:
a prompt's last partial block is re-run with the tokens generated
beside it, so no page of it is final when the prefill ends.

Bit-identical contract: a request decoded through the engine produces
exactly the tokens `models.transformer.generate()` would produce for it
solo (same rng, same sampling config) — with or without prefix sharing
or speculation. Greedy slots accept draft tokens only where they equal
the target argmax (`speculative.greedy_accept`); sampled slots ride the
same executable committing one token from the verify window's first
position, whose logits are bitwise the single-token tick's. See
tests/unit/test_serving.py and tests/unit/test_prefix_cache.py for the
enforced oracles.
"""

import collections
import dataclasses
import functools
import threading
import time
import typing

import jax
import jax.numpy as jnp
import numpy as np

from cloud_tpu.monitoring import spans
from cloud_tpu.parallel import runtime


class RetraceError(RuntimeError):
    """The warm engine traced or compiled something new — a static-shape
    leak in the serving path (the retrace sentinel)."""


#: The serving loop's programs, named by the function that is jitted:
#: the trace's program line reads `jit_<name>` (table in
#: monitoring/spans.py). The spans round them use the same names.
SERVE_TICK = "serve_tick"
SERVE_PREFILL = "serve_prefill"
SLOT_INSERT = "slot_insert"
SLOT_EVICT = "slot_evict"
SERVE_PREFILL_CHUNK = "serve_prefill_chunk"
PREFIX_GATHER = "prefix_gather"
SLOT_RESIZE = "slot_resize"
PAGE_SNAPSHOT = "page_snapshot"
PAGE_PROMOTE = "page_promote"

#: The dispatch log keeps this many notes where nobody takes them (an
#: engine driven without a Scheduler); a Scheduler takes them a tick.
DISPATCH_LOG_CAP = 4096


class Dispatched(typing.NamedTuple):
    """One note of the engine's dispatch log: a device program the
    serving path dispatched, written when the dispatch had returned."""
    name: str                 # from the "Programs" table of spans.py
    t: float                  # time.monotonic() after the dispatch
    rows: int = 0             # a prefill's bucket, a chunk's length
    rid: object = None        # where the caller passed one
    overlapped: bool = False  # a whole prefill dispatched while the
    #                           one before it was unfetched


def _program(name, impl, donate):
    """`impl` jitted as a program called `name`: jit names a program
    by the function it is given, and a bound `_impl` method would name
    it by the Python that happens to hold it."""
    from cloud_tpu.models.decoding import best_effort_donation

    @functools.wraps(impl)
    def program(*args):
        return impl(*args)
    program.__name__ = program.__qualname__ = name
    return best_effort_donation(runtime.instrumented_jit(
        program, donate_argnums=donate))


_THREEFRY_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def host_prng_key(seed):
    """`jax.random.PRNGKey(seed)`'s two words for an int seed, as a
    host array: the seed's low word behind its high word, which is 0
    unless 64-bit mode is on."""
    seed = int(seed)
    high = seed >> 32 if jax.config.jax_enable_x64 else 0
    return np.array([high & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def host_split(key, num):
    """`jax.random.split(key, num)` of a raw threefry key, computed on
    the host: row i is the Threefry-2x32 block of the counter (0, i)
    under `key`, the layout `jax_threefry_partitionable` gives (the
    engine refuses to start without it). No device program, no
    read-back; `key` may be a host array or, at the cost of the read,
    a device one. tests/unit/test_serving.py holds it to
    `jax.random.split` row for row."""
    k0, k1 = (np.uint32(word) for word in np.asarray(key).reshape(2))
    ks = (k0, k1, k0 ^ k1 ^ np.uint32(0x1BD11BDA))
    with np.errstate(over="ignore"):
        x0 = np.zeros((num,), np.uint32) + ks[0]
        x1 = np.arange(num, dtype=np.uint32) + ks[1]
        for block in range(5):
            for rot in _THREEFRY_ROTATIONS[block % 2]:
                x0 = x0 + x1
                x1 = (x1 << np.uint32(rot)) | (x1 >> np.uint32(32 - rot))
                x1 = x1 ^ x0
            x0 = x0 + ks[(block + 1) % 3]
            x1 = x1 + ks[(block + 2) % 3] + np.uint32(block + 1)
    return np.stack([x0, x1], axis=1)


def _key_schedule(rng, key_override, sampling, n_steps, width):
    """A request's keys, on the host: the key its prefill samples the
    first token with, and `generate()`'s split schedule for the ticks
    as `[width, 2]` rows (row i samples token i + 2; rows from
    `n_steps - 1` on stay zero). A greedy request reads no key, so
    `rng` is not touched for one and every row is zero. Under
    `key_override` both come from the rows handed in."""
    step_keys = np.zeros((width, 2), np.uint32)
    if key_override is not None:
        prefill_key = np.asarray(key_override[0], np.uint32).reshape(2)
        rest = np.asarray(key_override[1], np.uint32).reshape(-1, 2)
        if n_steps > 1:
            step_keys[:n_steps - 1] = rest[:n_steps - 1]
    elif sampling["temperature"]:
        key, prefill_key = host_split(rng, 2)
        if n_steps > 1:
            step_keys[:n_steps - 1] = host_split(key, n_steps - 1)
    else:
        prefill_key = np.zeros((2,), np.uint32)
    return prefill_key, step_keys


@dataclasses.dataclass
class PrefillResult:
    """A prefilled request waiting for slot insertion."""
    first_token: int        # sampled from the prompt's last position
    pcache: object          # dense [1, L] decode cache (device)
    dpcache: object         # draft-model dense cache (None unless spec)
    step_keys: np.ndarray   # [K, 2] uint32, generate()'s split schedule
    bucket: int             # pow2 SUFFIX bucket the prefill compiled at
    n_steps: int            # max_new_tokens for this request
    prompt_len: int         # full prompt length (prefix + suffix)
    # A block-diffusion request (no first token): its first open block,
    # (tokens [B], masked rows [B]), from `models.sdar.open_block`.
    open_block: tuple = None


@dataclasses.dataclass
class PrefillFlight:
    """A whole-prompt prefill on the device whose first token the host
    has not read (`DecodeEngine.prefill_dispatch`)."""
    first: object           # device [1] int32, its copy to the host begun
    result: PrefillResult   # `first_token` None until `prefill_finish`


def _plain(tree):
    """Nested-Mapping pytree -> plain dicts (flax may hand back
    FrozenDicts; keep one structure so donation pairs buffers)."""
    try:
        items = tree.items()
    except AttributeError:
        return tree
    return {k: _plain(v) for k, v in items}


#: A paged-attention subtree's page arrays, each beside the dense
#: prefill cache's leaf that a prefix gather fills from it and an
#: insert scatters into it: keys and values a row a token, or
#: (`DeepseekLM`) one latent row a token and no key or value pool.
_PAGE_ROWS = (("key_pages", "cached_key"), ("value_pages", "cached_value"),
              ("latent_pages", "cached_latent"))


def _is_paged(tree):
    return "key_pages" in tree or "latent_pages" in tree


def _page_rows(att):
    """(pool leaf, dense leaf) of the page arrays `att` holds."""
    return [(pool, dense) for pool, dense in _PAGE_ROWS if pool in att]


def _map_attention(cache, fn, *rest):
    """Applies `fn` to every paged-attention subtree (detected by its
    page arrays), walking `rest` trees in parallel."""
    if isinstance(cache, dict):
        if _is_paged(cache):
            return fn(cache, *rest)
        return {k: _map_attention(cache[k], fn,
                                  *[r[k] if isinstance(r, dict) else r
                                    for r in rest])
                for k in cache}
    return cache


def _map_slot_state(cache, fn, *rest):
    """Applies `fn` to every leaf OUTSIDE the paged-attention subtrees,
    walking `rest` trees in parallel. Such a leaf is a slot's own
    state, slot-major (`[slots, ...]` in the pool cache, `[1, ...]` in
    a dense prefill cache): `TransformerLM`'s `pos_count`, a Mamba-2
    layer's `conv_state` and `ssm_state` (models/mamba2.py). No page
    holds it, so insertion copies the prefill's row into the slot's,
    eviction zeroes the row and a resize moves it."""
    if isinstance(cache, dict):
        if _is_paged(cache):
            return cache
        return {k: _map_slot_state(cache[k], fn,
                                   *[r[k] if isinstance(r, dict) else r
                                     for r in rest])
                for k in cache}
    return fn(cache, *rest)


def _rows_where(keep, leaf):
    """`leaf` on the rows where `keep` [slots] holds, zero on the others."""
    return jnp.where(keep.reshape((-1,) + (1,) * (leaf.ndim - 1)), leaf,
                     jnp.zeros((), leaf.dtype))


_GATHER_READS = ("key_pages", "value_pages", "latent_pages", "key_scales",
                 "value_scales")


def _pool_pages_view(cache):
    """Geometry-free view of a pool cache for the prefix gather. The
    gather reads only the page arrays (pool-indexed, fixed shape), but
    per-slot state (page_table [slots, ppn], slot_steps [slots],
    slot_valid [slots, L], and the leaves of `_map_slot_state`) rides
    along in the pytree and would bind the executable's signature to
    one slot count — a prefix hit after an elastic resize would then
    retrace. Whitelisting the page arrays here, outside the jit
    boundary (keys kept, unread leaves None'd), keeps one executable
    across every geometry rung."""
    view = _map_attention(
        cache, lambda att: {k: (v if k in _GATHER_READS else None)
                            for k, v in att.items()})
    return _map_slot_state(view, lambda leaf: None)


def attention_shape(model):
    """(query heads, key/value heads, head size) of a served model:
    what a page row holds is key/value heads x head size."""
    heads = model.num_heads
    kv_heads = getattr(model, "num_kv_heads", None) or heads
    head_dim = getattr(model, "head_dim", None) or model.d_model // heads
    return heads, kv_heads, head_dim


def _served_model(model, role):
    """The five classes whose attention writes and reads the page pool:
    `TransformerLM`, `LlamaLM` and `NemotronHLM` a key row and a value
    row a token (`decoding.paged_kv_attention`; `NemotronHLM`'s Mamba-2
    layers keep a per-slot state beside the pool, `state_layers`),
    `DeepseekLM` one latent row a token (`latent_row` lanes,
    `MLAttention`), and `EvaByteLM` a ring of window rows and a table
    of summary rows a slot (`layout`). The class of the model decides
    every difference, there is no switch."""
    from cloud_tpu.models.deepseek import DeepseekLM
    from cloud_tpu.models.evabyte import EvaByteLM
    from cloud_tpu.models.llama import LlamaLM
    from cloud_tpu.models.nemotron_h import NemotronHLM
    from cloud_tpu.models.transformer import TransformerLM
    from cloud_tpu.parallel import SEQUENCE_PARALLEL_IMPLS

    if not isinstance(model, (TransformerLM, LlamaLM, NemotronHLM,
                              EvaByteLM, DeepseekLM)):
        raise NotImplementedError(
            "graftserve serves TransformerLM, LlamaLM, NemotronHLM, "
            "EvaByteLM and DeepseekLM (their attention reads the page "
            "pool); got {} as the {}.".format(type(model).__name__, role))
    if model.attention_impl in SEQUENCE_PARALLEL_IMPLS or (
            isinstance(model, LlamaLM) and model.attn_logit_softcap):
        raise NotImplementedError(
            "the paged pool serves no attn_logit_softcap and no "
            "sequence-parallel attention_impl.")


def weights_to_compute_dtype(leaves, dtype):
    # A function of its own for its name: a trace's program line reads
    # `jit_weights_to_compute_dtype`.
    return [leaf.astype(dtype) for leaf in leaves]


_cast_weights = jax.jit(weights_to_compute_dtype, static_argnums=1)


def held_params(model, params):
    """The tree the engine's programs read for `model`, given `params`:
    a leaf that the model's modules cast to `compute_dtype` at every
    use (the class says which: `promoted_at_use(path)`) is cast once,
    here, all of them in one program; every other leaf is the leaf
    given. The programs then compute from the operands they made for
    themselves before, and a tick no longer converts the weights it
    reads. A class that declares nothing, and a tree that is in the
    compute type already, get `params` itself back. A leaf the cast
    would widen stays as given: it would cost the bytes every tick
    streams."""
    promoted = getattr(model, "promoted_at_use", None)
    if promoted is None:
        return params
    dtype = jnp.dtype(model.compute_dtype)
    paths, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = [leaf for _, leaf in paths]
    cast = [i for i, (path, leaf) in enumerate(paths)
            if leaf.dtype.itemsize > dtype.itemsize
            and promoted(tuple(str(getattr(k, "key", k)) for k in path))]
    if not cast:
        return params
    for i, leaf in zip(cast, _cast_weights([leaves[i] for i in cast],
                                           dtype)):
        leaves[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _tree_bytes(tree):
    """Bytes of a tree's leaves (arrays, or shapes alone)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


def _moe_counters(stats):
    """An expert model's sown counters of one apply (`moe.MOE_STATS`:
    a tuple a call under each expert layer's path), summed over the
    layers by name; {} for a model with no expert layer."""
    totals = {}

    def walk(tree):
        for name, value in tree.items():
            if isinstance(value, tuple):
                totals[name] = totals.get(name, 0) + sum(value)
            else:
                walk(value)
    walk(stats)
    return totals


def _sample_one(logits, key, temperature, top_k, top_p):
    """One slot's sampler: `generate()`'s sample() with the sampling
    config as runtime values. Disabled values are exact identities —
    top_k = vocab keeps every logit, top_p = 1.0 selects the unwarped
    branch, temperature = 0 selects greedy — so the warped results are
    bitwise those of `decoding.warp_logits` with the static config.
    """
    lf = logits.astype(jnp.float32)
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    # kth-largest VALUE equals lax.top_k(...)[0][-1] for any tie
    # pattern, so the `< kth` mask matches the static warper's.
    kth = jnp.take(jnp.flip(jnp.sort(lf)), top_k - 1)
    lk = jnp.where(lf < kth, -1e30, lf)
    scaled = lk / jnp.where(temperature > 0.0, temperature, 1.0)
    # Nucleus membership in descending sorted order, scattered back
    # through the inverse permutation — warp_logits' exact recipe
    # (including its scatter-built inverse).
    sort_idx = jnp.flip(jnp.argsort(scaled))
    sorted_scaled = scaled[sort_idx]
    probs = jax.nn.softmax(sorted_scaled)
    cum = jnp.cumsum(probs)
    inv = jnp.zeros_like(sort_idx).at[sort_idx].set(
        jnp.arange(sort_idx.shape[0]))
    keep = (cum - probs < top_p)[inv]
    warped = jnp.where(top_p < 1.0,
                       jnp.where(keep, scaled, -1e30), scaled)
    sampled = jax.random.categorical(key, warped).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


def _sample_slots(logits, keys, temperature, top_k, top_p):
    """All-slot sampler with a greedy fast path: the sorts behind
    top-k/top-p cost more than the whole model apply at smoke scale
    (XLA CPU sort), so a tick whose ACTIVE traffic is all greedy picks
    the argmax branch at runtime — one executable either way, and the
    sampled branch is `_sample_one` verbatim."""
    greedy = jnp.argmax(logits.astype(jnp.float32),
                        axis=-1).astype(jnp.int32)
    return jax.lax.cond(
        jnp.any(temperature > 0.0),
        lambda: jax.vmap(_sample_one)(logits, keys, temperature,
                                      top_k, top_p),
        lambda: greedy)


@functools.lru_cache(maxsize=64)
def _serve_prefill_fns(decoder, temperature, top_k, top_p):
    """Jitted canonical (right-pad) prefill for one decoder/sampling
    config: run the suffix window, sample the last REAL position's row.
    `last_idx` is dynamic, so every suffix length in a bucket shares
    the executable — including prefix-HIT suffixes starting mid-cache
    (the gathered cache's write pointer supplies the start). The row is
    kept [1, V] so the categorical draw matches `generate()` bitwise
    (same gumbel shape)."""

    @functools.partial(runtime.instrumented_jit, donate_argnums=1)
    def serve_prefill(params, cache, tokens, rng, mask, last_idx):
        logits, vars_ = decoder.apply({"params": params, "cache": cache},
                                      tokens, mask, mutable=["cache"])
        row = jax.lax.dynamic_slice_in_dim(
            logits, last_idx, 1, axis=1)[:, 0].astype(jnp.float32)
        if not temperature:
            tok = jnp.argmax(row, axis=-1).astype(jnp.int32)
        else:
            from cloud_tpu.models.decoding import warp_logits
            warped = warp_logits(row, temperature, top_k, top_p)
            tok = jax.random.categorical(rng, warped,
                                         axis=-1).astype(jnp.int32)
        return vars_["cache"], tok

    from cloud_tpu.models.decoding import best_effort_donation
    return best_effort_donation(serve_prefill)


@functools.lru_cache(maxsize=64)
def _cache_prefill_fn(decoder):
    """Jitted cache-only prefill: run a window, keep the cache, sample
    nothing. Two callers share it (per decoder, per window shape):
    draft-model prefills (the draft never emits tokens directly — it
    proposes inside the tick) and the INTERMEDIATE chunks of a chunked
    prefill, which only advance the cache — the tail chunk samples."""

    def prefill(params, cache, tokens, mask):
        _, vars_ = decoder.apply({"params": params, "cache": cache},
                                 tokens, mask, mutable=["cache"])
        return vars_["cache"]

    return _program(SERVE_PREFILL_CHUNK, prefill, 1)


def chunk_plan(n_suffix, chunk_size, max_seq_len, whole_tail=False):
    """Chunk layout for an `n_suffix`-token prefill at fixed chunk
    width `chunk_size`: `(n_full, tail, tail_bucket)` — `n_full` full
    chunks of `chunk_size` real tokens, then one tail chunk of `tail`
    in [1, chunk_size] real tokens run at the pow2 `tail_bucket` width
    (the SAME executable family as a whole prefill of a short suffix,
    so single-chunk prefills degenerate to exactly today's path). With
    `chunk_size` a power of two the written extent
    `n_full * chunk_size + tail_bucket` never exceeds
    `bucket_length(n_suffix)`, so the whole-prefill in-cache check
    also bounds the chunked writes. `whole_tail`: the tail runs at
    `chunk_size` too (a model prefilled a window at a time: one
    executable for every prompt length)."""
    from cloud_tpu.models.decoding import bucket_length
    n_full = (n_suffix - 1) // chunk_size
    tail = n_suffix - n_full * chunk_size
    return n_full, tail, (chunk_size if whole_tail
                          else bucket_length(tail, max_seq_len))


class ChunkedPrefill:
    """An in-flight chunked prefill: one request's suffix split into
    fixed-width windows that the scheduler interleaves with decode
    ticks (`step()` runs ONE chunk; the final chunk returns the
    `PrefillResult` a whole prefill would have).

    Bit-identity: the dense decode attention always computes over the
    full [1, L] cache with per-position validity masks, and positions
    come from the running real-token count — so a window written in
    chunks holds bitwise the values the whole window writes, and the
    tail chunk's last-real-position logits (where the first token is
    sampled) are bitwise the whole prefill's. The rng schedule is
    untouched: only the tail chunk draws, with the same split the
    whole prefill uses.

    Construction is host-side only (the chunk PLAN and the key
    schedule, `_key_schedule`); the first
    `step()` acquires the dense cache(s) and runs the optional prefix
    gather. Every device dispatch therefore happens on the stepping
    thread — the scheduler steps chunks on the tick thread, whose
    ticks donate the pool cache the gather reads."""

    def __init__(self, engine, prompt, max_new_tokens, rng, sampling,
                 chunk_size, prefix_len=0, gather_vec=None,
                 key_override=None, rid=None):
        from cloud_tpu.models.decoding import bucket_length

        prompt = np.asarray(prompt, np.int32).reshape(-1)
        prompt_len = int(prompt.shape[0])
        prefix_len = int(prefix_len)
        if not 0 <= prefix_len < prompt_len:
            raise ValueError(
                "prefix_len must be in [0, prompt_len); got {} for a "
                "{}-token prompt.".format(prefix_len, prompt_len))
        n_suffix = prompt_len - prefix_len
        if prefix_len + bucket_length(
                n_suffix, engine.max_seq_len) > engine.max_seq_len:
            raise ValueError(
                "prefix ({}) + suffix bucket exceeds max_seq_len {}; "
                "the scheduler trims the match to keep the padded "
                "suffix in-cache.".format(prefix_len,
                                          engine.max_seq_len))
        self.engine = engine
        self.rid = rid           # labels the chunks' spans
        self.chunk_size = int(chunk_size)
        self.prompt_len = prompt_len
        self.prefix_len = prefix_len
        self.max_new_tokens = int(max_new_tokens)
        self._suffix = prompt[prefix_len:]
        self._sampling = dict(sampling)
        self._gather_vec = gather_vec
        n_full, tail, tail_bucket = chunk_plan(
            n_suffix, self.chunk_size, engine.max_seq_len,
            whole_tail=engine.layout is not None)
        self.n_chunks = n_full + 1
        self.chunks_done = 0
        self._tail = tail
        self._tail_bucket = tail_bucket
        self._prefill_rng, self._step_keys = _key_schedule(
            rng, key_override, self._sampling, self.max_new_tokens,
            engine.max_new_cap - 1)
        self._cache = None
        self._dcache = None
        self._closed = False

    def chunk_tokens(self, i):
        """Real tokens chunk `i` carries (chunk_size, or the tail)."""
        return self.chunk_size if i < self.n_chunks - 1 else self._tail

    def _acquire(self):
        from cloud_tpu.models.decoding import CACHE_ZERO, acquire_cache
        engine = self.engine
        cache = _plain(acquire_cache(engine._dense, 1))
        engine._note(CACHE_ZERO, rid=self.rid)
        gvec = None
        if self.prefix_len:
            gvec = jnp.asarray(self._gather_vec, jnp.int32)
            cache = engine._gather(cache, engine.cache, gvec,
                                   np.int32(self.prefix_len),
                                   rid=self.rid)
        self._cache = cache
        if engine.spec_on:
            dcache = _plain(acquire_cache(engine._dense_draft, 1))
            engine._note(CACHE_ZERO, rid=self.rid)
            if self.prefix_len:
                dcache = engine._gather(dcache, engine.draft_cache,
                                        gvec, np.int32(self.prefix_len),
                                        rid=self.rid)
            self._dcache = dcache

    def step(self):
        """Runs the next chunk. Intermediate chunks return None (cache
        advanced, nothing sampled); the final chunk samples the first
        token and returns the `PrefillResult` — blocking until the
        token is on host, the TTFT point, exactly like `prefill()`."""
        if self._closed:
            raise RuntimeError(
                "ChunkedPrefill already consumed or abandoned.")
        with spans.span("serve_prefill_chunk", rid=self.rid):
            return self._step()

    def _step(self):
        engine = self.engine
        if self._cache is None:
            self._acquire()
        i = self.chunks_done
        C = self.chunk_size
        if i < self.n_chunks - 1:
            tokens = jnp.asarray(self._suffix[None, i * C:(i + 1) * C])
            mask = jnp.ones((1, C), bool)
            self._cache = _cache_prefill_fn(engine._dense)(
                engine._params, self._cache, tokens, mask)
            if engine.spec_on:
                self._dcache = _cache_prefill_fn(engine._dense_draft)(
                    engine._draft_params, self._dcache, tokens, mask)
            engine._note(SERVE_PREFILL_CHUNK, rows=C, rid=self.rid)
            self.chunks_done = i + 1
            return None
        tail, bucket = self._tail, self._tail_bucket
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :tail] = self._suffix[i * C:]
        mask = np.zeros((1, bucket), bool)
        mask[0, :tail] = True
        fn = _serve_prefill_fns(
            engine._dense, float(self._sampling["temperature"]),
            self._sampling["top_k"], self._sampling["top_p"])
        pcache, first = fn(engine._params, self._cache,
                           jnp.asarray(tokens), self._prefill_rng,
                           jnp.asarray(mask), np.int32(tail - 1))
        self._cache = None
        dpcache = None
        if engine.spec_on:
            dpcache = _cache_prefill_fn(engine._dense_draft)(
                engine._draft_params, self._dcache,
                jnp.asarray(tokens), jnp.asarray(mask))
            self._dcache = None
        engine._note(SERVE_PREFILL_CHUNK, rows=bucket, rid=self.rid)
        first_host = int(runtime.device_fetch(first)[0])
        self.chunks_done = i + 1
        self._closed = True
        return PrefillResult(first_token=first_host, pcache=pcache,
                             dpcache=dpcache, step_keys=self._step_keys,
                             bucket=bucket, n_steps=self.max_new_tokens,
                             prompt_len=self.prompt_len)

    def abandon(self):
        """Parks any held dense cache(s) back in the reuse pool (the
        drain/fail path; a consumed prefill's caches belong to its
        PrefillResult and go back via `release_prefill`)."""
        from cloud_tpu.models.decoding import release_cache
        self._closed = True
        if self._cache is not None:
            release_cache(self.engine._dense, 1, self._cache)
            self._cache = None
        if self._dcache is not None:
            release_cache(self.engine._dense_draft, 1, self._dcache)
            self._dcache = None


class DecodeEngine:
    """Continuous-batching decode over `slots` slots of a paged pool.

    Single-owner device state: exactly one thread may call
    `insert`/`tick`/`evict` (the scheduler's tick thread); MISS-path
    `prefill` (prefix_len == 0) is safe to call concurrently from an
    admission thread. HIT-path prefill reads `self.cache`, which the
    tick donates every call — it must run on the tick thread.
    """

    def __init__(self, model, params, slots, page_size, num_pages,
                 max_new_cap=None, draft_model=None, draft_params=None,
                 spec_k=0, page_dtype="", ladder=None):
        _served_model(model, "model")
        if (jax.config.jax_default_prng_impl != "threefry2x32"
                or not jax.config.jax_threefry_partitionable):
            raise NotImplementedError(
                "the engine derives a request's keys on the host as "
                "threefry2x32 under jax_threefry_partitionable splits "
                "them (`host_split`); under another generator a served "
                "request would no longer match `generate()`.")
        #: The rows a slot keeps where they are not one a token
        #: (`ops.eva.EvaLayout`, from the model's class; None for the
        #: classes that keep a row a token): a ring of the current
        #: window's rows, overwritten in place, and a table of summary
        #: rows. Such pages cannot be shared by a prefix, kept on the
        #: host or rolled back after a rejected draft, and the model
        #: is prefilled a window at a time.
        self.layout = getattr(model, "layout", None)
        cache_rows = (self.layout.rows if self.layout is not None
                      else model.max_seq_len)
        if cache_rows % page_size:
            raise ValueError(
                "a slot's {} cache rows (max_seq_len {}) must be a "
                "multiple of page_size ({}).".format(
                    cache_rows, model.max_seq_len, page_size))
        if page_dtype not in ("", "int8"):
            raise ValueError(
                "page_dtype must be '' or 'int8'; got {!r}.".format(
                    page_dtype))
        if self.layout is not None:
            self.layout.check(page_size)
        self.model = model
        self.slots = int(slots)
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.pages_per_slot = cache_rows // page_size
        self.max_seq_len = model.max_seq_len
        self.max_new_cap = int(max_new_cap or model.max_seq_len)
        if self.max_new_cap < 2:
            raise ValueError("max_new_cap must be >= 2.")
        # graftflex geometry ladder: the slot counts this engine may
        # resize between. Page tables are pool-indexed, so a resize
        # migrates slot ROWS only (a fixed-shape gather per geometry
        # pair) — KV pages never move and one PagePool serves every
        # rung. A singleton ladder is the fixed-geometry engine.
        ladder = tuple(int(s) for s in (ladder or (self.slots,)))
        if any(s < 1 for s in ladder):
            raise ValueError(
                "ladder rungs must be positive; got {}.".format(ladder))
        if list(ladder) != sorted(set(ladder)):
            raise ValueError(
                "ladder must be strictly increasing; got {}.".format(
                    ladder))
        if len(ladder) > 1 and any(s & (s - 1) for s in ladder):
            raise ValueError(
                "ladder rungs must be powers of two (the pre-warmed "
                "geometry set stays small); got {}.".format(ladder))
        if self.slots not in ladder:
            raise ValueError(
                "initial slots ({}) must be a ladder rung; got "
                "{}.".format(self.slots, ladder))
        self.ladder = ladder
        #: THE tree the programs read (each takes it as an argument, so
        #: it may be assigned to between calls): `params` with the
        #: leaves the model casts at use cast once (`held_params`). The
        #: tree given is not kept; what it weighed is (`stats()`).
        self._params = held_params(model, params)
        self.weight_bytes_given = _tree_bytes(params)
        self.spec_k = int(spec_k)
        self.spec_on = draft_model is not None and self.spec_k > 0
        #: Layers that keep a recurrent state a slot (the model's class
        #: says so; 0 for the classes that have none). Pages cannot
        #: give such a state back, so a model with any gets no prefix
        #: reuse, no host tier and no speculation until state
        #: snapshots exist (ROADMAP 2.5).
        self.state_layers = getattr(model, "state_layers", 0)
        #: The block length of a model that generates by denoising
        #: blocks (its class carries one: `models.sdar.SdarLM`); 0 for
        #: the classes that decode a token a slot and tick. Such a model
        #: ticks a block a slot (`_block_tick_impl`).
        self.block = getattr(model, "block_length", 0)
        if self.block and (self.spec_on or page_dtype or len(ladder) > 1):
            raise NotImplementedError(
                "spec_k > 0, quantized pages and a geometry ladder are "
                "not served for a block-diffusion model ({}): its tick "
                "rewrites the open block's rows in place, which no "
                "draft, page scale or resize walk expects.".format(
                    type(model).__name__))
        if self.layout is not None and (self.spec_on or page_dtype):
            raise NotImplementedError(
                "spec_k > 0 and quantized pages are not served for a "
                "model whose slots keep a ring and summary rows ({}): "
                "a verify window would overwrite ring rows that no "
                "rewind gives back, and a summary row is made from "
                "the page's rows as computed.".format(
                    type(model).__name__))
        if self.state_layers and self.spec_on:
            raise NotImplementedError(
                "spec_k > 0 is not served for a model with recurrent "
                "layers ({} has {}): a rejected draft token would have "
                "to roll the state back, and no snapshot of it exists."
                .format(type(model).__name__, self.state_layers))
        # "" = pages in compute_dtype; "int8" = graftpack quantized
        # pages (per-page per-head f32 scale sidecars in the same
        # cache subtrees — models/transformer.py).
        self.page_dtype = str(page_dtype)
        # The SAME decode clone generate() derives, so the engine's
        # dense prefill caches come from the shared reuse pool solo
        # generate() calls in the process also draw from.
        self._dense = model.clone(decode=True, dropout_rate=0.0)
        self._paged = model.clone(decode=True, dropout_rate=0.0,
                                  kv_page_size=page_size,
                                  kv_num_pages=num_pages,
                                  kv_page_dtype=self.page_dtype)

        from cloud_tpu.models.decoding import empty_cache
        self.cache = _plain(empty_cache(self._paged, self.slots))

        if self.spec_on:
            _served_model(draft_model, "draft_model")
            if draft_model.vocab_size != model.vocab_size:
                raise ValueError(
                    "draft vocab_size ({}) must match target ({}) — "
                    "accept compares token ids.".format(
                        draft_model.vocab_size, model.vocab_size))
            if draft_model.max_seq_len != model.max_seq_len:
                raise ValueError(
                    "draft max_seq_len ({}) must match target ({}) — "
                    "both caches share the page geometry.".format(
                        draft_model.max_seq_len, model.max_seq_len))
            self._draft_params = held_params(draft_model, draft_params)
            self.weight_bytes_given += _tree_bytes(draft_params)
            self._dense_draft = draft_model.clone(decode=True,
                                                  dropout_rate=0.0)
            # Same page_size/num_pages: page id i means the same token
            # span in both pools, so one page table (and one prefix
            # trie) serves target and draft caches.
            self._paged_draft = draft_model.clone(
                decode=True, dropout_rate=0.0, kv_page_size=page_size,
                kv_num_pages=num_pages,
                kv_page_dtype=self.page_dtype)
            self.draft_cache = _plain(
                empty_cache(self._paged_draft, self.slots))
        else:
            self._draft_params = None
            self._dense_draft = None
            self._paged_draft = None
            self.draft_cache = None

        key_width = self.max_new_cap - 1
        self.ctl = {
            "active": jnp.zeros((slots,), jnp.bool_),
            "done": jnp.zeros((slots,), jnp.bool_),
            "cur_tok": jnp.zeros((slots,), jnp.int32),
            "steps_done": jnp.zeros((slots,), jnp.int32),
            "max_steps": jnp.zeros((slots,), jnp.int32),
            "temperature": jnp.zeros((slots,), jnp.float32),
            "top_k": jnp.ones((slots,), jnp.int32),
            "top_p": jnp.ones((slots,), jnp.float32),
            "eos": jnp.zeros((slots,), jnp.int32),
            "has_eos": jnp.zeros((slots,), jnp.bool_),
            "step_keys": jnp.zeros((slots, key_width, 2), jnp.uint32),
        }
        if self.block:
            # A slot's open block: its rows' tokens (the mask token on a
            # masked row), which rows are masked, its first position,
            # the steps it has had, the masked rows it opened with, the
            # request's denoising steps a block, and where its new
            # tokens end.
            b = self.block
            self.ctl.update({
                "block_tokens": jnp.zeros((slots, b), jnp.int32),
                "block_masked": jnp.zeros((slots, b), jnp.bool_),
                "block_start": jnp.zeros((slots,), jnp.int32),
                "block_step": jnp.zeros((slots,), jnp.int32),
                "block_masks0": jnp.zeros((slots,), jnp.int32),
                "denoise_steps": jnp.ones((slots,), jnp.int32),
                "gen_end": jnp.zeros((slots,), jnp.int32),
            })
            self._block_prefill = _program(
                SERVE_PREFILL, self._block_prefill_impl, (1,))
        if self.spec_on:
            self._tick = _program(SERVE_TICK, self._spec_tick_impl,
                                  (2, 3, 4))
            self._insert = _program(SLOT_INSERT, self._insert_spec_impl,
                                    (0, 1, 2))
            self._evict = _program(SLOT_EVICT, self._evict_spec_impl,
                                   (0, 1, 2))
            self._resize = _program(SLOT_RESIZE, self._resize_spec_impl,
                                    (0, 1, 2))
        else:
            self._tick = _program(SERVE_TICK, (
                self._block_tick_impl if self.block else self._tick_impl),
                (1, 2))
            self._insert = _program(SLOT_INSERT, (
                self._insert_block_impl if self.block
                else self._insert_impl), (0, 1))
            self._evict = _program(SLOT_EVICT, self._evict_impl, (0, 1))
            self._resize = _program(SLOT_RESIZE, self._resize_impl,
                                    (0, 1))
        self._gather_pages = _program(PREFIX_GATHER, self._gather_impl,
                                      (0,))
        # Host-tier executables: snapshot READS the pool cache (no
        # donation — the tick keeps it); promote replaces it.
        self._snapshot = _program(PAGE_SNAPSHOT, self._snapshot_impl, ())
        self._promote = _program(PAGE_PROMOTE, self._promote_impl, (0,))
        self._warm_stats = None
        # The dispatch log: every device program the serving path
        # dispatches, noted once its dispatch has returned, by
        # whichever thread dispatched it. The lock is held for the
        # append and the swap alone, never across a dispatch.
        self._dispatched = collections.deque(maxlen=DISPATCH_LOG_CAP)
        self._dispatched_lock = threading.Lock()
        #: The last tick's counters, still on the device: an expert
        #: model's (`_moe_counters`) and a recurrent model's
        #: `ssm_slot_steps`; {} for a model with neither and under
        #: speculation. The scheduler fetches them with the tick's
        #: tokens, in the one read-back a tick makes.
        self.tick_counters = {}

    def _gather(self, dense_cache, pool_cache, page_vec, prefix_len,
                rid=None):
        self._refuse_page_reuse("a prefix hit")
        # The view strips slot-count-bound leaves so the gather
        # signature is identical at every geometry rung.
        out = self._gather_pages(dense_cache, _pool_pages_view(pool_cache),
                                 page_vec, prefix_len)
        self._note(PREFIX_GATHER, rid=rid)
        return out

    def _note(self, name, rows=0, rid=None, overlapped=False):
        # The clock is read under the lock: the log is in time order.
        with self._dispatched_lock:
            self._dispatched.append(Dispatched(
                name, time.monotonic(), rows, rid, overlapped))

    @property
    def weight_bytes_served(self):
        """Bytes of the parameters the programs read now, the draft
        model's among them: what a tick streams of weights, beside
        `weight_bytes_given`, what the trees handed in weighed."""
        return (_tree_bytes(self._params)
                + _tree_bytes(self._draft_params))

    def take_dispatched(self):
        """The notes since the last call, oldest first (`Dispatched`:
        program, time, rows, rid), and an empty log behind them. A
        draft model's twin of a program rides in its target's note,
        and every chunk of a chunked prefill is noted
        `serve_prefill_chunk`, the tail too, which runs
        `serve_prefill` to sample. A note is written after its
        dispatch returned, so one thread's may land behind a program
        another thread dispatched later."""
        with self._dispatched_lock:
            taken = list(self._dispatched)
            self._dispatched.clear()
        return taken

    def _refuse_page_reuse(self, what):
        if self.block:
            raise NotImplementedError(
                "{} is not served for a block-diffusion model: a "
                "prompt's last partial block is run again with the "
                "tokens generated beside it, so its page is not the "
                "prompt's alone.".format(what))
        if self.layout is not None:
            raise NotImplementedError(
                "{} is not served for a model whose slots keep a ring "
                "and summary rows: a ring page is overwritten in place "
                "when the next window begins, and a summary row stands "
                "for a chunk of one request's own keys.".format(what))
        if self.state_layers:
            raise NotImplementedError(
                "{} is not served for a model with recurrent layers: "
                "pages hold keys and values, and the state the layers "
                "had after the prefix is kept nowhere.".format(what))

    # -- prefill ------------------------------------------------------

    def prefill(self, prompt, max_new_tokens, rng, sampling,
                prefix_len=0, gather_vec=None, key_override=None,
                rid=None):
        """Canonical right-pad prefill for one request. `sampling` is a
        normalized dict: temperature (float), top_k (int|None), top_p
        (float|None), eos_token (int|None).

        prefix_len > 0 is a prefix-cache HIT: `gather_vec` (a
        pool.page_vec covering ceil(prefix_len / page_size) resident
        pages) seeds the dense cache with the first `prefix_len`
        cached positions, and the model runs over the suffix only.
        The rng schedule is unchanged — prefix reuse never moves a
        sample draw, which is the bit-identity contract.

        `key_override=(prefill_key, step_keys_rest)` is the graftstorm
        requeue hook: instead of deriving the schedule by splitting
        `rng`, the prefill samples with the exact uint32[2] key the
        faulted run would have used for this position and arms the
        remaining original schedule (shifted so the continuation's
        first tick reads row 0). That re-bases a request interrupted
        after n tokens onto keys n, n+1, ... of its original split —
        the per-slot graftguard resume discipline, so the continuation
        completes bit-identical to the uninterrupted decode.

        Returns a `PrefillResult`; blocks until the first token is on
        host (the TTFT point). `rid` labels the call's spans: the
        whole of it is `serve_prefill` (gather + dense prefill + the
        blocking first-token fetch, the device side of TTFT). It is
        `prefill_dispatch` and `prefill_finish` back to back, for the
        callers that may not run ahead: the tick thread's, whose
        hit-path gather reads the pool cache the next tick donates."""
        with spans.span(SERVE_PREFILL, rid=rid):
            if self.layout is not None:
                # A window at a time, never a dense cache: the chunked
                # path run to its end.
                chunked = self.prefill_chunks(
                    prompt, max_new_tokens, rng, sampling,
                    self.layout.window, prefix_len=prefix_len,
                    gather_vec=gather_vec, key_override=key_override,
                    rid=rid)
                while True:
                    result = chunked.step()
                    if result is not None:
                        return result
            return self.prefill_finish(
                self._dispatch_prefill(prompt, max_new_tokens, rng,
                                       sampling, prefix_len, gather_vec,
                                       key_override, rid), rid=rid)

    def prefill_dispatch(self, prompt, max_new_tokens, rng, sampling,
                         prefix_len=0, gather_vec=None,
                         key_override=None, rid=None, overlapped=False):
        """The first half of `prefill()` for a model prefilled whole:
        everything up to and including the jitted call, and the start
        of the first token's copy to the host. Returns a
        `PrefillFlight` without waiting for the device, so the caller
        can prepare and dispatch the next request's prefill before it
        reads this one's token (`prefill_finish`). The span is
        `serve_prefill`, as far as the dispatch. `overlapped`: the
        caller's prefill before this one is still unfetched; it goes
        into the dispatch log's note."""
        if self.layout is not None:
            raise NotImplementedError(
                "a model whose slots keep a ring and summary rows is "
                "prefilled a window at a time (`prefill()` or "
                "`prefill_chunks()`), never as one dispatch.")
        with spans.span(SERVE_PREFILL, rid=rid):
            return self._dispatch_prefill(prompt, max_new_tokens, rng,
                                          sampling, prefix_len,
                                          gather_vec, key_override, rid,
                                          overlapped)

    def prefill_finish(self, flight, rid=None):
        """The second half: blocks until `flight`'s first token is on
        the host (the TTFT point) and returns its `PrefillResult`."""
        with spans.span("prefill_fetch", rid=rid):
            first = int(runtime.device_fetch(flight.first)[0])
        if not self.block:   # a block-diffusion prefill samples nothing
            flight.result.first_token = first
        return flight.result

    def _dispatch_prefill(self, prompt, max_new_tokens, rng, sampling,
                          prefix_len, gather_vec, key_override, rid,
                          overlapped=False):
        from cloud_tpu.models.decoding import (CACHE_ZERO, acquire_cache,
                                               bucket_length)

        if self.block:
            return self._dispatch_block_prefill(prompt, max_new_tokens,
                                                prefix_len, rid, overlapped)
        with spans.span("prefill_host", rid=rid):
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            prompt_len = int(prompt.shape[0])
            prefix_len = int(prefix_len)
            if not 0 <= prefix_len < prompt_len:
                raise ValueError(
                    "prefix_len must be in [0, prompt_len); got {} for a "
                    "{}-token prompt.".format(prefix_len, prompt_len))
            n_suffix = prompt_len - prefix_len
            bucket = bucket_length(n_suffix, self.max_seq_len)
            if prefix_len + bucket > self.max_seq_len:
                raise ValueError(
                    "prefix ({}) + suffix bucket ({}) exceeds max_seq_len "
                    "{}; the scheduler trims the match to keep the padded "
                    "suffix in-cache.".format(prefix_len, bucket,
                                              self.max_seq_len))
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :n_suffix] = prompt[prefix_len:]
            mask = np.zeros((1, bucket), bool)
            mask[0, :n_suffix] = True
            # Host arrays: a split row and an override row are one
            # aval (uint32[2], the legacy raw key layout categorical
            # accepts), so both reuse the warmed prefill executable.
            prefill_rng, step_keys = _key_schedule(
                rng, key_override, sampling, int(max_new_tokens),
                self.max_new_cap - 1)
            cache = _plain(acquire_cache(self._dense, 1))
            self._note(CACHE_ZERO, rid=rid)
            gvec = None
            if prefix_len:
                gvec = jnp.asarray(gather_vec, jnp.int32)
                cache = self._gather(cache, self.cache, gvec,
                                     np.int32(prefix_len), rid=rid)
            fn = _serve_prefill_fns(
                self._dense, float(sampling["temperature"]),
                sampling["top_k"], sampling["top_p"])
        with spans.span("prefill_dispatch", rid=rid):
            pcache, first = fn(self._params, cache, jnp.asarray(tokens),
                               prefill_rng, jnp.asarray(mask),
                               np.int32(n_suffix - 1))
            dpcache = None
            if self.spec_on:
                dcache = _plain(acquire_cache(self._dense_draft, 1))
                self._note(CACHE_ZERO, rid=rid)
                if prefix_len:
                    dcache = self._gather(dcache, self.draft_cache,
                                          gvec, np.int32(prefix_len),
                                          rid=rid)
                dpcache = _cache_prefill_fn(self._dense_draft)(
                    self._draft_params, dcache, jnp.asarray(tokens),
                    jnp.asarray(mask))
            # Queued ahead of whatever program is dispatched next.
            first.copy_to_host_async()
            self._note(SERVE_PREFILL, rows=bucket, rid=rid,
                       overlapped=overlapped)
        return PrefillFlight(first, PrefillResult(
            first_token=None, pcache=pcache, dpcache=dpcache,
            step_keys=step_keys, bucket=bucket,
            n_steps=int(max_new_tokens), prompt_len=prompt_len))

    def _dispatch_block_prefill(self, prompt, max_new_tokens, prefix_len,
                                rid, overlapped):
        """A block-diffusion request's prefill: the prompt's whole
        blocks, block-causally, into a dense cache; what remains of the
        prompt opens the first block (`PrefillResult.open_block`). The
        flight's `first` is the cache's token count, fetched only to
        know the prefill done: no token is sampled."""
        from cloud_tpu.models.decoding import (CACHE_ZERO, acquire_cache,
                                               bucket_length)
        from cloud_tpu.models.sdar import open_block

        with spans.span("prefill_host", rid=rid):
            if prefix_len:
                self._refuse_page_reuse("a prefix hit")
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            opened, masked, start = open_block(
                prompt, self.block, self.model.mask_token,
                end=len(prompt) + int(max_new_tokens))
            if not start:
                raise ValueError(
                    "a block-diffusion prompt needs at least one whole "
                    "block ({} tokens); got {}.".format(self.block,
                                                        len(prompt)))
            bucket = bucket_length(start, self.max_seq_len)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :start] = prompt[:start]
            mask = np.zeros((1, bucket), bool)
            mask[0, :start] = True
            cache = _plain(acquire_cache(self._dense, 1))
            self._note(CACHE_ZERO, rid=rid)
        with spans.span("prefill_dispatch", rid=rid):
            pcache, done = self._block_prefill(
                self._params, cache, jnp.asarray(tokens), jnp.asarray(mask))
            done.copy_to_host_async()
            self._note(SERVE_PREFILL, rows=bucket, rid=rid,
                       overlapped=overlapped)
        return PrefillFlight(done, PrefillResult(
            first_token=None, pcache=pcache, dpcache=None, step_keys=None,
            bucket=bucket, n_steps=int(max_new_tokens),
            prompt_len=int(prompt.shape[0]), open_block=(opened, masked)))

    def prefill_chunks(self, prompt, max_new_tokens, rng, sampling,
                       chunk_size, prefix_len=0, gather_vec=None,
                       key_override=None, rid=None):
        """Chunked-prefill continuation for one request: the suffix
        runs as `chunk_plan()` windows — fixed `chunk_size` chunks
        through the cache-only executable, then a pow2-bucketed tail
        through the SAME sampling executable a whole prefill of that
        suffix would use. `prefix_len`/`gather_vec` seed the first
        chunk's start offset (prefix-cache hit) and `key_override`
        re-bases a requeued continuation, both exactly as `prefill()`.
        Returns a `ChunkedPrefill`; no device work happens until its
        first `step()` (which must run on the tick thread when
        `prefix_len > 0` — the gather reads the tick-donated pool
        cache)."""
        if self.block:
            raise NotImplementedError(
                "a block-diffusion model is prefilled whole (its blocks' "
                "mask is block-causal, and no chunk plan keeps to the "
                "blocks).")
        chunk_size = int(chunk_size)
        if chunk_size < 1 or chunk_size & (chunk_size - 1):
            raise ValueError(
                "chunk_size must be a power of two >= 1 (the pow2 "
                "bound keeps chunked writes inside the whole-prefill "
                "bucket); got {}.".format(chunk_size))
        if chunk_size > self.max_seq_len:
            raise ValueError(
                "chunk_size ({}) exceeds max_seq_len ({}).".format(
                    chunk_size, self.max_seq_len))
        return ChunkedPrefill(self, prompt, max_new_tokens, rng,
                              sampling, chunk_size,
                              prefix_len=prefix_len,
                              gather_vec=gather_vec,
                              key_override=key_override, rid=rid)

    def release_prefill(self, result):
        """Parks a consumed (or abandoned) prefill's dense cache(s)
        back in the decode-cache reuse pool."""
        from cloud_tpu.models.decoding import release_cache
        release_cache(self._dense, 1, result.pcache)
        result.pcache = None
        if result.dpcache is not None:
            release_cache(self._dense_draft, 1, result.dpcache)
            result.dpcache = None

    # -- slot ops (tick thread) ---------------------------------------

    def insert(self, slot, result, page_vec, scatter_vec, sampling):
        """Writes a prefilled request into free slot `slot`. The page
        vectors split ownership: `page_vec` is the slot's logical page
        table (shared prefix pages included); `scatter_vec` routes
        chunk i to page_vec[i] where the slot OWNS the page (fresh
        pages, including the copy-on-write page a mid-page divergence
        reconstructs) and to the scratch page 0 where the content is
        already resident and shared. One fixed-shape executable for
        every bucket — the prefill cache is always full-length dense.
        """
        if self.block:
            self._insert_block(slot, result, page_vec, scatter_vec,
                               sampling)
            return
        vocab = self.model.vocab_size
        top_k = sampling["top_k"]
        top_p = sampling["top_p"]
        eos = sampling["eos_token"]
        args = (np.int32(slot), jnp.asarray(page_vec, jnp.int32),
                jnp.asarray(scatter_vec, jnp.int32),
                jnp.asarray(result.step_keys),
                np.int32(result.n_steps), np.int32(result.first_token),
                np.float32(sampling["temperature"]),
                np.int32(vocab if top_k is None else top_k),
                np.float32(1.0 if top_p is None else top_p),
                np.int32(0 if eos is None else eos),
                bool(eos is not None))
        if self.spec_on:
            self.cache, self.draft_cache, self.ctl = self._insert(
                self.cache, self.draft_cache, self.ctl,
                _plain(result.pcache), _plain(result.dpcache), *args)
        else:
            self.cache, self.ctl = self._insert(
                self.cache, self.ctl, _plain(result.pcache), *args)
        self._note(SLOT_INSERT)
        self.release_prefill(result)

    def _insert_block(self, slot, result, page_vec, scatter_vec, sampling):
        """`insert` of a block-diffusion request: the prompt's whole
        blocks into the slot's pages, its first block opened in `ctl`
        with the request's denoising steps (`sampling`'s
        `denoising_steps`, B where it gives none)."""
        opened, masked = result.open_block
        steps = sampling.get("denoising_steps") or self.block
        start = result.prompt_len // self.block * self.block
        self.cache, self.ctl = self._insert(
            self.cache, self.ctl, _plain(result.pcache), np.int32(slot),
            jnp.asarray(page_vec, jnp.int32),
            jnp.asarray(scatter_vec, jnp.int32), jnp.asarray(opened),
            jnp.asarray(masked), np.int32(masked.sum()), np.int32(steps),
            np.int32(start), np.int32(result.prompt_len + result.n_steps))
        self._note(SLOT_INSERT)
        self.release_prefill(result)

    def tick(self):
        """Advances every active slot. Plain engine: one token per
        call, device out-array `[2, S]` (row 0: sampled token, row 1:
        finished flag). Speculative engine: up to spec_k + 1 tokens per
        call, out-array `[spec_k + 4, S]` — rows 0..spec_k committed
        tokens (-1 on inactive slots), row spec_k + 1 the commit count,
        row spec_k + 2 the finished flag, row spec_k + 3 the accepted
        draft count (-1 on non-speculating slots). Block engine
        (`block` B): a denoising step or a clean pass of each slot's
        open block, out-array `[2B + 3, S]` — rows 0..B-1 the tokens the
        step committed (-1 past the count), rows B..2B-1 their offsets
        in the block, row 2B the count (0 on a clean pass), row 2B + 1
        the finished flag, row 2B + 2 the block's first position. The
        scheduler fetches it with `runtime.device_fetch`.

        A finished slot retires itself: the tick that reports a slot
        `finished` clears its `ctl["active"]`, so the scheduler may
        dispatch the next tick before it has read this one (it keeps
        one tick in flight). To that blind tick the slot is inactive
        like any other: its row is masked out of the model, its
        `slot_steps` and `slot_valid` rows stay as they are, its `out`
        column reads -1 (commit count 0) and `finished` 0, so a finish
        is reported exactly once. The paged write is unconditional
        (`decoding.paged_kv_attention`): the masked row's key and
        value land at the slot's own write pointer, which after the
        finishing tick is at most position `prompt + max_new - 1` — in
        a page the request reserved for itself, or in the scratch page
        where its table has no entry that far. That position is never
        valid and never attended, and it is never in a shared page
        (those lie strictly below every holder's pointer). `evict`
        still zeroes the page-table and validity rows, and the page
        ids return to the pool only when the scheduler commits the
        finish, behind which every later insert is ordered."""
        if self.spec_on:
            (self.cache, self.draft_cache, self.ctl, out) = self._tick(
                self._params, self._draft_params, self.cache,
                self.draft_cache, self.ctl)
        else:
            self.cache, self.ctl, out, self.tick_counters = self._tick(
                self._params, self.cache, self.ctl)
        self._note(SERVE_TICK)
        return out

    def evict(self, evict_mask):
        """Frees every slot where `evict_mask` is True: page-table and
        validity rows go back to scratch/zero, the control row disarms.
        The physical page ids go back to the host pool separately
        (scheduler bookkeeping)."""
        if self.spec_on:
            self.cache, self.draft_cache, self.ctl = self._evict(
                self.cache, self.draft_cache, self.ctl,
                jnp.asarray(evict_mask, bool))
        else:
            self.cache, self.ctl = self._evict(
                self.cache, self.ctl, jnp.asarray(evict_mask, bool))
        self._note(SLOT_EVICT)

    def resize(self, new_slots, perm):
        """Moves the engine to ladder rung `new_slots` at a tick
        boundary. `perm` is int32 `[new_slots]`: new slot i takes old
        slot `perm[i]`'s rows (-1 = empty). Geometry-BOUND state only
        moves — page tables, validity, positions, and the control rows
        (rng schedules, eos latches, step counters) gather through one
        fixed-shape executable per (old, new) pair; the KV pages (and
        the draft twin's, under the same perm) stay exactly where they
        are in the shared pool. In-flight slots therefore continue
        bit-identical: their step_keys rows, steps_done counters and
        done/eos latches ride the gather unchanged. Tick thread only —
        must run between ticks, never mid-tick."""
        new_slots = int(new_slots)
        if new_slots not in self.ladder:
            raise ValueError(
                "resize target {} is not a ladder rung {}.".format(
                    new_slots, self.ladder))
        perm = np.asarray(perm, np.int32).reshape(-1)
        if perm.shape[0] != new_slots:
            raise ValueError(
                "perm must have {} rows; got {}.".format(
                    new_slots, perm.shape[0]))
        live = perm[perm >= 0]
        if (perm >= self.slots).any() or len(set(live.tolist())) \
                != live.shape[0]:
            raise ValueError(
                "perm rows must be -1 or unique old-slot indices "
                "< {}; got {}.".format(self.slots, perm.tolist()))
        pv = jnp.asarray(perm, jnp.int32)
        if self.spec_on:
            self.cache, self.draft_cache, self.ctl = self._resize(
                self.cache, self.draft_cache, self.ctl, pv)
        else:
            self.cache, self.ctl = self._resize(self.cache, self.ctl,
                                                pv)
        self._note(SLOT_RESIZE)
        self.slots = new_slots

    # -- retrace sentinel ---------------------------------------------

    def mark_warm(self):
        """Snapshots the compile counters; `check_no_retrace()` raises
        on any growth after this point. Also arms graftsan's GS005
        retrace-attribution: under a `sanitize()` scope, any trace
        after this mark is reported with the exact signature leaf
        whose avals moved, not just a count."""
        self._warm_stats = runtime.compile_stats()
        runtime.notify_warm_mark()

    def check_no_retrace(self):
        if self._warm_stats is None:
            return
        now = runtime.compile_stats()
        grew = {k: now[k] - self._warm_stats[k]
                for k in ("n_traces", "n_compiles")
                if now[k] > self._warm_stats[k]}
        if grew:
            raise RetraceError(
                "serving path traced/compiled after warm-up: {} "
                "(static-shape leak).".format(grew))

    # -- jitted bodies ------------------------------------------------

    def _gather_impl(self, dense_cache, pool_cache, page_vec,
                     prefix_len):
        """Seeds a fresh dense [1, L] cache with the first `prefix_len`
        positions of the pool pages in `page_vec` (a full page_vec —
        [pages_per_slot], scratch-padded past the match). The invalid
        tail is zeroed, so the seeded cache is bitwise the cache a
        right-pad prefill of those `prefix_len` tokens would have
        produced — the suffix prefill continues from it exactly as if
        the whole prompt had been run."""
        L = self.max_seq_len
        valid = jnp.arange(L) < prefix_len

        def seed(att, datt):
            out = dict(datt)
            for pool, dense in _page_rows(att):
                # Pool rows are [H*D] (a latent row: [W]) wide; the
                # dense cache is [1, L, H, D] ([1, L, W]).
                shape = datt[dense].shape
                rows = att[pool][page_vec]        # [ppn, P, width]
                if "key_scales" in att:
                    # Int8 pool -> dense compute-dtype cache:
                    # dequantize with the per-page per-head scales
                    # (never-written pages carry scale 0 -> exact
                    # zeros).
                    rows = rows.reshape(*rows.shape[:2], *shape[2:])
                    scales = att[pool.replace("_pages", "_scales")][
                        page_vec][:, None, :, None]
                    rows = (rows.astype(jnp.float32) * scales).astype(
                        datt[dense].dtype)
                rows = rows.reshape(shape)
                out[dense] = jnp.where(
                    valid.reshape((1, L) + (1,) * (len(shape) - 2)),
                    rows, jnp.zeros((), rows.dtype))
            out["cache_index"] = prefix_len.astype(jnp.int32)
            out["slot_valid"] = valid[None]
            out["slot_pos"] = jnp.where(
                valid, jnp.arange(L, dtype=jnp.int32), 0)[None]
            out["token_count"] = jnp.full((1,), prefix_len, jnp.int32)
            return out

        result = _map_attention(pool_cache, seed, dense_cache)
        # _map_attention keeps non-attention leaves from its FIRST
        # tree; the only one is TransformerLM's pos_count, stripped to
        # None by the caller's _pool_pages_view (its pool shape [slots]
        # would bind the geometry) — install the dense [1] counter at
        # the prefix depth.
        if "pos_count" in result:
            result["pos_count"] = jnp.full((1,), prefix_len, jnp.int32)
        return result

    def _scatter_request(self, cache, pcache, slot, page_vec,
                         scatter_vec):
        """One request's dense prefill cache into the paged pool:
        chunk i of the [1, L] dense view goes to scatter_vec[i] (its
        fresh page, or scratch when shared content is already there);
        the page table gets page_vec. slot_steps comes from
        token_count (REAL tokens — cache_index includes the right-pad,
        which must be overwritten by decode writes, not skipped).

        Int8 pools quantize here, per chunk per head: invalid (right-
        pad) positions are zeroed BEFORE the amax so pad garbage never
        inflates a page's scale, and each owned page's scale resets to
        its chunk amax / 127 — which is what makes recycled pages'
        stale scales unobservable (every owned page passes through
        this scatter or the promote before a decode write can grow its
        scale)."""
        ppn, page = self.pages_per_slot, self.page_size

        def scatter(att, patt):
            out = dict(att)
            if "key_scales" in att:
                vm = patt["slot_valid"][0].astype(jnp.float32).reshape(
                    ppn, page)[:, :, None, None]

                def quant(chunks):
                    cf = chunks.astype(jnp.float32) * vm
                    amax = jnp.max(jnp.abs(cf), axis=(1, 3))  # [ppn,H]
                    scale = amax / 127.0
                    safe = jnp.where(scale > 0, scale, 1.0)
                    q = jnp.clip(jnp.round(cf / safe[:, None, :, None]),
                                 -127, 127).astype(jnp.int8)
                    return q, scale

            for pool, dense in _page_rows(att):
                chunks = patt[dense][0].reshape(
                    ppn, page, *patt[dense].shape[2:])
                if "key_scales" in att:
                    chunks, scale = quant(chunks)
                    scales = pool.replace("_pages", "_scales")
                    out[scales] = att[scales].at[scatter_vec].set(scale)
                # Owned ids are unique and nonzero, so fresh chunks
                # land exactly; shared/overflow chunks collapse onto
                # scratch, whose content is never attended.
                out[pool] = att[pool].at[scatter_vec].set(
                    chunks.reshape(ppn, page, -1))   # [.., H*D] rows
            out["page_table"] = att["page_table"].at[slot].set(page_vec)
            out["slot_steps"] = att["slot_steps"].at[slot].set(
                patt["token_count"][0])
            out["slot_valid"] = att["slot_valid"].at[slot].set(
                patt["slot_valid"][0])
            return out

        # The slot's own state: the whole row, so a slot that is used
        # again never sees what the last request left.
        return _map_slot_state(
            _map_attention(cache, scatter, pcache),
            lambda leaf, row: leaf.at[slot].set(row[0]), pcache)

    def _arm_ctl(self, ctl, slot, step_keys_row, max_steps, first_tok,
                 temperature, top_k, top_p, eos, has_eos):
        out_ctl = dict(ctl)
        out_ctl["active"] = ctl["active"].at[slot].set(True)
        out_ctl["done"] = ctl["done"].at[slot].set(
            has_eos & (first_tok == eos))
        out_ctl["cur_tok"] = ctl["cur_tok"].at[slot].set(first_tok)
        out_ctl["steps_done"] = ctl["steps_done"].at[slot].set(1)
        out_ctl["max_steps"] = ctl["max_steps"].at[slot].set(max_steps)
        out_ctl["temperature"] = ctl["temperature"].at[slot].set(
            temperature)
        out_ctl["top_k"] = ctl["top_k"].at[slot].set(top_k)
        out_ctl["top_p"] = ctl["top_p"].at[slot].set(top_p)
        out_ctl["eos"] = ctl["eos"].at[slot].set(eos)
        out_ctl["has_eos"] = ctl["has_eos"].at[slot].set(has_eos)
        out_ctl["step_keys"] = ctl["step_keys"].at[slot].set(
            step_keys_row)
        return out_ctl

    def _insert_impl(self, cache, ctl, pcache, slot, page_vec,
                     scatter_vec, step_keys_row, max_steps, first_tok,
                     temperature, top_k, top_p, eos, has_eos):
        new_cache = self._scatter_request(cache, pcache, slot, page_vec,
                                          scatter_vec)
        out_ctl = self._arm_ctl(ctl, slot, step_keys_row, max_steps,
                                first_tok, temperature, top_k, top_p,
                                eos, has_eos)
        return new_cache, out_ctl

    def _insert_spec_impl(self, cache, dcache, ctl, pcache, dpcache,
                          slot, page_vec, scatter_vec, step_keys_row,
                          max_steps, first_tok, temperature, top_k,
                          top_p, eos, has_eos):
        new_cache = self._scatter_request(cache, pcache, slot, page_vec,
                                          scatter_vec)
        new_dcache = self._scatter_request(dcache, dpcache, slot,
                                           page_vec, scatter_vec)
        out_ctl = self._arm_ctl(ctl, slot, step_keys_row, max_steps,
                                first_tok, temperature, top_k, top_p,
                                eos, has_eos)
        return new_cache, new_dcache, out_ctl

    def _tick_impl(self, params, cache, ctl):
        from cloud_tpu.models.moe import MOE_STATS

        active = ctl["active"]
        logits, vars_ = self._paged.apply(
            {"params": params, "cache": cache},
            ctl["cur_tok"][:, None], active[:, None],
            mutable=["cache", MOE_STATS])
        logits = logits[:, 0]  # [S, V]
        # Slot s's step i consumes generate()'s step_rngs[i]; after
        # insertion steps_done is 1 (the prefill token), so the first
        # tick reads key row 0.
        key_idx = jnp.clip(ctl["steps_done"] - 1, 0,
                           ctl["step_keys"].shape[1] - 1)
        keys = jnp.take_along_axis(
            ctl["step_keys"], key_idx[:, None, None], 1)[:, 0]
        # Inactive slots keep their stale sampling rows; zeroing the
        # temperature they feed the sampler keeps the greedy fast path
        # available whenever the LIVE traffic is all-greedy.
        live_temp = jnp.where(active, ctl["temperature"], 0.0)
        nxt = _sample_slots(logits, keys, live_temp, ctl["top_k"],
                            ctl["top_p"])
        latched = ctl["has_eos"] & ctl["done"]
        nxt = jnp.where(latched, ctl["eos"], nxt)
        done = ctl["done"] | (ctl["has_eos"] & (nxt == ctl["eos"]))
        steps = ctl["steps_done"] + active.astype(jnp.int32)
        finished = active & (done | (steps >= ctl["max_steps"]))
        out_ctl = dict(ctl)
        # A finished slot retires itself (see `tick`'s docstring).
        out_ctl["active"] = active & ~finished
        out_ctl["cur_tok"] = jnp.where(active, nxt, ctl["cur_tok"])
        out_ctl["done"] = jnp.where(active, done, ctl["done"])
        out_ctl["steps_done"] = steps
        out = jnp.stack([jnp.where(active, nxt, -1),
                         finished.astype(jnp.int32)])
        counters = _moe_counters(_plain(vars_.get(MOE_STATS, {})))
        if self.state_layers:
            counters["ssm_slot_steps"] = self.state_layers * jnp.sum(
                active.astype(jnp.int32))
        return _plain(vars_["cache"]), out_ctl, out, counters

    def _block_prefill_impl(self, params, cache, tokens, mask):
        _, vars_ = self._dense.apply({"params": params, "cache": cache},
                                     tokens, mask, mutable=["cache"])
        cache = _plain(vars_["cache"])
        # A layer's token count: what the host fetches to know it done.
        count = next(leaf for path, leaf in
                     jax.tree_util.tree_flatten_with_path(cache)[0]
                     if path[-1].key == "token_count")
        return cache, count

    def _insert_block_impl(self, cache, ctl, pcache, slot, page_vec,
                           scatter_vec, tokens, masked, masks0, steps,
                           start, gen_end):
        new_cache = self._scatter_request(cache, pcache, slot, page_vec,
                                          scatter_vec)
        out = dict(ctl)
        for key, value in (("active", True), ("block_tokens", tokens),
                           ("block_masked", masked),
                           ("block_start", start), ("block_step", 0),
                           ("block_masks0", masks0),
                           ("denoise_steps", steps), ("gen_end", gen_end)):
            out[key] = ctl[key].at[slot].set(value)
        return new_cache, out

    def _block_tick_impl(self, params, cache, ctl):
        """One block tick (`tick`'s docstring has the out-array): every
        active slot runs its open block's B rows over the pool. A block
        with masked rows takes a denoising step (`models.sdar.
        denoise_step`: the step's `transfer_count` most confident masked
        rows take their argmax); one without runs clean, and its rows
        are then final: the slot's pointer moves past them, the next
        block opens masked (`models.sdar.masked_rows`), and a slot
        whose new tokens all lie
        behind the pointer has finished (and retires itself, as a
        finished slot of the plain tick does)."""
        from cloud_tpu.models.decoding import paged_slot_advance
        from cloud_tpu.models.moe import MOE_STATS
        from cloud_tpu.models.sdar import (denoise_step, masked_rows,
                                           transfer_count)

        b = self.block
        active = ctl["active"]
        tokens, masked = ctl["block_tokens"], ctl["block_masked"]
        closing = active & ~jnp.any(masked, axis=1)
        stepping = active & ~closing
        logits, vars_ = self._paged.apply(
            {"params": params, "cache": cache}, tokens,
            jnp.broadcast_to(active[:, None], tokens.shape),
            mutable=["cache", MOE_STATS])
        count = jnp.where(stepping, transfer_count(
            ctl["block_masks0"], ctl["denoise_steps"], ctl["block_step"]),
            0)
        tokens, masked, commit = denoise_step(logits, tokens, masked, count)
        n = jnp.sum(commit.astype(jnp.int32), axis=1)
        # The committed rows first, in the block's order.
        order = jnp.argsort(~commit, axis=1, stable=True).astype(jnp.int32)
        got = jnp.arange(b)[None, :] < n[:, None]
        committed = jnp.where(got, jnp.take_along_axis(tokens, order, 1),
                              -1)
        offsets = jnp.where(got, order, -1)
        moved = jnp.where(closing, b, 0)
        cache = paged_slot_advance(_plain(vars_["cache"]), moved)
        start = ctl["block_start"] + moved
        finished = closing & (start >= ctl["gen_end"])
        out_ctl = dict(ctl)
        out_ctl["active"] = active & ~finished
        out_ctl["block_tokens"] = jnp.where(
            closing[:, None], self.model.mask_token, tokens)
        opened = masked_rows(start, ctl["gen_end"], b)
        out_ctl["block_masked"] = jnp.where(closing[:, None], opened, masked)
        out_ctl["block_start"] = start
        out_ctl["block_step"] = jnp.where(
            closing, 0, ctl["block_step"] + stepping.astype(jnp.int32))
        out_ctl["block_masks0"] = jnp.where(
            closing, jnp.sum(opened.astype(jnp.int32), axis=1),
            ctl["block_masks0"])
        out = jnp.concatenate([
            jnp.transpose(committed), jnp.transpose(offsets), n[None],
            finished.astype(jnp.int32)[None], ctl["block_start"][None],
        ], axis=0)                                        # [2B + 3, S]
        counters = _moe_counters(_plain(vars_.get(MOE_STATS, {})))
        counted = lambda x: jnp.sum(x.astype(jnp.int32))
        counters.update(
            diffusion_slot_steps=counted(stepping),
            diffusion_tokens_committed=jnp.sum(n),
            diffusion_blocks_closed=counted(closing),
            diffusion_rows_computed=b * counted(active))
        return cache, out_ctl, out, counters

    def _spec_tick_impl(self, params, draft_params, cache, dcache, ctl):
        """Draft/verify speculation, one executable per tick:

          1. draft scan: k greedy single-token steps from cur_tok
             (writes k draft-cache entries per active slot);
          2. verify: ONE (k+1)-token target forward over
             [cur_tok, d_1..d_k] (writes k+1 target-cache entries);
          3. accept: greedy slots keep the longest draft prefix that
             matches the target argmax chain plus the target's own
             next token (`greedy_accept` — speculative.py's pinned
             math); sampled slots commit one token from position 0,
             whose logits are bitwise the plain tick's;
          4. rewind: both caches roll back to exactly
             prompt + steps' - 1 entries (`paged_slot_rewind`); a
             fully-accepted slot's draft cache is one entry SHORT, so
             a masked catch-up draft forward writes d_k's entry.

        Invariant, before and after every tick: target and draft
        caches both hold `prompt_len + steps_done - 1` entries —
        cur_tok is never in either cache (it is the next input).
        """
        from cloud_tpu.models.decoding import paged_slot_rewind
        from cloud_tpu.models.speculative import greedy_accept

        k = self.spec_k
        # Width from the traced aval, not self.slots: the ladder
        # retraces this body once per rung, and the host attribute may
        # already point at the NEXT rung while a cached executable
        # replays an earlier one.
        slots = ctl["active"].shape[0]
        active = ctl["active"]
        mask1 = active[:, None]

        def draft_step(carry, _):
            dc, tok = carry
            dlogits, dvars = self._paged_draft.apply(
                {"params": draft_params, "cache": dc},
                tok[:, None], mask1, mutable=["cache"])
            nxt = jnp.argmax(dlogits[:, 0].astype(jnp.float32),
                             axis=-1).astype(jnp.int32)
            return (_plain(dvars["cache"]), nxt), nxt

        (dcache, _), drafts = jax.lax.scan(
            draft_step, (dcache, ctl["cur_tok"]), None, length=k)
        drafts = jnp.transpose(drafts, (1, 0))  # [S, k]

        verify_in = jnp.concatenate(
            [ctl["cur_tok"][:, None], drafts], axis=1)  # [S, k+1]
        maskk = jnp.broadcast_to(mask1, (slots, k + 1))
        logits, vars_ = self._paged.apply(
            {"params": params, "cache": cache},
            verify_in, maskk, mutable=["cache"])
        cache = _plain(vars_["cache"])
        greedy = jnp.argmax(logits.astype(jnp.float32),
                            axis=-1).astype(jnp.int32)  # [S, k+1]
        n_acc = greedy_accept(drafts, greedy)  # [S]

        # Sampled (temperature > 0) slots ride the same executable
        # committing ONE token from position 0 — the plain tick's
        # sampler over the plain tick's logits, key schedule included.
        key_idx = jnp.clip(ctl["steps_done"] - 1, 0,
                           ctl["step_keys"].shape[1] - 1)
        keys = jnp.take_along_axis(
            ctl["step_keys"], key_idx[:, None, None], 1)[:, 0]
        live_temp = jnp.where(active, ctl["temperature"], 0.0)
        sampled0 = _sample_slots(logits[:, 0], keys, live_temp,
                                 ctl["top_k"], ctl["top_p"])

        is_spec = active & (ctl["temperature"] == 0.0)
        n_acc = jnp.where(is_spec, n_acc, 0)
        bonus = jnp.take_along_axis(greedy, n_acc[:, None], 1)[:, 0]
        pick = jnp.where(is_spec, bonus, sampled0)
        committed = jnp.concatenate(
            [drafts, jnp.zeros((slots, 1), jnp.int32)], axis=1)
        committed = committed.at[jnp.arange(slots), n_acc].set(pick)
        latched = ctl["has_eos"] & ctl["done"]
        committed = jnp.where(latched[:, None], ctl["eos"][:, None],
                              committed)

        base_c = jnp.where(is_spec, n_acc + 1, 1)
        # Commit stops at the first eos: tokens past it are never
        # emitted (the scheduler latch-fills the tail on completion,
        # exactly generate()'s where(done, eos, ...) behavior).
        eos_hit = (ctl["has_eos"][:, None]
                   & (committed == ctl["eos"][:, None]))
        hit_idx = jnp.where(eos_hit, jnp.arange(k + 1)[None, :], k + 1)
        first_eos = jnp.min(hit_idx, axis=1)
        c = jnp.minimum(base_c, first_eos + 1)
        done_new = ctl["done"] | (ctl["has_eos"] & (first_eos < base_c))
        steps = ctl["steps_done"] + jnp.where(active, c, 0)
        finished = active & (done_new | (steps >= ctl["max_steps"]))
        cur_tok = committed[jnp.arange(slots), jnp.maximum(c - 1, 0)]

        # Rewind both caches to prompt + steps' - 1 entries. Target
        # wrote k+1 and keeps c; draft wrote k and keeps c, except the
        # full-accept slot (c == k+1) which is one SHORT — the masked
        # catch-up forward below writes d_k's missing entry (mask 0
        # slots neither move their pointers nor validate anything).
        delta_t = jnp.where(active, k + 1 - c, 0)
        cache = paged_slot_rewind(cache, delta_t, self.max_seq_len)
        if "pos_count" in cache:
            cache["pos_count"] = cache["pos_count"] - delta_t
        delta_d = jnp.where(active, jnp.maximum(k - c, 0), 0)
        dcache = paged_slot_rewind(dcache, delta_d, self.max_seq_len)
        if "pos_count" in dcache:
            dcache["pos_count"] = dcache["pos_count"] - delta_d
        catch = active & (c == k + 1)
        _, dvars = self._paged_draft.apply(
            {"params": draft_params, "cache": dcache},
            drafts[:, k - 1][:, None], catch[:, None],
            mutable=["cache"])
        dcache = _plain(dvars["cache"])

        out_ctl = dict(ctl)
        out_ctl["active"] = active & ~finished
        out_ctl["cur_tok"] = jnp.where(active, cur_tok, ctl["cur_tok"])
        out_ctl["done"] = jnp.where(active, done_new, ctl["done"])
        out_ctl["steps_done"] = steps
        out = jnp.concatenate([
            jnp.where(active[None, :], jnp.transpose(committed), -1),
            jnp.where(active, c, 0)[None, :],
            finished.astype(jnp.int32)[None, :],
            jnp.where(is_spec, n_acc, -1)[None, :],
        ], axis=0)  # [k+4, S]
        return cache, dcache, out_ctl, out

    def _snapshot_impl(self, cache, page_vec):
        """Per-attention-layer K/V page blocks (+ scale sidecars) for
        `page_vec` ([pages_per_slot] int32, scratch-padded) — the
        device half of a host-tier demote. Reads the pool cache, never
        donates it; one fixed-shape executable for any page count."""
        def snap(att):
            entry = {pool: att[pool][page_vec]
                     for pool, _ in _page_rows(att)}
            if "key_scales" in att:
                entry["key_scales"] = att["key_scales"][page_vec]
                entry["value_scales"] = att["value_scales"][page_vec]
            return entry

        tree = _map_attention(cache, snap)
        tree.pop("pos_count", None)
        return tree

    def _promote_impl(self, cache, host_tree, page_vec):
        """Scatters a host-tier entry's page blocks back into the pool
        at `page_vec` (full-width, scratch-padded past the promoted
        extension — padded rows collapse onto scratch exactly like the
        insert scatter's shared chunks)."""
        def prom(att, h):
            out = dict(att)
            for pool, _ in _page_rows(att):
                out[pool] = att[pool].at[page_vec].set(h[pool])
            if "key_scales" in att:
                out["key_scales"] = att["key_scales"].at[page_vec].set(
                    h["key_scales"])
                out["value_scales"] = att["value_scales"].at[
                    page_vec].set(h["value_scales"])
            return out

        # The snapshot strips pos_count (it is slot state, not page
        # content); put a placeholder back so the parallel walk indexes
        # the same top-level keys the cache has.
        host_tree = dict(host_tree)
        host_tree.setdefault("pos_count", 0)
        return _map_attention(cache, prom, host_tree)

    # -- host page tier (tick thread) ---------------------------------

    def snapshot_pages(self, page_ids):
        """Host numpy snapshot of `page_ids`' pool content (the demote
        D2H): a pytree mirroring the cache's attention subtrees, each
        holding `[n, P, H*D]` K/V blocks (+ `[n, H]` scales in int8
        mode) with n == len(page_ids), rows in logical page order.
        Tick thread only — reads the tick-donated cache."""
        self._refuse_page_reuse("the host tier")
        n = len(page_ids)
        vec = jnp.asarray(self.pool_page_vec(page_ids), jnp.int32)
        snapshot = self._snapshot(self.cache, vec)
        self._note(PAGE_SNAPSHOT)
        tree = jax.device_get(snapshot)
        return jax.tree_util.tree_map(lambda a: a[:n], tree)

    def promote_pages(self, host_tree, page_ids, n_skip=0):
        """Writes a host-tier snapshot back into the pool (the promote
        H2D): logical page i of `host_tree` lands in physical page
        `page_ids[i]`, except the first `n_skip` logical pages (already
        resident via the prefix trie) and any `page_ids` entry of 0,
        which collapse onto scratch. Tick thread only."""
        self._refuse_page_reuse("the host tier")
        vec = self.pool_page_vec(page_ids)
        vec[:n_skip] = 0
        n = len(page_ids)
        ppn = self.pages_per_slot

        def pad(a):
            if a.shape[0] == ppn:
                return a
            widths = [(0, ppn - n)] + [(0, 0)] * (a.ndim - 1)
            return np.pad(np.asarray(a), widths)

        padded = jax.tree_util.tree_map(pad, host_tree)
        self.cache = self._promote(self.cache, padded,
                                   jnp.asarray(vec, jnp.int32))
        self._note(PAGE_PROMOTE)

    def pool_page_vec(self, page_ids):
        """Full-width scratch-padded page vector (kvpool.page_vec's
        layout) — kept here so engine-level callers don't need the
        pool object."""
        vec = np.zeros((self.pages_per_slot,), np.int32)
        vec[:len(page_ids)] = page_ids
        return vec

    def page_hbm_bytes(self):
        """HBM bytes ONE physical page costs summed over every
        attention layer (K + V blocks, plus the f32 scale sidecars in
        int8 mode; the draft pool included when speculating — it keys
        on the same page ids). Feeds PagePool.page_bytes for the
        KV-hierarchy gauges."""
        def per_model(m):
            _, kv_heads, head_dim = attention_shape(m)
            item = (1 if self.page_dtype == "int8"
                    else jnp.dtype(m.compute_dtype).itemsize)
            per_layer = 2 * self.page_size * kv_heads * head_dim * item
            if getattr(m, "latent_row", 0):
                # One latent row a token and no key and value pools.
                per_layer = self.page_size * m.latent_row * item
            if self.page_dtype == "int8":
                per_layer += 2 * kv_heads * 4
            # Layers that hold pages: all of them, but for a class
            # that says otherwise.
            return per_layer * getattr(m, "attention_layers",
                                       m.num_layers)

        total = per_model(self.model)
        if self.spec_on:
            total += per_model(self._paged_draft)
        return int(total)

    def state_hbm_bytes(self):
        """HBM bytes of the recurrent state resident beside the pool:
        every slot's row of every `_map_slot_state` leaf of a model
        with recurrent layers (0 for the others; it is allocated whole
        whether or not a slot is occupied)."""
        if not self.state_layers:
            return 0
        sizes = []
        _map_slot_state(self.cache, lambda leaf: sizes.append(
            leaf.size * leaf.dtype.itemsize))
        return int(sum(sizes))

    def _clear_slots(self, cache, keep):
        def clear(att):
            out = dict(att)
            out["page_table"] = jnp.where(keep[:, None],
                                          att["page_table"], 0)
            out["slot_steps"] = jnp.where(keep, att["slot_steps"], 0)
            out["slot_valid"] = att["slot_valid"] & keep[:, None]
            return out

        return _map_slot_state(_map_attention(cache, clear),
                               lambda leaf: _rows_where(keep, leaf))

    def _evict_impl(self, cache, ctl, evict_mask):
        keep = ~evict_mask
        new_cache = self._clear_slots(cache, keep)
        out_ctl = dict(ctl)
        out_ctl["active"] = ctl["active"] & keep
        out_ctl["done"] = ctl["done"] & keep
        out_ctl["steps_done"] = jnp.where(keep, ctl["steps_done"], 0)
        out_ctl["cur_tok"] = jnp.where(keep, ctl["cur_tok"], 0)
        out_ctl["max_steps"] = jnp.where(keep, ctl["max_steps"], 0)
        return new_cache, out_ctl

    def _evict_spec_impl(self, cache, dcache, ctl, evict_mask):
        new_cache, out_ctl = self._evict_impl(cache, ctl, evict_mask)
        new_dcache = self._clear_slots(dcache, ~evict_mask)
        return new_cache, new_dcache, out_ctl

    def _resize_slots(self, cache, perm):
        """Geometry-bound slot rows gathered to the new width; the
        page arrays flow through donated and untouched. An empty new
        row (perm -1, src clipped to 0) zeroes exactly the leaves
        `_evict_impl` zeroes, so a fresh rung looks like freshly
        evicted slots."""
        mask = perm >= 0
        src = jnp.clip(perm, 0)

        def rs(att):
            out = dict(att)
            out["page_table"] = jnp.where(mask[:, None],
                                          att["page_table"][src], 0)
            out["slot_steps"] = jnp.where(mask, att["slot_steps"][src],
                                          0)
            out["slot_valid"] = att["slot_valid"][src] & mask[:, None]
            return out

        return _map_slot_state(
            _map_attention(cache, rs),
            lambda leaf: _rows_where(mask, leaf[src]))

    def _resize_ctl(self, ctl, perm):
        """Control rows under the same perm. The masked leaves mirror
        `_evict_impl`'s zeroing; sampling config / eos / step_keys rows
        gather unmasked — evict leaves them stale too, and a clipped
        src just copies a real row's staleness. In-flight rows carry
        their exact rng schedule, latch and counters, which is the
        bit-identity contract across a resize."""
        mask = perm >= 0
        src = jnp.clip(perm, 0)
        out_ctl = {k: v[src] for k, v in ctl.items()}
        out_ctl["active"] = ctl["active"][src] & mask
        out_ctl["done"] = ctl["done"][src] & mask
        out_ctl["steps_done"] = jnp.where(mask, ctl["steps_done"][src],
                                          0)
        out_ctl["cur_tok"] = jnp.where(mask, ctl["cur_tok"][src], 0)
        out_ctl["max_steps"] = jnp.where(mask, ctl["max_steps"][src], 0)
        return out_ctl

    def _resize_impl(self, cache, ctl, perm):
        return (self._resize_slots(cache, perm),
                self._resize_ctl(ctl, perm))

    def _resize_spec_impl(self, cache, dcache, ctl, perm):
        return (self._resize_slots(cache, perm),
                self._resize_slots(dcache, perm),
                self._resize_ctl(ctl, perm))


__all__ = ["ChunkedPrefill", "DecodeEngine", "PrefillResult",
           "RetraceError", "chunk_plan"]
