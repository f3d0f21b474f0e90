"""EvaByte-style byte-level decoder LM: EVA attention over a two-tier
cache.

The fifth LM family, for the `evabyte` checkpoints (EvaByte 6.5B). A
block is `x + Attn(N(x))`, `x + MLP(N(x))` with the residual sum kept
in float32, `llama.FusedRMSNorm` (its `scale` holds the multiplier
`1 + g` of the family's unit-offset norm), `llama.SwiGLU` and
`llama.apply_rope` (rotate-half, over the whole head, on q and k at
absolute positions before anything else). The head predicts
`num_pred_heads` bytes a position from one matrix; head 0 is the next
byte, and `__call__` returns its logits unless `all_heads` asks for
all of them (decoding several bytes a step by self-speculation over
the heads is left out: ROADMAP 2.8).

EVA attention (`ops/eva.py` has the equations and the layout): a query
attends exactly over its own ALIGNED window of `window_size` tokens and
over one summary row per `chunk_size` tokens of everything before that
window, in one softmax. Three forms, one arithmetic:

  a sequence   (`decode=False`) every window of the sequence at once:
               what tests and training-shaped calls use;
  a window     (`decode=True`, no pages) the serving prefill's chunk: a
               call's tokens lie inside ONE window (the scheduler
               prefills such a model a window at a time); it attends
               `[summaries so far | the window, causal]`, leaves the
               window in the ring and the summaries of the chunks it
               completed, never a dense cache;
  a tick       (`decode=True`, `kv_page_size`) one token a slot over the
               paged pool: the slot's ring and summary rows are rows of
               the pool's pages, read through `ops.paged_attention`'s
               walk over the slot's one contiguous run of live rows.

The module keeps the decode contract of the other families (`decode=`,
the "cache" collection, `kv_page_*`), so `serving.Scheduler` serves it.
"""

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from cloud_tpu.models.llama import (FusedRMSNorm, SwiGLU, _DenseKernel,
                                    apply_rope)
from cloud_tpu.ops.eva import EvaLayout, chunk_summaries

_NEG_INF = -1e30


class EvaAttention(nn.Module):
    """Multi-head EVA attention (no grouped queries in the family)."""

    num_heads: int
    head_dim: int
    layout: EvaLayout
    compute_dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    rope_theta: float = 100000.0
    decode: bool = False
    param_dtype: jnp.dtype = jnp.float32
    # Paged-pool decode (serving/engine.py): page rows are
    # num_heads * head_dim wide, and a page is a chunk.
    page_size: int = 0
    num_pages: int = 0
    page_dtype: str = ""

    def _rope(self, x, positions):
        return apply_rope(x, positions, self.rope_theta, "rotate_half")

    @nn.compact
    def __call__(self, x, mask=None):
        d_model = x.shape[-1]
        heads, depth = self.num_heads, self.head_dim
        dense = lambda name: nn.DenseGeneral(
            (heads, depth), axis=-1, use_bias=False,
            dtype=self.compute_dtype, param_dtype=self.param_dtype,
            name=name)
        q, k, v = dense("query")(x), dense("key")(x), dense("value")(x)
        # A head's two learned vectors: the chunk softmax's direction
        # and the summary key's offset.
        vector = lambda name: self.param(
            name, nn.initializers.normal(0.02), (heads, depth),
            jnp.float32)
        phi, mu = vector("phi"), vector("mu")
        if not self.decode:
            if mask is not None:
                raise NotImplementedError(
                    "EVA over a whole sequence takes no padding mask.")
            out = self._sequence(q, k, v, phi, mu)
        elif self.page_size:
            out = self._tick(q, k, v, phi, mu, mask)
        else:
            out = self._window(q, k, v, phi, mu, mask)
        return nn.DenseGeneral(d_model, axis=(-2, -1), use_bias=False,
                               dtype=self.compute_dtype,
                               param_dtype=self.param_dtype,
                               name="out")(out.astype(self.compute_dtype))

    def _scale(self):
        return 1.0 / np.sqrt(self.head_dim)

    def _flash_selected(self):
        return self.attention_impl == "flash" or (
            self.attention_impl == "auto"
            and jax.default_backend() == "tpu")

    # -- a sequence ---------------------------------------------------

    def _sequence(self, q, k, v, phi, mu):
        """Every window of `[B, T, H, D]` at once: window n attends
        `[summaries of the chunks before it | itself, causal]`."""
        lay = self.layout
        batch, seq, heads, depth = q.shape
        positions = jnp.arange(seq)
        q, k = self._rope(q, positions), self._rope(k, positions)
        window = lay.window
        pad = -seq % window
        if pad:
            widths = ((0, 0), (0, pad), (0, 0), (0, 0))
            q, k, v = (jnp.pad(a, widths) for a in (q, k, v))
        windows = (seq + pad) // window
        # A ragged tail's last chunk holds pads; it lies in the last
        # window, whose summaries no query of the call sees.
        chunked = lambda a: a.reshape(batch, -1, lay.chunk, heads, depth)
        k_sum, v_sum = chunk_summaries(chunked(k), chunked(v), phi, mu)
        by_window = lambda a: a.reshape(batch, windows, window, heads,
                                        depth)
        qw, kw, vw = by_window(q), by_window(k), by_window(v)
        scale = self._scale()
        exact = jnp.einsum("bnqhd,bnkhd->bnhqk", qw, kw,
                           preferred_element_type=jnp.float32) * scale
        behind = jnp.einsum("bnqhd,bchd->bnhqc", qw, k_sum,
                            preferred_element_type=jnp.float32) * scale
        causal = jnp.tril(jnp.ones((window, window), bool))
        exact = jnp.where(causal, exact, _NEG_INF)
        seen = (jnp.arange(k_sum.shape[1])[None, :]
                < lay.chunks_per_window * jnp.arange(windows)[:, None])
        behind = jnp.where(seen[None, :, None, None, :], behind, _NEG_INF)
        weights = nn.softmax(
            jnp.concatenate([behind, exact], axis=-1), axis=-1).astype(
                self.compute_dtype)
        n_sum = k_sum.shape[1]
        out = (jnp.einsum("bnhqc,bchd->bnqhd", weights[..., :n_sum], v_sum)
               + jnp.einsum("bnhqk,bnkhd->bnqhd", weights[..., n_sum:], vw))
        return out.reshape(batch, seq + pad, heads, depth)[:, :seq]

    # -- a window (the serving prefill's chunk) -----------------------

    def _window(self, q, k, v, phi, mu, mask):
        """A call whose tokens lie inside one window, over a dense
        per-example cache of `layout.rows` rows laid out as a slot's
        logical table (so the engine's insert scatters it page by page
        as it does any dense prefill cache). `mask` [B, S] marks real
        tokens, a prefix of the call (right-padded): pads write no row
        and enter no summary."""
        lay = self.layout
        batch, seq, heads, depth = q.shape
        rows, n_sum = lay.rows, lay.summary_rows
        shape = (batch, rows, heads, depth)
        cached_k = self.variable("cache", "cached_key", jnp.zeros, shape,
                                 self.compute_dtype)
        cached_v = self.variable("cache", "cached_value", jnp.zeros,
                                 shape, self.compute_dtype)
        slot_valid = self.variable("cache", "slot_valid", jnp.zeros,
                                   (batch, rows), jnp.bool_)
        token_count = self.variable("cache", "token_count", jnp.zeros,
                                    (batch,), jnp.int32)
        m = (jnp.ones((batch, seq), jnp.int32) if mask is None
             else mask.astype(jnp.int32))
        start = token_count.value
        pos = start[:, None] + jnp.cumsum(m, 1) - m        # [B, S]
        end = start + m.sum(axis=1)
        q, k = self._rope(q, pos), self._rope(k, pos)

        example = jnp.arange(batch)[:, None]
        real = m.astype(bool)
        store = lambda cache, new, row: cache.at[
            example, jnp.where(real, row, rows)].set(
                new.astype(self.compute_dtype), mode="drop")
        ring_row = lay.ring_row(pos)
        keys = store(cached_k.value, k, ring_row)
        values = store(cached_v.value, v, ring_row)
        # The summaries of the chunks this window has completed so
        # far, from the ring as stored (what a tick reads back from
        # the chunk's page).
        per_window = lay.chunks_per_window
        chunked = lambda cache: cache[:, n_sum:].reshape(
            batch, per_window, lay.chunk, heads, depth)
        k_sum, v_sum = chunk_summaries(chunked(keys), chunked(values),
                                       phi, mu)
        window_index = start // lay.window                 # [B]
        filled = end - window_index * lay.window
        in_window = jnp.arange(per_window)[None, :]
        complete = (in_window + 1) * lay.chunk <= filled[:, None]
        sum_row = jnp.where(
            complete, lay.summary_row(
                window_index[:, None] * per_window + in_window), rows)
        keys = keys.at[example, sum_row].set(k_sum, mode="drop")
        values = values.at[example, sum_row].set(v_sum, mode="drop")
        cached_k.value, cached_v.value = keys, values
        token_count.value = end
        slot_valid.value = lay.visible(jnp.maximum(end - 1, 0))

        if seq > 1 and self._flash_selected():
            # The queries at their own ring rows of a `rows`-long
            # frame, the cache as keys: causal in row order is
            # `[every summary row | ring rows up to the query's]`,
            # and the key mask leaves of the summaries those before
            # this window.
            from cloud_tpu.ops.attention import flash_attention
            frame = store(jnp.zeros(shape, self.compute_dtype), q,
                          ring_row)
            row = jnp.arange(rows)[None, :]
            seen = row >= (n_sum - per_window * window_index)[:, None]
            out = flash_attention(frame, keys, values, causal=True,
                                  sm_scale=self._scale(), mask=seen)
            return out[example, ring_row]
        allowed = lay.visible(pos)                         # [B, S, rows]
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, keys,
                            preferred_element_type=jnp.float32)
        logits = jnp.where(allowed[:, None], logits * self._scale(),
                           _NEG_INF)
        weights = nn.softmax(logits, axis=-1).astype(self.compute_dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, values)

    # -- a tick over the paged pool -----------------------------------

    def _tick(self, q, k, v, phi, mu, mask):
        """One token a slot (`decoding.paged_kv_attention`'s contract,
        with the two kinds of row): slot s at depth t = `slot_steps[s]`
        writes ring row `t mod window`, and where the token completes
        a chunk also the chunk's summary row, made from the page that
        holds the chunk; then reads its run of live rows. Every shape
        is the tick's own: nothing changes when a window ends but
        which rows the mask lets through."""
        from cloud_tpu.ops import paged_attention

        lay = self.layout
        slots, seq, heads, depth = q.shape
        page = self.page_size
        if seq != 1:
            raise NotImplementedError(
                "an EVA layer's tick takes one token a slot; a verify "
                "window would overwrite ring rows it cannot roll back.")
        if self.page_dtype:
            raise NotImplementedError(
                "an EVA layer's pages are kept in the compute dtype; "
                "got page_dtype {!r}.".format(self.page_dtype))
        lay.check(page)
        width = heads * depth
        pool = (self.num_pages, page, width)
        key_pages = self.variable("cache", "key_pages", jnp.zeros, pool,
                                  self.compute_dtype)
        value_pages = self.variable("cache", "value_pages", jnp.zeros,
                                    pool, self.compute_dtype)
        page_table = self.variable(
            "cache", "page_table", jnp.zeros, (slots, lay.rows // page),
            jnp.int32)
        slot_steps = self.variable("cache", "slot_steps", jnp.zeros,
                                   (slots,), jnp.int32)
        slot_valid = self.variable("cache", "slot_valid", jnp.zeros,
                                   (slots, lay.rows), jnp.bool_)
        active = (jnp.ones((slots,), bool) if mask is None
                  else mask.reshape(slots).astype(bool))
        t = slot_steps.value
        q, k = self._rope(q, t[:, None]), self._rope(k, t[:, None])

        def physical(row, live):
            """(page, offset) of a logical row; scratch where not
            `live` (an inactive slot's table row is zero besides)."""
            phys = jnp.take_along_axis(page_table.value,
                                       (row // page)[:, None], 1)[:, 0]
            return jnp.where(live, phys, 0), row % page

        ring_page, ring_off = physical(lay.ring_row(t), active)
        closes = active & ((t + 1) % lay.chunk == 0)
        sum_page, sum_off = physical(lay.summary_row(t // lay.chunk),
                                     closes)
        slot = jnp.arange(slots)

        def write(pages, new):
            """The token's row and its chunk's summary row in one
            scatter; the summary from the chunk's page as it stands
            with this token in it."""
            row = new[:, 0].astype(self.compute_dtype)       # [S, H, D]
            chunk = pages[ring_page].reshape(slots, page, heads, depth)
            return row, chunk.at[slot, ring_off].set(row)

        k_row, k_chunk = write(key_pages.value, k)
        v_row, v_chunk = write(value_pages.value, v)
        k_sum, v_sum = chunk_summaries(k_chunk, v_chunk, phi, mu)
        where = (jnp.concatenate([ring_page, sum_page]),
                 jnp.concatenate([ring_off, sum_off]))
        fold = lambda a, b: jnp.concatenate([a, b]).reshape(-1, width)
        key_pages.value = key_pages.value.at[where].set(
            fold(k_row, k_sum))
        value_pages.value = value_pages.value.at[where].set(
            fold(v_row, v_sum))

        allowed = lay.visible(t) & active[:, None]           # [S, rows]
        slot_steps.value = t + active.astype(jnp.int32)
        slot_valid.value = jnp.where(active[:, None], allowed,
                                     slot_valid.value)
        # `window=`: the mask has a lower edge (the first summary row
        # the slot has), so the walk starts at its first live page.
        return paged_attention(
            q, key_pages.value, value_pages.value, page_table.value,
            allowed[:, None, :], sm_scale=self._scale(),
            impl=self.attention_impl, window=lay.window)


class EvaByteBlock(nn.Module):
    """`cfg` is the model (its fields are the block's numbers)."""

    cfg: "EvaByteLM"

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        norm = lambda name: FusedRMSNorm(
            epsilon=cfg.norm_eps, dtype=cfg.compute_dtype,
            impl=cfg.attention_impl, name=name)
        y = EvaAttention(
            cfg.num_heads, cfg.head_size, cfg.layout, cfg.compute_dtype,
            cfg.attention_impl, cfg.rope_theta, decode=cfg.decode,
            param_dtype=cfg.param_dtype, page_size=cfg.kv_page_size,
            num_pages=cfg.kv_num_pages, page_dtype=cfg.kv_page_dtype,
            name="attention")(norm("norm_attn")(x), mask)
        # The residual stream stays float32 (`fp32_skip_add`).
        x = x + y.astype(jnp.float32)
        y = SwiGLU(cfg.d_ff, cfg.compute_dtype, impl=cfg.attention_impl,
                   param_dtype=cfg.param_dtype,
                   name="mlp")(norm("norm_mlp")(x))
        return x + y.astype(jnp.float32)


class EvaByteLM(nn.Module):
    vocab_size: int = 320
    num_layers: int = 4
    num_heads: int = 8
    d_model: int = 512
    d_ff: int = 1408
    max_seq_len: int = 4096
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    head_dim: Optional[int] = None  # None -> d_model // num_heads
    rope_theta: float = 100000.0
    norm_eps: float = 1e-5
    compute_dtype: jnp.dtype = jnp.bfloat16
    # Stored dtype of the matrices; norm scales and the heads' phi and
    # mu stay float32.
    param_dtype: jnp.dtype = jnp.float32
    attention_impl: str = "auto"
    dropout_rate: float = 0.0     # the decode contract's; unused
    decode: bool = False
    # True: logits of every prediction head, [B, S, heads, vocab].
    all_heads: bool = False
    # Paged-pool decode (serving/engine.py), as LlamaLM's.
    kv_page_size: int = 0
    kv_num_pages: int = 0
    kv_page_dtype: str = ""

    def __post_init__(self):
        object.__setattr__(self, "param_dtype",
                           jnp.dtype(self.param_dtype))
        self.layout.check()
        super().__post_init__()

    @property
    def head_size(self):
        return self.head_dim or self.d_model // self.num_heads

    @property
    def layout(self):
        """The rows a slot keeps (`ops.eva.EvaLayout`): the engine
        sizes a slot's page table, the pool a request's reservation
        and the scheduler its prefill chunk from it. A served model
        that has one overwrites pages in place (the ring) and keeps
        rows that stand for many tokens (the summaries), so no page of
        it is shared by a prefix, kept on the host or rolled back
        after a rejected draft: serving/engine.py refuses all three
        by this."""
        return EvaLayout(self.window_size, self.chunk_size,
                         self.max_seq_len)

    @nn.compact
    def __call__(self, tokens, mask=None, deterministic=True):
        del deterministic
        seq = tokens.shape[1]
        if seq > self.max_seq_len:
            raise ValueError(
                "Sequence length {} exceeds max_seq_len {}.".format(
                    seq, self.max_seq_len))
        x = nn.Embed(self.vocab_size, self.d_model,
                     dtype=self.compute_dtype,
                     param_dtype=self.param_dtype, name="embed")(tokens)
        x = x.astype(jnp.float32)
        cfg = self.clone(parent=None)
        for i in range(self.num_layers):
            x = EvaByteBlock(cfg, name="block_%d" % i)(x, mask)
        x = FusedRMSNorm(epsilon=self.norm_eps, dtype=self.compute_dtype,
                         impl=self.attention_impl, name="norm_final")(x)
        # One matrix for the heads, head 0 first; float32 logits
        # (`fp32_logits`).
        kernel = _DenseKernel(self.num_pred_heads * self.vocab_size,
                              self.param_dtype, name="lm_head")(
                                  self.d_model)
        logits = jnp.dot(x, kernel.astype(self.compute_dtype),
                         preferred_element_type=jnp.float32)
        if self.all_heads:
            return logits.reshape(*logits.shape[:-1], self.num_pred_heads,
                                  self.vocab_size)
        return logits[..., :self.vocab_size]


__all__ = ["EvaAttention", "EvaByteBlock", "EvaByteLM"]
