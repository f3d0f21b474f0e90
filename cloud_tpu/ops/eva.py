"""EVA attention's two-tier cache: the chunk summary as one op, and the
geometry of the rows a slot keeps.

EVA ("Efficient Attention via Control Variates", arXiv:2302.04542, in
the form the EvaByte family serves) attends exactly over the query's
own window of `window` tokens and, behind it, over ONE summary row per
`chunk` tokens, in one softmax. A slot therefore keeps two kinds of row
of the same width (heads x head size):

  ring       the current window's keys and values, row `t mod window`
             for token t, overwritten in place when the next window
             begins (its rows are dead the moment the window ends);
  summaries  `k~_c = sum_m a_m k_m + mu`, `v~_c = sum_m a_m v_m` with
             `a = softmax_m(k_m . phi)` over chunk c's tokens
             (`chunk_summaries`), one row per chunk of everything
             before the current window.

`EvaLayout` orders both in one logical table a slot, summaries first
and REVERSED, the ring after them: chunk c sits at row
`summary_rows - 1 - c`, token t at row `summary_rows + t mod window`.
A query at depth t attends summary rows of chunks `< (window / chunk) *
(t // window)` and ring rows `<= t mod window`, which in this order is
ONE contiguous run of rows ending at the query's own: the paged decode
walk (`ops/paged_attention.py`, from the first live group to the last)
reads it with no hole, and the flash prefill sees `[summaries |
window]` with a plain causal edge (the ring row of a token is its
position in the window).

jnp throughout: the traced tick and prefill put the summary op far
under 5 % of device time (PERF.md section 5), so it has no Pallas form.
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def chunk_summaries(k, v, phi, mu):
    """Summary rows of whole chunks.

    k, v: [..., C, H, D] (the C tokens of a chunk, rotated keys);
    phi, mu: [H, D], a head's two learned vectors. Returns
    `(k~, v~)`, each [..., H, D] in k's dtype: `a = softmax over the C
    positions of (k . phi)` (no scale inside: a factor folds into
    phi), `k~ = sum a k + mu`, `v~ = sum a v`. float32 inside.
    """
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    scores = jnp.einsum("...chd,hd->...ch", kf, phi.astype(jnp.float32))
    a = jax.nn.softmax(scores, axis=-2)
    k_sum = jnp.einsum("...ch,...chd->...hd", a, kf) + mu.astype(
        jnp.float32)
    v_sum = jnp.einsum("...ch,...chd->...hd", a, vf)
    return k_sum.astype(k.dtype), v_sum.astype(v.dtype)


class EvaLayout(NamedTuple):
    """The rows a slot of an EVA layer keeps, and which a query reads.
    All arithmetic works on Python ints and on traced arrays alike."""

    window: int       # W: tokens attended exactly
    chunk: int        # C: tokens a summary row stands for
    max_seq_len: int  # longest sequence a slot may hold

    @property
    def chunks_per_window(self):
        return self.window // self.chunk

    @property
    def summary_rows(self):
        """Summary rows a slot addresses: one a chunk of `max_seq_len`."""
        return self.max_seq_len // self.chunk

    @property
    def rows(self):
        """Logical rows a slot addresses: summaries, then the ring."""
        return self.summary_rows + self.window

    def check(self, page_size=0):
        w, c, n = self.window, self.chunk, self.max_seq_len
        if c < 1 or w % c or n % w:
            raise ValueError(
                "EVA needs chunk | window | max_seq_len; got chunk {}, "
                "window {}, max_seq_len {}.".format(c, w, n))
        if page_size and (page_size != c or (w // c) % c):
            raise ValueError(
                "an EVA layer's page is its chunk (a full token page "
                "becomes one summary row) and a window's summaries "
                "are whole pages: page_size {} vs chunk {}, {} chunks "
                "a window.".format(page_size, c, w // c))

    def ring_row(self, t):
        """Logical row of token t's key and value."""
        return self.summary_rows + t % self.window

    def summary_row(self, c):
        """Logical row of chunk c's summary."""
        return self.summary_rows - 1 - c

    def visible(self, t):
        """[..., rows] bool: the rows a query at depth `t` ([...]
        int) attends, its own ring row included: ring rows
        `<= t mod window`, summaries of the chunks before its window.
        Nothing else decides it: rows above a slot's fill, the last
        window's stale keys and the last request's summaries are
        invisible whatever the pool holds there."""
        t = jnp.asarray(t)[..., None]
        row = jnp.arange(self.rows)
        first = self.summary_rows - self.chunks_per_window * (
            t // self.window)
        return (row >= first) & (row <= self.ring_row(t))

    def rows_read(self, t):
        """(summary rows, ring rows) a query at depth t attends."""
        return (self.chunks_per_window * (t // self.window),
                t % self.window + 1)

    def rows_walked(self, t, group_rows):
        """Rows the paged walk fetches for that query: whole groups of
        `group_rows` rows from the first live one to the last."""
        summaries, _ = self.rows_read(t)
        first = (self.summary_rows - summaries) // group_rows
        last = self.ring_row(t) // group_rows
        return (last - first + 1) * group_rows

    def pages(self, tokens, page_size):
        """(ring pages, summary pages) a request that writes `tokens`
        positions holds for its life: the ring is reused in place, and
        every window begun keeps its summaries."""
        ring = -(-min(tokens, self.window) // page_size)
        windows = -(-tokens // self.window)
        return ring, windows * self.chunks_per_window // page_size

    def page_vec(self, page_ids, page_size):
        """A full-width page-table row for a request's reserved
        `page_ids` (`pages(tokens)` of them, ring pages first; the
        count alone says how many are which): the ring's at the
        ring's rows, the summaries' just below them, scratch (0)
        everywhere else."""
        a_window = self.chunks_per_window // page_size
        whole_ring = self.window // page_size
        n = len(page_ids)
        ring = (max(n - a_window, 0) if n <= whole_ring + a_window
                else whole_ring)
        base = self.summary_rows // page_size
        vec = np.zeros((self.rows // page_size,), np.int32)
        vec[base:base + ring] = page_ids[:ring]
        vec[base - (n - ring):base] = page_ids[ring:]
        return vec


__all__ = ["EvaLayout", "chunk_summaries"]
