"""EVA attention (`models/evabyte.py`, `ops/eva.py`) at toy widths, float32:
the module's whole-sequence form against the plain reference
(`cellbench/reference/evabyte.py`), the two identities that tie it to plain
causal attention, the chunk summary against a hand-written softmax, and the
layout's arithmetic at the cell's numbers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.reference import common as ref
from cellbench.reference import evabyte as reference
from cloud_tpu import ops
from cloud_tpu.models import EvaByteLM
from cloud_tpu.ops.eva import EvaLayout, chunk_summaries

F32 = jnp.float32
TOY = dict(vocab_size=64, num_layers=2, num_heads=2, d_model=32, d_ff=64,
           max_seq_len=128, window_size=32, chunk_size=4, num_pred_heads=3,
           compute_dtype=F32)
CFG = dict(window_size=32, chunk_size=4, rms_norm_eps=1e-5, rope_theta=100000.0,
           num_pred_heads=3, vocab_size=64)


def seeded(model, tokens, vectors=30.0):
    """Parameters with `phi` and `mu` scaled up, so that the in-chunk
    weights are far from uniform."""
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * vectors if str(path[-1].key) in ("phi", "mu")
        else x, params)


def reference_logits(params, cfg, tokens):
    """Every head's logits of the plain reference: [T, heads, V]."""
    mm = ref.make_mm("float32")
    x = reference.embed(params, tokens, cfg)
    for name in reference.layer_names(params):
        x = reference.layer(x, params[name], cfg, mm)
    return reference.all_heads(x, reference.head_params(params), cfg, mm)[0]


# 3+ windows with a ragged tail, a whole number of windows, under a window.
@pytest.mark.parametrize("length", [107, 96, 21])
def test_sequence_form_is_the_reference(length):
    model = EvaByteLM(all_heads=True, **TOY)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, length), 0, 64)
    params = seeded(model, tokens)
    got = model.apply({"params": params}, tokens)[0]
    want = reference_logits(params, CFG, tokens)
    assert got.shape == (length, 3, 64)
    np.testing.assert_allclose(got, want, atol=2e-5)
    head0 = EvaByteLM(**TOY).apply({"params": params}, tokens)[0]
    np.testing.assert_array_equal(head0, got[:, 0])


def plain_causal(model, params, tokens):
    """The same weights through plain causal attention: a window as long as
    the sequence has nothing behind it."""
    wide = model.clone(window_size=model.max_seq_len)
    return wide.apply({"params": params}, tokens)


@pytest.mark.parametrize("case", ["within_a_window", "chunks_of_one"])
def test_identities_with_plain_causal_attention(case):
    """For T <= W, EVA is plain causal attention whatever phi and mu are; with
    C = 1 and mu = 0 every chunk is one token (k~ = k, v~ = v) and EVA is
    plain causal attention at any T and W, whatever phi is."""
    if case == "within_a_window":
        model, length = EvaByteLM(**TOY), 32
    else:
        model, length = EvaByteLM(**dict(TOY, chunk_size=1)), 107
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, length), 0, 64)
    params = seeded(model, tokens)
    if case == "chunks_of_one":
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: x * 0 if str(path[-1].key) == "mu" else x, params)
    np.testing.assert_allclose(model.apply({"params": params}, tokens),
                               plain_causal(model, params, tokens), atol=2e-5)
    if case == "chunks_of_one":
        moved = jax.tree_util.tree_map_with_path(
            lambda path, x: -3 * x if str(path[-1].key) == "phi" else x,
            params)
        np.testing.assert_allclose(model.apply({"params": moved}, tokens),
                                   model.apply({"params": params}, tokens),
                                   atol=2e-5)


def test_chunk_summaries_against_a_hand_written_softmax():
    rng = np.random.default_rng(0)
    k, v = rng.normal(size=(2, 3, 5, 4, 2, 8)).astype(np.float32)
    phi, mu = rng.normal(size=(2, 2, 8)).astype(np.float32)
    got_k, got_v = chunk_summaries(jnp.asarray(k), jnp.asarray(v),
                                   jnp.asarray(phi), jnp.asarray(mu))
    assert ops.chunk_summaries is chunk_summaries
    assert got_k.shape == got_v.shape == (3, 5, 2, 8)
    for b, c, h in [(0, 0, 0), (2, 4, 1), (1, 3, 0)]:
        scores = k[b, c, :, h] @ phi[h]
        a = np.exp(scores - scores.max())
        a /= a.sum()
        np.testing.assert_allclose(got_k[b, c, h], a @ k[b, c, :, h] + mu[h],
                                   atol=1e-5)
        np.testing.assert_allclose(got_v[b, c, h], a @ v[b, c, :, h], atol=1e-5)


def test_layout_at_the_cells_numbers():
    lay = EvaLayout(window=2048, chunk=16, max_seq_len=32768)
    lay.check(16)
    assert (lay.summary_rows, lay.rows, lay.chunks_per_window) == (2048, 4096, 128)
    # At depth p a query reads 128 floor(p / 2048) + (p mod 2048) + 1 rows:
    # at most 1920 + 2048 at 32768, where full attention reads 32768.
    assert lay.rows_read(2048) == (128, 1)
    assert lay.rows_read(32767) == (1920, 2048)
    assert lay.rows_read(19200) == (128 * 9, 19200 % 2048 + 1)
    # One contiguous run: the first visible row to the query's own.
    for depth in (0, 5, 2047, 2048, 19200, 32767):
        seen = np.asarray(lay.visible(depth))
        rows = np.flatnonzero(seen)
        assert len(rows) == sum(lay.rows_read(depth))
        assert rows[-1] == lay.ring_row(depth) and (np.diff(rows) == 1).all()
    # The walk fetches whole groups of 8 pages (128 rows): its lower end is
    # exact (a window's summaries are a group), its upper rounds the ring up.
    assert lay.rows_walked(19200, 128) == 128 * 9 + 768 + 128
    assert lay.rows_walked(2047, 128) == 2048
    # 128 ring pages at most, 8 summary pages a window begun.
    assert lay.pages(100, 16) == (7, 8)
    assert lay.pages(2048, 16) == (128, 8)
    assert lay.pages(2049, 16) == (128, 16)
    assert lay.pages(28032 + 1535, 16) == (128, 120)
    vec = lay.page_vec(list(range(1, 24)), 16)      # 15 ring + 8 summary
    assert vec.shape == (256,) and list(vec[128:143]) == list(range(1, 16))
    assert list(vec[120:128]) == list(range(16, 24)) and vec[:120].sum() == 0
    vec = lay.page_vec(list(range(1, 145)), 16)     # 128 ring + 16 summary
    assert list(vec[112:128]) == list(range(129, 145)) and vec[128] == 1
    assert not lay.page_vec([], 16).any()
    with pytest.raises(ValueError, match="page is its chunk"):
        lay.check(8)
    with pytest.raises(ValueError, match="chunk . window"):
        EvaByteLM(**dict(TOY, window_size=30))
