"""Flash-attention kernel vs the jnp reference (interpret mode on CPU).

Mirrors the reference's golden-test style (exact-artifact pinning,
reference core/tests/unit/*) applied to numerics: the Pallas kernel must
match the pure-jnp oracle for forward and all three gradients, across
causal/non-causal and padded (non-block-multiple) sequence lengths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # numeric-heavy: excluded from the fast tier

from cloud_tpu.ops import attention, flash_attention, mha_reference
from cloud_tpu.ops.attention import flash_plan

TOL = 2e-5


def _qkv(batch=1, seq=256, heads=2, head_dim=64, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.normal(size=(batch, seq, heads, head_dim)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    q, k, v = _qkv(seq=128)
    g = jnp.asarray(
        np.random.default_rng(1).normal(size=q.shape), jnp.float32)

    def flash_loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, interpret=True) * g)

    def ref_loss(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) * g)

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(
            a, b, atol=5e-5, rtol=5e-5,
            err_msg="grad wrt {} diverges".format(name))


def test_padded_sequence_forward_and_grad():
    # 200 is not a multiple of the 128 block: exercises the padding path.
    q, k, v = _qkv(seq=200)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=TOL)

    got = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(
        lambda q, k, v: jnp.sum(mha_reference(q, k, v, causal=True)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


def test_short_sequence_pads_up_to_one_block():
    q, k, v = _qkv(seq=48)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=TOL)


def test_custom_scale():
    q, k, v = _qkv(seq=128)
    out = flash_attention(q, k, v, causal=False, sm_scale=0.5,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=False, sm_scale=0.5)
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=TOL)


def test_dispatcher_reference_on_cpu_and_mask_rules():
    q, k, v = _qkv(seq=64)
    # auto on CPU -> reference path.
    out = attention(q, k, v, causal=True, impl="auto")
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=TOL)
    with pytest.raises(ValueError):
        attention(q, k, v, impl="bogus")


@pytest.mark.parametrize("causal", [True, False])
def test_masked_forward_matches_reference(causal):
    """Per-example padding masks stay on the flash path and match the
    masked reference exactly."""
    q, k, v = _qkv(batch=3, seq=256)
    lengths = [256, 130, 77]  # full, partial-block, sub-block
    mask = np.zeros((3, 256), bool)
    for b, n in enumerate(lengths):
        mask[b, :n] = True
    mask = jnp.asarray(mask)
    out = flash_attention(q, k, v, causal=causal, mask=mask,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=causal, mask=mask)
    # ALL rows compare — since round 4 the reference adopts the
    # kernel's fully-masked-rows-output-zeros convention, so kernel
    # and oracle agree on every row (padded query rows still see the
    # valid keys, so they carry real — identical — values).
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=TOL, rtol=TOL)


def test_masked_non_contiguous_mask():
    """Arbitrary (scattered) key masks, not just padding prefixes."""
    q, k, v = _qkv(batch=2, seq=128)
    rng = np.random.default_rng(3)
    mask = jnp.asarray(rng.random((2, 128)) > 0.3)
    out = flash_attention(q, k, v, causal=False, mask=mask,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=False, mask=mask)
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=TOL)


def test_masked_gradients_match_reference():
    q, k, v = _qkv(batch=2, seq=128)
    mask_np = np.zeros((2, 128), bool)
    mask_np[0, :128] = True
    mask_np[1, :90] = True
    mask = jnp.asarray(mask_np)
    # Full (unmasked) cotangent: kernel and reference agree on every
    # row since the round-4 convention unification, so the grad parity
    # check covers padded query rows too.
    g = jnp.asarray(
        np.random.default_rng(4).normal(size=q.shape), jnp.float32)

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, mask=mask,
                                       interpret=True) * g)

    def ref_loss(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True, mask=mask) * g)

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(
            a, b, atol=5e-5, rtol=5e-5,
            err_msg="masked grad wrt {} diverges".format(name))


def test_masked_multi_head_mask_broadcast():
    """The [B, S] mask must apply to every head of its example (the
    kernel indexes the mask by program_id // heads)."""
    q, k, v = _qkv(batch=2, seq=128, heads=4)
    mask_np = np.zeros((2, 128), bool)
    mask_np[0, :50] = True
    mask_np[1, :128] = True
    mask = jnp.asarray(mask_np)
    out = flash_attention(q, k, v, causal=False, mask=mask,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=False, mask=mask)
    np.testing.assert_allclose(out[0, :50], ref[0, :50], atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(out[1], ref[1], atol=TOL, rtol=TOL)


def test_jit_wrapped():
    q, k, v = _qkv(seq=128)
    fn = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True))
    np.testing.assert_allclose(
        fn(q, k, v), mha_reference(q, k, v, causal=True),
        atol=TOL, rtol=TOL)


# ---------------------------------------------------------------------------
# Grouped-query attention (GQA): k/v with fewer heads than q
# ---------------------------------------------------------------------------


def _gqa_qkv(batch=2, seq=128, heads=4, kv_heads=2, head_dim=32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(
        rng.normal(size=(batch, seq, heads, head_dim)), jnp.float32)
    k = jnp.asarray(
        rng.normal(size=(batch, seq, kv_heads, head_dim)), jnp.float32)
    v = jnp.asarray(
        rng.normal(size=(batch, seq, kv_heads, head_dim)), jnp.float32)
    return q, k, v


def _expand(x, heads):
    return jnp.repeat(x, heads // x.shape[2], axis=2)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kv_heads", [1, 2])
def test_gqa_forward_matches_expanded(causal, kv_heads):
    """Native GQA == explicitly repeating kv heads (MQA at kv_heads=1)."""
    q, k, v = _gqa_qkv(kv_heads=kv_heads)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = mha_reference(q, _expand(k, 4), _expand(v, 4), causal=causal)
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("kv_heads", [1, 2])
def test_gqa_gradients_match_expanded(kv_heads):
    """dk/dv at H_kv width must equal autodiff through an explicit
    repeat (which sums each group's contributions) — the kernel does
    that sum in its VMEM accumulator over the fused (group, q-block)
    grid dim."""
    q, k, v = _gqa_qkv(seq=64, kv_heads=kv_heads)
    g = jnp.asarray(
        np.random.default_rng(1).normal(size=q.shape), jnp.float32)

    def flash_loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, interpret=True) * g)

    def ref_loss(q, k, v):
        return jnp.sum(
            mha_reference(q, _expand(k, 4), _expand(v, 4),
                          causal=True) * g)

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            a, b, atol=5e-5, rtol=5e-5,
            err_msg="GQA grad wrt {} diverges".format(name))


def test_gqa_masked_and_padded():
    """GQA composes with the key-mask fast path and non-block-multiple
    sequence lengths."""
    q, k, v = _gqa_qkv(seq=100)
    mask_np = np.zeros((2, 100), bool)
    mask_np[0, :37] = True
    mask_np[1, :] = True
    mask = jnp.asarray(mask_np)
    out = flash_attention(q, k, v, causal=True, mask=mask, interpret=True)
    ref = mha_reference(q, _expand(k, 4), _expand(v, 4), causal=True,
                        mask=mask)
    np.testing.assert_allclose(out[0, :37], ref[0, :37], atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(out[1], ref[1], atol=TOL, rtol=TOL)


def test_gqa_reference_handles_fewer_kv_heads():
    q, k, v = _gqa_qkv(seq=64)
    ref = mha_reference(q, k, v, causal=True)
    exp = mha_reference(q, _expand(k, 4), _expand(v, 4), causal=True)
    np.testing.assert_allclose(ref, exp, atol=TOL, rtol=TOL)


def test_gqa_shape_validation():
    q, k, v = _gqa_qkv(heads=4, kv_heads=3)
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, k, v, interpret=True)
    q, k, v = _gqa_qkv()
    with pytest.raises(ValueError, match="identical"):
        flash_attention(q, k, v[:, :, :1], interpret=True)
    # The oracle validates the same way (round-2 advisor finding: a
    # mismatched v used to die later as an opaque einsum shape error).
    with pytest.raises(ValueError, match="identical"):
        mha_reference(q, k, v[:, :, :1])


def test_shape_chosen_tiles_and_explicit_overrides():
    """The default tiles come from the shapes (`flash_plan`), explicit
    `block_q=` / `block_k=` still win, and neither changes numerics; a
    pair that does not divide still raises."""
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 512, 2, 64)),
                           jnp.float32) for _ in range(3))
    ref = mha_reference(q, k, v, causal=True)
    assert flash_plan(512, 64)[:4] == (256, 512, 512, 512)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    out2 = flash_attention(q, k, v, causal=True, interpret=True,
                           block_q=128, block_k=256)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k, v, causal=True, interpret=True,
                        block_q=192)


def _loss_pair(g, expand=None, **kwargs):
    """(flash loss, reference loss) over q, k, v for cotangent `g`."""
    oracle = {name: value for name, value in kwargs.items()
              if name not in ("block_q", "block_k")}

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=True,
                                       **kwargs) * g)

    def ref_loss(q, k, v):
        if expand:
            k, v = _expand(k, expand), _expand(v, expand)
        return jnp.sum(mha_reference(q, k, v, **oracle) * g)
    return flash_loss, ref_loss


_TILING_CASES = {
    # (a) the shape-chosen 256 x 512 tiles: 4 row blocks x 2 k blocks.
    "default_tiles_multi_block": dict(seq=1024, heads=2, kv_heads=2,
                                      head_dim=16),
    # (b) the cells' groups at their head sizes.
    "group7_d64": dict(seq=256, heads=7, kv_heads=1, head_dim=64,
                       block_q=64, block_k=128),
    "group8_d128": dict(seq=256, heads=8, kv_heads=1, head_dim=128,
                        block_q=128, block_k=64),
    # (d) S not a multiple of the tile, default and forced tiles.
    "ragged_default": dict(seq=300, heads=4, kv_heads=2, head_dim=32),
    "ragged_small_tiles": dict(seq=200, heads=4, kv_heads=2,
                               head_dim=32, block_q=32, block_k=64),
    # (e) non-causal: every tile is live, the pad columns are masked.
    "non_causal": dict(seq=200, heads=2, kv_heads=2, head_dim=32,
                       causal=False, block_q=64, block_k=32),
    "window_band": dict(seq=256, heads=4, kv_heads=2, head_dim=32,
                        window=40, block_q=32, block_k=32),
    "window_default_tiles": dict(seq=512, heads=2, kv_heads=1,
                                 head_dim=32, window=128),
}


@pytest.mark.parametrize("case", sorted(_TILING_CASES))
def test_tiling_forward_and_gradients(case):
    """Forward and all three gradients against the oracle at toy sizes
    that force several row blocks and several k blocks a walk."""
    spec = dict(_TILING_CASES[case])
    seq, heads, kv_heads, head_dim = (spec.pop(name) for name in (
        "seq", "heads", "kv_heads", "head_dim"))
    spec.setdefault("causal", True)
    q, k, v = _gqa_qkv(batch=1, seq=seq, heads=heads,
                       kv_heads=kv_heads, head_dim=head_dim)
    g = jnp.asarray(
        np.random.default_rng(1).normal(size=q.shape), jnp.float32)
    flash_loss, ref_loss = _loss_pair(g, expand=heads, **spec)
    got = jax.value_and_grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for name, a, b in zip("qkv", got[1], want[1]):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            a, b, atol=1e-4, rtol=1e-4,
            err_msg="{}: grad wrt {} diverges".format(case, name))


@pytest.mark.parametrize("window", [None, 24])
def test_prefill_frame_with_key_mask(window):
    """(c) The serving prefill's use (`GQAttention._decode_attention`):
    the prompt's queries laid at their rows of a longer frame, the
    cache's key mask, a window band scaled down; only the prompt's rows
    are read."""
    frame, start, prompt = 256, 37, 150
    q, k, v = _gqa_qkv(batch=2, seq=frame, heads=8, kv_heads=2,
                       head_dim=32)
    rows = np.arange(frame)
    valid = jnp.asarray(
        np.stack([(rows >= start) & (rows < start + prompt),
                  rows < start + prompt]))
    q = jnp.where(valid[:, :, None, None], q, 0.0)
    out = flash_attention(q, k, v, causal=True, mask=valid,
                          window=window, block_q=32, block_k=64,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=True, mask=valid, window=window)
    np.testing.assert_allclose(out[:, start:start + prompt],
                               ref[:, start:start + prompt],
                               atol=TOL, rtol=TOL)


class TestSlidingWindow:
    """window=: banded causal attention (Mistral convention — row i
    attends keys in (i-window, i]). The reference is checked against a
    dense explicit-band oracle; the kernel against the reference,
    including the walk's band bounds (_live_blocks) at window widths that
    kill whole tiles."""

    def _dense_band(self, q, k, v, window):
        seq = q.shape[1]
        scale = 1.0 / np.sqrt(q.shape[-1])
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        row = jnp.arange(seq)[:, None]
        col = jnp.arange(seq)[None, :]
        allowed = (col <= row) & (col > row - window)
        logits = jnp.where(allowed, logits, -1e30)
        weights = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

    @pytest.mark.parametrize("window", [1, 17, 128, 300])
    def test_reference_matches_dense_band(self, window):
        q, k, v = _qkv()
        ref = mha_reference(q, k, v, causal=True, window=window)
        oracle = self._dense_band(q, k, v, window)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(oracle),
                                   atol=TOL, rtol=TOL)

    @pytest.mark.parametrize("window", [1, 17, 128, 300])
    def test_flash_matches_reference(self, window):
        q, k, v = _qkv()
        ref = mha_reference(q, k, v, causal=True, window=window)
        out = flash_attention(q, k, v, causal=True, window=window,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=TOL, rtol=TOL)

    def test_flash_gradients_match_reference(self):
        q, k, v = _qkv(seed=3)
        window = 48

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, causal=True, window=window,
                                   interpret=True).sum()

        def loss_ref(q, k, v):
            return mha_reference(q, k, v, causal=True,
                                 window=window).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_window_with_gqa_and_key_mask(self):
        q, _, _ = _qkv(batch=2, heads=4, seed=4)
        rng = np.random.default_rng(5)
        k, v = (jnp.asarray(rng.normal(size=(2, 256, 2, 64)),
                            jnp.float32) for _ in range(2))
        mask = jnp.asarray(
            np.arange(256)[None, :] < np.array([[256], [200]]))
        ref = mha_reference(q, k, v, causal=True, window=32, mask=mask)
        out = flash_attention(q, k, v, causal=True, window=32,
                              mask=mask, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=TOL, rtol=TOL)

    def test_window_requires_causal(self):
        q, k, v = _qkv(seq=128)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, causal=False, window=8,
                            interpret=True)
        with pytest.raises(ValueError, match="causal"):
            mha_reference(q, k, v, causal=False, window=8)

    def test_dispatcher_forwards_window(self):
        q, k, v = _qkv(seq=128)
        ref = mha_reference(q, k, v, causal=True, window=16)
        out = attention(q, k, v, causal=True, window=16, impl="flash")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=TOL, rtol=TOL)


class TestLogitSoftcap:
    """logit_softcap=: Gemma2-style tanh capping, cap * tanh(s / cap)
    applied after the softmax scale and before masking. The reference
    is checked against a dense explicit oracle; the kernel against the
    reference, forward and gradients (the backward kernels fold the
    tanh derivative into dS)."""

    def _dense_capped(self, q, k, v, cap, causal=True):
        seq = q.shape[1]
        scale = 1.0 / np.sqrt(q.shape[-1])
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        logits = cap * jnp.tanh(logits / cap)
        if causal:
            row = jnp.arange(seq)[:, None]
            col = jnp.arange(seq)[None, :]
            logits = jnp.where(col <= row, logits, -1e30)
        weights = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

    @pytest.mark.parametrize("cap", [5.0, 50.0])
    def test_reference_matches_dense_oracle(self, cap):
        q, k, v = _qkv()
        ref = mha_reference(q, k, v, causal=True, logit_softcap=cap)
        oracle = self._dense_capped(q, k, v, cap)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(oracle),
                                   atol=TOL, rtol=TOL)

    @pytest.mark.parametrize("causal", [True, False])
    def test_flash_matches_reference(self, causal):
        q, k, v = _qkv()
        ref = mha_reference(q, k, v, causal=causal, logit_softcap=30.0)
        out = flash_attention(q, k, v, causal=causal, logit_softcap=30.0,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=TOL, rtol=TOL)

    def test_flash_gradients_match_reference(self):
        # A small cap actually bends the logits (|s| ~ a few at d=64),
        # so the tanh derivative factor in dS is truly exercised.
        q, k, v = _qkv(seed=3, seq=128)
        cap = 3.0

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, causal=True,
                                   logit_softcap=cap,
                                   interpret=True).sum()

        def loss_ref(q, k, v):
            return mha_reference(q, k, v, causal=True,
                                 logit_softcap=cap).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_softcap_with_gqa_mask_and_custom_scale(self):
        q, _, _ = _qkv(batch=2, heads=4, seed=4)
        rng = np.random.default_rng(5)
        k, v = (jnp.asarray(rng.normal(size=(2, 256, 2, 64)),
                            jnp.float32) for _ in range(2))
        mask = jnp.asarray(
            np.arange(256)[None, :] < np.array([[256], [200]]))
        kwargs = dict(causal=True, logit_softcap=10.0, sm_scale=0.2,
                      mask=mask)
        ref = mha_reference(q, k, v, **kwargs)
        out = flash_attention(q, k, v, interpret=True, **kwargs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=TOL, rtol=TOL)

    def test_dispatcher_forwards_softcap(self):
        q, k, v = _qkv(seq=128)
        ref = mha_reference(q, k, v, causal=True, logit_softcap=20.0)
        out = attention(q, k, v, causal=True, logit_softcap=20.0,
                        impl="flash")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=TOL, rtol=TOL)
