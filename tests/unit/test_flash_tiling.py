"""The flash kernels' tiles, spans and schedule (`ops.attention.flash_plan`):
what the shape rule chooses, that neither the grid nor the in-kernel walk
visits a tile with no visible entry, and that the walk built from it matches
the oracle at toy sizes. (The wider numeric sweep is test_ops.py, slow tier.)
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.ops import flash_attention, mha_reference
from cloud_tpu.ops.attention import flash_plan

# `cloud_tpu.ops.attention` the attribute is the dispatcher function.
attention_lib = importlib.import_module("cloud_tpu.ops.attention")


def _visible(seq_pad, causal, window):
    rows = np.arange(seq_pad)[:, None]
    cols = np.arange(seq_pad)[None, :]
    seen = np.ones((seq_pad, seq_pad), bool)
    if causal:
        seen &= cols <= rows
        if window:
            seen &= cols > rows - window
    return seen


def _tiles_with_an_entry(seen, rows, cols):
    blocks = seen.reshape(seen.shape[0] // rows, rows,
                          seen.shape[1] // cols, cols)
    return int(blocks.any(axis=(1, 3)).sum())


# (seq, head_dim, group, window, block_q, block_k)
_SHAPES = [
    (1024, 64, 7, 0, None, None),
    (4096, 128, 8, 0, None, None),
    (4096, 128, 8, 128, None, None),
    (16384, 128, 4, 0, None, None),    # k/v do not fit: several k spans
    (16384, 128, 4, 1024, None, None),
    (1100, 64, 2, 0, None, None),
    (640, 64, 1, 200, None, None),
    (512, 32, 2, 0, 64, 128),
    (512, 32, 2, 96, 128, 32),
    (300, 32, 1, 0, None, None),
]


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("shape", _SHAPES, ids=lambda s: "-".join(
    str(x) for x in s))
def test_no_dead_step_and_no_dead_tile(shape, backward):
    """total == live, twice: the grid's span pairs and the score tiles
    the in-kernel walk visits are exactly those a dense mask shows to
    hold a visible entry — for causal and for window shapes."""
    seq, head_dim, group, window, block_q, block_k = shape
    plan = flash_plan(seq, head_dim, group, 2, True, window, block_q,
                      block_k)
    if backward:
        plan = flash_plan(plan.seq_pad, head_dim, group, 2, True, window,
                          plan.block_q, block_k, backward=True)
    assert plan.seq_pad >= seq
    assert plan.seq_pad % plan.span_q == plan.seq_pad % plan.span_k == 0
    assert plan.span_q % plan.block_q == plan.span_k % plan.block_k == 0
    seen = _visible(plan.seq_pad, True, window)
    count = plan.tiles(True, window)
    assert count["walked"] == count["live"] == _tiles_with_an_entry(
        seen, plan.block_q, plan.block_k)
    assert count["pairs"] == count["pairs_live"] == _tiles_with_an_entry(
        seen, plan.span_q, plan.span_k)
    assert count["walked"] <= count["dense"]


def test_non_causal_walks_the_square():
    plan = flash_plan(640, 64, causal=False)
    count = plan.tiles(causal=False)
    assert count["walked"] == count["live"] == count["dense"]


def test_transposed_bounds_match_the_forward_ones():
    """The dk/dv walk (row blocks that see a k block) visits the same
    set of tiles as the forward / dq walk (k blocks a row block sees)."""
    for window in (0, 48, 200):
        for block_q, block_k in ((32, 64), (64, 32), (64, 64)):
            rows, cols = 512 // block_q, 512 // block_k
            forward = {(i, j) for i in range(rows) for j in range(
                *attention_lib._live_blocks(i * block_q, block_q, block_k,
                                            0, cols, True, window))}
            transposed = {(i, j) for j in range(cols) for i in range(
                *attention_lib._live_blocks(j * block_k, block_k, block_q,
                                            0, rows, True, window,
                                            transposed=True))}
            assert forward == transposed


# The benchmark's three shapes, pinned: (plan fields, pairs), tiles a q
# head, grid steps a call — forward, then backward.
_CELLS = {
    "qwen25_train": dict(
        shape=(1024, 64, 7, 0), batch=4, kv_heads=2,
        forward=((256, 1024, 1024, 1024, 1024), 4, 4, 8),
        backward=((256, 256, 1024, 1024, 1024), 10, 16, 8)),
    "kexaone_prefill_full": dict(
        shape=(4096, 128, 8, 0), batch=1, kv_heads=8,
        forward=((256, 1024, 512, 4096, 4096), 40, 64, 64),
        backward=((256, 256, 512, 4096, 4096), 136, 256, 64)),
    "kexaone_prefill_window": dict(
        shape=(4096, 128, 8, 128), batch=1, kv_heads=8,
        forward=((128, 128, 512, 4096, 4096), 63, 1024, 64),
        backward=((128, 128, 512, 4096, 4096), 63, 1024, 64)),
}


@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_cell_shapes_pinned(cell):
    spec = _CELLS[cell]
    seq, head_dim, group, window = spec["shape"]
    plan = flash_plan(seq, head_dim, group, 2, True, window)
    for name, plan in (("forward", plan), ("backward", flash_plan(
            plan.seq_pad, head_dim, group, 2, True, window, plan.block_q,
            backward=True))):
        fields, walked, dense, steps = spec[name]
        assert plan[:5] == fields, name
        count = plan.tiles(True, window)
        assert (count["walked"], count["dense"]) == (walked, dense), name
        assert set(plan.grid_steps(spec["batch"],
                                   spec["kv_heads"]).values()) == {steps}
    # The 128 x 128 grid this replaced issued a step a tile and q head.
    old_steps = spec["batch"] * spec["kv_heads"] * group * (seq // 128) ** 2
    assert old_steps // steps in (448, 1024)


def test_explicit_tiles_win_and_must_divide():
    assert flash_plan(512, 64, block_q=128, block_k=256)[:2] == (128, 256)
    assert flash_plan(512, 64, block_q=64)[:2] == (64, 512)
    with pytest.raises(ValueError, match="divide"):
        flash_plan(512, 64, block_q=192)
    with pytest.raises(ValueError, match="divide"):
        flash_plan(512, 64, block_q=96, block_k=64)


def test_flash_block_environment_names_are_gone(monkeypatch):
    """The tiles are the shape rule's; no process-wide pin is read."""
    monkeypatch.setenv("CLOUD_TPU_FLASH_BLOCK_Q", "192")
    monkeypatch.setenv("CLOUD_TPU_FLASH_BLOCK_K", "64")
    assert flash_plan(512, 64)[:2] == (256, 512)
    q, k, v = (jnp.ones((1, 64, 1, 16), jnp.float32),) * 3
    flash_attention(q, k, v, interpret=True)


def _qkv(seq, heads, kv_heads, head_dim, seed=0):
    rng = np.random.default_rng(seed)
    make = lambda h: jnp.asarray(
        rng.normal(size=(1, seq, h, head_dim)), jnp.float32)
    return make(heads), make(kv_heads), make(kv_heads)


_WALKS = {
    "several_row_and_k_blocks": dict(seq=128, heads=2, kv_heads=2,
                                     block_q=32, block_k=32),
    "group_of_heads_share_a_step": dict(seq=96, heads=6, kv_heads=2,
                                        block_q=32, block_k=16),
    "ragged_sequence": dict(seq=75, heads=2, kv_heads=1, block_q=16,
                            block_k=32),
    "window_band": dict(seq=128, heads=2, kv_heads=1, window=24,
                        block_q=16, block_k=16),
    "non_causal": dict(seq=75, heads=2, kv_heads=2, causal=False,
                       block_q=32, block_k=32),
    "several_spans_on_the_grid": dict(seq=128, heads=4, kv_heads=2,
                                      block_q=16, block_k=16,
                                      vmem_budget=96 * 1024),
    "several_spans_under_a_window": dict(seq=128, heads=2, kv_heads=2,
                                         window=40, block_q=16,
                                         block_k=16,
                                         vmem_budget=96 * 1024),
}


@pytest.mark.parametrize("case", sorted(_WALKS))
def test_walk_matches_reference(case, monkeypatch):
    """Forward and gradients through the scheduled walk at toy sizes
    (interpret mode), including a VMEM budget so small that q and k/v
    take several spans and the accumulators park between grid steps."""
    spec = dict(_WALKS[case])
    seq, heads, kv_heads = (spec.pop(n) for n in ("seq", "heads",
                                                  "kv_heads"))
    budget = spec.pop("vmem_budget", None)
    if budget:
        monkeypatch.setattr(attention_lib, "_VMEM_BUDGET", budget)
        plan = flash_plan(seq, 16, heads // kv_heads, 4,
                          window=spec.get("window", 0),
                          block_q=spec["block_q"],
                          block_k=spec["block_k"])
        assert plan.span_q < plan.seq_pad and plan.span_k < plan.seq_pad
        assert len(plan.pairs) > 2
    spec.setdefault("causal", True)
    q, k, v = _qkv(seq, heads, kv_heads, 16)
    g = jnp.asarray(np.random.default_rng(1).normal(size=q.shape),
                    jnp.float32)
    oracle = {n: spec[n] for n in ("causal", "window") if n in spec}
    got = jax.value_and_grad(lambda *a: jnp.sum(flash_attention(
        *a, interpret=True, **spec) * g), (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: jnp.sum(mha_reference(
        *a, **oracle) * g), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for name, a, b in zip("qkv", got[1], want[1]):
        np.testing.assert_allclose(
            a, b, atol=5e-5, rtol=5e-5,
            err_msg="{}: grad wrt {}".format(case, name))


def test_all_masked_rows_are_zero_with_zero_gradients():
    """A row with no visible key (its keys all masked out) outputs
    zeros and sends no gradient: the mask value never becomes a
    probability (`_unmasked_floor`)."""
    q, k, v = _qkv(64, 2, 1, 16)
    mask = jnp.asarray(np.arange(64)[None, :] >= 20)  # rows < 20: none
    out, grads = jax.value_and_grad(lambda *a: jnp.sum(flash_attention(
        *a, mask=mask, block_q=16, block_k=16, interpret=True) ** 2),
        (0, 1, 2))(q, k, v)
    ref = mha_reference(q, k, v, mask=mask)
    got = flash_attention(q, k, v, mask=mask, block_q=16, block_k=16,
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(got[:, :20]), 0.0)
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(grads[0][:, :20]), 0.0)
    assert np.isfinite(out) and all(
        np.isfinite(np.asarray(x)).all() for x in grads)
