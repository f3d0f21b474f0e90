"""Paged decode-attention kernel vs the gathered-lax reference.

The parity suite the serving tick's fused page gather rides on
(cloud_tpu/ops/paged_attention.py): the Pallas kernel in interpret
mode, the off-TPU lax page-walk form, and the gathered reference must
agree — across the plain seq=1 tick, the speculative seq=k+1 verify
window, shared/CoW donor pages, and the masking edge cases the engine
relies on (scratch page 0 never contributes; an evicted slot's rows
come out exact-zero from the kernel).

Interpret-mode pallas_call is orders of magnitude slower than lax, so
every shape here is tiny; the serving-scale behavior is pinned by the
smoke gates (serving/smoke.py) with CLOUD_TPU_PAGED_KERNEL=1.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# The ops package re-exports the `paged_attention` FUNCTION under the
# same name as this module, shadowing the package attribute — go
# through sys.modules for the module itself.
import cloud_tpu.ops.paged_attention  # noqa: F401  (registers module)

pa = sys.modules["cloud_tpu.ops.paged_attention"]

TOL = 2e-5


def _scenario(slots=3, pages_per_slot=4, page_size=16, heads=2,
              head_dim=64, seq=1, dtype=jnp.float32, seed=0):
    """A miniature engine cache: page 0 is the scratch page, slot i
    owns `pages_per_slot` distinct pages, per-slot positions stagger so
    the causal frontier crosses page boundaries."""
    rng = np.random.default_rng(seed)
    num_pages = slots * pages_per_slot + 1
    cache_len = pages_per_slot * page_size
    # Pool rows are [H*D] wide: one token's K (or V) for every head.
    shape = (num_pages, page_size, heads, head_dim)
    fold = lambda a: a.reshape(num_pages, page_size, heads * head_dim)
    key_pages = jnp.asarray(fold(rng.normal(size=shape)), dtype)
    value_pages = jnp.asarray(fold(rng.normal(size=shape)), dtype)
    q = jnp.asarray(rng.normal(size=(slots, seq, heads, head_dim)),
                    dtype)
    page_table = jnp.asarray(
        1 + np.arange(slots * pages_per_slot).reshape(
            slots, pages_per_slot), jnp.int32)
    # Slot s decodes at position pos_s; verify-window row t may attend
    # through pos_s + t (the engine's causal contract).
    pos = np.array([(7 + 11 * s) % (cache_len - seq) for s in
                    range(slots)])
    allowed = (np.arange(cache_len)[None, None, :]
               <= (pos[:, None] + np.arange(seq))[:, :, None])
    return q, key_pages, value_pages, page_table, jnp.asarray(allowed)


def _all_impls(q, kp, vp, pt, allowed):
    ref = pa.paged_attention_reference(q, kp, vp, pt, allowed)
    walk = pa._paged_walk_lax(q, kp, vp, pt, allowed,
                              1.0 / np.sqrt(q.shape[-1]))
    kern = pa.paged_decode_attention(q, kp, vp, pt, allowed,
                                     interpret=True)
    return ref, walk, kern


def test_plain_tick_parity():
    """seq=1 — the shape every non-speculative serving tick runs."""
    ref, walk, kern = _all_impls(*_scenario(seq=1))
    np.testing.assert_allclose(kern, ref, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(walk, ref, atol=TOL, rtol=TOL)


def test_verify_window_parity():
    """seq=k+1 (speculative verify window, here k=3): per-row causal
    frontier; rows are sublane-padded inside the kernel (4 -> 8) and
    the pad rows must never leak into the sliced output."""
    ref, walk, kern = _all_impls(*_scenario(seq=4))
    np.testing.assert_allclose(kern, ref, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(walk, ref, atol=TOL, rtol=TOL)


def test_walk_matches_interpret_kernel_tightly():
    """The lax page-walk is the kernel's off-TPU execution: same math,
    same page order, same online-softmax update sequence. It must track
    the interpret-mode kernel much tighter than either tracks the
    reference (which softmaxes in one pass)."""
    q, kp, vp, pt, allowed = _scenario(seq=4)
    walk = pa._paged_walk_lax(q, kp, vp, pt, allowed,
                              1.0 / np.sqrt(q.shape[-1]))
    kern = pa.paged_decode_attention(q, kp, vp, pt, allowed,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(walk), np.asarray(kern),
                               atol=1e-6, rtol=1e-6)


def test_bf16_parity():
    """bf16 pages (the serving dtype): kernel within bf16 resolution of
    the reference."""
    ref, walk, kern = _all_impls(*_scenario(seq=1, dtype=jnp.bfloat16))
    np.testing.assert_allclose(
        np.asarray(kern, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(
        np.asarray(walk, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2)


def test_shared_donor_pages():
    """graftshare CoW: a prefix-cache hit leaves multiple slots'
    page tables pointing at the SAME donor pages. The gather-free
    kernel must read shared pages identically to the reference."""
    q, kp, vp, pt, allowed = _scenario(slots=3, seq=1)
    pt = np.asarray(pt).copy()
    pt[1, :2] = pt[0, :2]  # slots 0 and 1 share two donor pages
    pt[2, 0] = pt[0, 0]    # three-way share of the first page
    pt = jnp.asarray(pt)
    ref, walk, kern = _all_impls(q, kp, vp, pt, allowed)
    np.testing.assert_allclose(kern, ref, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(walk, ref, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("impl_name", ["reference", "walk", "kernel"])
def test_scratch_page_never_contributes(impl_name):
    """Page 0 is the pool's scratch page: unallocated page-table tail
    entries point at it and their positions are always masked. Filling
    it with large finite garbage (NOT NaN — 0 * NaN = NaN would poison
    any impl) must not move a single output bit."""
    q, kp, vp, pt, allowed = _scenario(slots=2, pages_per_slot=3,
                                       seq=1)
    pt = np.asarray(pt).copy()
    pt[:, -1] = 0  # tail entries parked on the scratch page
    pt = jnp.asarray(pt)
    # Mask off everything the scratch page would back.
    allowed = np.asarray(allowed).copy()
    allowed[:, :, -16:] = False
    allowed = jnp.asarray(allowed)

    def run(kp):
        if impl_name == "reference":
            return pa.paged_attention_reference(q, kp, vp, pt, allowed)
        if impl_name == "walk":
            return pa._paged_walk_lax(q, kp, vp, pt, allowed,
                                      1.0 / np.sqrt(q.shape[-1]))
        return pa.paged_decode_attention(q, kp, vp, pt, allowed,
                                         interpret=True)

    clean = run(kp)
    garbage = run(kp.at[0].set(1e30))
    np.testing.assert_array_equal(np.asarray(clean),
                                  np.asarray(garbage))


def test_evicted_slot_outputs_exact_zeros():
    """An evicted/inactive slot has `allowed` all-False. The kernel and
    walk output EXACT zeros there (explicit p=where(mask,...,0)); the
    reference's one-pass softmax instead averages garbage uniformly.
    The engine never consumes those rows — this pins the intentional
    divergence so a refactor can't silently change it."""
    q, kp, vp, pt, allowed = _scenario(slots=3, seq=1)
    allowed = np.asarray(allowed).copy()
    allowed[1] = False  # slot 1 evicted
    allowed = jnp.asarray(allowed)
    walk = pa._paged_walk_lax(q, kp, vp, pt, allowed,
                              1.0 / np.sqrt(q.shape[-1]))
    kern = pa.paged_decode_attention(q, kp, vp, pt, allowed,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(walk)[1],
                                  np.zeros_like(np.asarray(walk)[1]))
    np.testing.assert_array_equal(np.asarray(kern)[1],
                                  np.zeros_like(np.asarray(kern)[1]))
    # Live slots still match the reference exactly as usual.
    ref = pa.paged_attention_reference(q, kp, vp, pt, allowed)
    for s in (0, 2):
        np.testing.assert_allclose(np.asarray(kern)[s],
                                   np.asarray(ref)[s],
                                   atol=TOL, rtol=TOL)


def test_impl_selection_off_tpu():
    """On CPU, impl='reference' (and 'auto'/'flash') is bitwise the
    gathered reference; impl='paged' is bitwise the lax page-walk."""
    q, kp, vp, pt, allowed = _scenario(seq=1)
    ref = pa.paged_attention_reference(q, kp, vp, pt, allowed)
    walk = pa._paged_walk_lax(q, kp, vp, pt, allowed,
                              1.0 / np.sqrt(q.shape[-1]))
    for impl in ("reference", "auto", "flash"):
        got = pa.paged_attention(q, kp, vp, pt, allowed, impl=impl)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    got = pa.paged_attention(q, kp, vp, pt, allowed, impl="paged")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(walk))


def test_env_override_beats_impl(monkeypatch):
    """CLOUD_TPU_PAGED_KERNEL is the deployment A/B switch: '0' forces
    the reference even under impl='paged'; '1' forces the kernel path
    even under impl='reference'."""
    q, kp, vp, pt, allowed = _scenario(seq=1)
    ref = pa.paged_attention_reference(q, kp, vp, pt, allowed)
    walk = pa._paged_walk_lax(q, kp, vp, pt, allowed,
                              1.0 / np.sqrt(q.shape[-1]))
    monkeypatch.setenv("CLOUD_TPU_PAGED_KERNEL", "0")
    got = pa.paged_attention(q, kp, vp, pt, allowed, impl="paged")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    monkeypatch.setenv("CLOUD_TPU_PAGED_KERNEL", "1")
    got = pa.paged_attention(q, kp, vp, pt, allowed, impl="reference")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(walk))


def test_shape_validation():
    q, kp, vp, pt, allowed = _scenario(seq=1)
    with pytest.raises(ValueError, match="allowed must be"):
        pa.paged_decode_attention(q, kp, vp, pt, allowed[:, :, :-1])
    with pytest.raises(ValueError, match="identical shapes"):
        pa.paged_decode_attention(q, kp, vp[:-1], pt, allowed)


# -- int8 quantized pages (graftpack, ISSUE 17) -----------------------


def _quantize_pages(pages, heads=2):
    """Per-page per-head symmetric int8 quantization — the same
    contract the engine's page-write paths use: scale = amax / 127 over
    the page's (positions, head_dim) block, dequant = int8 * scale. An
    all-zero (never-written) page gets scale 0 so it dequantizes to
    exact zeros."""
    folded = np.asarray(pages, np.float32)
    arr = folded.reshape(folded.shape[:2] + (heads, -1))
    amax = np.max(np.abs(arr), axis=(1, 3))          # [num_pages, H]
    scale = (amax / 127.0).astype(np.float32)
    safe = np.where(scale > 0, scale, 1.0)
    q = np.clip(np.rint(arr / safe[:, None, :, None]), -127, 127)
    return (jnp.asarray(q.reshape(folded.shape), jnp.int8),
            jnp.asarray(scale))


def _dequantize_pages(pages, scales):
    heads = scales.shape[1]
    arr = np.asarray(pages, np.float32)
    unfolded = arr.reshape(arr.shape[:2] + (heads, -1))
    return jnp.asarray((unfolded * np.asarray(scales)[:, None, :, None]
                        ).reshape(arr.shape))


def _int8_scenario(**kwargs):
    """A `_scenario` whose K/V pages are quantized to int8 + scales,
    plus the dequantized f32 pages every impl's output must match."""
    q, kp, vp, pt, allowed = _scenario(**kwargs)
    heads = q.shape[2]
    kq, ks = _quantize_pages(kp, heads)
    vq, vs = _quantize_pages(vp, heads)
    return (q, (kq, ks, _dequantize_pages(kq, ks)),
            (vq, vs, _dequantize_pages(vq, vs)), pt, allowed)


def _all_impls_int8(q, k3, v3, pt, allowed):
    kq, ks, _ = k3
    vq, vs, _ = v3
    ref = pa.paged_attention_reference(q, kq, vq, pt, allowed,
                                       key_scales=ks, value_scales=vs)
    walk = pa._paged_walk_lax(q, kq, vq, pt, allowed,
                              1.0 / np.sqrt(q.shape[-1]),
                              key_scales=ks, value_scales=vs)
    kern = pa.paged_decode_attention(q, kq, vq, pt, allowed,
                                     interpret=True, key_scales=ks,
                                     value_scales=vs)
    return ref, walk, kern


@pytest.mark.parametrize("seq", [1, 4])
def test_int8_parity_across_impls(seq):
    """Quantized pages: reference/walk/kernel must agree with each
    other AND with the fp reference run on the explicitly dequantized
    pages — the dequant must be mathematically inside the attention,
    not an approximation of it."""
    q, k3, v3, pt, allowed = _int8_scenario(seq=seq)
    ref, walk, kern = _all_impls_int8(q, k3, v3, pt, allowed)
    oracle = pa.paged_attention_reference(q, k3[2], v3[2], pt, allowed)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(oracle),
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(walk), np.asarray(oracle),
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(oracle),
                               atol=TOL, rtol=TOL)


def test_int8_shared_donor_pages():
    """CoW-shared donor pages carry ONE scale row per page — slots
    sharing a page must dequantize it identically."""
    q, k3, v3, pt, allowed = _int8_scenario(slots=3, seq=1)
    pt = np.asarray(pt).copy()
    pt[1, :2] = pt[0, :2]
    pt[2, 0] = pt[0, 0]
    pt = jnp.asarray(pt)
    ref, walk, kern = _all_impls_int8(q, k3, v3, pt, allowed)
    oracle = pa.paged_attention_reference(q, k3[2], v3[2], pt, allowed)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(oracle),
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(walk), np.asarray(oracle),
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(oracle),
                               atol=TOL, rtol=TOL)


def test_int8_zero_scale_page_is_exact_zero():
    """A never-written page carries scale 0: whatever int8 garbage the
    pool left in it must dequantize to exact zeros and (masked) move no
    output bit — the promote path relies on this for the scratch-padded
    page-table tail."""
    q, k3, v3, pt, allowed = _int8_scenario(slots=2, pages_per_slot=3,
                                            seq=1)
    kq, ks, _ = k3
    vq, vs, _ = v3
    pt = np.asarray(pt).copy()
    pt[:, -1] = 0  # tail parked on scratch page 0
    pt = jnp.asarray(pt)
    allowed = np.asarray(allowed).copy()
    allowed[:, :, -16:] = False
    allowed = jnp.asarray(allowed)

    def run(kq, ks):
        return pa.paged_decode_attention(q, kq, vq, pt, allowed,
                                         interpret=True, key_scales=ks,
                                         value_scales=vs)

    clean = run(kq, ks)
    garbage = run(kq.at[0].set(127), ks.at[0].set(0.0))
    np.testing.assert_array_equal(np.asarray(clean),
                                  np.asarray(garbage))


def test_int8_evicted_slot_outputs_exact_zeros():
    """The kernel/walk all-False-mask contract survives quantization:
    an evicted slot's rows are exact zeros, not dequant noise."""
    q, k3, v3, pt, allowed = _int8_scenario(slots=3, seq=1)
    allowed = np.asarray(allowed).copy()
    allowed[1] = False
    allowed = jnp.asarray(allowed)
    _, walk, kern = _all_impls_int8(q, k3, v3, pt, allowed)
    np.testing.assert_array_equal(np.asarray(walk)[1],
                                  np.zeros_like(np.asarray(walk)[1]))
    np.testing.assert_array_equal(np.asarray(kern)[1],
                                  np.zeros_like(np.asarray(kern)[1]))


def test_int8_scale_validation():
    """Both-or-neither scales; int8 pages required; [N, H] f32 shape."""
    q, kp, vp, pt, allowed = _scenario(seq=1)
    kq, ks = _quantize_pages(kp)
    vq, vs = _quantize_pages(vp)
    with pytest.raises(ValueError, match="given together"):
        pa.paged_decode_attention(q, kq, vq, pt, allowed,
                                  interpret=True, key_scales=ks)
    with pytest.raises(ValueError, match="int8 pages"):
        pa.paged_decode_attention(q, kp, vp, pt, allowed,
                                  interpret=True, key_scales=ks,
                                  value_scales=vs)
    with pytest.raises(ValueError, match="num_pages, heads"):
        pa.paged_decode_attention(q, kq, vq, pt, allowed,
                                  interpret=True, key_scales=ks[:-1],
                                  value_scales=vs)


def test_int8_dispatch_through_public_entrypoint():
    """paged_attention() forwards scales to whichever impl it picks."""
    q, k3, v3, pt, allowed = _int8_scenario(seq=1)
    kq, ks, _ = k3
    vq, vs, _ = v3
    ref = pa.paged_attention_reference(q, kq, vq, pt, allowed,
                                       key_scales=ks, value_scales=vs)
    walk = pa._paged_walk_lax(q, kq, vq, pt, allowed,
                              1.0 / np.sqrt(q.shape[-1]),
                              key_scales=ks, value_scales=vs)
    got = pa.paged_attention(q, kq, vq, pt, allowed, impl="reference",
                             key_scales=ks, value_scales=vs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    got = pa.paged_attention(q, kq, vq, pt, allowed, impl="paged",
                             key_scales=ks, value_scales=vs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(walk))


# -- the walk's groups and its live bound (ISSUE 27) --------------------

EDGE_PAGES = 19   # not a multiple of the group: the last one is partial
KINDS = ("f32", "bf16", "int8")


@pytest.fixture
def group_of_8(monkeypatch):
    """At toy widths every slot fits one group; a budget this small
    gives the cells' G = 8 (one 128-key lane tile of 16-token pages)."""
    monkeypatch.setattr(pa, "_VMEM_BUDGET", 300 * 1024)
    group = pa.group_pages(16, 2, 128, 4, 3, EDGE_PAGES)
    assert group == 8
    return group


def _edge_case(kind, seq, allowed_fn, page_table_fn=None, slots=2):
    """reference / walk / interpreted kernel, and the tolerance of the
    page type, over `EDGE_PAGES` pages a slot with the caller's mask."""
    dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
    make = _int8_scenario if kind == "int8" else _scenario
    q, k, v, pt, _ = make(slots=slots, pages_per_slot=EDGE_PAGES,
                          seq=seq, dtype=dtype, seed=5)
    cache_len = EDGE_PAGES * 16
    allowed = jnp.asarray(allowed_fn(np.arange(cache_len)[None, None, :],
                                     np.arange(seq)[None, :, None]))
    if page_table_fn is not None:
        pt = jnp.asarray(page_table_fn(np.asarray(pt).copy()))
    if kind == "int8":
        ref, walk, kern = _all_impls_int8(q, k, v, pt, allowed)
        ref = pa.paged_attention_reference(q, k[2], v[2], pt, allowed)
    else:
        ref, walk, kern = _all_impls(q, k, v, pt, allowed)
    tol = 2e-2 if kind == "bf16" else TOL
    as_f32 = lambda x: np.asarray(x, np.float32)
    return as_f32(ref), as_f32(walk), as_f32(kern), tol


@pytest.mark.parametrize("live", ["0", "1", "G-1", "G", "G+1", "all"])
@pytest.mark.parametrize("seq", [1, 3])
@pytest.mark.parametrize("kind", KINDS)
def test_depths_on_the_groups_edges(group_of_8, kind, seq, live):
    """Slot 0 is `live` pages deep (the frontier of its last query row
    one key short of the page's end), slot 1 ten pages: the walk ends
    where the slot does, whole groups before it and a part of one at
    it, and an evicted slot (0 pages) reads exact zeros."""
    pages = {"0": 0, "1": 1, "G-1": group_of_8 - 1, "G": group_of_8,
             "G+1": group_of_8 + 1, "all": EDGE_PAGES}[live]
    depth = np.array([max(pages * 16 - 1, 0), 10 * 16 - 5])
    frontier = lambda row: np.where(
        depth[:, None, None] > 0,
        depth[:, None, None] - (seq - 1) + row, 0)
    ref, walk, kern, tol = _edge_case(
        kind, seq, lambda key, row: key < frontier(row))
    first = 0 if pages else 1
    np.testing.assert_allclose(kern[first:], ref[first:], atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(walk[first:], ref[first:], atol=tol,
                               rtol=tol)
    if kind == "f32":
        np.testing.assert_allclose(walk, kern, atol=1e-6, rtol=1e-6)
    if not pages:
        np.testing.assert_array_equal(kern[0], np.zeros_like(kern[0]))
        np.testing.assert_array_equal(walk[0], np.zeros_like(walk[0]))


@pytest.mark.parametrize("seq", [1, 3])
@pytest.mark.parametrize("kind", KINDS)
def test_hole_inside_the_live_range(group_of_8, kind, seq):
    """`allowed` stays the only mask: keys 40-199 are denied (the
    rest of group 0 and part of group 1) below a frontier in group 2;
    the live bound is the last page allowed, not a count of keys."""
    ref, walk, kern, tol = _edge_case(
        kind, seq, lambda key, row: (key < 270 + row) & (
            (key < 40) | (key >= 200)) | np.zeros((2, 1, 1), bool))
    np.testing.assert_allclose(kern, ref, atol=tol, rtol=tol)
    np.testing.assert_allclose(walk, ref, atol=tol, rtol=tol)


@pytest.mark.parametrize("kind", KINDS)
def test_last_live_page_shared_with_another_slot(group_of_8, kind):
    """Slot 0's last live page (its tenth, in group 1) is also slot
    1's third: two tables name one physical page at different places
    of their walks."""
    depth = np.array([10 * 16 - 3, 3 * 16 - 7])

    def share(pt):
        pt[1, 2] = pt[0, 9]
        return pt

    ref, walk, kern, tol = _edge_case(
        kind, 1, lambda key, row: key < depth[:, None, None] + 0 * row,
        page_table_fn=share)
    np.testing.assert_allclose(kern, ref, atol=tol, rtol=tol)
    np.testing.assert_allclose(walk, ref, atol=tol, rtol=tol)


def test_group_pages_from_the_shapes():
    """G at the serve cells' shape and how it follows the shapes: a
    whole number of 128-key lane tiles, fewer for wider or deeper
    calls, the whole slot where that is smaller."""
    assert pa.group_pages(16, 25, 1600, 2, 1, 64) == 8
    assert pa.group_pages(16, 25, 1600, 1, 1, 64) == 16   # int8 pages
    assert pa.group_pages(16, 25, 1600, 2, 1, 4) == 4     # one group
    assert pa.group_pages(16, 13, 832, 2, 1, 64) == 24    # tp = 2
    assert pa.group_pages(16, 100, 6400, 2, 1, 64) == 8   # never 0
    assert pa.group_pages(128, 25, 1600, 2, 1, 8) == 1
    for depth, want in ((0, 0), (1, 128), (128, 128), (129, 256)):
        assert pa.walked_tokens(depth, 16, 8) == want


# ---------------------------------------------------------------------------
# Grouped queries (H_kv-wide rows) and a window layer's band
# ---------------------------------------------------------------------------


def _grouped_case(kind, seq, window, depths=(250, 37, 0), heads=8,
                  kv_heads=2, head_dim=128):
    """3 slots over `EDGE_PAGES` 16-token pages whose rows hold
    `kv_heads` heads; slot s is `depths[s]` tokens deep (0: evicted).
    Returns (reference, walk, interpreted kernel, tolerance)."""
    rng = np.random.default_rng(11)
    slots = len(depths)
    num_pages = slots * EDGE_PAGES + 1
    cache_len = EDGE_PAGES * 16
    dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
    q = jnp.asarray(rng.normal(size=(slots, seq, heads, head_dim)), dtype)
    rows = lambda: rng.normal(size=(num_pages, 16, kv_heads * head_dim))
    k, v = rows(), rows()
    pt = jnp.asarray(1 + rng.permutation(num_pages - 1).reshape(
        slots, EDGE_PAGES), jnp.int32)
    depth = np.asarray(depths)[:, None, None]
    at = depth - seq + np.arange(seq)[None, :, None]     # query positions
    keys = np.arange(cache_len)[None, None, :]
    allowed = (keys <= at) & (depth > 0)
    if window:
        allowed &= keys > at - window
    allowed = jnp.asarray(allowed)
    scales = {}
    if kind == "int8":
        def quantize(x):
            x = x.reshape(num_pages, 16, kv_heads, head_dim)
            scale = np.abs(x).max(axis=(1, 3)) / 127.0
            quant = np.round(x / scale[:, None, :, None])
            return (jnp.asarray(quant.reshape(num_pages, 16, -1), jnp.int8),
                    jnp.asarray(scale, jnp.float32))
        (k, ks), (v, vs) = quantize(k), quantize(v)
        scales = dict(key_scales=ks, value_scales=vs)
    else:
        k, v = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
    ref = pa.paged_attention_reference(q, k, v, pt, allowed, **scales)
    walk = pa._paged_walk_lax(q, k, v, pt, allowed,
                              1.0 / np.sqrt(head_dim), **scales)
    kern = pa.paged_decode_attention(q, k, v, pt, allowed, interpret=True,
                                     window=window, **scales)
    as_f32 = lambda x: np.asarray(x, np.float32)
    return (as_f32(ref), as_f32(walk), as_f32(kern),
            2e-2 if kind == "bf16" else TOL)


@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("seq", [1, 3])
@pytest.mark.parametrize("kind", KINDS)
def test_grouped_queries_and_window_parity(group_of_8, kind, seq, window):
    """8 query heads on 2 key/value heads, rows 256 wide: kernel and
    walk against the reference (`GQAttention`'s dense einsums), with
    and without a band whose first key lies past the first group of a
    250-token slot; an evicted slot reads exact zeros."""
    ref, walk, kern, tol = _grouped_case(kind, seq, window)
    np.testing.assert_allclose(kern[:2], ref[:2], atol=tol, rtol=tol)
    np.testing.assert_allclose(walk[:2], ref[:2], atol=tol, rtol=tol)
    assert not kern[2].any() and not walk[2].any()


def test_window_walk_starts_at_the_first_live_group(group_of_8):
    """A window layer's schedule: a slot 250 tokens deep with a band
    of 40 keys (positions 210-249: pages 13-15, all in group 1) takes
    one grid step, not two; a full layer's takes both."""
    depth = np.array([250, 37, 0])[:, None, None]
    keys = np.arange(EDGE_PAGES * 16)[None, None, :]
    full = jnp.asarray((keys < depth) & (depth > 0))
    band = jnp.asarray(np.asarray(full) & (keys > depth - 1 - 40))
    table = jnp.zeros((3, EDGE_PAGES), jnp.int32)
    most = 3 * (-(-EDGE_PAGES // group_of_8))

    def steps(mask, banded):
        _, _, live = pa._grouped(table, mask, 16, group_of_8)
        first = (pa._first_groups(mask, 16, group_of_8) if banded
                 else None)
        slot_of, group_of, total = pa._schedule(live, group_of_8, most,
                                                first)
        total = int(total)
        return list(zip(np.asarray(slot_of)[:total].tolist(),
                        np.asarray(group_of)[:total].tolist()))

    assert steps(full, False) == [(0, 0), (0, 1), (1, 0), (2, 0)]
    assert steps(band, True) == [(0, 1), (1, 0), (2, 0)]
