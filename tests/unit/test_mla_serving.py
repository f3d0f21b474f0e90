"""A toy `DeepseekLM` (latent attention, grouped experts, a share of them
held) through `Scheduler` against the plain expanded reference
(`cellbench/reference/axk1.py`): a prefix miss and a prefix hit that shares
pages with a live slot (copy-on-write of the partial page); the absorbed
latent read against the expanded form; the paged walk `paged_mla_decode`
(interpreted) against its lax walk and the gathered reference; the
expanded prefill's kernel `mla_prefill` (interpreted) against the XLA
blocks and a dense softmax; the expert
shares of a grouped layer adding up to the uncut layer; the choice of
prefill form from the shapes."""

import importlib
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import harness, weights
from cellbench.reference import axk1 as reference
from cloud_tpu.models import DeepseekLM, DeepseekMoE
from cloud_tpu.models.deepseek import absorbed_form, mla_softmax_scale
from cloud_tpu.models.llama import SwiGLU
from cloud_tpu.serving import Scheduler, ServeRequest

paged = importlib.import_module("cloud_tpu.ops.paged_attention")
CONFIG = os.path.join(harness.ROOT, "cellbench", "configs", "a.x-k1-ep16.json")


def toy_config():
    """The cell's configuration at toy widths, float32 (the shrink of
    tests/cellbench/toy_sizes_axk1.py)."""
    from tests.cellbench import toy_sizes_axk1

    cfg = harness.load_json(CONFIG)

    class Cell:
        config = cfg
        traffic = {}
    toy_sizes_axk1.shrink(Cell)
    return cfg


@pytest.fixture(scope="module")
def built():
    cfg = toy_config()
    model = weights.build_model(cfg)
    params = weights.make_params(weights.param_shapes(model), 2 ** 31 + 5)
    return cfg, model, params


def gaps(cfg, params, tokens, prompt_len):
    """How far each served token's logit lies below the reference's best at
    its position (float32 reference, its expanded form)."""
    rows = np.arange(prompt_len - 1, len(tokens) - 1)
    logits, _, _ = reference.logits_rows(params, cfg, np.asarray(tokens), rows)
    logits = np.asarray(logits)
    return logits.max(-1) - logits[np.arange(len(rows)),
                                   np.asarray(tokens)[rows + 1]]


def test_served_miss_and_hit_against_the_expanded_reference(built):
    """Prefill plus 16 decoded tokens, through the paged latent pool: a
    miss, then a hit sharing its first pages while it is still decoding
    (the match ends mid-page: copy-on-write of the partial page), and a
    hit whose question prefills absorbed. Float32 program and reference,
    so the served token is the reference's best to rounding: every gap
    under 1e-4 (logits of order 1; the int8 control reads 1e-2 and more,
    tests/cellbench/test_axk1_cell.py)."""
    cfg, model, params = built
    rng = np.random.default_rng(3)
    base = rng.integers(2, 256, 45).tolist()
    prompts = [base, base[:30] + rng.integers(2, 256, 20).tolist(),
               base + rng.integers(2, 256, 6).tolist()]
    sched = Scheduler(model, params, slots=4, page_size=4,
                      strict_no_retrace=True)
    sched.start()
    try:
        sched.warmup([8, 16, 32, 64])
        first = sched.submit(ServeRequest(prompt=prompts[0], temperature=0.0,
                                          max_new_tokens=16))
        # Admitted while the first decodes: it maps the first's pages.
        deadline = time.monotonic() + 120
        while (sched.stats()["tokens_emitted"] < 2
               and time.monotonic() < deadline):
            time.sleep(0.01)
        rest = [sched.submit(ServeRequest(prompt=p, max_new_tokens=16,
                                             temperature=0.0))
                for p in prompts[1:]]
        results = [first.result(120)] + [f.result(120) for f in rest]
        stats = sched.stats()
    finally:
        sched.close()
    assert stats["prefix_hits"] == 2 and stats["prefix_misses"] == 1
    assert stats["pool"]["cow_copies"] >= 1
    assert stats["mla_prefill_tokens_absorbed"] > 0
    assert stats["mla_prefill_tokens_expanded"] > 0
    assert stats["mla_rows_read"] == 3 * stats["kv_live_tokens"]
    for prompt, result in zip(prompts, results):
        got = gaps(cfg, params, result.tokens, len(prompt))
        assert len(got) == 16 and got.max() < 1e-4, got


def test_absorbed_decode_equals_the_expanded_forward(built):
    """The dense decode cache read expanded (a 40-token prefill) and
    absorbed (a 24-token prefill, the steps after either) against the
    training forward, which expands `kv_b` over every token: logits equal
    to float32 rounding."""
    _, model, params = built
    tokens = np.random.default_rng(4).integers(2, 256, (2, 42)).astype(
        np.int32)
    decoder = model.clone(decode=True)
    assert not model.latent_absorbed(40) and model.latent_absorbed(24)
    step = jax.jit(lambda c, t: decoder.apply(
        {"params": params, "cache": c}, t, mutable=["cache"]))
    for split in (40, 24):
        full = model.apply({"params": params}, tokens[:, :split + 2])
        cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
                lambda: decoder.init(jax.random.PRNGKey(0),
                                     tokens[:, :1]))["cache"])
        out, state = step(cache, tokens[:, :split])
        steps = [out]
        for t in range(split, split + 2):
            out, state = step(state["cache"], tokens[:, t:t + 1])
            steps.append(out)
        np.testing.assert_allclose(np.concatenate(steps, 1), full,
                                   atol=2e-5, rtol=0)


def test_expanded_prefill_kernel_equals_the_xla_blocks(built):
    """A 40-token prefill behind 24 cached tokens, expanded: the Pallas
    kernel `mla_prefill` (interpreted, `attention_impl="flash"`) and
    `_latent_attention`'s XLA blocks give the same logits, and so do the
    two steps after it."""
    _, model, params = built
    tokens = np.random.default_rng(5).integers(2, 256, (2, 66)).astype(
        np.int32)
    assert not model.latent_absorbed(40) and model.latent_absorbed(24)
    outs = []
    for impl in ("auto", "flash"):
        decoder = model.clone(decode=True, attention_impl=impl)
        step = jax.jit(lambda c, t, d=decoder: d.apply(
            {"params": params, "cache": c}, t, mutable=["cache"]))
        cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
                lambda: decoder.init(jax.random.PRNGKey(0),
                                     tokens[:, :1]))["cache"])
        steps = []
        for lo, hi in ((0, 24), (24, 64), (64, 65), (65, 66)):
            out, state = step(cache, tokens[:, lo:hi])
            cache = state["cache"]
            steps.append(out)
        outs.append(np.concatenate(steps, 1))
    np.testing.assert_allclose(outs[1], outs[0], atol=2e-5, rtol=0)


def _prefill_reference(q_nope, q_rope, k_nope, k_rope, v, start, valid,
                       scale):
    """Dense masked softmax, the semantics `mla_prefill` walks."""
    s = (jnp.einsum("bhqn,bhkn->bhqk", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhqp,bkp->bhqk", q_rope, k_rope,
                      preferred_element_type=jnp.float32)) * scale
    seq, cache_len = q_nope.shape[2], k_nope.shape[2]
    allowed = ((jnp.arange(cache_len)[None, :]
                <= start + jnp.arange(seq)[:, None])[None, None]
               & valid[:, None, None, :])
    s = jnp.where(allowed, s, -1e30)
    p = jnp.where(allowed, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    out = jnp.einsum("bhqk,bhkw->bhqw", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    total = p.sum(-1, keepdims=True)
    return out / jnp.where(total == 0.0, 1.0, total)


@pytest.mark.parametrize("dtype,seq,cache_len,start", [
    (jnp.float32, 20, 64, 30),        # one key block, a straddled edge
    (jnp.float32, 40, 64, 50),        # rows past the cache's end
    (jnp.bfloat16, 100, 2048, 1500),  # two key blocks, the first plain
    (jnp.bfloat16, 600, 2048, 0),     # two query blocks, no prefix
])
def test_prefill_kernel_against_the_dense_softmax(dtype, seq, cache_len,
                                                  start):
    """`mla_prefill` (interpreted): queries at a dynamic cache row
    against per-head keys beside one shared rope key, invalid keys
    masked, blocks past a query block's causal edge skipped, equals the
    dense masked softmax to rounding; a row that sees no key is zero."""
    from cloud_tpu.ops.attention import mla_prefill_attention

    rng = np.random.default_rng(seq)
    wide = cache_len > 64
    nope, rope, value = (128, 64, 128) if wide else (16, 8, 16)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    args = (normal(2, 3, seq, nope), normal(2, 3, seq, rope),
            normal(2, 3, cache_len, nope), normal(2, cache_len, rope),
            normal(2, 3, cache_len, value), jnp.int32(start),
            jnp.asarray(rng.random((2, cache_len)) > 0.1)
            & (jnp.arange(cache_len) < start + seq)[None], 0.13)
    got = mla_prefill_attention(*args)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_prefill_reference(*args)),
        atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("dtype,depth,per_slot,step_keys", [
    # A step larger than a slot's table: one step a slot.
    (jnp.float32, (37, 150, 0), 40, None),
    (jnp.bfloat16, (37, 150, 0), 40, None),
    # 128-key steps of 32 pages: 2, 1 and 3 steps (an odd count puts the
    # next slot's first step in the other buffer), each slot's last
    # group partial, and every step's copies started by a step of the
    # slot before or of its own.
    (jnp.float32, (150, 37, 300), 100, 128),
    (jnp.bfloat16, (150, 37, 300), 100, 128),
    # An evicted slot between two live ones: its step copies nothing.
    (jnp.float32, (300, 0, 150), 100, 128),
    # A whole table (4 steps, 100 pages padded to 128 with the scratch
    # page), then a slot one key into its second group, then evicted.
    (jnp.float32, (400, 129, 0), 100, 128),
], ids=["float32", "bfloat16", "float32-steps", "bfloat16-steps",
        "float32-evicted-between", "float32-full-table"])
def test_paged_walk_kernel_and_reference_agree(dtype, depth, per_slot,
                                               step_keys, capfd,
                                               monkeypatch, request):
    """One row a token read once: the interpreted Pallas walk, its lax walk
    (the same online-softmax order) and the gathered reference agree to
    rounding, on slots at different depths, an evicted one (zeros), with a
    row wider than its useful values. Over several steps a slot the walk
    runs in TPU interpret mode, which moves the kernel's own page copies as
    the chip would: a copy that lands only when waited for (a step that
    did not wait for its pages reads stale rows), and one that lands when
    started, with every semaphore counted back to zero at the kernel's
    exit (a copy started and never waited for) and no copy landing in
    rows a step still reads."""
    from jax.experimental.pallas import tpu as pltpu

    rng = np.random.default_rng(7)
    slots, heads, width, value, page = 3, 8, 256, 128, 4
    pages = jnp.asarray(rng.normal(size=(slots * per_slot + 1, page, width)),
                        dtype)
    q = jnp.asarray(rng.normal(size=(slots, 1, heads, width)), dtype)
    table = jnp.asarray(1 + rng.permutation(slots * per_slot).reshape(
        slots, per_slot), jnp.int32)
    depth = np.array(depth)
    allowed = jnp.asarray(np.arange(per_slot * page)[None, None, :]
                          < depth[:, None, None])
    args = (q, pages, table, allowed, 0.1, value)
    if step_keys is None:
        kernels = [paged.paged_mla_attention(*args, impl="paged",
                                             interpret=True)]
    else:
        # The step is a constant of the module, read when the call is
        # traced: set it here, and trace afresh on both sides of the test.
        monkeypatch.setattr(paged, "_MLA_STEP_KEYS", step_keys)
        paged._paged_mla_call.clear_cache()
        request.addfinalizer(paged._paged_mla_call.clear_cache)
        assert paged.mla_group_pages(page, per_slot) == 32
        kernels = [paged._paged_mla_call(
            q, pages, table, allowed, sm_scale=0.1, value_width=value,
            interpret=pltpu.InterpretParams(dma_execution_mode=mode,
                                            detect_races=True))
                   for mode in ("on_wait", "eager")]
        printed = capfd.readouterr().out
        assert "non-zero count" not in printed, printed
        assert "RACE DETECTED" not in printed, printed
    walk = paged._paged_mla_walk_lax(*args)
    want = paged.paged_mla_reference(*args)
    # float32: the rounding of the online softmax's rescales grows with
    # the steps a slot takes (3e-6 over three).
    tol = ((2e-6 if step_keys is None else 5e-6) if dtype == jnp.float32
           else 2e-2)
    live = depth > 0
    for kernel in kernels:
        np.testing.assert_allclose(np.asarray(kernel, np.float32),
                                   np.asarray(walk, np.float32), atol=tol)
        np.testing.assert_allclose(np.asarray(kernel[live], np.float32),
                                   np.asarray(want[live], np.float32),
                                   atol=tol)
        assert not np.asarray(kernel[~live], np.float32).any()


def test_expert_shares_add_up_to_the_uncut_grouped_layer():
    """Sixteen holders of two experts each, routing in 8 groups of 4 (the
    4 best eligible, 4 chosen): their outputs, the shared expert counted
    once, add up to the layer that holds every expert."""
    x = jnp.asarray(np.random.default_rng(9).normal(size=(2, 6, 16)),
                    jnp.float32)
    layer = lambda held: DeepseekMoE(
        num_experts=32, top_k=4, d_ff=8, n_group=8, topk_group=4,
        routed_scaling_factor=2.5, compute_dtype=jnp.float32,
        held_experts=held)
    whole = layer(None)
    params = whole.init(jax.random.PRNGKey(0), x)["params"]
    params["router_bias"] = jnp.linspace(-0.01, 0.01, 32)
    want, _ = whole.apply({"params": params}, x)
    parts = []
    for share in range(16):
        held = (2 * share, 2 * share + 1)
        mine = dict(params)
        for name in ("expert_gate", "expert_up", "expert_down"):
            mine[name] = params[name][jnp.asarray(held)]
        parts.append(layer(held).apply({"params": mine}, x)[0])
    # Each part carries the shared expert once: remove 15 of the 16.
    only_shared = SwiGLU(8, jnp.float32).apply(
        {"params": params["shared"]}, x)
    np.testing.assert_allclose(sum(parts) - 15 * only_shared, want,
                               atol=2e-5)


def test_model_takes_the_published_rope_scaling_and_held_experts():
    """What `weights.build_model` passes from the file: the rope_scaling
    dict becomes the recipe, the softmax scale follows from its keys
    (0.1309 at A.X K1's widths), the held experts reach every expert
    layer, and every parameter but the router's is stored in the file's
    type."""
    cfg = harness.load_json(CONFIG)
    model = weights.build_model(cfg)
    assert model.rope_scaling.kind == "yarn" and model.attn_scale is None
    assert mla_softmax_scale(192, model.rope_scaling) == pytest.approx(
        0.1309, abs=1e-4)
    assert model.latent_row == 640
    shapes = weights.param_shapes(model)
    for name in ("block_1", "block_4"):
        assert shapes[name]["moe"]["expert_up"].shape == (12, 7168, 2048)
    dtypes = {str(leaf.dtype) for path, leaf in
              jax.tree_util.tree_flatten_with_path(shapes)[0]
              if "router" not in jax.tree_util.keystr(path)}
    assert dtypes == {"bfloat16"}


def test_prefill_form_from_the_shapes():
    """Absorbed below 171 queries at A.X K1's widths, expanded above: the
    tick and a 128-token prefill absorbed, a 256-token one expanded."""
    form = lambda q: absorbed_form(q, 64, 512, 128, 64, 128)
    assert form(1) and form(128) and form(170)
    assert not form(171) and not form(256) and not form(1024)


def test_engine_refuses_int8_latent_pages(built):
    _, model, params = built
    with pytest.raises(NotImplementedError, match="int8 latent pages"):
        Scheduler(model, params, slots=2, page_size=4, kv_dtype="int8")
