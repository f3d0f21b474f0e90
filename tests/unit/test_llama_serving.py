"""`LlamaLM` behind the paged pool: the second class `DecodeEngine` /
`Scheduler` serve. A request through the engine gives the tokens
`generate()` gives for it alone, as `TransformerLM`'s do
(tests/unit/test_serving.py), in the shapes that differ from it: grouped
queries (H_kv-wide page rows), RoPE at each slot's own depth, q/k norms,
window and full layers side by side with a window that is no multiple of
the page size, the norm on the sub-layers' outputs, and an expert layer
that holds 4 of 16 experts. Toy widths, float32, on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.models import LlamaLM, generate
from cloud_tpu.serving import DecodeEngine, Scheduler, ServeRequest

F32 = jnp.float32


def _init(model, seed=1):
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    # Norm scales away from 1 and a router bias away from 0, so that
    # every term is in play.
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def exaone_shaped(**changes):
    kwargs = dict(
        vocab_size=96, num_layers=5, num_heads=4, num_kv_heads=2, d_model=32,
        d_ff=64, max_seq_len=64, head_dim=16, rope_theta=1e6,
        rope_style="rotate_half", norm_eps=1e-5, compute_dtype=F32,
        qk_norm=True, attn_kinds="LLLG", sliding_window=12,
        post_block_norms=True, pre_block_norms=False, global_rope=False,
        moe_experts=16, moe_top_k=4, moe_router="sigmoid", moe_d_ff=24,
        moe_shared_experts=1, moe_routed_scale=2.5, moe_capacity_factor=None,
        moe_held_experts=[0, 1, 2, 3], first_k_dense=1)
    kwargs.update(changes)
    return LlamaLM(**kwargs)


MODELS = {
    "qwen_shaped": lambda: LlamaLM(
        vocab_size=96, num_layers=2, num_heads=4, num_kv_heads=2, d_model=32,
        d_ff=64, max_seq_len=64, rope_style="rotate_half", qkv_bias=True,
        compute_dtype=F32),
    "exaone_shaped": exaone_shaped,
}


@pytest.fixture(scope="module", params=sorted(MODELS))
def served(request):
    model = MODELS[request.param]()
    return model, _init(model)


def _solo(model, params, req):
    toks = generate(model, params, jnp.asarray(req.prompt, jnp.int32)[None],
                    req.max_new_tokens, rng=jax.random.PRNGKey(req.rng_seed),
                    temperature=req.temperature, top_k=req.top_k)
    return np.asarray(toks)[0]


def test_engine_tokens_equal_generate_tokens(served):
    """Prompts shorter and longer than the window (12) and than a page
    (8), two slots for five requests (slots are reused), greedy and
    sampled."""
    model, params = served
    rng = np.random.default_rng(0)
    requests = [ServeRequest(
        prompt=rng.integers(2, 96, n).tolist(), max_new_tokens=new,
        rng_seed=10 + n, temperature=temp, top_k=top_k)
        for n, new, temp, top_k in ((5, 9, 0.0, None), (20, 10, 0.0, None),
                                    (33, 8, 0.8, 8), (9, 12, 0.0, None),
                                    (13, 6, 0.0, None))]
    with Scheduler(model, params, slots=2, page_size=8) as sched:
        sched.warmup([8, 16, 32, 64], sampling_configs=[()] + [
            (("temperature", r.temperature), ("top_k", r.top_k))
            for r in requests if r.temperature])
        results = [f.result(timeout=600) for f in
                   [sched.submit(r, timeout=60) for r in requests]]
        sched.engine.check_no_retrace()
        stats = sched.stats()
    for req, res in zip(requests, results):
        np.testing.assert_array_equal(res.tokens, _solo(model, params, req))
    if model.moe_experts:
        # 4 expert layers, 4 choices a token, 4 of 16 experts held.
        assert stats["moe_pairs_routed"] > stats["moe_pairs_held"] > 0
        assert 0 < stats["moe_experts_touched"] <= 16 * stats["ticks"]
        assert sum(stats["moe_expert_load"]) == stats["moe_pairs_held"]
        # Two rows choosing 4 of 16 touch few of the held: grouped ticks.
        assert stats["moe_pairs_dense"] == 0
    else:
        assert stats["moe_pairs_routed"] == stats["moe_pairs_dense"] == 0
        assert stats["moe_expert_load"] == []


def test_engine_refuses_what_the_pool_cannot_serve():
    from cloud_tpu.models import DeepseekLM
    model = DeepseekLM(vocab_size=64, num_layers=1, num_heads=2, d_model=32,
                       max_seq_len=32)
    with pytest.raises(NotImplementedError, match="TransformerLM, LlamaLM, NemotronHLM and EvaByteLM"):
        DecodeEngine(model, None, slots=2, page_size=8, num_pages=9)
    capped = MODELS["qwen_shaped"]().clone(attn_logit_softcap=30.0)
    with pytest.raises(NotImplementedError, match="softcap"):
        DecodeEngine(capped, None, slots=2, page_size=8, num_pages=17)


def test_prefill_window_through_the_flash_kernel():
    """Where the flash kernel is selected, a prefill window over the
    dense decode cache runs through it (an L-long frame of the window's
    rows), left- or right-padded, at an offset into the cache, on window
    and full layers: logits as the dense einsum's."""
    base = exaone_shaped(num_layers=4, max_seq_len=32)
    params = _init(base)
    tokens = np.random.default_rng(1).integers(2, 96, (1, 16)).astype(np.int32)
    masks = {"right": np.arange(16)[None] < 11, "left": np.arange(16)[None] >= 5}

    def prefill_logits(impl, mask, chunks):
        decoder = base.clone(decode=True, attention_impl=impl)
        from cloud_tpu.models.decoding import empty_cache
        cache, out = empty_cache(decoder, 1), []
        for lo, hi in chunks:
            logits, variables = decoder.apply(
                {"params": params, "cache": cache}, jnp.asarray(tokens[:, lo:hi]),
                jnp.asarray(mask[:, lo:hi]), mutable=["cache"])
            cache = variables["cache"]
            out.append(np.asarray(logits))
        return np.concatenate(out, axis=1)

    for side, mask in masks.items():
        for chunks in (((0, 16),), ((0, 8), (8, 16))):
            want = prefill_logits("reference", mask, chunks)
            got = prefill_logits("flash", mask, chunks)
            np.testing.assert_allclose(got[mask], want[mask], atol=2e-4,
                                       rtol=2e-4, err_msg=side)
