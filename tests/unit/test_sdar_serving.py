"""Block-diffusion serving (`models.sdar.SdarLM`) on the CPU at toy widths:
the block-causal flash forward and its tiles against the oracle, the block
read of the paged pool (`paged_block_decode`) against the gathered reference,
`TopKMoEMLP`'s served dtype, engine tokens against a solo `sdar.generate` bit
for bit with denoising steps 1, 2 and 4 mixed in one tick and inserts and
evictions mid-block, and the program against the plain reference
(`cellbench/reference/sdar.py`): its whole-sequence logits, and every token
it served at the step it was committed.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.models import SdarLM
from cloud_tpu.models import sdar
from cloud_tpu.models.moe import TopKMoEMLP
from cloud_tpu.ops import flash_attention, mha_reference
from cloud_tpu.ops.attention import flash_plan
from cloud_tpu.ops.paged_attention import (paged_attention_reference,
                                           paged_decode_attention)
from cloud_tpu.serving import Scheduler, ServeRequest

attention_lib = importlib.import_module("cloud_tpu.ops.attention")
F32 = jnp.float32
BLOCK = 4
# 16 experts, 2 chosen: 4 slots x 4 rows touch under 95 % of them, so the
# tick and a solo block both run the grouped form (`moe.batched_over_held`).
TOY = dict(vocab_size=256, num_layers=2, num_heads=4, num_kv_heads=2,
           d_model=64, d_ff=96, head_dim=16, max_seq_len=64, moe_experts=16,
           moe_top_k=2, moe_d_ff=32, mask_token=255, rope_theta=1e6,
           compute_dtype=F32)


@pytest.fixture(scope="module")
def toy():
    model = SdarLM(**TOY)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.mark.parametrize("block", [4, 3, 1])
def test_block_causal_flash_matches_the_oracle(block):
    """Interpreted kernel, forward and gradients, against `mha_reference`
    with the same mask: key j seen by row i iff j // B <= i // B, a key
    mask of padding on top; B = 1 is the causal mask."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, 300, 4, 16))
    k = jax.random.normal(keys[1], (1, 300, 2, 16))
    v = jax.random.normal(keys[2], (1, 300, 2, 16))
    mask = jnp.arange(300)[None] < 290
    out = flash_attention(q, k, v, mask=mask, block=block, block_q=128,
                          block_k=128)
    want = mha_reference(q, k, v, mask=mask, block=block)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5
    if block == 1:
        assert float(jnp.max(jnp.abs(
            want - mha_reference(q, k, v, mask=mask)))) == 0.0
    loss = lambda fn: jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
                               (0, 1, 2))(q, k, v)
    got = loss(lambda q, k, v: flash_attention(q, k, v, block=block,
                                               block_q=128, block_k=128))
    ref = loss(lambda q, k, v: mha_reference(q, k, v, block=block))
    for a, b in zip(got, ref):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


@pytest.mark.parametrize("seq,block,tiles", [(1024, 4, (128, 128)),
                                             (700, 64, (128, 256)),
                                             (512, 96, (64, 64))])
def test_block_causal_plan_visits_every_live_tile_and_no_other(seq, block,
                                                               tiles):
    """The grid's span pairs and the in-kernel walk's tiles are exactly
    the tiles that hold an entry of the block-causal mask, forward and
    transposed (dk/dv), by the arithmetic the kernel uses."""
    plan = flash_plan(seq, 64, 2, 2, True, 0, *tiles, block=block)
    rows = np.arange(plan.seq_pad)[:, None]
    seen = np.arange(plan.seq_pad)[None, :] // block <= rows // block
    bq, bk = plan.block_q, plan.block_k
    live = seen.reshape(plan.seq_pad // bq, bq, plan.seq_pad // bk,
                        bk).any(axis=(1, 3))
    count = plan.tiles(True, 0, block)
    assert count["walked"] == count["live"] == int(live.sum())
    forward = {(i, j) for i in range(plan.seq_pad // bq) for j in range(
        *attention_lib._live_blocks(i * bq, bq, bk, 0, plan.seq_pad // bk,
                                    True, 0, attn_block=block))}
    transposed = {(i, j) for j in range(plan.seq_pad // bk) for i in range(
        *attention_lib._live_blocks(j * bk, bk, bq, 0, plan.seq_pad // bq,
                                    True, 0, transposed=True,
                                    attn_block=block))}
    assert forward == transposed == set(zip(*np.nonzero(live)))
    # B = 1 plans what the causal mask plans.
    assert flash_plan(seq, 64, 2, 2, True, 0, *tiles, block=1) == flash_plan(
        seq, 64, 2, 2, True, 0, *tiles)


def test_block_read_kernel_matches_the_gathered_reference():
    """B rows a slot over a paged pool, keys up to the end of each slot's
    open block, one slot evicted: the interpreted `paged_block_decode`
    call against the gathered reference."""
    slots, ppn, page, heads, kv_heads, depth = 3, 6, 4, 4, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    pool = (slots * ppn + 1, page, kv_heads * depth)
    kp = jax.random.normal(keys[0], pool)
    vp = jax.random.normal(keys[1], pool)
    q = jax.random.normal(keys[2], (slots, BLOCK, heads, depth))
    table = 1 + jnp.arange(slots * ppn, dtype=jnp.int32).reshape(slots, ppn)
    ends = jnp.asarray([12, 20, 0])
    allowed = jnp.broadcast_to(
        (jnp.arange(ppn * page)[None, :] < ends[:, None])[:, None],
        (slots, BLOCK, ppn * page))
    got = paged_decode_attention(q, kp, vp, table, allowed, interpret=True,
                                 block=BLOCK)
    want = paged_attention_reference(q, kp, vp, table, allowed)
    # The live slots; the evicted one's rows are the kernel's zeros (the
    # reference averages what a fully masked row sees).
    assert float(jnp.max(jnp.abs(got[:2] - want[:2]))) < 1e-5
    assert float(jnp.max(jnp.abs(got[2]))) == 0.0
    text = str(jax.make_jaxpr(lambda *a: paged_decode_attention(
        *a, interpret=True, block=BLOCK))(q, kp, vp, table, allowed))
    assert "name=attention._paged_decode_attention.paged_block_decode" in text


def test_topk_moe_stores_its_experts_in_the_served_dtype():
    layer = TopKMoEMLP(num_experts=8, top_k=2, d_ff=16,
                       param_dtype=jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 32)))[
        "params"]
    for name in ("expert_gate", "expert_up", "expert_down"):
        assert params[name].dtype == jnp.bfloat16
    assert params["router"].dtype == F32
    # The served model's layers hand it theirs.
    model = SdarLM(**dict(TOY, param_dtype=jnp.bfloat16))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    assert shapes["block_1"]["moe"]["expert_up"].dtype == jnp.bfloat16


def test_transfer_schedule_is_sdar_get_num_transfer_tokens():
    assert [sdar.transfer_count(4, 4, s) for s in range(4)] == [1] * 4
    assert [sdar.transfer_count(4, 2, s) for s in range(2)] == [2, 2]
    assert [sdar.transfer_count(3, 2, s) for s in range(2)] == [2, 1]
    assert [sdar.transfer_count(1, 4, s) for s in range(4)] == [1, 0, 0, 0]
    conf = jnp.asarray([[0.1, 0.5, 0.5, 0.9]])
    masked = jnp.asarray([[True, True, True, False]])
    picked = sdar.select(conf, masked, jnp.asarray([2]))
    assert picked.tolist() == [[False, True, True, False]]


def _requests():
    """Prompts ending inside a block and on its edge, 1 to 13 new tokens
    (the last block cut short, or one token alone), steps 1, 2 and 4."""
    rng = np.random.default_rng(5)
    out = []
    for i in range(12):
        prompt = rng.integers(2, 250, int(rng.integers(4, 30)))
        out.append((prompt, int(rng.integers(1, 14)), (1, 2, 4)[i % 3]))
    return out


def test_engine_tokens_equal_a_solo_generate_bit_for_bit(toy):
    """Twelve requests through 4 slots: a tick holds slots at different
    steps of their blocks with 1, 2 and 4 steps a block, slots that close a
    block while others open one, and requests that finish (evicted) and
    arrive (inserted) while the others are mid-block. Each one's tokens
    and commit steps are its solo `sdar.generate`'s, bit for bit, and its
    record's times add up."""
    model, params = toy
    requests = _requests()
    with Scheduler(model, params, slots=4, page_size=4,
                   strict_no_retrace=True) as sched:
        sched.warmup([8, 16, 32])
        futures = [sched.submit(ServeRequest(
            prompt=p.tolist(), max_new_tokens=n, temperature=0.0,
            denoising_steps=s)) for p, n, s in requests]
        results = [f.result(timeout=600) for f in futures]
        stats = sched.stats()
    for (prompt, new, steps), result in zip(requests, results):
        tokens, commit = sdar.generate(model, params, prompt, new,
                                       denoising_steps=steps, page_size=4)
        assert np.array_equal(result.tokens, tokens)
        assert np.array_equal(result.commit_steps, commit)
        rec = result.trace
        assert len(rec.token_times) == new - 1
        assert sum(rec.phases().values()) == pytest.approx(result.latency_s)
        assert rec.t_first <= min(rec.token_times, default=rec.t_first)
    blocks = sum((len(p) + n - 1) // BLOCK - len(p) // BLOCK + 1
                 for p, n, _ in requests)
    assert stats["diffusion_blocks_closed"] == blocks
    assert stats["diffusion_tokens_committed"] == sum(
        n for _, n, _ in requests)
    assert stats["diffusion_rows_computed"] == BLOCK * (
        stats["diffusion_slot_steps"] + blocks)
    assert stats["prefix_hits"] == 0


def test_a_block_tick_batched_over_the_held_experts_serves_as_grouped(
        toy, monkeypatch):
    """16 slots x 4 rows choosing 2 of 16 experts touch (nearly) every
    one, so the block tick runs its expert layers batched over the held
    experts (`moe.batched_over_held`). In float32 it serves the tokens
    and commit steps of the same ticks forced grouped, and counts a
    product a live row, held expert and expert layer, where the grouped
    run counts none."""
    from cloud_tpu.models import moe

    model, params = toy
    slots = 16
    assert moe.batched_over_held(slots * BLOCK, TOY["moe_top_k"],
                                 TOY["moe_experts"], None)
    requests = _requests()
    served = {}
    for form in ("batched", "grouped"):
        if form == "grouped":
            monkeypatch.setattr(moe, "batched_over_held", lambda *a: False)
        with Scheduler(model, params, slots=slots, page_size=4) as sched:
            results = [f.result(timeout=600) for f in [
                sched.submit(ServeRequest(
                    prompt=p.tolist(), max_new_tokens=n, temperature=0.0,
                    denoising_steps=s)) for p, n, s in requests]]
            served[form] = (results, sched.stats())
    for batched, grouped in zip(served["batched"][0], served["grouped"][0]):
        assert np.array_equal(batched.tokens, grouped.tokens)
        assert np.array_equal(batched.commit_steps, grouped.commit_steps)
    stats = served["batched"][1]
    assert stats["diffusion_rows_computed"] > 0
    assert stats["moe_pairs_dense"] == (
        stats["diffusion_rows_computed"] * TOY["moe_experts"]
        * TOY["num_layers"])
    assert served["grouped"][1]["moe_pairs_dense"] == 0
    assert served["grouped"][1]["diffusion_rows_computed"] == stats[
        "diffusion_rows_computed"]


def test_requests_the_block_tick_cannot_serve_are_refused(toy):
    model, params = toy
    with Scheduler(model, params, slots=2, page_size=4) as sched:
        for bad in (dict(prompt=[3, 4, 5], max_new_tokens=4),
                    dict(prompt=[3] * 8, max_new_tokens=4,
                         denoising_steps=5),
                    dict(prompt=[3] * 8, max_new_tokens=4, temperature=1.0),
                    dict(prompt=[3] * 8, max_new_tokens=4, eos_token=7),
                    dict(prompt=[3] * 8, max_new_tokens=60)):
            with pytest.raises(ValueError):
                sched.submit(ServeRequest(**dict(dict(temperature=0.0),
                                                 **bad)))
    with pytest.raises(NotImplementedError):
        Scheduler(model, params, slots=2, page_size=4, prefill_chunk=8)


def test_program_against_the_plain_reference(toy):
    """The plain reference (float32, HIGHEST) against the program: the
    block-causal forward's logits over a whole sequence, and, for tokens
    served with steps 1, 2 and 4 mixed in ticks with inserts and evictions
    mid-block, each token at the step it was committed: the reference's
    argmax, its logit within 1e-4 of the best, and the program's choice of
    rows the reference's confidence ranks first."""
    from cellbench.drivers import closed_loop_diffusion as driver
    from cellbench.reference import common
    from cellbench.reference import sdar as ref

    model, params = toy
    cfg = {"rms_norm_eps": 1e-6, "rope_theta": 1e6, "num_experts_per_tok": 2,
           "norm_topk_prob": True,
           "assumed": {"block_length": BLOCK, "mask_token": 255}}
    # Whole blocks: the reference's padding then lies in blocks of its own.
    tokens = np.random.default_rng(2).integers(2, 250, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, jnp.asarray(tokens[None]))[0]
    spec, mm = ref.layer_spec(cfg), common.make_mm("float32")
    h = ref.embed(params, jnp.asarray(np.pad(tokens, (0, 256 - 40))), cfg)
    for name in ref.layer_names(params):
        h, _, _ = ref.layer_forward(h, params[name], spec, mm)
    x = ref._rms(h[:40], params["norm_final"]["scale"], 1e-6)
    want = jnp.einsum("nd,dv->nv", x, params["lm_head"]["kernel"],
                      precision=jax.lax.Precision.HIGHEST)
    assert float(jnp.max(jnp.abs(logits - want))) < 1e-4

    requests = _requests()[:6]
    with Scheduler(model, params, slots=3, page_size=4) as sched:
        results = [f.result(timeout=600) for f in [sched.submit(ServeRequest(
            prompt=p.tolist(), max_new_tokens=n, temperature=0.0,
            denoising_steps=s)) for p, n, s in requests]]
    for (prompt, new, _), result in zip(requests, results):
        picked, best, argmax, _, steps = ref.replay(
            params, cfg, result.tokens, len(prompt), result.commit_steps)
        assert np.array_equal(argmax, result.tokens[len(prompt):])
        assert float(np.max(best - picked)) < 1e-4
        # Each step committed the reference's most confident masked rows.
        assert max(driver.rank_excess([steps]), default=0.0) < 1e-5


def test_parameter_arithmetic_of_the_cell():
    """The whole model is the published 30.53 B, the stage the cell holds
    (layers 0-5, every expert, embedding and head) 4.361 B."""
    from cellbench import harness, weights
    from cellbench.counts import sdar as counts

    cfg = harness.load_cell("sdar_blockdiff_gen").config
    shapes = weights.param_shapes(weights.build_model(cfg))
    size = lambda tree: sum(l.size for l in jax.tree_util.tree_leaves(tree))
    assert size(shapes) == counts.model_params(cfg)
    assert round(size(shapes) / 1e9, 3) == 4.361
    assert round(counts.model_params(cfg, 48) / 1e9, 2) == 30.53
    layer = size(shapes["block_0"])
    assert round(layer / 1e6, 2) == 623.12
    assert round(size(shapes["block_0"]["moe"]) / 1e6, 2) == 604.24
