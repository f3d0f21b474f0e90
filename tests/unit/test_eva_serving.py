"""A toy `EvaByteLM` through `Scheduler`: the prefill a window at a time and
ticks across window ends against the plain reference
(`cellbench/reference/evabyte.py`), slots at different depths in one tick, a
slot used again, the pool's accounting of both kinds of page, and what the
model's class refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.reference import common as ref
from cellbench.reference import evabyte as reference
from cloud_tpu.models import EvaByteLM, TransformerLM
from cloud_tpu.models.decoding import empty_cache
from cloud_tpu.serving import Scheduler, ServeRequest
from cloud_tpu.serving.kvpool import RingSummaryPagePool

F32, BF16 = jnp.float32, jnp.bfloat16
TOY = dict(vocab_size=64, num_layers=2, num_heads=2, d_model=32, d_ff=64,
           max_seq_len=128, window_size=32, chunk_size=4, num_pred_heads=3)
CFG = dict(window_size=32, chunk_size=4, rms_norm_eps=1e-5, rope_theta=100000.0,
           num_pred_heads=3, vocab_size=64)
# (prompt, new tokens): prefills of one to three windows, a window's end met
# while decoding by all but the fourth, by the first twice.
REQUESTS = [(50, 50), (7, 30), (33, 20), (90, 4), (31, 5), (64, 3)]


@pytest.fixture(scope="module")
def params():
    model = EvaByteLM(compute_dtype=F32, **TOY)
    tree = model.init(jax.random.PRNGKey(1),
                      jnp.zeros((1, 8), jnp.int32))["params"]
    # Far from uniform in-chunk weights, so that a wrong summary shows.
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * 30 if str(path[-1].key) in ("phi", "mu") else x,
        tree)


def prompts(seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.integers(2, 64, n).tolist(), new) for n, new in REQUESTS]


def served_gaps(params, prompt, tokens):
    """How far each served token's logit lies below the reference's best at
    its position (head 0, float32)."""
    mm = ref.make_mm("float32")
    x = reference.embed(params, jnp.asarray(tokens)[None], CFG)
    for name in reference.layer_names(params):
        x = reference.layer(x, params[name], CFG, mm)
    logits = np.asarray(reference.head(
        x, reference.head_params(params), CFG, mm)[0])
    rows = np.arange(len(prompt) - 1, len(tokens) - 1)
    return logits[rows].max(-1) - logits[rows, np.asarray(tokens)[rows + 1]]


# The bfloat16 program against the float32 reference: its first choice lies
# within 0.05 of the reference's best (logits here are of order 1; the
# float32 program's is the reference's own at every position).
@pytest.mark.parametrize("dtype,tolerance", [(F32, 0.0), (BF16, 0.05)])
def test_prefill_by_windows_and_ticks_across_window_ends(params, dtype,
                                                         tolerance):
    model = EvaByteLM(compute_dtype=dtype, **TOY)
    with Scheduler(model, params, slots=3, page_size=4,
                   strict_no_retrace=True) as sched:
        sched.warmup([8, 64])
        assert sched.trie is None and sched.stats()["prefill_chunk_size"] == 32
        asked = prompts()
        # Six requests over three slots: different depths in one tick, and
        # every slot used again after a longer or shorter request.
        futures = [sched.submit(ServeRequest(prompt=p, max_new_tokens=n,
                                             temperature=0.0))
                   for p, n in asked]
        results = [f.result(timeout=600) for f in futures]
        # Window ends in prefills and in ticks compiled nothing.
        sched.engine.check_no_retrace()
        stats = sched.stats()
        sched.assert_drained()
        assert sched.pool.leak_report() == {}
        assert sched.pool.available() == sched.pool.capacity
    for (prompt, new), result in zip(asked, results):
        assert len(result.tokens) == len(prompt) + new
        gaps = served_gaps(params, prompt, result.tokens)
        assert gaps.max() <= tolerance, gaps.max()
    # Every tick's rows, from the depths alone.
    lay = model.layout
    rows = [lay.rows_read(len(p) + i) for p, n in asked for i in range(n - 1)]
    assert stats["eva_summary_rows_read"] == sum(s for s, _ in rows)
    assert stats["eva_rows_read"] == sum(s + r for s, r in rows)
    assert stats["kv_live_tokens"] == stats["eva_rows_read"]
    assert stats["kv_walked_tokens"] >= stats["kv_live_tokens"]
    assert stats["eva_windows_closed"] == {
        "ticks": sum((len(p) + i + 1) % 32 == 0
                     for p, n in asked for i in range(n - 1)),
        "prefills": sum(len(p) // 32 for p, _ in asked)}
    assert stats["eva_cache_bytes"] == 0 and stats["prefix_hits"] == 0
    assert stats["pool"]["page_kinds"] == {"ring_pages_per_slot": 8,
                                           "summary_pages_per_slot": 8}


def test_window_form_leaves_summaries_and_a_partial_window(params):
    """The prefill's chunk: logits of every window are the sequence form's,
    and the cache it leaves is the summaries of whole chunks and the last,
    partial window: `layout.rows` rows, never one a token."""
    model = EvaByteLM(compute_dtype=F32, **TOY)
    dense = model.clone(decode=True)
    tokens = np.random.default_rng(3).integers(2, 64, 75)
    full = model.apply({"params": params}, jnp.asarray(tokens)[None])[0]
    cache = empty_cache(dense, 1)
    att = cache["block_0"]["attention"]
    assert att["cached_key"].shape == (1, 32 + 32, 2, 16)
    for lo in range(0, 75, 32):
        n = min(32, 75 - lo)
        chunk, mask = np.zeros((1, 32), np.int32), np.zeros((1, 32), bool)
        chunk[0, :n], mask[0, :n] = tokens[lo:lo + n], True
        logits, out = dense.apply({"params": params, "cache": cache},
                                  jnp.asarray(chunk), jnp.asarray(mask),
                                  mutable=["cache"])
        cache = out["cache"]
        np.testing.assert_allclose(logits[0, :n], full[lo:lo + n], atol=2e-5)
    att = cache["block_0"]["attention"]
    assert int(att["token_count"][0]) == 75
    written = np.flatnonzero(np.abs(np.asarray(att["cached_key"][0])).sum((1, 2)))
    # 18 whole chunks' summaries (rows 31 ... 14) and a ring still holding
    # all 32 rows, 11 of them the last window's.
    assert list(written) == list(range(32 - 18, 64))
    assert list(np.flatnonzero(att["slot_valid"][0])) == list(range(16, 32 + 11))


def test_a_slot_used_again_sees_none_of_the_last_requests_rows(params):
    """One slot: a request of three windows, then a short one in the same
    slot, whose tokens are those it gets from an empty server."""
    model = EvaByteLM(compute_dtype=F32, **TOY)
    rng = np.random.default_rng(5)
    long, short = rng.integers(2, 64, 80).tolist(), rng.integers(2, 64, 9).tolist()
    ask = lambda sched, p, n: sched.submit(ServeRequest(
        prompt=p, max_new_tokens=n, temperature=0.0)).result(timeout=600)
    with Scheduler(model, params, slots=1, page_size=4) as sched:
        ask(sched, long, 40)
        after = ask(sched, short, 30).tokens
    with Scheduler(model, params, slots=1, page_size=4) as sched:
        alone = ask(sched, short, 30).tokens
    assert list(after) == list(alone)
    assert served_gaps(params, short, after).max() == 0.0


def test_engine_prefill_runs_the_windows_to_the_end(params):
    """`DecodeEngine.prefill` (what a caller without a scheduler uses) is the
    chunked path run to its end: the first token is the reference's, and the
    result carries a cache of `layout.rows` rows."""
    model = EvaByteLM(compute_dtype=F32, **TOY)
    sched = Scheduler(model, params, slots=2, page_size=4)
    prompt = np.random.default_rng(7).integers(2, 64, 70).tolist()
    result = sched.engine.prefill(
        prompt, 4, jax.random.PRNGKey(0),
        dict(temperature=0.0, top_k=None, top_p=None, eos_token=None))
    assert result.prompt_len == 70 and result.bucket == 32
    att = result.pcache["block_0"]["attention"]
    assert att["cached_key"].shape[1] == model.layout.rows == 64
    assert int(att["token_count"][0]) == 70
    assert served_gaps(params, prompt, prompt + [result.first_token]).max() == 0.0
    sched.engine.release_prefill(result)


def test_pages_needed_at_the_cells_numbers():
    lay = EvaByteLM(max_seq_len=32768, **{k: v for k, v in TOY.items()
                                          if k not in ("max_seq_len", "window_size",
                                                       "chunk_size")}).layout
    pool = RingSummaryPagePool(lay, 16 * 256 + 1, 16)
    assert pool.pages_per_slot == 256
    # The longest request of the cell: 128 ring pages and 8 summary pages for
    # each of the 15 windows it begins; kept a row a token it would be 1848.
    assert pool.pages_needed(28032, 1536) == 128 + 15 * 8
    assert pool.pages_needed(8832, 1536) == 128 + 6 * 8
    assert pool.pages_needed(100, 20) == 8 + 8
    assert pool.pages_needed(32768 - 1536, 1537) == 256
    with pytest.raises(ValueError, match="ring pages .* summary pages"):
        pool.pages_needed(32768, 2)
    pages = pool.reserve(pool.pages_needed(28032, 1536))
    vec = pool.page_vec(pages)
    assert np.count_nonzero(vec) == 248 and not vec[:8].any()
    assert pool.pool_stats()["pages_held"] == 248
    pool.free(pages)
    assert pool.leak_report() == {}


@pytest.mark.parametrize("what,kwargs,message", [
    ("host_tier", dict(host_tier=True), "host_tier is not served"),
    ("spec_k", dict(spec_k=2), "spec_k > 0 and quantized pages are not served"),
    ("kv_dtype", dict(kv_dtype="int8"), "quantized pages are not served"),
])
def test_refused_by_the_models_class(params, what, kwargs, message):
    model = EvaByteLM(compute_dtype=F32, **TOY)
    if what == "spec_k":
        kwargs = dict(kwargs, draft_model=model, draft_params=params)
    with pytest.raises(NotImplementedError, match=message):
        Scheduler(model, params, slots=2, page_size=4, **kwargs)


def test_prefix_cache_is_off_and_reuse_refused_with_a_message(params):
    model = EvaByteLM(compute_dtype=F32, **TOY)
    sched = Scheduler(model, params, slots=2, page_size=4, prefix_cache=True)
    assert sched.trie is None
    engine = sched.engine
    with pytest.raises(NotImplementedError, match="a prefix hit is not served"):
        engine._gather(None, engine.cache, None, None)
    with pytest.raises(NotImplementedError, match="the host tier is not served"):
        engine.snapshot_pages([1])
    with pytest.raises(ValueError, match="page is its chunk"):
        Scheduler(model, params, slots=2, page_size=8)
    # Another class's pool is the plain one.
    other = TransformerLM(vocab_size=64, num_layers=1, num_heads=2, d_model=32,
                          d_ff=64, max_seq_len=32, compute_dtype=F32)
    plain = other.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 4), jnp.int32))["params"]
    assert type(Scheduler(other, plain, slots=2, page_size=8).pool).__name__ == (
        "PagePool")
