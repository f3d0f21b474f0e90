"""The tree the engine holds (`engine.held_params`): the leaves a model's
modules cast to the compute type at every use are cast once, when the
engine takes the tree, and nothing the programs compute changes by it.

`TransformerLM` with a bfloat16 compute type and the float32 parameters
`model.init` gives (the gpt2-xl cells' pairing) is the class that
declares such leaves (`promoted_at_use`). Held to it here, at toy
widths on the CPU: logits and tokens bit for bit, the LayerNorm vectors
that must NOT be cast (the control), the classes that declare nothing,
what is kept of the tree given, the draft model's tree, the counters.
"""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.models import TransformerLM, generate
from cloud_tpu.serving import DecodeEngine, Scheduler, ServeRequest
from cloud_tpu.serving import engine as engine_lib

BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
TOY = dict(vocab_size=64, num_heads=2, d_model=32, d_ff=64, max_seq_len=32,
           norm_eps=1e-5)
GREEDY = dict(temperature=0.0, top_k=None, top_p=None, eos_token=None)
SAMPLED = dict(temperature=0.8, top_k=8, top_p=None, eos_token=None)


def perturbed(model, seed=1):
    """`model.init`'s tree with every leaf moved, so that biases and
    norm vectors are in play and none is a round number."""
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def leaves_by_path(tree):
    return {"/".join(str(k.key) for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def is_norm(path):
    return path.split("/")[-2].startswith("ln_")


@pytest.fixture(scope="module")
def model():
    return TransformerLM(num_layers=2, compute_dtype=BF16, **TOY)


@pytest.fixture(scope="module")
def params(model):
    return perturbed(model)


def solo(model, params, req):
    """The request's tokens from `generate()` alone over `params`."""
    tokens = generate(model, params, jnp.asarray(req.prompt, jnp.int32)[None],
                      req.max_new_tokens, rng=jax.random.PRNGKey(req.rng_seed),
                      temperature=req.temperature, top_k=req.top_k)
    return np.asarray(tokens)[0]


def new_engine(model, params, **extra):
    return DecodeEngine(model, params, slots=2, page_size=8, num_pages=9,
                        **extra)


# ------------------------------------------------- (a) nothing changes

def served_logits(engine, tree, sampling):
    """One prefill and six ticks of two requests through `engine`'s own
    programs with `tree` as their parameters: the tokens, and the
    logits its decode clones (`_dense` for the prefill, `_paged` for
    the tick) give from `tree` over the very caches the programs
    read."""
    from cloud_tpu.models.decoding import empty_cache

    engine._params = tree
    prompts = [np.asarray([5, 9, 3, 17, 40], np.int32),
               np.asarray([7, 2, 8], np.int32)]
    logits, tokens = [], []
    for slot, prompt in enumerate(prompts):
        padded = np.zeros((1, 8), np.int32)
        padded[0, :len(prompt)] = prompt
        logits.append(jax.jit(lambda p, c, t, m: engine._dense.apply(
            {"params": p, "cache": c}, t, m, mutable=["cache"])[0])(
                tree, engine_lib._plain(empty_cache(engine._dense, 1)),
                jnp.asarray(padded), jnp.asarray(padded > 0)))
        result = engine.prefill(prompt, 8, jax.random.PRNGKey(slot),
                                sampling)
        tokens.append([result.first_token])
        vec = engine.pool_page_vec([1 + 2 * slot, 2 + 2 * slot])
        engine.insert(slot, result, vec, vec, sampling)
    tick_logits = jax.jit(lambda p, c, ctl: engine._paged.apply(
        {"params": p, "cache": c}, ctl["cur_tok"][:, None],
        ctl["active"][:, None], mutable=["cache"])[0])
    for _ in range(6):
        logits.append(tick_logits(tree, engine.cache, engine.ctl))
        out = np.asarray(engine.tick())
        for slot in range(2):
            tokens[slot].append(int(out[0, slot]))
    engine.evict(np.ones((2,), bool))
    return [np.asarray(x) for x in logits], tokens


@pytest.mark.parametrize("sampling", [GREEDY, SAMPLED],
                         ids=["greedy", "sampled"])
def test_held_tree_gives_the_given_trees_logits_and_tokens(model, params,
                                                           sampling):
    engine = new_engine(model, params)
    held = engine._params
    want_logits, want_tokens = served_logits(engine, params, sampling)
    got_logits, got_tokens = served_logits(engine, held, sampling)
    assert want_tokens == got_tokens
    for want, got in zip(want_logits, got_logits):
        assert want.dtype == np.float32 and np.abs(want).max() > 0.1
        np.testing.assert_array_equal(want, got)


def test_served_tokens_are_generates_from_the_given_tree(model, params):
    """Through the Scheduler, which is handed the float32 tree and
    nothing else: every request is its solo `generate()` over that
    tree, and nothing is traced after warm-up."""
    rng = np.random.default_rng(0)
    requests = [ServeRequest(
        prompt=rng.integers(2, 64, n).tolist(), max_new_tokens=new,
        rng_seed=10 + n, temperature=temp, top_k=top_k)
        for n, new, temp, top_k in ((5, 9, 0.0, None), (12, 10, 0.8, 8),
                                    (3, 7, 0.0, None), (17, 6, 0.8, 8))]
    with Scheduler(model, params, slots=2, page_size=8) as sched:
        sched.warmup([8, 16, 32], sampling_configs=[
            (), (("temperature", 0.8), ("top_k", 8))])
        results = [f.result(timeout=600) for f in
                   [sched.submit(r, timeout=60) for r in requests]]
        sched.engine.check_no_retrace()
    for req, res in zip(requests, results):
        np.testing.assert_array_equal(res.tokens, solo(model, params, req))


# ------------------------------------------ (b) the rule, and a control

def test_held_tree_casts_what_the_modules_cast_and_no_more(model, params):
    given = leaves_by_path(params)
    held = leaves_by_path(new_engine(model, params)._params)
    assert given.keys() == held.keys()
    norms = [p for p in given if is_norm(p)]
    assert len(norms) == 2 * (2 * model.num_layers + 1)
    for path, leaf in held.items():
        assert given[path].dtype == F32
        if is_norm(path):
            assert leaf is given[path]
        else:
            assert leaf.dtype == BF16, path
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(given[path].astype(BF16)))


def test_casting_the_norm_vectors_too_would_change_the_logits(model,
                                                              params):
    """The control that keeps the rule the modules' own: `nn.LayerNorm`
    uses its scale and bias in float32, so a tree with every leaf in
    bfloat16 is another model."""
    tokens = jnp.asarray([[5, 9, 3, 17, 40, 2, 11, 60]], jnp.int32)
    apply = jax.jit(lambda p: model.apply({"params": p}, tokens))
    want = np.asarray(apply(params))
    held = engine_lib.held_params(model, params)
    np.testing.assert_array_equal(want, np.asarray(apply(held)))
    every = jax.tree_util.tree_map(lambda x: x.astype(BF16), params)
    assert np.abs(np.asarray(apply(every)) - want).max() > 1e-3


def test_float32_router_of_an_expert_layer_stays_float32():
    model = TransformerLM(num_layers=1, moe_experts=4, compute_dtype=BF16,
                          **TOY)
    params = perturbed(model)
    tree = engine_lib.held_params(model, params)
    held = leaves_by_path(tree)
    assert held["block_0/moe/router"].dtype == F32
    assert held["block_0/moe/expert_in"].dtype == BF16
    assert held["block_0/moe/expert_out"].dtype == BF16
    tokens = jnp.asarray([[5, 9, 3, 17, 40, 2, 11, 60]], jnp.int32)
    apply = jax.jit(lambda p: model.apply({"params": p}, tokens,
                                          mutable=["losses"])[0])
    np.testing.assert_array_equal(np.asarray(apply(params)),
                                  np.asarray(apply(tree)))


def test_a_tree_in_the_compute_type_is_held_as_given():
    model = TransformerLM(num_layers=1, compute_dtype=F32, **TOY)
    params = perturbed(model)
    assert engine_lib.held_params(model, params) is params
    # A cast that would widen a leaf is the module's, at use.
    narrow = jax.tree_util.tree_map(lambda x: x.astype(BF16), params)
    assert engine_lib.held_params(model, narrow) is narrow


# ---------------------------------- (c) classes that declare nothing

def toy_family(workload):
    """A served cell's model at its family's toy widths, with the
    cell's own parameter and compute types (bfloat16, as given)."""
    import copy

    from cellbench import harness, weights
    from tests.cellbench import conftest as exaone
    from tests.cellbench import toy_sizes_evabyte, toy_sizes_nemotron_h

    shrink = {"kexaone_decode_long": exaone.shrink_exaone_moe,
              "nemotron3s_decode_reason": toy_sizes_nemotron_h.shrink,
              "evabyte_decode_32k": toy_sizes_evabyte.shrink}[workload]
    cell = copy.deepcopy(harness.load_cell(workload))
    types = {k: cell.config["assumed"][k]
             for k in ("param_dtype", "compute_dtype")}
    shrink(cell)
    cell.config["assumed"].update(types)
    model = weights.build_model(cell.config)
    return model, weights.make_params(weights.param_shapes(model), 7), cell


@pytest.mark.parametrize("workload", [
    "kexaone_decode_long", "nemotron3s_decode_reason", "evabyte_decode_32k"])
def test_bfloat16_classes_hold_the_tree_they_are_given(workload):
    """`LlamaLM`, `NemotronHLM` and `EvaByteLM` declare no leaf, so the
    engine's programs read the very arrays it was handed and cannot
    differ from the parent's."""
    model, params, cell = toy_family(workload)
    assert type(model).__name__ in ("LlamaLM", "NemotronHLM", "EvaByteLM")
    assert not hasattr(model, "promoted_at_use")
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(params)} >= {BF16}
    page = int(cell.config["assumed"]["page_size"])
    layout = getattr(model, "layout", None)
    rows = layout.rows if layout is not None else model.max_seq_len
    engine = DecodeEngine(model, params, slots=2, page_size=page,
                          num_pages=2 * rows // page + 1)
    assert engine._params is params
    given, held = leaves_by_path(params), leaves_by_path(engine._params)
    assert all(held[path] is given[path] for path in given)
    assert engine.weight_bytes_served == engine.weight_bytes_given == sum(
        leaf.nbytes for leaf in given.values())


# ------------------------------------------ (d) what the engine keeps

def test_engine_keeps_no_leaf_it_has_cast(model):
    params = perturbed(model, seed=3)
    cast = [weakref.ref(leaf) for path, leaf in leaves_by_path(params).items()
            if not is_norm(path)]
    engine = new_engine(model, params)
    del params
    gc.collect()
    assert cast and all(ref() is None for ref in cast)
    assert engine.weight_bytes_served < engine.weight_bytes_given


def test_stats_count_the_bytes_given_and_the_bytes_served(model, params):
    given = leaves_by_path(params)
    norm_bytes = sum(leaf.nbytes for path, leaf in given.items()
                     if is_norm(path))
    total = sum(leaf.nbytes for leaf in given.values())
    with Scheduler(model, params, slots=2, page_size=8) as sched:
        stats = sched.stats()
        assert stats["weight_bytes_given"] == total
        assert stats["weight_bytes_served"] == (
            norm_bytes + (total - norm_bytes) // 2)
        # The tree is an argument of every program: a driver may put
        # another in its place, and the count follows the tree read.
        sched.engine._params = params
        assert sched.stats()["weight_bytes_served"] == total
        assert sched.stats()["weight_bytes_given"] == total


# ------------------------------------------------ (e) the draft's tree

def test_draft_parameters_take_the_same_path(model, params):
    from cloud_tpu.serving.smoke import split_draft

    draft_model = TransformerLM(num_layers=1, compute_dtype=BF16, **TOY)
    target, draft = split_draft(params, draft_layers=1)
    engine = new_engine(model, target, draft_model=draft_model,
                        draft_params=draft, spec_k=2)
    given, held = leaves_by_path(draft), leaves_by_path(engine._draft_params)
    for path, leaf in held.items():
        if is_norm(path):
            assert leaf is given[path]
        else:
            assert leaf.dtype == BF16 and given[path].dtype == F32
    both = sum(leaf.nbytes for tree in (target, draft)
               for leaf in jax.tree_util.tree_leaves(tree))
    assert engine.weight_bytes_given == both
    assert both // 2 < engine.weight_bytes_served < both
    requests = [ServeRequest(prompt=[5, 9, 3, 17], max_new_tokens=9,
                             rng_seed=4, temperature=0.0),
                ServeRequest(prompt=[7, 2, 8], max_new_tokens=7,
                             rng_seed=5, temperature=0.8, top_k=8)]
    with Scheduler(model, target, slots=2, page_size=8,
                   draft_model=draft_model, draft_params=draft,
                   spec_k=2) as sched:
        results = [f.result(timeout=600) for f in
                   [sched.submit(r, timeout=60) for r in requests]]
    for req, res in zip(requests, results):
        np.testing.assert_array_equal(res.tokens, solo(model, target, req))


# ---------------------------------------------- (f) one program, once

def test_cast_is_one_program_and_warm_ticks_trace_nothing(model, params,
                                                          monkeypatch):
    calls = []
    plain = engine_lib._cast_weights
    monkeypatch.setattr(engine_lib, "_cast_weights",
                        lambda leaves, dtype: calls.append(len(leaves))
                        or plain(leaves, dtype))
    engine = new_engine(model, params)
    cast = [p for p in leaves_by_path(params) if not is_norm(p)]
    assert calls == [len(cast)]
    for warm in (False, True):
        for slot, prompt in enumerate(([5, 9, 3], [7, 2, 8, 4, 6])):
            result = engine.prefill(np.asarray(prompt, np.int32), 6,
                                    jax.random.PRNGKey(slot), GREEDY)
            vec = engine.pool_page_vec([1 + 2 * slot, 2 + 2 * slot])
            engine.insert(slot, result, vec, vec, GREEDY)
        for _ in range(4):
            engine.tick()
        engine.evict(np.ones((2,), bool))
        if not warm:
            engine.mark_warm()
    engine.check_no_retrace()
    assert calls == [len(cast)]
