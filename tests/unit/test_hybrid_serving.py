"""`NemotronHLM` through `Scheduler`: a model whose Mamba-2 layers keep a
fixed-size state a slot beside the paged pool. At toy widths in float32 on
the CPU every served token is the plain reference's first choice over prompt
+ served tokens (cellbench/reference/nemotron_h.py), whatever the slot went
through: insertion after another request's eviction, a chunked prefill, a
resize that moves it to another row. What pages cannot give back is refused
or never reached: no prefix hit, no host tier, no speculation.
"""

import copy

import numpy as np
import pytest

from cellbench import harness, weights
from cellbench.drivers import closed_loop_hybrid as driver
from tests.cellbench import toy_sizes_nemotron_h as toy

CELL = "nemotron3s_decode_reason"
SEED = 77


@pytest.fixture(scope="module")
def built():
    cell = copy.deepcopy(harness.load_cell(CELL))
    toy.shrink(cell)
    model = weights.build_model(cell.config)
    shapes = weights.param_shapes(model)
    params = driver.seeded_init(weights.make_params(shapes, SEED), cell.config,
                                SEED)
    return cell.config, model, shapes, params


def prompts_of(lengths, seed=5):
    rng = harness.rng(seed, 1)
    return [rng.integers(2, 256, n).astype(np.int32) for n in lengths]


def worst_gap(built, served, prompts, max_new):
    cfg, _, shapes, _ = built
    sequences = [(tokens, len(p)) for tokens, p in zip(served, prompts)]
    gaps, _ = driver.served_gaps(cfg, shapes, SEED, sequences, 64, max_new)
    assert len(gaps) == sum(len(t) - len(p) for t, p in zip(served, prompts))
    return max(gaps)


def serve(sched, prompts, new_tokens):
    from cloud_tpu.serving import ServeRequest

    futures = [sched.submit(ServeRequest(
        prompt=p.tolist(), max_new_tokens=n, temperature=0.0))
        for p, n in zip(prompts, new_tokens)]
    return [np.asarray(f.result(timeout=600).tokens) for f in futures]


@pytest.mark.parametrize("chunk", [None, 8], ids=["whole", "chunked"])
def test_prefill_and_decode_through_the_pool_are_the_reference(built, chunk):
    """Six requests through two slots: every slot is used again after an
    eviction, with prompts shorter and longer than the Mamba chunk (8) and no
    multiple of the page (8); whole prefills and prefills in chunks of 8."""
    from cloud_tpu.serving import Scheduler

    _, model, _, params = built
    prompts = prompts_of((5, 13, 30, 41, 9, 24))
    with Scheduler(model, params, slots=2, page_size=8,
                   prefill_chunk=chunk) as sched:
        served = serve(sched, prompts, [14] * 6)
    stats = sched.stats()       # closed: the last tick's counters are in
    assert worst_gap(built, served, prompts, 14) < 1e-4
    # Two state layers: a step of a slot is counted in each.
    assert stats["ssm_slot_steps"] == 2 * (stats["tokens_emitted"] - 6)
    state = 2 * 2 * (4 * 8 * 16 * 4 + 3 * (32 + 2 * 2 * 16) * 4)
    assert stats["ssm_state_bytes"] == stats["pool"]["state_bytes"] == state
    assert stats["pool"]["cache_bytes_total"] == (
        stats["pool"]["kv_bytes_total"] + state)
    # Pages of the one attention layer only.
    assert stats["kv"]["page_bytes"] == 2 * 8 * 2 * 16 * 4
    assert stats["moe_pairs_routed"] > stats["moe_pairs_held"] > 0
    # Two rows choosing 4 of 16 experts leave held experts untouched: the
    # tick's expert layers run grouped.
    assert stats["moe_pairs_dense"] == 0


def test_a_tick_that_touches_every_held_expert_runs_batched(built):
    """Twelve slots choosing 4 of 16 experts are expected to touch 97 % of
    the held ones, so the tick's two expert layers run batched over the 4
    held experts (`moe.batched_over_held`): `moe_pairs_dense` counts slots
    advanced x held experts a layer, restarts with `moe_pairs_held` at
    warm-up's end, and every served token is still the reference's."""
    from cloud_tpu.models import moe
    from cloud_tpu.serving import Scheduler

    _, model, _, params = built
    assert moe.batched_over_held(12, 4, 16, None)
    prompts = prompts_of((5, 13, 30, 41, 9, 24, 17, 8, 33, 12, 21, 6, 27, 10))
    with Scheduler(model, params, slots=12, page_size=8) as sched:
        sched.warmup([8, 16, 32, 64])
        warm = sched.stats()
        assert warm["ticks"] == warm["moe_pairs_held"] == 0
        assert warm["moe_pairs_dense"] == 0
        served = serve(sched, prompts, [10] * len(prompts))
    stats = sched.stats()
    assert worst_gap(built, served, prompts, 10) < 1e-4
    assert stats["moe_pairs_held"] > 0
    # 4 held experts in each of 2 expert layers, for every slot a tick
    # advanced (`ssm_slot_steps` counts those once a state layer, of 2).
    assert stats["moe_pairs_dense"] == 4 * stats["ssm_slot_steps"] > 0


def test_a_resize_that_moves_slots_keeps_every_request_right(built):
    """Four slots, two short requests and two long ones; the shrink to two
    slots waits for the short ones and moves the long ones' rows (state,
    window, page table) to slots 0 and 1 mid-flight."""
    from cloud_tpu.serving import Scheduler

    _, model, _, params = built
    prompts = prompts_of((7, 19, 11, 33), seed=6)
    new_tokens = [3, 26, 3, 26]
    with Scheduler(model, params, slots=4, page_size=8,
                   ladder=(2, 4)) as sched:
        from cloud_tpu.serving import ServeRequest

        futures = [sched.submit(ServeRequest(
            prompt=p.tolist(), max_new_tokens=n, temperature=0.0))
            for p, n in zip(prompts, new_tokens)]
        futures[0].result(timeout=600)
        futures[2].result(timeout=600)
        sched.request_resize(2, reason="test", timeout=300)
        served = [np.asarray(f.result(timeout=600).tokens) for f in futures]
        events = sched.stats()["geometry"]["resize_events"]
    assert [(e["from"], e["to"]) for e in events] == [(4, 2)]
    assert worst_gap(built, served, prompts, 26) < 1e-4


def test_a_repeated_prompt_is_no_prefix_hit(built):
    from cloud_tpu.serving import Scheduler

    _, model, _, params = built
    prompt = prompts_of((24,), seed=8)
    with Scheduler(model, params, slots=2, page_size=8) as sched:
        assert sched.trie is None and sched.host_tier is None
        first = serve(sched, prompt, [6])
        second = serve(sched, prompt, [6])
        stats = sched.stats()
    assert np.array_equal(first[0], second[0])
    assert stats["prefix_hits"] == 0 and stats["prefix_misses"] == 2
    assert stats["prefix_tokens_served"] == 0 and "prefix_cache" not in stats


def test_what_pages_cannot_give_back_is_refused(built):
    from cloud_tpu.serving import Scheduler
    from cloud_tpu.serving.engine import DecodeEngine

    _, model, _, params = built
    with pytest.raises(NotImplementedError, match="recurrent layers"):
        Scheduler(model, params, slots=2, page_size=8, draft_model=model,
                  draft_params=params, spec_k=2)
    with pytest.raises(NotImplementedError, match="recurrent layers"):
        Scheduler(model, params, slots=2, page_size=8, host_tier=True)
    engine = DecodeEngine(model, params, slots=2, page_size=8, num_pages=17)
    assert engine.state_layers == 2
    sampling = dict(temperature=0.0, top_k=None, top_p=None, eos_token=None)
    import jax
    with pytest.raises(NotImplementedError, match="prefix hit"):
        engine.prefill(np.arange(2, 20), 4, jax.random.PRNGKey(0), sampling,
                       prefix_len=8, gather_vec=engine.pool_page_vec([1]))
    with pytest.raises(NotImplementedError, match="host tier"):
        engine.snapshot_pages([1])
