"""graftserve: paged KV pool + continuous-batching scheduler.

Two contracts under test. Determinism: every request served through the
scheduler is bit-identical to its solo `generate()` decode, regardless
of arrival order, slot assignment, sampling config, or eviction timing
(slot reuse across requests makes this doubly a cross-request-leakage
check). Backpressure: page-pool exhaustion surfaces as a blocked
reserve / bounded-queue `queue.Full`, never as an OOM or a retrace.
"""

import dataclasses
import queue
import threading
import time

import numpy as np
import pytest

from cloud_tpu.serving.kvpool import PagePool


class TestPagePool:

    def test_rejects_degenerate_pools(self):
        with pytest.raises(ValueError):
            PagePool(1, 16, 2)  # scratch page alone is not a pool
        with pytest.raises(ValueError):
            PagePool(4, 0, 2)
        with pytest.raises(ValueError):
            PagePool(4, 16, 0)

    def test_capacity_excludes_scratch_page(self):
        pool = PagePool(8, 16, 4)
        assert pool.capacity == 7
        assert pool.available() == 7

    def test_pages_needed_final_token_not_written(self):
        pool = PagePool(16, 4, 8)
        # A slot writes bucket + max_new - 1 positions: the final
        # sampled token is returned, never cached.
        assert pool.pages_needed(4, 1) == 1
        assert pool.pages_needed(4, 2) == 2
        assert pool.pages_needed(3, 2) == 1
        assert pool.pages_needed(8, 9) == 4

    def test_pages_needed_rejects_over_slot_requests(self):
        pool = PagePool(16, 4, pages_per_slot=2)
        with pytest.raises(ValueError):
            pool.pages_needed(8, 2)  # 9 tokens > 2 pages * 4

    def test_reserve_free_roundtrip_never_hands_out_scratch(self):
        pool = PagePool(5, 16, 4)
        pages = pool.reserve(4)
        assert sorted(pages) == [1, 2, 3, 4]  # page 0 stays scratch
        assert pool.available() == 0
        pool.free(pages)
        assert pool.available() == 4

    def test_reserve_zero_is_empty(self):
        pool = PagePool(4, 16, 4)
        assert pool.reserve(0) == []

    def test_reserve_over_capacity_raises_immediately(self):
        pool = PagePool(4, 16, 8)
        with pytest.raises(ValueError):
            pool.reserve(4)  # could never succeed: capacity is 3

    def test_exhaustion_is_a_timeout_not_an_error(self):
        pool = PagePool(4, 16, 4)
        held = pool.reserve(3)
        assert pool.reserve(1, timeout=0.05) is None
        pool.free(held)
        assert pool.reserve(1, timeout=0.05) is not None

    def test_blocked_reserve_wakes_on_free(self):
        pool = PagePool(3, 16, 2)
        held = pool.reserve(2)
        got = []
        waiter = threading.Thread(
            target=lambda: got.append(pool.reserve(1, timeout=10)))
        waiter.start()
        time.sleep(0.05)
        assert not got  # still blocked while the pool is empty
        pool.free(held[:1])
        waiter.join(timeout=10)
        assert got and got[0] is not None and len(got[0]) == 1

    def test_close_unblocks_reserve_with_none(self):
        pool = PagePool(3, 16, 2)
        pool.reserve(2)
        got = []
        waiter = threading.Thread(
            target=lambda: got.append(pool.reserve(1, timeout=10)))
        waiter.start()
        time.sleep(0.05)
        pool.close()
        waiter.join(timeout=10)
        assert got == [None]

    def test_double_free_and_out_of_range_free_raise(self):
        pool = PagePool(4, 16, 3)
        pages = pool.reserve(2)
        pool.free(pages)
        with pytest.raises(ValueError):
            pool.free(pages)  # double free
        with pytest.raises(ValueError):
            pool.free([0])  # scratch is not freeable
        with pytest.raises(ValueError):
            pool.free([99])

    def test_page_vec_is_full_width_scratch_padded(self):
        pool = PagePool(8, 16, pages_per_slot=4)
        vec = pool.page_vec([3, 1])
        assert vec.shape == (4,)
        assert vec.dtype == np.int32
        np.testing.assert_array_equal(vec, [3, 1, 0, 0])

    def test_reserve_waiters_gauge_tracks_blocked_reserve(self):
        """graftlens starvation signal: the waiter count is live while
        a reserve blocks and returns to zero on every exit path."""
        pool = PagePool(3, 16, 2)
        assert pool.reserve_waiters() == 0
        assert pool.pool_stats()["reserve_waiters"] == 0
        held = pool.reserve(2)
        seen = []
        waiter = threading.Thread(
            target=lambda: pool.reserve(1, timeout=10) and None)
        waiter.start()
        for _ in range(100):
            time.sleep(0.005)
            count = pool.reserve_waiters()
            if count:
                seen.append(count)
                break
        assert seen == [1]
        pool.free(held[:1])
        waiter.join(timeout=10)
        assert pool.reserve_waiters() == 0
        # The timeout path decrements too (no leaked waiter).
        assert pool.reserve(2, timeout=0.05) is None
        assert pool.reserve_waiters() == 0


# -- scheduler end-to-end (jit-heavy: slow tier) ----------------------


@pytest.fixture(scope="module")
def model():
    import jax.numpy as jnp

    from cloud_tpu.models import TransformerLM
    return TransformerLM(vocab_size=64, num_layers=2, num_heads=2,
                         d_model=32, d_ff=64, max_seq_len=32,
                         compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model):
    import jax
    import jax.numpy as jnp
    return model.init(jax.random.PRNGKey(1),
                      jnp.zeros((1, 4), jnp.int32))["params"]


def _oracle(model, params, req):
    """Solo generate() — the scheduler's bit-identical reference."""
    import jax
    import jax.numpy as jnp

    from cloud_tpu.models import generate
    toks = generate(model, params,
                    jnp.asarray(req.prompt, jnp.int32)[None],
                    req.max_new_tokens,
                    rng=jax.random.PRNGKey(req.rng_seed),
                    temperature=req.temperature, top_k=req.top_k,
                    top_p=req.top_p, eos_token=req.eos_token)
    return np.asarray(toks)[0]


def _mixed_requests():
    """8 requests x mixed lengths x every sampling mode, 2 slots'
    worth of concurrency -> guaranteed slot reuse and eviction."""
    from cloud_tpu.serving import ServeRequest
    rng = np.random.default_rng(7)
    configs = [
        dict(temperature=0.0),
        dict(temperature=1.0),
        dict(temperature=0.7, top_k=8),
        dict(temperature=0.9, top_p=0.9),
        dict(temperature=0.8, top_k=12, top_p=0.95),
        dict(temperature=0.0),
        dict(temperature=1.3),
        dict(temperature=0.6, top_k=4),
    ]
    requests = []
    for i, cfg in enumerate(configs):
        plen = int(rng.integers(2, 10))
        requests.append(ServeRequest(
            prompt=rng.integers(1, 64, (plen,)).astype(np.int32).tolist(),
            max_new_tokens=int(rng.integers(2, 8)),
            rng_seed=100 + i, **cfg))
    return requests


@pytest.mark.slow
class TestSchedulerDeterminism:

    def test_randomized_arrival_bit_identical_to_solo(self, model,
                                                      params):
        from cloud_tpu.serving import Scheduler
        requests = _mixed_requests()
        order = np.random.default_rng(3).permutation(len(requests))
        with Scheduler(model, params, slots=2, page_size=16) as sched:
            futures = {int(i): sched.submit(requests[int(i)],
                                            timeout=30)
                       for i in order}
            results = {i: f.result(timeout=300)
                       for i, f in futures.items()}
        for i, req in enumerate(requests):
            np.testing.assert_array_equal(
                results[i].tokens, _oracle(model, params, req),
                err_msg="request {} diverged from solo "
                        "generate()".format(i))

    def test_early_eos_eviction_matches_generate(self, model, params):
        from cloud_tpu.serving import Scheduler, ServeRequest
        base = ServeRequest(prompt=[5, 9, 3], max_new_tokens=8,
                            temperature=0.0, rng_seed=11)
        free_run = _oracle(model, params, base)
        # eos = the 2nd greedy continuation token: the engine must
        # evict the slot early and host-fill the eos tail exactly as
        # generate()'s done-latch does.
        eos = int(free_run[len(base.prompt) + 1])
        req = dataclasses.replace(base, eos_token=eos)
        with Scheduler(model, params, slots=2) as sched:
            res = sched.submit(req, timeout=30).result(timeout=300)
        np.testing.assert_array_equal(res.tokens,
                                      _oracle(model, params, req))

    def test_degenerate_budgets_complete_without_slots(self, model,
                                                       params):
        from cloud_tpu.serving import Scheduler, ServeRequest
        with Scheduler(model, params, slots=2) as sched:
            zero = sched.submit(ServeRequest(
                prompt=[4, 2], max_new_tokens=0)).result(timeout=60)
            one = sched.submit(ServeRequest(
                prompt=[4, 2], max_new_tokens=1, temperature=0.0,
                rng_seed=5), timeout=30).result(timeout=300)
        np.testing.assert_array_equal(zero.tokens, [4, 2])
        np.testing.assert_array_equal(
            one.tokens,
            _oracle(model, params, ServeRequest(
                prompt=[4, 2], max_new_tokens=1, temperature=0.0,
                rng_seed=5)))

    def test_submit_validates_requests(self, model, params):
        from cloud_tpu.serving import Scheduler, ServeRequest
        sched = Scheduler(model, params, slots=2)  # no threads needed
        with pytest.raises(ValueError):
            sched.submit(ServeRequest(prompt=[], max_new_tokens=2))
        with pytest.raises(ValueError):
            sched.submit(ServeRequest(prompt=[1], max_new_tokens=-1))
        with pytest.raises(ValueError):
            sched.submit(ServeRequest(prompt=[1] * 30,
                                      max_new_tokens=10))
        with pytest.raises(ValueError):
            sched.submit(ServeRequest(prompt=[1], max_new_tokens=2,
                                      top_k=0))
        with pytest.raises(ValueError):
            sched.submit(ServeRequest(prompt=[1], max_new_tokens=2,
                                      top_p=1.5))


@pytest.mark.slow
class TestBackpressure:

    def test_pool_exhaustion_blocks_admission_no_retrace(self, model,
                                                         params):
        from cloud_tpu.parallel import runtime
        from cloud_tpu.serving import Scheduler, ServeRequest
        # capacity = 1 page; every request needs exactly 1 page, so at
        # most ONE request is ever resident even with 2 slots free —
        # each later admission must block on the pool, then proceed
        # when the eviction returns its page.
        requests = [ServeRequest(prompt=[2 + i, 7, 11],
                                 max_new_tokens=6, temperature=0.0,
                                 rng_seed=i) for i in range(3)]
        with Scheduler(model, params, slots=2, page_size=16,
                       num_pages=2) as sched:
            first = [f.result(timeout=300) for f in
                     [sched.submit(r, timeout=30) for r in requests]]
            warm = runtime.compile_stats()
            second = [f.result(timeout=300) for f in
                      [sched.submit(r, timeout=30) for r in requests]]
            after = runtime.compile_stats()
        # Exhaustion produced zero retraces/compiles once warm: paging
        # is host bookkeeping, never a new executable.
        assert after["n_traces"] == warm["n_traces"]
        assert after["n_compiles"] == warm["n_compiles"]
        for req, a, b in zip(requests, first, second):
            np.testing.assert_array_equal(a.tokens,
                                          _oracle(model, params, req))
            np.testing.assert_array_equal(a.tokens, b.tokens)

    def test_oversized_request_rejected_not_deadlocked(self, model,
                                                       params):
        from cloud_tpu.serving import Scheduler, ServeRequest
        sched = Scheduler(model, params, slots=2, page_size=16,
                          num_pages=2)
        with pytest.raises(ValueError):
            # Needs 2 pages; the pool can only ever free 1 — waiting
            # could never succeed, so submit() rejects it outright.
            sched.submit(ServeRequest(prompt=[1] * 16,
                                      max_new_tokens=8))

    def test_bounded_queue_backpressure_reaches_caller(self, model,
                                                      params):
        from cloud_tpu.serving import Scheduler, ServeRequest
        sched = Scheduler(model, params, slots=2, max_queue=1)
        # Not started: nothing drains the queue, so the second submit
        # hits the bound and the caller sees queue.Full — backpressure
        # by contract, not a silent unbounded buffer.
        req = ServeRequest(prompt=[1, 2], max_new_tokens=2)
        sched.submit(req, timeout=1)
        with pytest.raises(queue.Full):
            sched.submit(req, timeout=0.05)


class TestSchedulerStats:
    """stats() is the bench/loadgen readout: it must be total — no
    traffic, hit-only traffic, and miss-only traffic all snapshot
    cleanly (empty histograms read count 0, never raise)."""

    def test_zero_request_snapshot(self, model, params):
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=2)  # never started
        stats = sched.stats()
        assert stats["requests_completed"] == 0
        assert stats["prefix_hit_rate"] == 0.0
        assert stats["spec_accept_rate"] == 0.0
        for key in ("ttft", "ttft_hit", "ttft_miss", "token_latency",
                    "queue_wait", "reserve_wait"):
            assert stats[key]["count"] == 0
        assert stats["pool"]["reserve_waiters"] == 0

    def test_hit_only_traffic(self, model, params):
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=2)
        sched._record_ttft(0.01, hit=True)
        sched._record_ttft(0.03, hit=True)
        stats = sched.stats()
        assert stats["prefix_hit_rate"] == 1.0
        assert stats["ttft_hit"]["count"] == 2
        assert stats["ttft_miss"]["count"] == 0
        assert stats["ttft"]["count"] == 2

    def test_miss_only_traffic(self, model, params):
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=2)
        sched._record_ttft(0.02, hit=False)
        stats = sched.stats()
        assert stats["prefix_hit_rate"] == 0.0
        assert stats["ttft_hit"]["count"] == 0
        assert stats["ttft_miss"]["count"] == 1

    @pytest.mark.parametrize("depths", [(1, 16, 17), (5, 31), (32,)])
    def test_kv_walk_sums(self, model, params, depths):
        """`kv_live_tokens` sums the occupied slots' depths a tick and
        `kv_walked_tokens` what the paged kernel's walk fetches for
        them: the kernel's own helper, whole groups of pages."""
        import types

        from cloud_tpu.ops.paged_attention import (group_pages,
                                                   walked_tokens)
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=4, page_size=4)
        group = group_pages(4, model.num_heads, model.d_model, 4, 1,
                            model.max_seq_len // 4)
        assert sched._kv_group == group
        for slot, depth in enumerate(depths):
            sched._slots[slot] = types.SimpleNamespace(
                request=types.SimpleNamespace(prompt=[1] * (depth - 1)),
                emitted=[2],
                rec=types.SimpleNamespace(token_times=[], rid=None))
        fetched = (np.zeros(4, np.int32), np.zeros(4, bool))
        sched._distribute(fetched, 0.01, 0.0)
        stats = sched.stats()
        assert stats["kv_live_tokens"] == sum(depths)
        assert stats["kv_walked_tokens"] == sum(
            walked_tokens(d, 4, group) for d in depths)
        assert stats["kv_live_tokens"] <= stats["kv_walked_tokens"]
        # The next tick sees every slot one token deeper.
        sched._distribute(fetched, 0.01, 0.0)
        assert sched.stats()["kv_live_tokens"] == 2 * sum(depths) + len(
            depths)

    def test_partial_wait_histograms(self, model, params):
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=2)
        sched._queue_wait_hist.observe(0.004)
        stats = sched.stats()
        assert stats["queue_wait"]["count"] == 1
        assert stats["reserve_wait"]["count"] == 0
