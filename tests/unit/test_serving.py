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

    @pytest.mark.parametrize("copied", [False, True])
    @pytest.mark.parametrize("depths", [(1, 16, 17), (5, 31), (32,)])
    def test_kv_walk_sums(self, model, params, depths, copied):
        """`kv_live_tokens` sums the occupied slots' depths a tick and
        `kv_walked_tokens` what the paged kernel's walk fetches for
        them: the kernel's own helper, whole groups of pages, or whole
        pages where the kernel copies its live pages itself (rows of
        128 lanes, 8-row float32 pages)."""
        import types

        import jax
        import jax.numpy as jnp

        from cloud_tpu.models import TransformerLM
        from cloud_tpu.ops.paged_attention import (copies_pages,
                                                   group_pages,
                                                   walked_tokens)
        from cloud_tpu.serving import Scheduler
        page = 4
        if copied:
            model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                                  d_model=128, d_ff=64, max_seq_len=32,
                                  compute_dtype=jnp.float32)
            params = model.init(jax.random.PRNGKey(1),
                                jnp.zeros((1, 4), jnp.int32))["params"]
            page = 8
        assert copies_pages(page, model.d_model, 4) == copied
        sched = Scheduler(model, params, slots=4, page_size=page)
        group = 1 if copied else group_pages(
            page, model.num_heads, model.d_model, 4, 1,
            model.max_seq_len // page)
        assert sched._kv_group == group
        for slot, depth in enumerate(depths):
            sched._slots[slot] = types.SimpleNamespace(
                request=types.SimpleNamespace(prompt=[1] * (depth - 1)),
                emitted=[2],
                rec=types.SimpleNamespace(token_times=[], rid=None))
        fetched = (np.zeros(4, np.int32), np.zeros(4, bool))
        live = [(slot, state) for slot, state in enumerate(sched._slots)
                if state is not None]
        sched._distribute(live, fetched, 0.01, 0.0)
        stats = sched.stats()
        assert stats["kv_live_tokens"] == sum(depths)
        assert stats["kv_walked_tokens"] == sum(
            walked_tokens(d, page, group) for d in depths)
        assert stats["kv_live_tokens"] <= stats["kv_walked_tokens"]
        # The next tick sees every slot one token deeper.
        sched._distribute(live, fetched, 0.01, 0.0)
        assert sched.stats()["kv_live_tokens"] == 2 * sum(depths) + len(
            depths)

    def test_partial_wait_histograms(self, model, params):
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=2)
        sched._queue_wait_hist.observe(0.004)
        stats = sched.stats()
        assert stats["queue_wait"]["count"] == 1
        assert stats["reserve_wait"]["count"] == 0


# -- the one-deep tick pipeline (fast tier) ---------------------------


def _greedy(prompt, max_new, seed=0, **kwargs):
    from cloud_tpu.serving import ServeRequest
    return ServeRequest(prompt=list(prompt), max_new_tokens=max_new,
                        temperature=0.0, rng_seed=seed, **kwargs)


class TestTickPipeline:
    """Tick n+1 is on the device before tick n is fetched, and whatever
    does not dispatch a tick first drains the one in flight."""

    def test_dispatch_runs_one_ahead_and_drains(self, model, params,
                                                monkeypatch):
        from cloud_tpu.serving import Scheduler
        from tests.unit.tick_log import TickLog, check_order
        sched = Scheduler(model, params, slots=2, page_size=16)
        log = TickLog(sched, monkeypatch)
        with sched:
            sched.warmup([4], sampling_configs=[(("temperature",
                                                  0.0),)])
            # The counter resets where `tick_paces` does, and warm-up's
            # last (blind) tick is not left for the traffic's count.
            stats = sched.stats()
            assert (stats["ticks"], stats["tick_paces"],
                    stats["ticks_overlapped"]) == (0, 0, 0)
            assert sched._flight is None
            check_order(log.since())
            mark = log.mark()
            requests = [_greedy([3 + i, 5, 7], 12, seed=i)
                        for i in range(4)]
            futures = [sched.submit(r, timeout=30) for r in requests]
            results = [f.result(timeout=300) for f in futures]
            sched.assert_drained()
            stats = sched.stats()
        entries = log.since(mark)
        ticks, overlapped = check_order(entries)
        assert stats["ticks"] == ticks
        assert stats["ticks_overlapped"] == overlapped
        # Two slots decode twelve tokens side by side: the steady state
        # is there, and it overlaps.
        assert overlapped >= 8
        kinds = [e[0] for e in entries]
        for i, entry in enumerate(entries):
            if entry[0] == "nap":
                # A nap and the idle wait come straight after a drain.
                assert "drain" in kinds[max(i - 2, 0):i], entries[:i + 1]
        assert stats["tick_paces"] == sum(
            e == ("nap", 0.005) for e in entries)
        # close() left nothing in flight either.
        check_order(log.since())
        for req, res in zip(requests, results):
            np.testing.assert_array_equal(res.tokens,
                                          _oracle(model, params, req))

    def test_row_of_a_blind_tick_reaches_no_request(self, model, params):
        """Commit reads the dispatch's snapshot: a tick dispatched
        before its predecessor's finish was known carries a row for the
        slot that finished (and may since hold another request). That
        row is dropped; the rows of slots still running are kept."""
        import types

        from cloud_tpu.serving import Scheduler, reqtrace
        from cloud_tpu.serving.scheduler import _Flight

        def flight(t_dispatch, slots):
            return _Flight(None, {}, reqtrace.TickRecord(
                0, 0, 4, t_dispatch, overlapped=False), slots)

        def state(prompt_len):
            return types.SimpleNamespace(
                request=types.SimpleNamespace(prompt=[1] * prompt_len),
                emitted=[2], trace_ticks=0,
                rec=types.SimpleNamespace(token_times=[], rid=None))

        sched = Scheduler(model, params, slots=4, page_size=4)
        gone, stays, newcomer = state(3), state(5), state(7)
        first = flight(1.0, [gone, stays, None, None])
        # Since the dispatch: slot 0's request finished (tick before)
        # and another was inserted there; slot 2 was filled too.
        sched._slots = [newcomer, stays, state(2), None]
        fetched = (np.array([-1, 9, -1, -1], np.int32),
                   np.zeros(4, np.int32))
        sched._commit_tick(first, fetched, {}, 2.0)
        assert (first.record.t_fetched, first.record.live,
                first.record.kv_live) == (2.0, 1, 5 + 1)
        assert stays.emitted == [2, 9]
        assert stays.rec.token_times == [2.0]
        assert gone.emitted == newcomer.emitted == [2]
        stats = sched.stats()
        assert stats["ticks"] == 1
        assert stats["kv_live_tokens"] == 5 + 1
        assert stats["token_latency"]["count"] == 1
        # Dispatch to commit for the first tick after a drain ...
        assert stats["token_latency"]["sum"] == pytest.approx(1.0)
        # ... and commit to commit while the pipeline is full.
        sched._commit_tick(
            flight(1.5, [newcomer, stays, None, None]),
            (np.array([4, 8, -1, -1], np.int32), np.zeros(4, np.int32)),
            {}, 2.5)
        stats = sched.stats()
        assert stats["token_latency"]["sum"] == pytest.approx(1.0 + 2 * 0.5)
        assert stats["decode_gap"]["count"] == 2
        assert newcomer.emitted == [2, 4] and stays.emitted == [2, 9, 8]


def _spec_pair(model, params):
    import jax.numpy as jnp

    from cloud_tpu.models import TransformerLM
    from cloud_tpu.serving.smoke import split_draft
    draft_model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                                d_model=32, d_ff=64, max_seq_len=32,
                                compute_dtype=jnp.float32)
    target, draft = split_draft(params, draft_layers=1)
    return target, dict(draft_model=draft_model, draft_params=draft,
                        spec_k=2)


@pytest.mark.parametrize("engine", ["plain", "speculative"])
def test_collisions_with_a_tick_in_flight_stay_bit_identical(
        model, params, engine):
    """Two slots, a dozen short requests, some with an early eos:
    finishes, evictions and inserts into the slot just freed all meet
    a tick in flight, and every result is its solo `generate()`."""
    from cloud_tpu.serving import Scheduler
    target, extra = params, {}
    if engine == "speculative":
        target, extra = _spec_pair(model, params)
    rng = np.random.default_rng(31)
    requests = []
    for i in range(12):
        plen = int(rng.integers(2, 7))
        req = _greedy(rng.integers(1, 64, (plen,)).tolist(),
                      int(rng.integers(2, 5)), seed=200 + i)
        if i % 3 == 0:
            # eos = its own 2nd continuation token: it leaves early.
            free_run = _oracle(model, target, dataclasses.replace(
                req, max_new_tokens=4))
            req = dataclasses.replace(req, max_new_tokens=4,
                                      eos_token=int(free_run[plen + 1]))
        requests.append(req)
    refs = [_oracle(model, target, r) for r in requests]
    with Scheduler(model, target, slots=2, page_size=16,
                   **extra) as sched:
        futures = [sched.submit(r, timeout=30) for r in requests]
        results = [f.result(timeout=300) for f in futures]
        sched.assert_drained(clear_prefix=True)
        stats = sched.stats()
        assert sched.pool.leak_report() == {}
    for i, (ref, res) in enumerate(zip(refs, results)):
        np.testing.assert_array_equal(
            res.tokens, ref, err_msg="request {}".format(i))
    assert stats["requests_completed"] == 12
    assert stats["ticks_overlapped"] > 0


@pytest.mark.parametrize("engine", ["plain", "speculative"])
def test_finished_slot_retires_itself(model, params, engine):
    """Engine level: after a tick reports a slot finished, a second
    tick WITHOUT `evict` returns -1 and `finished` 0 for it, leaves its
    `slot_steps` and `slot_valid` rows as they were and every page it
    does not own byte for byte."""
    import jax

    from cloud_tpu.serving.engine import DecodeEngine, _map_attention
    target, extra = params, {}
    if engine == "speculative":
        target, extra = _spec_pair(model, params)
    eng = DecodeEngine(model, target, slots=2, page_size=4, num_pages=17,
                       **extra)
    k = eng.spec_k if eng.spec_on else 0
    sampling = dict(temperature=0.0, top_k=None, top_p=None,
                    eos_token=None)
    # Slot 0 leaves after its first tick (max_new 2); slot 1 stays.
    plans = [([5, 9, 3], 2, [1, 2]), ([7, 2, 8, 4, 6], 12, [3, 4, 5, 6, 7])]
    for slot, (prompt, max_new, pages) in enumerate(plans):
        result = eng.prefill(np.asarray(prompt, np.int32), max_new,
                             jax.random.PRNGKey(slot), sampling)
        vec = eng.pool_page_vec(pages)
        eng.insert(slot, result, vec, vec, sampling)

    def rows(cache):
        found = []
        _map_attention(cache, lambda att: found.append(att) or att)
        return found

    first = np.asarray(eng.tick())
    finished_row = first[k + 2] if eng.spec_on else first[1]
    assert list(finished_row) == [1, 0]
    assert first[0][0] >= 0
    assert list(np.asarray(eng.ctl["active"])) == [False, True]
    caches = [eng.cache] + ([eng.draft_cache] if eng.spec_on else [])
    before = [[{name: np.asarray(att[name]) for name in
                ("slot_steps", "slot_valid", "key_pages", "value_pages")}
               for att in rows(cache)] for cache in caches]

    second = np.asarray(eng.tick())
    if eng.spec_on:
        assert list(second[:k + 1, 0]) == [-1] * (k + 1)
        assert second[k + 1, 0] == 0 and second[k + 2, 0] == 0
        assert second[k + 1, 1] >= 1
    else:
        assert second[0, 0] == -1 and second[1, 0] == 0
        assert second[0, 1] >= 0
    caches = [eng.cache] + ([eng.draft_cache] if eng.spec_on else [])
    owned = plans[0][2]
    others = [p for p in range(17) if p not in owned + plans[1][2]]
    for was, cache in zip(before, caches):
        for old, att in zip(was, rows(cache)):
            assert np.asarray(att["slot_steps"])[0] == old["slot_steps"][0]
            np.testing.assert_array_equal(
                np.asarray(att["slot_valid"])[0], old["slot_valid"][0])
            # The running slot did move.
            assert np.asarray(att["slot_steps"])[1] > old["slot_steps"][1]
            for name in ("key_pages", "value_pages"):
                now = np.asarray(att[name])
                # Pages of nobody (scratch, page 0, aside) and every
                # valid position of the finished slot's own pages.
                np.testing.assert_array_equal(now[others[1:]],
                                              old[name][others[1:]])
                flat_now = now[owned].reshape(-1, now.shape[-1])
                flat_old = old[name][owned].reshape(-1, now.shape[-1])
                valid = old["slot_valid"][0][:flat_now.shape[0]]
                np.testing.assert_array_equal(flat_now[valid],
                                              flat_old[valid])


# -- a request's key schedule, on the host (fast tier) ----------------

KEY_CAP = 160  # the engines below: step_keys of KEY_CAP - 1 rows


@pytest.mark.parametrize("seed", [0, 1, 7, 2**31 - 1, 2**31 + 5,
                                  2**32 - 1, 2**40 + 3, -1, -5])
def test_host_key_is_prngkey(seed):
    import jax

    from cloud_tpu.serving.engine import host_prng_key
    key = host_prng_key(seed)
    assert key.dtype == np.uint32 and key.shape == (2,)
    np.testing.assert_array_equal(key, np.asarray(jax.random.PRNGKey(seed)))


@pytest.mark.parametrize("n", [1, 2, 31, 127, KEY_CAP - 1])
def test_host_split_is_jax_split_row_for_row(n):
    import jax

    from cloud_tpu.serving.engine import host_split
    for seed in (0, 3, 12345, 2**31 + 7):
        key = jax.random.PRNGKey(seed)
        got = host_split(np.asarray(key), n)
        assert got.dtype == np.uint32 and got.shape == (n, 2)
        np.testing.assert_array_equal(got,
                                      np.asarray(jax.random.split(key, n)))
        # A device key reads back to the same rows.
        np.testing.assert_array_equal(host_split(key, n), got)


@pytest.fixture(scope="module")
def key_engine(model, params):
    from cloud_tpu.serving.engine import DecodeEngine
    return DecodeEngine(model, params, slots=2, page_size=4, num_pages=17,
                        max_new_cap=KEY_CAP)


def _run_prefill(engine, form, prompt, max_new, rng, sampling, **kwargs):
    if form == "whole":
        return engine.prefill(prompt, max_new, rng, sampling, **kwargs)
    chunked = engine.prefill_chunks(prompt, max_new, rng, sampling, 2,
                                    **kwargs)
    while True:
        result = chunked.step()
        if result is not None:
            return result


@pytest.mark.parametrize("form", ["whole", "chunked"])
@pytest.mark.parametrize("n", [1, 2, 31, 127, KEY_CAP - 1])
def test_sampled_prefill_arms_generates_schedule(key_engine, form, n):
    """`step_keys` of a sampled request: `generate()`'s schedule, the
    rows `jax.random.split(key, max_new_tokens - 1)` gives, from a host
    key and from a device key alike; and the prefill samples with the
    key `generate()` would."""
    import jax

    from cloud_tpu.serving.engine import host_prng_key
    sampling = dict(temperature=0.8, top_k=None, top_p=None,
                    eos_token=None)
    prompt = np.asarray([5, 9, 3], np.int32)
    firsts = []
    for seed in (4, 2**31 + 9):
        rng = jax.random.PRNGKey(seed)
        key, _ = jax.random.split(rng)
        want = np.asarray(jax.random.split(key, n))
        for given in (host_prng_key(seed), rng):
            result = _run_prefill(key_engine, form, prompt, n + 1, given,
                                  sampling)
            keys = result.step_keys
            assert keys.dtype == np.uint32
            assert keys.shape == (KEY_CAP - 1, 2)
            np.testing.assert_array_equal(keys[:n], want)
            assert not keys[n:].any()
            firsts.append(result.first_token)
            key_engine.release_prefill(result)
    assert firsts[0] == firsts[1] and firsts[2] == firsts[3]


@pytest.mark.parametrize("form", ["whole", "chunked"])
def test_greedy_prefill_reads_no_key_and_one_token(key_engine, form,
                                                   monkeypatch):
    """A request with temperature 0 reads no key: no split is
    dispatched, its `rng` is never looked at, its rows stay zero, and
    the one thing read back from the device is its first token."""
    import jax

    from cloud_tpu.parallel import runtime

    def refuse(*args, **kwargs):
        raise AssertionError("a greedy admission split a key")

    class Untouchable:
        def __array__(self, *args, **kwargs):
            raise AssertionError("a greedy admission read its rng")

    fetched = []
    fetch = runtime.device_fetch

    def logged_fetch(tree):
        fetched.append(tree)
        return fetch(tree)

    monkeypatch.setattr(jax.random, "split", refuse)
    monkeypatch.setattr(runtime, "device_fetch", logged_fetch)
    sampling = dict(temperature=0.0, top_k=None, top_p=None,
                    eos_token=None)
    result = _run_prefill(key_engine, form, np.asarray([5, 9, 3], np.int32),
                          40, Untouchable(), sampling)
    assert result.step_keys.shape == (KEY_CAP - 1, 2)
    assert not result.step_keys.any()
    (first,) = fetched
    assert first.shape == (1,) and int(first[0]) == result.first_token
    key_engine.release_prefill(result)


def test_key_override_rows_reach_the_schedule(key_engine):
    """The requeue hook: the prefill key and the rest of the ORIGINAL
    schedule are taken as handed in, whatever the seed would give."""
    import jax

    sampling = dict(temperature=0.8, top_k=None, top_p=None,
                    eos_token=None)
    rng = jax.random.PRNGKey(11)
    key, _ = jax.random.split(rng)
    rows = np.asarray(jax.random.split(key, 9))
    prompt = np.asarray([5, 9, 3], np.int32)
    whole = key_engine.prefill(prompt, 10, rng, sampling)
    # After three tokens: row 2 samples the continuation's first
    # token, rows 3.. its ticks.
    cont = np.concatenate([prompt, [whole.first_token, 1, 2]]).astype(
        np.int32)
    for form in ("whole", "chunked"):
        result = _run_prefill(key_engine, form, cont, 7, None, sampling,
                              key_override=(rows[2], rows[3:]))
        np.testing.assert_array_equal(result.step_keys[:6], rows[3:])
        assert not result.step_keys[6:].any()
        key_engine.release_prefill(result)
    key_engine.release_prefill(whole)


# -- the one-deep prefill pipeline (fast tier) ------------------------


def _miss(i, max_new=6, **kwargs):
    """Distinct first tokens: no request is another's prefix."""
    return _greedy([10 + i, 5, 7], max_new, seed=i, **kwargs)


class TestPrefillPipeline:
    """Prefill n+1 is on the device before prefill n's first token is
    fetched, and the prefill in flight is collected before the
    admission thread waits for anything but the device."""

    def test_dispatch_runs_one_ahead_within_a_window(self, model, params,
                                                     monkeypatch):
        from cloud_tpu.serving import Scheduler
        from tests.unit.tick_log import PrefillLog, check_prefill_order
        sched = Scheduler(model, params, slots=4, page_size=16)
        log = PrefillLog(sched, monkeypatch)
        with sched:
            sched.warmup([4], sampling_configs=[(("temperature",
                                                  0.0),)])
            # Warm-up's own overlaps are not left for the traffic's
            # count, and nothing of it is still in flight.
            assert sched.stats()["prefills_overlapped"] == 0
            assert sched._miss_flight is None
            check_prefill_order(log.since())
            mark = log.mark()
            requests = [_miss(i) for i in range(4)]
            with log.hold():
                futures = [sched.submit(r, timeout=30) for r in requests]
            results = [f.result(timeout=300) for f in futures]
            sched.assert_drained()
            stats = sched.stats()
        entries = [e for e in log.since(mark) if e[0] != "wait"]
        # One window of four misses: each is dispatched before the one
        # ahead of it is fetched; the last is fetched when the queue is
        # found empty, not at the next arrival.
        n0 = entries[0][1]
        assert entries == [
            ("dispatch", n0), ("dispatch", n0 + 1), ("fetch", n0),
            ("dispatch", n0 + 2), ("fetch", n0 + 1),
            ("dispatch", n0 + 3), ("fetch", n0 + 2), ("fetch", n0 + 3)]
        prefills, overlapped = check_prefill_order(log.since(mark))
        assert (prefills, overlapped) == (4, 3)
        assert stats["prefills_overlapped"] == overlapped
        assert stats["prefix_misses"] == prefills
        # close() left nothing in flight either.
        check_prefill_order(log.since())
        assert sched._miss_flight is None
        for req, res in zip(requests, results):
            np.testing.assert_array_equal(res.tokens,
                                          _oracle(model, params, req))
            # The record's phases still tile TTFT.
            phases = res.trace.phases()
            assert sum(phases[k] for k in (
                "queue", "window", "reserve", "prefill")) == \
                pytest.approx(res.ttft_s, abs=1e-9)

    def test_lone_request_is_fetched_without_an_arrival(self, model,
                                                        params,
                                                        monkeypatch):
        from cloud_tpu.serving import Scheduler
        from tests.unit.tick_log import PrefillLog, check_prefill_order
        sched = Scheduler(model, params, slots=2, page_size=16)
        log = PrefillLog(sched, monkeypatch)
        with sched:
            request = _miss(0)
            result = sched.submit(request, timeout=30).result(timeout=300)
            entries = log.since()
            stats = sched.stats()
        at = entries.index(("dispatch", 0))
        # Straight after its dispatch, before the thread looks at its
        # queue again.
        assert entries[at + 1] == ("fetch", 0), entries
        check_prefill_order(entries)
        assert stats["prefills_overlapped"] == 0
        np.testing.assert_array_equal(result.tokens,
                                      _oracle(model, params, request))

    def test_collected_before_a_reservation_that_waits(self, model,
                                                       params,
                                                       monkeypatch):
        """Two requests that each need most of the pool: the second's
        pages are freed only by ticks of the first, so the first is
        fetched and handed over before the thread waits for them."""
        from cloud_tpu.serving import Scheduler
        from tests.unit.tick_log import PrefillLog, check_prefill_order
        sched = Scheduler(model, params, slots=2, page_size=4,
                          num_pages=8, prefix_cache=False)
        log = PrefillLog(sched, monkeypatch)
        requests = [_miss(i, max_new=14) for i in range(2)]
        with sched:
            with log.hold():
                futures = [sched.submit(r, timeout=30) for r in requests]
            results = [f.result(timeout=300) for f in futures]
            sched.assert_drained()
            stats = sched.stats()
            assert sched.pool.leak_report() == {}
        entries = log.since()
        check_prefill_order(entries)
        at = entries.index(("dispatch", 0))
        assert entries[at + 1:at + 3] == [("fetch", 0),
                                          ("wait", "pages")], entries
        assert stats["prefills_overlapped"] == 0
        assert stats["reserve_wait"]["count"] == 2
        for req, res in zip(requests, results):
            np.testing.assert_array_equal(res.tokens,
                                          _oracle(model, params, req))

    def test_collected_before_a_hit_is_handed_over(self, model, params,
                                                   monkeypatch):
        from cloud_tpu.serving import Scheduler
        from tests.unit.tick_log import PrefillLog, check_prefill_order
        # Windows of one: the hit's turn comes with the miss in flight.
        sched = Scheduler(model, params, slots=4, page_size=4,
                          admission_window=1)
        log = PrefillLog(sched, monkeypatch)
        shared = [9, 8, 7, 6, 5, 4, 3, 2]
        first = _greedy(shared + [1], 4, seed=1)
        miss = _miss(0)
        hit = _greedy(shared + [11, 12], 4, seed=2)
        with sched:
            sched.submit(first, timeout=30).result(timeout=300)
            mark = log.mark()
            with log.hold():
                futures = [sched.submit(r, timeout=30)
                           for r in (miss, hit)]
            results = [f.result(timeout=300) for f in futures]
            sched.assert_drained()
        entries = [e for e in log.since(mark) if e != ("wait", "queue")]
        n = entries[0][1]
        assert entries == [("dispatch", n), ("fetch", n),
                           ("wait", "hit")], entries
        check_prefill_order(log.since())
        assert results[1].prefix_len == 8 and results[0].prefix_len == 0
        for req, res in zip((miss, hit), results):
            np.testing.assert_array_equal(res.tokens,
                                          _oracle(model, params, req))

    def test_collected_at_close(self, model, params, monkeypatch):
        """A stop that finds a prefill in flight: its token is fetched,
        its request fails like every pending one, its pages go back."""
        from cloud_tpu.serving import Scheduler
        from tests.unit.tick_log import PrefillLog, check_prefill_order
        sched = Scheduler(model, params, slots=2, page_size=16)
        log = PrefillLog(sched, monkeypatch)
        log.on_dispatch = lambda n: sched._stop.set()
        sched.start()
        try:
            future = sched.submit(_miss(0), timeout=30)
            sched._prefill_thread.join(timeout=60)
            assert not sched._prefill_thread.is_alive()
        finally:
            sched.close()
        entries = [e for e in log.since() if e[0] != "wait"]
        assert entries == [("dispatch", 0), ("fetch", 0)]
        check_prefill_order(log.since())
        assert sched._miss_flight is None
        with pytest.raises(RuntimeError, match="scheduler closed"):
            future.result(timeout=30)
        assert sched.pool.leak_report() == {}


@pytest.mark.parametrize("engine", ["plain", "speculative"])
def test_overlapping_prefills_stay_bit_identical(model, params, engine,
                                                 monkeypatch):
    """Sampled and greedy requests admitted in overlapping prefills,
    with no eager key program anywhere in their admission: every
    result is its solo `generate()`, and the pool ends leak-free."""
    import jax

    from cloud_tpu.serving import Scheduler, ServeRequest
    from tests.unit.tick_log import PrefillLog, check_prefill_order
    target, extra = params, {}
    if engine == "speculative":
        target, extra = _spec_pair(model, params)
    configs = [dict(temperature=0.0), dict(temperature=1.0),
               dict(temperature=0.7, top_k=8)]
    rng = np.random.default_rng(35)
    requests = [ServeRequest(
        prompt=[20 + i] + rng.integers(1, 64, (2 + i % 2,)).tolist(),
        max_new_tokens=int(rng.integers(3, 8)), rng_seed=300 + i,
        **configs[i % 3]) for i in range(8)]
    refs = [_oracle(model, target, r) for r in requests]
    sched = Scheduler(model, target, slots=4, page_size=16, **extra)
    log = PrefillLog(sched, monkeypatch)

    def refuse(*args, **kwargs):
        raise AssertionError("an admission made a key on the device")

    with sched:
        # Every program is traced here, before the names are taken away.
        sched.warmup([4], sampling_configs=[tuple(c.items())
                                            for c in configs])
        mark = log.mark()
        monkeypatch.setattr(jax.random, "split", refuse)
        monkeypatch.setattr(jax.random, "PRNGKey", refuse)
        with log.hold():
            futures = [sched.submit(r, timeout=30) for r in requests]
        results = [f.result(timeout=300) for f in futures]
        sched.assert_drained(clear_prefix=True)
        stats = sched.stats()
        assert sched.pool.leak_report() == {}
    prefills, overlapped = check_prefill_order(log.since(mark))
    assert prefills == 8 and overlapped == 7
    assert stats["prefills_overlapped"] == overlapped
    for i, (ref, res) in enumerate(zip(refs, results)):
        np.testing.assert_array_equal(
            res.tokens, ref, err_msg="request {}".format(i))
