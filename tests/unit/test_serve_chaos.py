"""graftstorm: serving-side chaos — fault injection, typed requeue,
SLO-aware admission.

Fast tier pins the rig itself: the serving event grammar
(`slot_hang@tick`, `prefill_fail@tick`, `slot_evict@tick:slot`,
`pool_squeeze@tick:pages`) parses into one-shot tick-indexed events
that fire from `pre_tick` only (never from the training `pre_dispatch`
hook), `PagePool.squeeze` steals free pages without blocking, the
`ServeFault` taxonomy labels faults, and the admission decision is a
pure deterministic function of (request, queue position, histograms,
clock).

Slow tier pins recovery semantics end-to-end: a faulted slot's request
re-prefills from its retained progress with the ORIGINAL rng schedule
re-based, so it completes bit-identical to solo `generate()` under
greedy, nucleus, shared-prefix, and speculative decode; the faulted
slot's pages return to the pool exactly once (drained, leak-free); and
SLO sheds surface as typed `ServeShed` with reason + prediction while
never corrupting the insert-accounting of surviving requests.
"""

import dataclasses
import time

import numpy as np
import pytest

from cloud_tpu.analysis import chaos
from cloud_tpu.serving import faults
from cloud_tpu.serving.kvpool import PagePool


@pytest.fixture(autouse=True)
def _chaos_isolation(monkeypatch):
    monkeypatch.delenv("CLOUD_TPU_CHAOS", raising=False)
    monkeypatch.delenv("CLOUD_TPU_EVENT_LOG", raising=False)
    monkeypatch.delenv("CLOUD_TPU_SERVE_SLO_TTFT", raising=False)
    monkeypatch.delenv("CLOUD_TPU_SERVE_SHED", raising=False)
    chaos.uninstall()
    yield
    chaos.uninstall()


# -- grammar + one-shot semantics (fast) ------------------------------


class TestServeGrammar:

    def test_serving_kinds_parse_with_args(self):
        events = chaos.parse_spec(
            "slot_hang@3, prefill_fail@1,slot_evict@4:1,"
            "pool_squeeze@9:8")
        assert [(e.kind, e.step, e.arg) for e in events] == [
            ("slot_hang", 3, None), ("prefill_fail", 1, None),
            ("slot_evict", 4, 1.0), ("pool_squeeze", 9, 8.0)]

    @pytest.mark.parametrize("bad", [
        "slot_hang@soon",       # non-int tick
        "pool_squeeze@9:many",  # non-float arg
        "explode@3",            # still an unknown kind
    ])
    def test_malformed_raises(self, bad):
        with pytest.raises(ValueError, match="Malformed chaos event"):
            chaos.parse_spec(bad)

    def test_pre_tick_fires_once_with_catch_up(self):
        plan = chaos.ChaosPlan.parse("slot_hang@3,pool_squeeze@9:8")
        assert plan.pre_tick(2) == []
        fired = plan.pre_tick(3)
        assert [e.kind for e in fired] == ["slot_hang"]
        assert plan.pre_tick(3) == []          # one-shot
        # The tick loop idles while no slot is active, so a due event
        # catches up at the NEXT observed tick rather than being lost.
        late = plan.pre_tick(50)
        assert [(e.kind, e.arg) for e in late] == [("pool_squeeze", 8.0)]
        assert plan.remaining() == []

    def test_pre_tick_orders_by_configured_tick(self):
        plan = chaos.ChaosPlan.parse("prefill_fail@7,slot_hang@2")
        assert [e.kind for e in plan.pre_tick(10)] == [
            "slot_hang", "prefill_fail"]

    def test_pre_tick_none_is_noop(self):
        plan = chaos.ChaosPlan.parse("slot_hang@0")
        assert plan.pre_tick(None) == []
        assert [e["kind"] for e in plan.remaining()] == ["slot_hang"]

    def test_hooks_are_disjoint(self):
        # Training dispatches never fire serving events and vice versa:
        # the two hooks see the same plan but disjoint kind sets.
        plan = chaos.ChaosPlan.parse("slot_hang@1,preempt@2")
        plan.pre_dispatch(0, n_steps=2)        # slot_hang@1 not due here
        assert [e["kind"] for e in plan.remaining()] == [
            "slot_hang", "preempt"]
        assert [e.kind for e in plan.pre_tick(100)] == ["slot_hang"]
        from cloud_tpu.training import resilience
        with pytest.raises(resilience.Preemption):
            plan.pre_dispatch(2)


class TestFaultTaxonomy:

    def test_fault_kind_labels(self):
        assert faults.fault_kind(faults.SlotHang("x")) == "slot_hang"
        assert faults.fault_kind(faults.SlotEvicted("x")) == "slot_evict"
        assert faults.fault_kind(
            faults.PrefillFailed("x")) == "prefill_fail"
        assert faults.fault_kind(
            faults.PoolSqueezed("x")) == "pool_squeeze"
        assert faults.fault_kind(faults.ServeShed("x")) == "shed"
        assert faults.fault_kind(ValueError("x")) == "unknown"

    def test_shed_carries_decision_fields(self):
        exc = faults.ServeShed("no", reason="expired",
                               predicted_ttft=0.25, slo_ttft=0.1)
        assert isinstance(exc, faults.ServeFault)
        assert (exc.reason, exc.predicted_ttft, exc.slo_ttft) == (
            "expired", 0.25, 0.1)


class TestPoolSqueeze:

    def test_squeeze_is_nonblocking_and_partial(self):
        pool = PagePool(8, 16, 4)              # capacity 7
        held = pool.reserve(2)
        taken = pool.squeeze(10)               # only 5 free: take 5
        assert len(taken) == 5
        assert pool.available() == 0
        # Squeezed pages are ordinary refcount-1 allocations: freeing
        # them returns the pool to full and leaves no leak.
        pool.free(taken)
        pool.free(held)
        assert pool.available() == 7
        assert pool.leak_report() == {}

    def test_squeeze_empty_pool_takes_nothing(self):
        pool = PagePool(4, 16, 3)
        held = pool.reserve(3)
        assert pool.squeeze(2) == []
        pool.free(held)


# -- admission decision (fast, no threads) ----------------------------


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


def _request(**overrides):
    from cloud_tpu.serving import ServeRequest
    fields = dict(prompt=[1, 2, 3], max_new_tokens=4, temperature=0.0,
                  rng_seed=0)
    fields.update(overrides)
    return ServeRequest(**fields)


class TestAdmissionDecision:

    def test_decision_is_deterministic(self, model, params):
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=2, slo_ttft=0.1,
                          shed_policy="shed")    # never started
        for _ in range(40):
            sched._prefill_hist.observe(0.02)
        now = 1000.0
        req = _request()
        first = sched._admission_decision(req, t_submit=now - 0.01,
                                          position=2, meta={"defers": 0},
                                          now=now)
        again = sched._admission_decision(req, t_submit=now - 0.01,
                                          position=2, meta={"defers": 0},
                                          now=now)
        assert first == again
        assert first[0] == "admit"               # 0.01 + 3*0.02 < 0.1

    def test_deep_queue_position_sheds_predicted(self, model, params):
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=2, slo_ttft=0.1,
                          shed_policy="shed")
        for _ in range(40):
            sched._prefill_hist.observe(0.02)
        now = 1000.0
        verdict, reason, predicted = sched._admission_decision(
            _request(), t_submit=now - 0.01, position=20,
            meta={"defers": 0}, now=now)
        assert (verdict, reason) == ("shed", "predicted")
        assert predicted > 0.1

    def test_accrued_past_slo_sheds_expired(self, model, params):
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=2, slo_ttft=0.1,
                          shed_policy="defer")
        for _ in range(40):
            sched._prefill_hist.observe(0.02)
        now = 1000.0
        verdict, reason, _ = sched._admission_decision(
            _request(), t_submit=now - 0.5, position=20,
            meta={"defers": 0}, now=now)
        # Even under defer policy an already-blown budget sheds: the
        # caller would only see a late failure otherwise.
        assert (verdict, reason) == ("shed", "expired")

    def test_defer_policy_bounds_retries(self, model, params):
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=2, slo_ttft=0.1,
                          shed_policy="defer")
        for _ in range(40):
            sched._prefill_hist.observe(0.02)
        now = 1000.0
        kwargs = dict(t_submit=now - 0.01, position=20, now=now)
        assert sched._admission_decision(
            _request(), meta={"defers": 0}, **kwargs)[0] == "defer"
        verdict, reason, _ = sched._admission_decision(
            _request(), meta={"defers": sched._defer_max}, **kwargs)
        assert (verdict, reason) == ("shed", "deferred")

    def test_policy_off_always_admits(self, model, params):
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=2, slo_ttft=0.001,
                          shed_policy="off")
        assert sched._admission_decision(
            _request(), t_submit=0.0, position=99, meta={"defers": 0},
            now=1000.0) == ("admit", None, None)

    def test_env_knobs_configure_slo(self, model, params, monkeypatch):
        from cloud_tpu.serving import Scheduler
        monkeypatch.setenv("CLOUD_TPU_SERVE_SLO_TTFT", "0.25")
        monkeypatch.setenv("CLOUD_TPU_SERVE_SHED", "defer")
        sched = Scheduler(model, params, slots=2)
        assert sched._slo_ttft == 0.25
        assert sched._shed_policy == "defer"
        monkeypatch.setenv("CLOUD_TPU_SERVE_SHED", "off")
        assert Scheduler(model, params,
                         slots=2)._shed_policy == "off"


# -- recovery end-to-end (jit-heavy: slow tier) -----------------------


def _oracle(model, params, req):
    """Solo generate() — the requeue path's bit-identical reference."""
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


def _drained(sched):
    time.sleep(0.3)
    sched.assert_drained(clear_prefix=True)
    assert sched.pool.leak_report() == {}


@pytest.mark.slow
class TestRequeueBitIdentity:

    def test_greedy_survives_repeated_faults(self, model, params):
        from cloud_tpu.serving import Scheduler, ServeRequest
        requests = [
            ServeRequest(prompt=[5, 6, 7, 8, 9], max_new_tokens=10,
                         temperature=0.0, rng_seed=11),
            ServeRequest(prompt=[9, 8, 7], max_new_tokens=12,
                         temperature=0.0, rng_seed=12),
        ]
        # Ticks 2/5 hang whatever slot is active — a requeued request
        # can be hit AGAIN, which exercises the recursive re-base (the
        # retained schedule is itself already re-based).
        chaos.install("slot_hang@2,slot_hang@5,slot_evict@7:1")
        with Scheduler(model, params, slots=2) as sched:
            futures = [sched.submit(r, timeout=30) for r in requests]
            results = [f.result(timeout=300) for f in futures]
            stats = sched.stats()
            _drained(sched)
        for req, res in zip(requests, results):
            np.testing.assert_array_equal(res.tokens,
                                          _oracle(model, params, req))
        assert sum(stats["faults"].values()) == 3
        assert stats["requeues"] >= 1

    def test_top_p_rng_schedule_rebased(self, model, params):
        from cloud_tpu.serving import Scheduler, ServeRequest
        req = ServeRequest(prompt=[3, 1, 4, 1, 5], max_new_tokens=10,
                           temperature=0.9, top_p=0.9, rng_seed=21)
        chaos.install("slot_hang@3")
        with Scheduler(model, params, slots=2) as sched:
            res = sched.submit(req, timeout=30).result(timeout=300)
            stats = sched.stats()
            _drained(sched)
        # Sampled decode only matches solo generate() if the requeue
        # resumes the ORIGINAL per-step key schedule (a restarted
        # schedule would re-draw the early steps).
        np.testing.assert_array_equal(res.tokens,
                                      _oracle(model, params, req))
        assert stats["faults"] == {"slot_hang": 1}
        assert stats["requeues"] == 1

    def test_prefill_fail_retries_to_completion(self, model, params):
        from cloud_tpu.serving import Scheduler, ServeRequest
        first = ServeRequest(prompt=[2, 4, 6], max_new_tokens=4,
                             temperature=0.0, rng_seed=31)
        second = ServeRequest(prompt=[6, 4, 2, 1], max_new_tokens=6,
                              temperature=0.7, top_k=8, rng_seed=32)
        with Scheduler(model, params, slots=2) as sched:
            r1 = sched.submit(first, timeout=30).result(timeout=300)
            # Arm the failure directly (what `prefill_fail@tick` does
            # from the tick thread) so it deterministically hits
            # `second`'s admission prefill — which must free its
            # pages, requeue, and retry rather than surface.
            sched._prefill_fail_armed = 1
            r2 = sched.submit(second, timeout=30).result(timeout=300)
            stats = sched.stats()
            _drained(sched)
        np.testing.assert_array_equal(r1.tokens,
                                      _oracle(model, params, first))
        np.testing.assert_array_equal(r2.tokens,
                                      _oracle(model, params, second))
        assert stats["faults"] == {"prefill_fail": 1}
        assert stats["requeues"] == 1

    def test_prefix_hit_requeue(self, model, params):
        from cloud_tpu.serving import Scheduler, ServeRequest
        rng = np.random.default_rng(4)
        shared = rng.integers(1, 64, (16,)).astype(np.int32).tolist()
        opener = ServeRequest(prompt=shared + [7], max_new_tokens=3,
                              temperature=0.0, rng_seed=41)
        rider = ServeRequest(prompt=shared + [9, 11], max_new_tokens=8,
                             temperature=0.0, rng_seed=42)
        with Scheduler(model, params, slots=2,
                       prefix_cache=True) as sched:
            r1 = sched.submit(opener, timeout=30).result(timeout=300)
            chaos.install("slot_hang@%d" % (sched._ticks + 3))
            r2 = sched.submit(rider, timeout=30).result(timeout=300)
            stats = sched.stats()
            _drained(sched)
        np.testing.assert_array_equal(r1.tokens,
                                      _oracle(model, params, opener))
        np.testing.assert_array_equal(r2.tokens,
                                      _oracle(model, params, rider))
        assert stats["prefix_hits"] >= 1
        assert stats["faults"] == {"slot_hang": 1}

    def test_mid_speculation_requeue(self, model, params):
        import jax.numpy as jnp

        from cloud_tpu.models import TransformerLM
        from cloud_tpu.serving import Scheduler, ServeRequest
        from cloud_tpu.serving.smoke import split_draft
        draft_model = TransformerLM(vocab_size=64, num_layers=1,
                                    num_heads=2, d_model=32, d_ff=64,
                                    max_seq_len=32,
                                    compute_dtype=jnp.float32)
        target, draft = split_draft(params, draft_layers=1)
        req = ServeRequest(prompt=[8, 6, 4, 2], max_new_tokens=12,
                           temperature=0.0, rng_seed=51)
        chaos.install("slot_hang@2")
        with Scheduler(model, target, slots=2, draft_model=draft_model,
                       draft_params=draft, spec_k=2) as sched:
            res = sched.submit(req, timeout=30).result(timeout=300)
            stats = sched.stats()
            _drained(sched)
        np.testing.assert_array_equal(res.tokens,
                                      _oracle(model, target, req))
        assert stats["faults"] == {"slot_hang": 1}

    def test_pool_squeeze_releases_and_drains(self, model, params,
                                              monkeypatch):
        from cloud_tpu.serving import Scheduler, ServeRequest
        # Shrink the wall-clock hold so the idle tick loop (which keeps
        # polling the chaos hook at 50ms) releases the squeeze within
        # the test's drain window.
        monkeypatch.setattr("cloud_tpu.serving.scheduler.SQUEEZE_HOLD_S",
                            0.2)
        req = ServeRequest(prompt=[1, 2, 3], max_new_tokens=10,
                           temperature=0.0, rng_seed=61)
        chaos.install("pool_squeeze@2:4")
        with Scheduler(model, params, slots=2) as sched:
            res = sched.submit(req, timeout=30).result(timeout=300)
            stats = sched.stats()
            _drained(sched)   # squeeze released by deadline or close
        np.testing.assert_array_equal(res.tokens,
                                      _oracle(model, params, req))
        assert stats["faults"] == {"pool_squeeze": 1}


@pytest.mark.slow
class TestShedEndToEnd:

    def test_typed_shed_and_survivor_accounting(self, model, params):
        from cloud_tpu.serving import Scheduler, ServeRequest, ServeShed
        with Scheduler(model, params, slots=2, slo_ttft=1e-6,
                       shed_policy="shed") as sched:
            future = sched.submit(ServeRequest(
                prompt=[1, 2], max_new_tokens=4, temperature=0.0,
                rng_seed=71), timeout=30)
            with pytest.raises(ServeShed) as info:
                future.result(timeout=300)
            assert info.value.reason in ("expired", "predicted")
            assert info.value.slo_ttft == 1e-6
            stats = sched.stats()
            assert sum(stats["shed"].values()) == 1
            # Shedding must unwind the pending-insert accounting, or
            # the tick thread would wait forever for a phantom insert.
            survivor = ServeRequest(prompt=[4, 4], max_new_tokens=3,
                                    temperature=0.0, rng_seed=72)
            sched._slo_ttft = None               # re-open admission
            res = sched.submit(survivor, timeout=30).result(timeout=300)
            _drained(sched)
        np.testing.assert_array_equal(res.tokens,
                                      _oracle(model, params, survivor))


class TestFaultDrainsThePipeline:
    """The tick loop keeps one tick in flight; a fault reads a slot's
    `emitted`, so the tick in flight is committed first (fast tier)."""

    def test_fault_fired_with_a_tick_in_flight(self, model, params,
                                               monkeypatch):
        from cloud_tpu.serving import Scheduler, ServeRequest
        from tests.unit.tick_log import TickLog, check_order
        requests = [
            ServeRequest(prompt=[5, 6, 7, 8, 9], max_new_tokens=12,
                         temperature=0.0, rng_seed=81),
            ServeRequest(prompt=[9, 8, 7], max_new_tokens=12,
                         temperature=0.9, top_p=0.9, rng_seed=82),
        ]
        sched = Scheduler(model, params, slots=2)
        log = TickLog(sched, monkeypatch)

        def arm(n):
            # Events are indexed by the ticks that have run: this one
            # is due at the boundary that finds tick 3 in flight.
            if n == 0:
                chaos.install("slot_evict@4")
        log.on_dispatch = arm
        with sched:
            futures = [sched.submit(r, timeout=30) for r in requests]
            results = [f.result(timeout=300) for f in futures]
            stats = sched.stats()
            sched.assert_drained(clear_prefix=True)
            assert sched.pool.leak_report() == {}
        entries = log.since()
        check_order(entries)   # a fault finds nothing in flight
        at = entries.index(("chaos", "slot_evict"))
        assert entries[at - 2:at] == [("drain", True), ("fetch", 3)], \
            entries[:at + 1]
        dispatched = [e for e in entries[:at] if e[0] == "dispatch"]
        assert dispatched[-1] == ("dispatch", 3)
        assert stats["faults"] == {"slot_evict": 1}
        assert stats["requeues"] == 1
        for req, res in zip(requests, results):
            np.testing.assert_array_equal(res.tokens,
                                          _oracle(model, params, req))


class TestPrefillFaultsWithOneInFlight:
    """The admission thread keeps one prefill in flight; a fault goes
    to the request it belongs to, and the other one is unharmed (fast
    tier)."""

    @staticmethod
    def _pair():
        from cloud_tpu.serving import ServeRequest
        return [
            ServeRequest(prompt=[5, 6, 7, 8, 9], max_new_tokens=6,
                         temperature=0.0, rng_seed=91),
            ServeRequest(prompt=[9, 8, 7], max_new_tokens=6,
                         temperature=0.9, top_p=0.9, rng_seed=92),
        ]

    def test_prefill_fail_armed_behind_a_dispatch(self, model, params,
                                                  monkeypatch):
        """Armed while the first prefill is on the device: the hook
        fires before anything of the SECOND is dispatched, so the
        second frees its pages, is requeued and retried; the first is
        fetched behind the retry like any other."""
        from cloud_tpu.serving import Scheduler
        from tests.unit.tick_log import PrefillLog, check_prefill_order
        requests = self._pair()
        sched = Scheduler(model, params, slots=2)
        log = PrefillLog(sched, monkeypatch)

        def arm(n):
            if n == 0:
                with sched._chaos_lock:
                    sched._prefill_fail_armed += 1
        log.on_dispatch = arm
        seen = []
        note = sched._note_fault

        def logged_note(fault, rid=None, slot=None):
            seen.append((rid, sched.pool.available()))
            return note(fault, rid=rid, slot=slot)
        monkeypatch.setattr(sched, "_note_fault", logged_note)
        with sched:
            with log.hold():
                futures = [sched.submit(r, timeout=30) for r in requests]
            results = [f.result(timeout=300) for f in futures]
            stats = sched.stats()
            sched.assert_drained(clear_prefix=True)
            assert sched.pool.leak_report() == {}
            held_by_first = sched.pool.pages_needed(5, 6)
            capacity = sched.pool.capacity
        entries = [e for e in log.since() if e[0] != "wait"]
        assert entries == [("dispatch", 0), ("dispatch", 1), ("fetch", 0),
                           ("fetch", 1)], entries
        check_prefill_order(log.since())
        first, second = (r.trace.rid for r in results)
        assert log.rids == [first, second]
        # The fault is the second's, and its pages were back in the
        # pool when it was noted: only the first's were out.
        assert seen == [(second, capacity - held_by_first)]
        assert stats["faults"] == {"prefill_fail": 1}
        assert stats["requeues"] == 1
        assert stats["prefills_overlapped"] == 1
        for req, res in zip(requests, results):
            np.testing.assert_array_equal(res.tokens,
                                          _oracle(model, params, req))

    def test_failure_at_the_fetch_is_its_own_requests(self, model, params,
                                                      monkeypatch):
        """A failure that only surfaces when the first token is
        fetched, inside the NEXT request's turn: the request in flight
        fails and frees its pages, the one whose turn it is goes on."""
        from cloud_tpu.serving import Scheduler
        from tests.unit.tick_log import PrefillLog, check_prefill_order
        requests = self._pair()
        sched = Scheduler(model, params, slots=2)
        log = PrefillLog(sched, monkeypatch)
        finish = sched.engine.prefill_finish
        first_flight = []

        def failing_finish(flight, rid=None):
            if not first_flight:
                first_flight.append(flight)
                raise RuntimeError("device lost behind the dispatch")
            return finish(flight, rid=rid)
        monkeypatch.setattr(sched.engine, "prefill_finish", failing_finish)
        with sched:
            with log.hold():
                futures = [sched.submit(r, timeout=30) for r in requests]
            with pytest.raises(RuntimeError, match="device lost"):
                futures[0].result(timeout=300)
            survivor = futures[1].result(timeout=300)
            stats = sched.stats()
            sched.assert_drained(clear_prefix=True)
            assert sched.pool.leak_report() == {}
        assert [e for e in log.since() if e[0] == "dispatch"] == [
            ("dispatch", 0), ("dispatch", 1)]
        assert stats["requests_completed"] == 1
        assert stats["prefills_overlapped"] == 1
        np.testing.assert_array_equal(
            survivor.tokens, _oracle(model, params, requests[1]))
