"""graftflex elastic tick geometry: resize ladder + hysteresis policy.

Contracts. Ladder: pow2 rungs only, derived from slots_min/slots_max
(ctor or env knobs) or given explicitly; degenerate ladders are ctor
errors, never runtime surprises; the page pool is sized for the WIDEST
rung so a grow never waits on memory. Policy: `resize_decision` is a
pure function — grow eagerly at the high watermark, shrink only after
N consecutive quiet boundaries, oscillating load never flaps. Resize:
every forced jump decomposes into adjacent pre-warmed rung steps;
in-flight requests ride a resize bit-identically to solo generate()
under every sampling mode, with prefix hits, mid-speculation, and
chunked prefill; once warm, traffic plus resizes across all rungs adds
zero traces and zero compiles.
"""

import dataclasses
import time

import numpy as np
import pytest


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


class TestLadderValidation:

    def test_explicit_ladder_must_be_pow2_sorted_unique(self, model,
                                                        params):
        from cloud_tpu.serving import Scheduler
        with pytest.raises(ValueError):
            Scheduler(model, params, slots=2, ladder=(2, 3, 4))
        with pytest.raises(ValueError):
            Scheduler(model, params, slots=2, ladder=(4, 2))
        with pytest.raises(ValueError):
            Scheduler(model, params, slots=2, ladder=(2, 2, 4))
        with pytest.raises(ValueError):
            Scheduler(model, params, slots=2, ladder=(0, 2))

    def test_initial_slots_must_be_a_rung(self, model, params):
        from cloud_tpu.serving import Scheduler
        with pytest.raises(ValueError):
            Scheduler(model, params, slots=2, ladder=(4, 8))

    def test_min_max_derive_pow2_rungs(self, model, params):
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=4, slots_min=2,
                          slots_max=16)
        assert sched.engine.ladder == (2, 4, 8, 16)

    def test_min_above_max_rejected(self, model, params):
        from cloud_tpu.serving import Scheduler
        with pytest.raises(ValueError):
            Scheduler(model, params, slots=4, slots_min=8, slots_max=4)

    def test_env_knobs_derive_the_ladder(self, model, params,
                                         monkeypatch):
        from cloud_tpu.serving import Scheduler
        monkeypatch.setenv("CLOUD_TPU_SERVE_SLOTS_MIN", "2")
        monkeypatch.setenv("CLOUD_TPU_SERVE_SLOTS_MAX", "8")
        sched = Scheduler(model, params, slots=4)
        assert sched.engine.ladder == (2, 4, 8)

    def test_no_knobs_means_fixed_geometry(self, model, params):
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=4)
        assert sched.engine.ladder == (4,)

    def test_pool_sized_for_widest_rung(self, model, params):
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=2, slots_min=2,
                          slots_max=8)
        # 8 slots x (32/16) pages each — a grow never needs new pages.
        assert sched.pool.capacity == 8 * sched.engine.pages_per_slot

    def test_resize_target_must_be_a_rung(self, model, params):
        from cloud_tpu.serving import Scheduler
        sched = Scheduler(model, params, slots=2, slots_min=2,
                          slots_max=4)
        with pytest.raises(ValueError):
            sched.request_resize(3, wait=False)
        with pytest.raises(ValueError):
            sched.request_resize(16, wait=False)


class TestResizeDecision:
    """The hysteresis policy is pure: (ladder, slots, active, waiting,
    quiet_ticks, threshold) -> (target | None, quiet_ticks')."""

    @staticmethod
    def _decide(*a, **k):
        from cloud_tpu.serving import Scheduler
        return Scheduler.resize_decision(*a, **k)

    def test_grows_eagerly_at_high_watermark(self):
        assert self._decide((2, 4, 8), 4, 4, 1, 0, 32) == (8, 0)
        # Full but nothing waiting: the rung is exactly right.
        assert self._decide((2, 4, 8), 4, 4, 0, 0, 32) == (None, 0)
        # Waiting but not full: admission will fill the free slots.
        assert self._decide((2, 4, 8), 4, 3, 2, 0, 32) == (None, 0)

    def test_never_grows_past_the_top_rung(self):
        assert self._decide((2, 4), 4, 4, 9, 0, 32) == (None, 0)

    def test_shrinks_only_after_consecutive_quiet_ticks(self):
        target, quiet = None, 0
        for _ in range(5):
            target, quiet = self._decide((2, 4), 4, 1, 0, quiet, 6)
            assert target is None
        target, quiet = self._decide((2, 4), 4, 1, 0, quiet, 6)
        assert (target, quiet) == (2, 0)

    def test_burst_resets_the_quiet_counter_no_flapping(self):
        quiet = 0
        for _ in range(5):
            _, quiet = self._decide((2, 4), 4, 1, 0, quiet, 6)
        # One busy boundary wipes the accumulated quiet credit...
        _, quiet = self._decide((2, 4), 4, 3, 1, quiet, 6)
        assert quiet == 0
        # ...so the shrink needs a fresh full quiet run afterwards.
        target, quiet = self._decide((2, 4), 4, 1, 0, quiet, 6)
        assert target is None and quiet == 1

    def test_active_set_must_fit_the_lower_rung(self):
        assert self._decide((2, 4), 4, 3, 0, 99, 6) == (None, 0)

    def test_bottom_rung_never_shrinks(self):
        assert self._decide((2, 4), 2, 0, 0, 99, 6) == (None, 0)

    def test_oscillating_load_holds_the_wide_geometry(self):
        quiet, resizes = 0, 0
        for step in range(100):
            active, waiting = (1, 0) if step % 3 else (4, 2)
            target, quiet = self._decide((2, 4), 4, active, waiting,
                                         quiet, 6)
            resizes += target is not None
        assert resizes == 0


def _greedy(prompt, max_new, seed):
    from cloud_tpu.serving import ServeRequest
    return ServeRequest(prompt=list(prompt), max_new_tokens=max_new,
                        temperature=0.0, rng_seed=seed)


def _assert_matches_oracle(model, params, requests, results):
    for i, (req, res) in enumerate(zip(requests, results)):
        np.testing.assert_array_equal(
            res.tokens, _oracle(model, params, req),
            err_msg="request {} diverged from solo generate() across "
                    "a resize".format(i))


@pytest.mark.slow
class TestElasticBitIdentity:

    def test_resize_mid_flight_all_sampling_modes(self, model, params):
        """Grow 2->4 while mixed-sampling requests are in flight, then
        shrink back after the drain: rng schedules, eos latches and
        positions migrate bit-identically."""
        from cloud_tpu.serving import Scheduler, ServeRequest
        rng = np.random.default_rng(5)
        configs = [dict(temperature=0.0),
                   dict(temperature=1.0),
                   dict(temperature=0.9, top_p=0.9),
                   dict(temperature=0.7, top_k=8),
                   dict(temperature=0.0),
                   dict(temperature=0.8, top_k=12, top_p=0.95)]
        requests = [ServeRequest(
            prompt=rng.integers(1, 64, (int(rng.integers(2, 10)),))
            .astype(np.int32).tolist(),
            max_new_tokens=int(rng.integers(6, 12)),
            rng_seed=200 + i, **cfg) for i, cfg in enumerate(configs)]
        with Scheduler(model, params, slots=2, slots_min=2,
                       slots_max=4) as sched:
            futures = [sched.submit(r, timeout=30) for r in requests]
            sched.request_resize(4, reason="test", timeout=120)
            results = [f.result(timeout=300) for f in futures]
            sched.request_resize(2, reason="test", timeout=120)
            assert sched.engine.slots == 2
            events = sched.stats()["geometry"]["resize_events"]
        _assert_matches_oracle(model, params, requests, results)
        assert {(e["from"], e["to"]) for e in events} >= {(2, 4),
                                                          (4, 2)}

    def test_resize_with_prefix_hit(self, model, params):
        from cloud_tpu.serving import Scheduler
        # The trie shares whole pages (page_size=16), so the shared
        # prefix must span at least one full page to be cacheable.
        shared = [7, 3, 11, 2, 9, 4, 13, 8, 6, 1, 12, 10, 5, 14, 2, 3]
        first = _greedy(shared + [5], 6, seed=31)
        hit = _greedy(shared + [6, 1], 8, seed=32)
        with Scheduler(model, params, slots=2, slots_min=2,
                       slots_max=4) as sched:
            r_first = sched.submit(first,
                                   timeout=30).result(timeout=300)
            sched.request_resize(4, reason="test", timeout=120)
            r_hit = sched.submit(hit, timeout=30).result(timeout=300)
            assert sched.stats()["prefix_hits"] >= 1
            assert r_hit.prefix_len > 0
        _assert_matches_oracle(model, params, [first, hit],
                               [r_first, r_hit])

    def test_resize_mid_speculation(self, model, params):
        """Draft cache rows migrate under the same perm: speculative
        decode straddling a resize still matches solo generate()."""
        import jax
        import jax.numpy as jnp

        from cloud_tpu.models import TransformerLM
        from cloud_tpu.serving import Scheduler
        draft = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                              d_model=32, d_ff=64, max_seq_len=32,
                              compute_dtype=jnp.float32)
        draft_params = draft.init(jax.random.PRNGKey(2),
                                  jnp.zeros((1, 4), jnp.int32))["params"]
        requests = [_greedy([3 + i, 9, 5, 12], 10, seed=40 + i)
                    for i in range(4)]
        with Scheduler(model, params, slots=2, slots_min=2,
                       slots_max=4, draft_model=draft,
                       draft_params=draft_params, spec_k=2,
                       prefix_cache=False) as sched:
            futures = [sched.submit(r, timeout=30) for r in requests]
            sched.request_resize(4, reason="test", timeout=120)
            results = [f.result(timeout=300) for f in futures]
        _assert_matches_oracle(model, params, requests, results)

    def test_resize_with_chunked_prefill(self, model, params):
        from cloud_tpu.serving import Scheduler
        rng = np.random.default_rng(9)
        requests = [_greedy(rng.integers(1, 64, (18,)).astype(
            np.int32).tolist(), 8, seed=60 + i) for i in range(4)]
        with Scheduler(model, params, slots=2, slots_min=2,
                       slots_max=4, prefill_chunk=8) as sched:
            futures = [sched.submit(r, timeout=30) for r in requests]
            sched.request_resize(4, reason="test", timeout=120)
            results = [f.result(timeout=300) for f in futures]
        _assert_matches_oracle(model, params, requests, results)

    def test_forced_jump_decomposes_into_adjacent_steps(self, model,
                                                        params):
        """Only adjacent pairs are pre-warmed, so a 2->8 jump must
        replay as 2->4, 4->8 — the event stream IS the executable
        dispatch sequence."""
        from cloud_tpu.serving import Scheduler
        with Scheduler(model, params, slots=2, slots_min=2,
                       slots_max=8) as sched:
            sched.request_resize(8, timeout=120)
            sched.request_resize(2, timeout=120)
            events = sched.stats()["geometry"]["resize_events"]
        assert [(e["from"], e["to"]) for e in events] == [
            (2, 4), (4, 8), (8, 4), (4, 2)]
        assert all(e["reason"] == "manual" for e in events)

    def test_zero_new_traces_across_all_rungs(self, model, params):
        """After warmup's ladder walk, traffic on every rung plus the
        resizes between them adds zero traces and zero compiles."""
        from cloud_tpu.parallel import runtime
        from cloud_tpu.serving import Scheduler
        requests = [_greedy([2 + i, 7, 11], 6, seed=70 + i)
                    for i in range(6)]
        # Solo references BEFORE the capture window: generate() traces
        # its own executables, which the global sentinel would count.
        refs = [_oracle(model, params, r) for r in requests]
        with Scheduler(model, params, slots=2, slots_min=2,
                       slots_max=4,
                       strict_no_retrace=True) as sched:
            sched.warmup([4], sampling_configs=[(("temperature",
                                                  0.0),)])
            warm = runtime.compile_stats()
            for rung in (2, 4, 2):
                futures = [sched.submit(r, timeout=30)
                           for r in requests]
                results = [f.result(timeout=300) for f in futures]
                for ref, res in zip(refs, results):
                    np.testing.assert_array_equal(res.tokens, ref)
                target = 4 if rung == 2 else 2
                sched.request_resize(target, reason="test",
                                     timeout=120)
            after = runtime.compile_stats()
        assert after["n_traces"] == warm["n_traces"]
        assert after["n_compiles"] == warm["n_compiles"]

    def test_policy_grows_under_pressure_and_shrinks_when_quiet(
            self, model, params):
        """End-to-end hysteresis: a burst beyond the narrow rung grows
        the geometry without any forced request; the post-burst quiet
        run shrinks it back."""
        from cloud_tpu.serving import Scheduler
        requests = [_greedy([2 + i, 7, 11], 8, seed=80 + i)
                    for i in range(8)]
        with Scheduler(model, params, slots=2, slots_min=2,
                       slots_max=4, resize_quiet_ticks=4) as sched:
            sched.warmup([4], sampling_configs=[(("temperature",
                                                  0.0),)])
            futures = [sched.submit(r, timeout=30) for r in requests]
            results = [f.result(timeout=300) for f in futures]
            _assert_matches_oracle(model, params, requests, results)
            deadline = time.monotonic() + 60
            while (sched.engine.slots != 2
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            stats = sched.stats()["geometry"]
        assert stats["resizes"]["grow"] >= 1
        assert stats["resizes"]["shrink"] >= 1
        assert stats["slots"] == 2
        reasons = {e["reason"] for e in stats["resize_events"]}
        assert {"grow", "shrink"} <= reasons


@pytest.mark.slow
class TestGeometryStats:

    def test_per_tick_stats_stamp_their_geometry(self, model, params):
        """ISSUE 18 bugfix: tick stats land in the rung they ran
        under, so cross-width comparisons never mix silently."""
        from cloud_tpu.serving import Scheduler
        requests = [_greedy([2 + i, 7], 6, seed=90 + i)
                    for i in range(6)]
        with Scheduler(model, params, slots=2, slots_min=2,
                       slots_max=4) as sched:
            futures = [sched.submit(r, timeout=30) for r in requests]
            sched.request_resize(4, reason="test", timeout=120)
            [f.result(timeout=300) for f in futures]
            geometry = sched.stats()["geometry"]
        from cloud_tpu.serving import reqtrace
        ticks = [t for t in reqtrace.recent_ticks() if t.live]
        per_geom = geometry["per_geometry"]
        assert set(per_geom) <= {"2", "4"}
        assert sum(g["ticks"] for g in per_geom.values()) > 0
        for slots, g in per_geom.items():
            # A tick's record carries the rung it ran at.
            assert g["ticks"] == sum(t.slots == int(slots) for t in ticks)
            assert 0.0 <= g["occupancy_mean"] <= 4.0


class TestResizeDrainsThePipeline:
    """The tick loop keeps one tick in flight; the geometry moves only
    with nothing in flight (fast tier)."""

    def test_resize_asked_for_with_a_tick_in_flight(self, model, params,
                                                    monkeypatch):
        from cloud_tpu.serving import Scheduler
        from tests.unit.tick_log import TickLog, check_order
        requests = [_greedy([2 + i, 7, 11], 14, seed=300 + i)
                    for i in range(2)]
        sched = Scheduler(model, params, slots=2, slots_min=2,
                          slots_max=4)
        log = TickLog(sched, monkeypatch)

        def ask(n):
            # From the tick thread, straight after a dispatch: what
            # `request_resize(wait=False)` leaves for the next
            # boundary, which this tick is still in flight at.
            if n == 4:
                sched._requested_resize = (4, "test")
        log.on_dispatch = ask
        with sched:
            futures = [sched.submit(r, timeout=30) for r in requests]
            results = [f.result(timeout=300) for f in futures]
            sched.assert_drained()
            geometry = sched.stats()["geometry"]
        entries = log.since()
        check_order(entries)   # a resize finds nothing in flight
        at = entries.index(("resize", 4))
        # The boundary found tick 4 unfetched: it was drained (fetched
        # and committed with no tick dispatched behind it), then the
        # rows moved, then tick 5 went out with nothing before it.
        assert entries[at - 2:at] == [("drain", True), ("fetch", 4)], \
            entries[:at + 1]
        dispatched = [e for e in entries[:at] if e[0] == "dispatch"]
        assert dispatched[-1] == ("dispatch", 4)
        assert entries[at + 1] == ("dispatch", 5)
        assert geometry["slots"] == 4
        assert [(e["from"], e["to"], e["tick"])
                for e in geometry["resize_events"]] == [(2, 4, 5)]
        _assert_matches_oracle(model, params, requests, results)
