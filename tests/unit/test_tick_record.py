"""The per-tick record: one a committed tick, beside the per-request one.

Through a toy Scheduler on the CPU, on the miss, hit and chunked paths: the
served traffic's tick records number `stats()["ticks"]` and their fields add
up to the counters `stats()` keeps (`ticks_overlapped`, `kv_live_tokens`,
`kv_walked_tokens`, `tick_paces`), the dispatch log they carry names the
engine's own calls (whole prefills, chunks, inserts, ticks) with the request
and the rows of each, a tick's four times never decrease and `seq` has no
hole; two servers in one process do not mix, both rings are bounded, and
`reqtrace.clear()` leaves the ticks alone.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from cloud_tpu.monitoring import spans
from cloud_tpu.serving import Scheduler, ServeRequest, reqtrace
from cloud_tpu.serving import engine as engine_lib

PATHS = ("miss", "hit", "chunked")
BASE = [3, 5, 7, 9, 11, 13, 15, 17, 19]


@pytest.fixture(scope="module")
def model():
    from cloud_tpu.models import TransformerLM
    return TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                         d_model=32, d_ff=64, max_seq_len=32,
                         compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(1),
                      jnp.zeros((1, 4), jnp.int32))["params"]


def _serve(sched, prompts_and_news):
    futures = [sched.submit(ServeRequest(prompt=p, max_new_tokens=n,
                                         temperature=0.0))
               for p, n in prompts_and_news]
    return [f.result(timeout=300) for f in futures]


class Served:
    """One path's traffic: the results, `stats()` once drained, the tick
    records and the dispatch notes of the served traffic (cut by time:
    warm-up's are kept too), and every tick record of the server."""

    def __init__(self, model, params, path):
        kwargs = {"prefill_chunk": 4} if path == "chunked" else {}
        with Scheduler(model, params, slots=2, page_size=8,
                       **kwargs) as sched:
            sched.warmup([8, 16], sampling_configs=[(("temperature", 0.0),)])
            t0 = time.monotonic()
            if path == "hit":
                self.results = _serve(sched, [(BASE + [1], 4)]) + _serve(
                    sched, [(BASE + [1, 2], 3), (BASE + [4, 5, 6], 5)])
            else:
                self.results = _serve(sched, [
                    ([21] + BASE, 4), ([22] + BASE, 1), ([23] + BASE, 6),
                    ([24] + BASE[:3], 9), ([25] + BASE, 3)])
            sched.assert_drained()
            self.stats = sched.stats()
            # What was dispatched behind the last tick's dispatch (its
            # own note, the last eviction) no tick has taken.
            left = sched.engine.take_dispatched()
        self.server = self.results[0].trace.server
        self.all_ticks = reqtrace.recent_ticks(self.server)
        self.ticks = [t for t in self.all_ticks if t.t_fetched >= t0]
        self.notes = [n for t in self.ticks for n in t.dispatched
                      if n.t >= t0] + left

    def named(self, *names):
        return [n for n in self.notes if n.name in names]


@pytest.fixture(scope="module")
def served(model, params):
    reqtrace.uninstall()
    return {path: Served(model, params, path) for path in PATHS}


@pytest.mark.parametrize("path", PATHS)
def test_tick_records_number_the_ticks_and_add_up_to_the_counters(served,
                                                                  path):
    run = served[path]
    ticks, stats = run.ticks, run.stats
    assert len(ticks) == stats["ticks"] > 0
    assert sum(t.overlapped for t in ticks) == stats["ticks_overlapped"] > 0
    assert sum(t.kv_live for t in ticks) == stats["kv_live_tokens"] > 0
    assert sum(t.kv_walked for t in ticks) == stats["kv_walked_tokens"] > 0
    assert sum(t.naps for t in ticks) == stats["tick_paces"]
    assert sum(t.live for t in ticks) == sum(
        r.trace.new_tokens - 1 for r in run.results)
    assert {t.slots for t in ticks} == {2}
    assert all(t.drained != t.overlapped for t in ticks)
    # The first tick of the traffic came out of an idle wait.
    assert ticks[0].idle_s > 0 and ticks[0].drained


@pytest.mark.parametrize("path", PATHS)
def test_times_never_decrease_and_seq_has_no_hole(served, path):
    run = served[path]
    for tick in run.all_ticks:
        stamps = [tick.t_dispatch, tick.t_fetch0, tick.t_fetched,
                  tick.t_committed]
        assert None not in stamps and stamps == sorted(stamps)
    # Warm-up's ticks count: the ordinal starts with the server.
    assert [t.seq for t in run.all_ticks] == list(range(len(run.all_ticks)))
    assert len(run.all_ticks) > len(run.ticks)
    fetched = [t.t_fetched for t in run.all_ticks]
    assert fetched == sorted(fetched)
    # Each later token's commit time is its tick's `t_fetched`.
    times = {t.t_fetched for t in run.ticks}
    for result in run.results:
        assert set(result.trace.token_times) <= times


@pytest.mark.parametrize("path", PATHS)
def test_dispatch_log_names_the_engines_own_calls(served, path):
    run = served[path]
    stats = run.stats
    assert {n.name for n in run.notes} <= set(spans.names("Programs"))
    assert len(run.named(engine_lib.SERVE_TICK)) == stats["ticks"]
    inserted = [r for r in run.results if r.trace.new_tokens > 1]
    assert len(run.named(engine_lib.SLOT_INSERT)) == len(inserted)
    assert run.named(engine_lib.SLOT_EVICT)
    whole = run.named(engine_lib.SERVE_PREFILL)
    chunks = run.named(engine_lib.SERVE_PREFILL_CHUNK)
    if path == "chunked":
        assert not whole
        assert len(chunks) == stats["prefill_chunks_dispatched"] > len(
            run.results)
        # Whole chunks of four rows, and tails at their own width.
        assert {n.rows for n in chunks} == {2, 4}
    else:
        assert not chunks
        assert len(whole) == len(run.results)
        assert sum(n.overlapped for n in whole) == stats[
            "prefills_overlapped"]
    if path == "miss":
        assert len(whole) == stats["prefix_misses"]
    gathers = run.named(engine_lib.PREFIX_GATHER)
    assert len(gathers) == (2 if path == "hit" else 0)
    # A prefill's note carries its request and the width it ran at.
    by_rid = {r.trace.rid: r.trace for r in run.results}
    for note in whole:
        assert note.rows == by_rid[note.rid].bucket
    assert {n.rid for n in whole + chunks + gathers} == set(by_rid)
    # One zeroed dense cache a prefill, noted under the request too.
    zeros = run.named("cache_zero")
    assert sorted(n.rid for n in zeros) == sorted(by_rid)
    # A note is written after its dispatch, on the records' clock.
    for tick in run.ticks:
        assert all(n.t <= tick.t_dispatch for n in tick.dispatched)
        assert list(tick.dispatched) == sorted(tick.dispatched,
                                               key=lambda n: n.t)


def test_prefill_notes_overlapped_add_up_to_the_counter(model, params,
                                                        monkeypatch):
    """A window of three misses: the second and third are dispatched
    with the one before unfetched, and their notes say so."""
    from tests.unit.tick_log import PrefillLog

    sched = Scheduler(model, params, slots=4, page_size=8)
    log = PrefillLog(sched, monkeypatch)
    with sched:
        with log.hold():
            futures = [sched.submit(ServeRequest(
                prompt=[10 + i, 5, 7], max_new_tokens=4, temperature=0.0))
                for i in range(3)]
        for future in futures:
            future.result(timeout=300)
        sched.assert_drained()
        stats = sched.stats()
        left = sched.engine.take_dispatched()
    notes = [n for t in reqtrace.recent_ticks() for n in t.dispatched] + left
    whole = [n for n in notes if n.name == engine_lib.SERVE_PREFILL]
    assert [n.overlapped for n in whole] == [False, True, True]
    assert stats["prefills_overlapped"] == 2 == stats["prefix_misses"] - 1
    assert [n.rid for n in whole] == log.rids


def test_second_scheduler_keeps_its_ticks_apart(served, model, params):
    with Scheduler(model, params, slots=2, page_size=8) as sched:
        (result,) = _serve(sched, [([31] + BASE, 3)])
        sched.assert_drained()
        ticks = sched.stats()["ticks"]
    mine = reqtrace.recent_ticks()
    assert {t.server for t in mine} == {result.trace.server}
    assert len(mine) == ticks
    assert [t.seq for t in mine] == list(range(ticks))
    earlier = served["miss"]
    assert earlier.server != result.trace.server
    assert len(reqtrace.recent_ticks(earlier.server)) == len(
        earlier.all_ticks)
    servers = {t.server for t in reqtrace.recent_ticks(0)}
    assert {earlier.server, result.trace.server} <= servers


# ------------------------------------------------------------ the rings

def _tick(server, seq):
    record = reqtrace.TickRecord(seq, server, 2, 1.0, overlapped=False)
    record.t_fetch0 = record.t_fetched = record.t_committed = 1.0
    return record


def test_tick_ring_is_bounded_and_clear_leaves_it_alone():
    kept_before = reqtrace.recent_ticks(0)
    try:
        reqtrace.clear_ticks()
        server = reqtrace.new_server()
        for seq in range(reqtrace.TICKS_CAP + 10):
            reqtrace.publish_tick(_tick(server, seq))
        kept = reqtrace.recent_ticks()
        assert len(kept) == reqtrace.TICKS_CAP == 16384
        assert (kept[0].seq, kept[-1].seq) == (10, reqtrace.TICKS_CAP + 9)
        # A driver drops requests of its own from the first ring
        # (`closed_loop_hybrid.probe_states`); the ticks stay.
        reqtrace.clear()
        assert reqtrace.recent() == []
        assert len(reqtrace.recent_ticks()) == reqtrace.TICKS_CAP
        reqtrace.clear_ticks()
        assert reqtrace.recent_ticks(0) == []
    finally:
        reqtrace.clear_ticks()
        for record in kept_before:
            reqtrace.publish_tick(record)


def test_dispatch_log_is_bounded_where_nobody_takes_it(model, params):
    engine = engine_lib.DecodeEngine(model, params, slots=2, page_size=8,
                                     num_pages=9)
    for _ in range(3):
        engine.tick()
    first = engine.take_dispatched()
    assert [n.name for n in first] == [engine_lib.SERVE_TICK] * 3
    assert [n.rows for n in first] == [0, 0, 0]
    assert engine.take_dispatched() == []
    for i in range(engine_lib.DISPATCH_LOG_CAP + 5):
        engine._note(engine_lib.SLOT_EVICT, rows=i)
    kept = engine.take_dispatched()
    assert len(kept) == engine_lib.DISPATCH_LOG_CAP
    assert kept[0].rows == 5
