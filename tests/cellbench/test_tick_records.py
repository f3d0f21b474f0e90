"""The readers of the program's per-tick record (`cellbench/tick_records.py`
and the eight `layer_metrics` files that read it), on planted records with a
fake clock: a window worked out by hand comes back from the readers to the
digit, with nothing left over; a prefill's note that lands one tick late moves
no share by a point; and wherever the records cannot be trusted (a count that
is off, request records that failed their own check, too few ticks, a program
without the record) every reader returns None and raises nothing.
"""

import collections

import pytest

from cellbench import harness, tick_records
from cloud_tpu.serving import reqtrace

BENCH = harness.load_benchmark()
READERS = ("tick_period_clean_ms.serve", "window_prefill_share_pct.serve",
           "prefill_cost_ms.serve", "window_wait_share_pct.serve",
           "window_tick_share_pct.serve", "tick_overlap_share_pct.serve",
           "prefill_overlap_share_pct.serve", "kv_walk_live_share_pct.serve")
CELLS = ["gpt2xl_decode_sat", "gpt2xl_chat_open", "kexaone_decode_long",
         "nemotron3s_decode_reason"]
Note = collections.namedtuple("Note", "name t rows rid overlapped")

TICK_S, PREFILL_S, NAP_S, IDLE_S = 0.010, 0.050, 0.005, 0.300
T0 = 100.0
# The host's work between a tick's tokens and the next tick's dispatch.
HOST_S = 0.002


def read(name, observed):
    return harness.find("layer_metrics", name).read(observed)


def read_all(observed):
    return {name: read(name, observed) for name in READERS}


class Planted:
    """A window of `n` ticks on a device that is never idle but where said:
    a clean tick takes TICK_S; ticks 10, 30, 70 and 90 are behind a whole
    prefill of PREFILL_S, tick 35 behind three naps, tick 50 behind an idle
    stretch that ends in a prefill (every second prefill dispatched with the
    one before unfetched). On time, a prefill's note is written before the
    dispatch of the tick it ran ahead of; `late`, a millisecond after the
    tick before that one was fetched, so it stands in the NEXT tick's
    record."""

    def __init__(self, n=100, late=False):
        self.server = reqtrace.new_server()
        self.ticks, self.prefills = [], 0
        fetched, pending = T0, []
        for seq in range(n):
            start = fetched
            # The host dispatched this tick HOST_S after the tick before
            # the last was fetched; the last tick's note comes first.
            t_dispatch = start - TICK_S + HOST_S
            notes = [Note("serve_tick", t_dispatch - TICK_S, 0, None,
                          False)] + pending
            pending, naps, idle_s, length = [], 0, 0.0, TICK_S
            if seq == 50:
                idle_s, length = IDLE_S, IDLE_S + PREFILL_S + TICK_S
                notes.append(self.prefill(start + IDLE_S))
                t_dispatch = start + IDLE_S + PREFILL_S
            elif seq % 20 == 10:
                length = PREFILL_S + TICK_S
                if late:
                    pending.append(self.prefill(start + 0.001))
                else:
                    notes.append(self.prefill(t_dispatch - 0.001))
            elif seq == 35:
                naps, length = 3, 3 * NAP_S + TICK_S
                t_dispatch = start + 3 * NAP_S
            if seq % 7 == 3:
                notes.append(Note("slot_insert", t_dispatch - 0.0005, 0,
                                  None, False))
            fetched = start + length
            tick = reqtrace.TickRecord(
                seq, self.server, 16, t_dispatch,
                overlapped=not (naps or idle_s), dispatched=tuple(notes),
                naps=naps, idle_s=idle_s)
            tick.t_fetch0 = tick.t_dispatch + 0.001
            tick.t_fetched = fetched
            tick.t_committed = fetched + 0.0005
            tick.live, tick.kv_live, tick.kv_walked = 16, 600, 800
            self.ticks.append(tick)
        self.t1 = fetched
        self.requests = [self.request("r0", T0, self.t1),
                         self.request("r1", T0 + 0.2, self.t1 - 0.3)]

    def prefill(self, t):
        self.prefills += 1
        return Note("serve_prefill", t, 512, "r%d" % self.prefills,
                    self.prefills % 2 == 0)

    def request(self, rid, t_submit, t_done):
        record = reqtrace.RequestRecord(rid, self.server, 8, 4, t_submit)
        for name in ("t_dequeued", "t_admit", "t_reserved", "t_first",
                     "t_insert"):
            setattr(record, name, t_submit)
        record.t_done = t_done
        return record

    def publish(self):
        for record in self.requests:
            reqtrace.publish(record)
        for tick in self.ticks:
            reqtrace.publish_tick(tick)
        return {"counters": {"completed": len(self.requests),
                             "ticks": len(self.ticks)}}


@pytest.fixture(autouse=True)
def rings():
    """The rings as they were, whatever a test plants."""
    requests, ticks = reqtrace.recent(0), reqtrace.recent_ticks(0)
    reqtrace.clear()
    reqtrace.clear_ticks()
    yield
    reqtrace.clear()
    reqtrace.clear_ticks()
    for record in requests:
        reqtrace.publish(record)
    for tick in ticks:
        reqtrace.publish_tick(tick)


# ---------------------------------------------------------- by hand

WINDOW_S = 100 * TICK_S + 5 * PREFILL_S + 3 * NAP_S + IDLE_S


@pytest.mark.parametrize("late", [False, True], ids=["on_time", "late"])
def test_a_window_worked_out_by_hand(late):
    planted = Planted(late=late)
    assert planted.t1 - T0 == pytest.approx(WINDOW_S)
    got = read_all(planted.publish())
    # Late, four notes stand a millisecond into the interval their prefill
    # ran in, and that millisecond is left over: a quarter of a point.
    lost_s = 4 * 0.001 if late else 0.0
    lost_pct = 100 * lost_s / WINDOW_S
    assert lost_pct < 1.0
    assert got["tick_period_clean_ms.serve"] == pytest.approx(1e3 * TICK_S)
    assert got["window_tick_share_pct.serve"] == pytest.approx(
        100 * 100 * TICK_S / WINDOW_S)
    assert got["window_prefill_share_pct.serve"] == pytest.approx(
        100 * 5 * PREFILL_S / WINDOW_S - lost_pct)
    assert got["window_wait_share_pct.serve"] == pytest.approx(
        100 * (3 * NAP_S + IDLE_S) / WINDOW_S)
    assert got["prefill_cost_ms.serve"] == pytest.approx(
        1e3 * (PREFILL_S - lost_s / 5))
    # Nothing else is left over: the three shares are the window.
    assert sum(got["window_%s_share_pct.serve" % part]
               for part in ("tick", "prefill", "wait")) == pytest.approx(
                   100.0 - lost_pct)
    account = tick_records.account({"counters": {"completed": 2,
                                                 "ticks": 100}})
    assert account.left_s == pytest.approx(lost_s, abs=1e-9)
    assert (account.ticks, account.prefills) == (100, 5)
    # Ticks 35 and 50 followed a drain; two of the five prefills overlapped.
    assert got["tick_overlap_share_pct.serve"] == pytest.approx(98.0)
    assert got["prefill_overlap_share_pct.serve"] == pytest.approx(40.0)
    assert got["kv_walk_live_share_pct.serve"] == pytest.approx(75.0)
    assert all(value > 0 for value in got.values())


def test_a_share_that_is_nothing_is_left_out():
    """No nap, no idle wait, no prefill that overlapped, nothing walked: the
    readers of those return None (the line's values are all above 0)."""
    planted = Planted(n=29)
    for tick in planted.ticks:
        tick.kv_walked = tick.kv_live = 0
        tick.dispatched = tuple(n._replace(overlapped=False)
                                for n in tick.dispatched)
    got = read_all(planted.publish())
    assert got["window_wait_share_pct.serve"] is None
    assert got["prefill_overlap_share_pct.serve"] is None
    assert got["kv_walk_live_share_pct.serve"] is None
    assert got["window_prefill_share_pct.serve"] == pytest.approx(
        100 * PREFILL_S / (29 * TICK_S + PREFILL_S))
    assert got["prefill_cost_ms.serve"] == pytest.approx(1e3 * PREFILL_S)


def test_a_window_of_chunks_has_a_cost_a_chunk_and_no_overlap_share():
    planted = Planted(n=29)
    for tick in planted.ticks:
        tick.dispatched = tuple(
            n._replace(name="serve_prefill_chunk") if n.name == "serve_prefill"
            else n for n in tick.dispatched)
    got = read_all(planted.publish())
    assert got["prefill_cost_ms.serve"] == pytest.approx(1e3 * PREFILL_S)
    assert got["prefill_overlap_share_pct.serve"] is None


# ------------------------------------------- where nothing may be read

def none_from_every_reader(observed):
    got = read_all(observed)
    assert set(got.values()) == {None}, got


@pytest.mark.parametrize("off", [-3, 3])
def test_a_count_that_is_three_off_gives_none(off):
    observed = Planted().publish()
    observed["counters"]["ticks"] += off
    none_from_every_reader(observed)


@pytest.mark.parametrize("off", [-2, 2])
def test_a_count_that_is_two_off_is_a_tick_in_flight(off):
    observed = Planted().publish()
    observed["counters"]["ticks"] += off
    assert None not in read_all(observed).values()


def test_request_records_that_failed_their_check_give_none():
    observed = Planted().publish()
    observed["counters"]["completed"] += 1
    none_from_every_reader(observed)


@pytest.mark.parametrize("ticks", [0, 2])
def test_a_window_of_two_ticks_or_none_gives_none(ticks):
    none_from_every_reader(Planted(n=ticks).publish())


def test_a_window_without_a_clean_tick_gives_no_account():
    planted = Planted(n=12)
    for tick in planted.ticks:
        tick.naps = 1
    got = read_all(planted.publish())
    assert got["tick_period_clean_ms.serve"] is None
    assert got["window_tick_share_pct.serve"] is None
    # What needs no clean period is read all the same.
    assert got["kv_walk_live_share_pct.serve"] == pytest.approx(75.0)


def test_another_servers_ticks_are_not_read():
    first = Planted()
    first.publish()
    observed = Planted(n=40).publish()     # the server started last
    assert read("tick_overlap_share_pct.serve", observed) == pytest.approx(
        100.0 * 39 / 40)
    observed["counters"]["ticks"] = 100    # the first server's count
    none_from_every_reader(observed)


def test_a_program_without_the_record_gives_none(monkeypatch):
    observed = Planted().publish()
    monkeypatch.delattr(reqtrace, "recent_ticks")
    none_from_every_reader(observed)
    monkeypatch.undo()
    assert None not in read_all(observed).values()
    none_from_every_reader({})
    none_from_every_reader({"counters": {}})


def test_clear_of_the_request_ring_leaves_the_readers_their_ticks():
    """`closed_loop_hybrid.probe_states` empties the request ring after the
    window and publishes the window's records anew."""
    planted = Planted()
    observed = planted.publish()
    kept = reqtrace.recent(0)
    reqtrace.clear()
    for record in kept:
        reqtrace.publish(record)
    assert None not in read_all(observed).values()


# ------------------------------------------------- the benchmark's entries

@pytest.mark.parametrize("name", READERS)
def test_each_new_metric_has_a_reader_and_lists_accepted_cells(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert callable(harness.find("layer_metrics", name).read)
    assert entry["source"] == "program_span"
    assert entry["workloads"] == CELLS
    reported = {m["name"]: set(m["workloads"]) for m in BENCH["end_to_end"]
                if "workloads" in m}
    assert set(CELLS) <= reported[entry["moves"]]
    for cell in CELLS:
        assert name in {m["name"] for m in harness.load_cell(cell).per_layer}
    assert name not in {
        m["name"] for m in harness.load_cell("evabyte_decode_32k").per_layer}


def test_toy_scheduler_run_passes_through_the_readers():
    """A real window on the CPU toy Scheduler: the records pass the count
    check and the shares are shares."""
    import jax
    import jax.numpy as jnp

    from cloud_tpu.models import TransformerLM
    from cloud_tpu.serving import Scheduler, ServeRequest

    model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                          d_model=32, d_ff=64, max_seq_len=32,
                          compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    with Scheduler(model, params, slots=2, page_size=8) as sched:
        sched.warmup([8], sampling_configs=[(("temperature", 0.0),)])
        before = sched.stats()
        futures = [sched.submit(ServeRequest(
            prompt=[3 + i, 5, 7], max_new_tokens=20, temperature=0.0))
            for i in range(4)]
        for future in futures:
            future.result(timeout=300)
        sched.assert_drained()
        after = sched.stats()
    observed = {"counters": {
        "completed": after["requests_completed"],
        "ticks": after["ticks"] - before["ticks"]}}
    got = read_all(observed)
    win = tick_records.window(observed)
    assert abs(len(win.ticks) - observed["counters"]["ticks"]) <= 2
    assert got["tick_overlap_share_pct.serve"] > 50
    assert 0 < got["kv_walk_live_share_pct.serve"] <= 100
    assert got["tick_period_clean_ms.serve"] > 0
    shares = [got[name] or 0.0 for name in (
        "window_tick_share_pct.serve", "window_prefill_share_pct.serve",
        "window_wait_share_pct.serve")]
    assert all(0 <= share <= 100 for share in shares)
    assert all(value is None or value > 0 for value in got.values())
