"""The per-request record: always in memory, its phases tile the latency.

Through a toy Scheduler on the CPU, on the miss, hit and chunked paths:
`ServeResult.ttft_s` and `latency_s` are computed from the record, so the
phases `queue + window + reserve + prefill` add up to the first and all
seven to the second; one commit time a later token; times never decrease;
warm-up traffic stays out of `reqtrace.recent()`; the ring is bounded and
two servers in one process do not mix; the JSONL export is fed by the same
marks and is no longer written for the sake of one `complete`.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from cloud_tpu.serving import Scheduler, ServeRequest, reqtrace
from cloud_tpu.utils import events

PATHS = ("miss", "hit", "chunked")
BASE = [3, 5, 7, 9, 11, 13, 15, 17, 19]
TTFT_PHASES = ("queue", "window", "reserve", "prefill")


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


@pytest.fixture(scope="module")
def served(model, params):
    """path -> (results, recent() after warm-up, recent() at the end,
    stats), one Scheduler a path."""
    reqtrace.uninstall()
    out = {}
    for path in PATHS:
        reqtrace.clear()
        kwargs = {"prefill_chunk": 4} if path == "chunked" else {}
        with Scheduler(model, params, slots=2, page_size=8,
                       **kwargs) as sched:
            sched.warmup([8, 16], sampling_configs=[(("temperature", 0.0),)])
            after_warmup = reqtrace.recent()
            if path == "hit":
                # One request registers BASE's full page; once it is
                # done, two that share it are hits.
                _serve(sched, [(BASE + [1], 4)])
                results = _serve(sched, [(BASE + [1, 2], 3),
                                         (BASE + [4, 5, 6], 5)])
            else:
                # Distinct first tokens: no request finds another's
                # pages, whatever the order they are admitted in.
                results = _serve(sched, [([21] + BASE, 4), ([22] + BASE, 1),
                                         ([23] + BASE, 6)])
            stats = sched.stats()
        out[path] = (results, after_warmup, reqtrace.recent(), stats)
    return out


@pytest.mark.parametrize("path", PATHS)
def test_phases_add_up_to_ttft_and_latency(served, path):
    for result in served[path][0]:
        record = result.trace
        assert record.path == path
        phases = record.phases()
        assert list(phases)[:4] == list(TTFT_PHASES)
        assert sum(phases[p] for p in TTFT_PHASES) == pytest.approx(
            result.ttft_s, abs=1e-6)
        assert sum(phases.values()) == pytest.approx(result.latency_s,
                                                     abs=1e-6)
        assert result.ttft_s == record.ttft_s
        assert result.latency_s == record.latency_s
        assert all(v >= 0 for v in phases.values()), phases


@pytest.mark.parametrize("path", PATHS)
def test_one_commit_time_a_later_token_and_times_never_decrease(served, path):
    for result in served[path][0]:
        record = result.trace
        assert record.new_tokens == record.max_new_tokens
        assert len(record.token_times) == record.new_tokens - 1
        assert len(result.tokens) == record.prompt_len + record.new_tokens
        stamps = [record.t_submit, record.t_dequeued, record.t_admit,
                  record.t_reserved, record.t_first, record.t_insert,
                  *record.token_times, record.t_done]
        assert None not in stamps
        assert stamps == sorted(stamps)
        assert len(record.token_gaps()) == len(record.token_times)
        assert record.bucket > 0 and record.rid is not None
        if record.max_new_tokens == 1:      # completes at its prefill
            assert phases_empty(record, "await_slot", "decode")


def phases_empty(record, *names):
    return all(record.phases()[n] == 0.0 for n in names)


@pytest.mark.parametrize("path", PATHS)
def test_warmup_stays_out_and_recent_holds_the_served(served, path):
    results, after_warmup, recent, stats = served[path]
    assert after_warmup == []
    assert len(recent) == 3 == stats["requests_completed"]
    assert {r.rid for r in recent} >= {r.trace.rid for r in results}
    assert all(r.t_done is not None for r in recent)
    assert len({r.server for r in recent}) == 1


def test_hit_record_names_its_prefix(served):
    for result in served["hit"][0]:
        assert result.trace.prefix_len == result.prefix_len == 8
        # The suffix ran at a narrower width than the prompt's own.
        assert result.trace.bucket < 16


def test_stats_count_paces_and_carry_no_kernel_costs(served):
    results, _, _, stats = served["miss"]
    assert stats["tick_paces"] >= 0
    # A rung's tick times are its tick records' (they carry `slots`),
    # cut to the served traffic by time: warm-up's ticks are kept too.
    t0 = min(r.trace.t_submit for r in results)
    ticks = [t for t in reqtrace.recent_ticks(results[0].trace.server)
             if t.t_fetched >= t0 and t.live]
    for slots, geometry in stats["geometry"]["per_geometry"].items():
        assert set(geometry) == {"ticks", "occupancy_mean"}
        of_rung = [t for t in ticks if t.slots == int(slots)]
        assert len(of_rung) == geometry["ticks"] > 0
        assert sum(t.live for t in of_rung) == pytest.approx(
            geometry["ticks"] * geometry["occupancy_mean"])


def test_zero_token_request_gets_an_empty_record(model, params):
    with Scheduler(model, params, slots=2, page_size=8) as sched:
        result = sched.submit(ServeRequest(
            prompt=[3, 5], max_new_tokens=0)).result(timeout=30)
    assert result.ttft_s == result.latency_s == 0.0
    assert set(result.trace.phases().values()) == {0.0}


# ------------------------------------------------------------- the ring

def _finished(server, rid):
    record = reqtrace.RequestRecord(rid, server, 4, 2, 1.0)
    for name in ("t_dequeued", "t_admit", "t_reserved", "t_first",
                 "t_insert", "t_done"):
        setattr(record, name, 1.0)
    return record


@pytest.fixture
def empty_ring():
    reqtrace.clear()
    yield
    reqtrace.clear()


def test_ring_holds_at_most_its_cap(empty_ring):
    server = reqtrace.new_server()
    for i in range(reqtrace.RECENT_CAP + 10):
        reqtrace.publish(_finished(server, "r%d" % i))
    kept = reqtrace.recent()
    assert len(kept) == reqtrace.RECENT_CAP
    assert kept[0].rid == "r10" and kept[-1].rid == "r%d" % (
        reqtrace.RECENT_CAP + 9)
    reqtrace.clear()
    assert reqtrace.recent() == []


def test_two_servers_do_not_mix(empty_ring):
    first, second = reqtrace.new_server(), reqtrace.new_server()
    assert second == first + 1
    reqtrace.publish(_finished(first, "a"))
    reqtrace.publish(_finished(second, "b"))
    reqtrace.publish(_finished(first, "c"))
    assert [r.rid for r in reqtrace.recent()] == ["b"]       # last started
    assert [r.rid for r in reqtrace.recent(first)] == ["a", "c"]
    assert [r.rid for r in reqtrace.recent(0)] == ["a", "b", "c"]


def test_rids_are_unique_without_a_tracer():
    assert reqtrace.get() is None
    a, b = reqtrace.new_rid(), reqtrace.new_rid()
    assert a != b and a.startswith("r") and len(a) == 7


# ------------------------------------------------------ the JSONL export

@pytest.mark.parametrize("event", ["complete", "fail", "shed"])
def test_record_buffers_what_emit_makes_durable(tmp_path, event):
    """The scheduler's marks go through `record()`: a terminal event no
    longer costs the tick thread a file append."""
    path = str(tmp_path / "reqtrace.jsonl")
    tracer = reqtrace.RequestTracer(path=path, flush_every=1000)
    rid = tracer.new_request()
    tracer.record(rid, "submitted", prompt_len=2)
    tracer.record(rid, event)
    assert not os.path.exists(path) and tracer.events_emitted() == 2
    tracer.close()
    names = [r["payload"]["event"] for r in events.read_job_events(path)]
    assert names == ["submitted", event]
    tracer.emit(rid, event)                  # other callers: durable at once
    assert len(events.read_job_events(path)) == 3


def test_jsonl_of_one_request_is_fed_by_the_same_marks(model, params,
                                                       tmp_path):
    path = str(tmp_path / "reqtrace.jsonl")
    reqtrace.install(path=path)
    try:
        with Scheduler(model, params, slots=2, page_size=8) as sched:
            result = sched.submit(ServeRequest(
                prompt=[3, 5, 7], max_new_tokens=3,
                temperature=0.0)).result(timeout=300)
            # Buffered: nothing was written for the sake of `complete`.
            assert not os.path.exists(path)
        records = events.read_job_events(path, kind="reqtrace")
    finally:
        reqtrace.uninstall()
    payloads = [r["payload"] for r in records]
    assert {p["rid"] for p in payloads} == {result.trace.rid} == {"r000000"}
    assert [p["event"] for p in payloads] == [
        "submitted", "queued", "radix_probe", "pages_reserved", "prefill",
        "slot_insert", "complete"]
    by_event = {p["event"]: p for p in payloads}
    assert set(by_event["submitted"]) == {"rid", "event", "prompt_len",
                                          "max_new"}
    assert set(by_event["queued"]) == {"rid", "event", "wait_s"}
    assert set(by_event["pages_reserved"]) == {"rid", "event", "pages",
                                               "wait_s"}
    assert set(by_event["prefill"]) == {"rid", "event", "bucket",
                                        "prefix_len", "dur_s"}
    assert set(by_event["slot_insert"]) == {"rid", "event", "slot"}
    assert set(by_event["complete"]) == {"rid", "event", "ttft_s",
                                         "latency_s", "tokens", "prefix_len"}
    assert by_event["complete"]["ttft_s"] == result.ttft_s
    assert by_event["complete"]["latency_s"] == result.latency_s
