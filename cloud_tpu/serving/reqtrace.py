"""graftlens request tracing: per-request lifecycle events for graftserve.

Every request admitted to the Scheduler gets a process-unique request id
(rid) stamped at ``submit()``; the serving path then annotates its
lifecycle as typed events::

    submitted -> queued -> radix_probe -> pages_reserved -> prefill
              -> slot_insert -> tick_commit* -> complete | fail | shed

With chunked prefill enabled (``CLOUD_TPU_SERVE_PREFILL_CHUNK``), the
prefill phase is tiled by per-chunk events emitted at each dispatch::

    pages_reserved -> prefill_chunk{i, n, tokens, dur_s}*
                   -> prefill{..., chunks}

``prefill_chunk`` events are sub-phase detail INSIDE the
(pages_reserved, prefill] span, not lifecycle boundaries — phase sums
still telescope to the submitted -> complete wall time with or without
them, and ``collect --serve`` audits exactly that.

graftpack (the KV memory hierarchy) adds page-tier movement events:
``page_demote{pages, tokens}`` fires at a request's completion when its
written prefix pages snapshot to the host tier, and
``page_promote{pages, prefix_len}`` fires INSIDE a later request's
admission when host pages are copied back ahead of its suffix prefill —
a promoted request's ``prefill`` event then carries the promoted
``prefix_len``, which is how ``collect --serve`` splits follow-up-turn
TTFT into promoted vs device-cache-hit vs re-prefill classes.
``page_demote`` lands between the final tick and ``complete`` on the
same rid; neither event is a lifecycle boundary, so phase sums
telescope unchanged.

graftflex (elastic tick geometry) adds a GLOBAL event — emitted with
``rid=None`` because a resize belongs to the replica, not to any one
request: ``resize{from, to, reason, tick}`` fires at the tick
boundary where the slot count moves one ladder rung (``reason`` is
``grow``/``shrink`` for policy resizes, ``warmup`` for the ladder walk,
or a caller-supplied tag for forced resizes). A multi-rung forced jump
emits one event per adjacent step, so the event stream replays the
exact executable dispatches. ``tick_commit`` events carry a ``slots``
field stamping the geometry they committed under, which is how
``collect --serve`` splits occupancy per rung and draws the slot-count
counter lane; per-request phase sums are untouched (a resize is not a
lifecycle boundary — in-flight rows migrate bit-identically).

graftstorm (serving chaos) adds mid-lifecycle fault events: a chaos
injection that hits an in-flight request emits ``slot_fault`` (with the
taxonomy ``kind`` and the victim slot) followed by ``requeue`` (with
``tokens_done``, the retained progress) — the request then re-enters at
``pages_reserved``/``prefill`` and still terminates normally, so a
requeued rid is NOT an orphan. ``shed`` (with ``reason`` and
``predicted_ttft``) is the SLO-admission terminal: refused by policy,
never prefilled.

Events are buffered in-process and flushed as ``reqtrace`` JSONL records
whose envelope matches ``cloud_tpu.utils.events`` job-event records
(time / monotonic / host / pid / process_index / kind / payload), so
``read_job_events()`` and the fleet collector consume them unchanged.
``monitoring/collect.py --serve`` rolls them into a per-request waterfall
trace plus ``serve_report.json`` (TTFT/TPOT percentiles, queue-wait
breakdown, SLO goodput).

The record (always on). Every request the Scheduler serves carries one
`RequestRecord`, filled in memory by the scheduler's one marking call at
each boundary it passes, on `time.monotonic()`:

    t_submit    submit() took it
    t_dequeued  the admission thread popped its window off the queue
    t_admit     its own turn in the window began (the requests ahead of
                it in the window are prefilled first)
    t_reserved  its KV pages were reserved
    t_first     its first token was on the host (the TTFT point)
    t_insert    it was written into a decode slot
    token_times the tick's commit time of each later token
    t_done      the result was handed to the caller

so that the phases `queue`, `window`, `reserve` and `prefill` add up to
`ttft_s` exactly and, with `await_slot`, `decode` and `finish`, to
`latency_s` (`RequestRecord.phases()`); a phase a path does not have is
0, never missing. `ServeResult.trace` is the record, `ttft_s` and
`latency_s` are computed from it, and the last `RECENT_CAP` finished
ones are `recent()`: process-wide, bounded, the lock held for an
append. A record names the ordinal of the Scheduler that served it and
`recent()` gives the last started one's unless asked otherwise. Warm-up
traffic stays out of `recent()` and of the JSONL. Nothing is written
anywhere: the cost a request is some ten clock reads and one append,
and a tick, one float a live slot.

The tick record (always on). Every decode tick the Scheduler commits
leaves one `TickRecord`, made by the tick thread where the tick is
dispatched and published where it is committed, on the same clock:

    seq         this server's tick ordinal (warm-up's ticks count)
    slots       the geometry it ran at
    t_dispatch  `engine.tick()` was about to be called
    t_fetch0    the host began to wait for its tokens
    t_fetched   they were on the host (each later token's commit time
                in the request records)
    t_committed they were handed to their requests
    live        slots it advanced
    overlapped  dispatched while the tick before was unfetched
                (`drained`: it was not; nothing was in flight)
    kv_live     this tick's term of `kv_live_tokens`, and
    kv_walked   of `kv_walked_tokens`
    dispatched  the engine's dispatch log (`engine.Dispatched` notes:
                program, time, rows, rid) from the dispatch of the
                tick before, whose `serve_tick` note comes first, to
                this tick's: what the device was given to run ahead
                of this tick, by either thread
    naps        `tick_pace` naps since the tick before
    idle_s      seconds in `tick_idle` waits since the tick before

so that `t_fetched` of one tick to the next is the device's tick
period where `dispatched` holds `serve_tick` alone, and holds a
prefill, an insert, a nap where it names one. The last `TICKS_CAP` are
`recent_ticks()`, in a ring of their own with `recent()`'s rule for
`server`; `clear()` leaves it alone (`clear_ticks()` empties it).
Warm-up's ticks are kept like any other: a reader cuts by time. A tick
costs one swap of the engine's log, one small object and one append.

The JSONL (opt-in) is an export of the same marks. When
``CLOUD_TPU_REQTRACE`` is unset no tracer is installed — ``get()``
returns None, no events are built, and no file or thread is ever
created. The tracer itself never spawns threads either; the scheduler's
marks go through ``record()``, which only buffers, and buffered lines
are appended when the buffer fills, at ``flush()`` and at the
scheduler's ``close()`` — never on the tick thread's ``complete``.
``emit()`` (other callers) also flushes on a terminal event.
"""

import collections
import itertools
import json
import os
import socket
import sys
import threading
import time

from cloud_tpu.utils import storage

_TRUTHY_OFF = ("", "0", "off", "false", "none")

# Batched per-slot tick commits: one tick_commit event every N engine
# ticks per active slot (overridable via CLOUD_TPU_REQTRACE_TICK_EVERY).
DEFAULT_TICK_EVERY = 8

#: How many finished requests' records `recent()` keeps.
RECENT_CAP = 4096

#: How many committed ticks' records `recent_ticks()` keeps (a 45 s
#: window of 6 ms ticks is 7 500).
TICKS_CAP = 16384

_tracer = None
_lock = threading.Lock()
_recent = collections.deque(maxlen=RECENT_CAP)
_recent_ticks = collections.deque(maxlen=TICKS_CAP)
_recent_lock = threading.Lock()
_rids = itertools.count()
_servers = itertools.count(1)
_last_server = 0


class RequestRecord:
    """One request's boundaries (see the module docstring). Times are
    `time.monotonic()` seconds; a boundary not reached yet is None."""

    __slots__ = ("rid", "server", "path", "prompt_len", "bucket",
                 "max_new_tokens", "new_tokens", "prefix_len",
                 "t_submit", "t_dequeued", "t_admit", "t_reserved",
                 "t_first", "t_insert", "token_times", "t_done")

    def __init__(self, rid, server, prompt_len, max_new_tokens,
                 t_submit):
        self.rid = rid                # None for warm-up traffic
        self.server = server          # ordinal of its Scheduler
        # miss | hit | miss_on_tick | chunked | requeue
        self.path = "miss"
        self.prompt_len = prompt_len
        self.bucket = 0               # pow2 width its prefill ran at
        self.max_new_tokens = max_new_tokens
        self.new_tokens = 0           # tokens the device produced
        self.prefix_len = 0           # tokens served from the cache
        self.t_submit = t_submit
        self.t_dequeued = self.t_admit = self.t_reserved = None
        self.t_first = self.t_insert = self.t_done = None
        self.token_times = []

    @property
    def ttft_s(self):
        return self.t_first - self.t_submit

    @property
    def latency_s(self):
        return self.t_done - self.t_submit

    def phases(self):
        """The finished request's latency, tiled: the first four add up
        to `ttft_s`, all seven to `latency_s`."""
        last = self.token_times[-1] if self.token_times else self.t_insert
        return {
            "queue": self.t_dequeued - self.t_submit,
            "window": self.t_admit - self.t_dequeued,
            "reserve": self.t_reserved - self.t_admit,
            "prefill": self.t_first - self.t_reserved,
            "await_slot": self.t_insert - self.t_first,
            "decode": last - self.t_insert,
            "finish": self.t_done - last,
        }

    def token_gaps(self):
        """Seconds from each later token to the one before it; the
        first from `t_first`, so it holds the wait for a slot."""
        times = [self.t_first] + self.token_times
        return [b - a for a, b in zip(times, times[1:])]


class TickRecord:
    """One decode tick's times and what shared the device with it (see
    the module docstring). Times are `time.monotonic()` seconds."""

    __slots__ = ("seq", "server", "slots", "t_dispatch", "t_fetch0",
                 "t_fetched", "t_committed", "live", "overlapped",
                 "kv_live", "kv_walked", "dispatched", "naps", "idle_s")

    def __init__(self, seq, server, slots, t_dispatch, overlapped,
                 dispatched=(), naps=0, idle_s=0.0):
        self.seq = seq
        self.server = server
        self.slots = slots
        self.t_dispatch = t_dispatch
        self.t_fetch0 = self.t_fetched = self.t_committed = None
        self.live = 0
        self.overlapped = overlapped
        self.kv_live = self.kv_walked = 0
        self.dispatched = dispatched
        self.naps = naps
        self.idle_s = idle_s

    @property
    def drained(self):
        """Nothing was in flight when it was dispatched."""
        return not self.overlapped


def new_rid():
    """A process-unique request id ("r000042")."""
    return "r%06d" % next(_rids)


def new_server():
    """The ordinal of a Scheduler that starts now; `recent()` follows
    the last one."""
    global _last_server
    with _recent_lock:
        _last_server = next(_servers)
        return _last_server


def publish(record):
    """Keeps a finished request's record among the last RECENT_CAP."""
    with _recent_lock:
        _recent.append(record)


def recent(server=None):
    """The kept records, oldest first: of the Scheduler started last,
    of the one with ordinal `server`, or (`server=0`) of all."""
    return _of_server(_recent, server)


def _of_server(ring, server):
    with _recent_lock:
        records = list(ring)
        if server is None:
            server = _last_server
    if server == 0:
        return records
    return [r for r in records if r.server == server]


def clear():
    """Empties `recent()`, and not `recent_ticks()`: a caller that
    drops requests of its own from the first has no tick to drop."""
    with _recent_lock:
        _recent.clear()


def publish_tick(record):
    """Keeps a committed tick's record among the last TICKS_CAP."""
    with _recent_lock:
        _recent_ticks.append(record)


def recent_ticks(server=None):
    """The kept tick records, oldest first, with `recent()`'s rule for
    `server`."""
    return _of_server(_recent_ticks, server)


def clear_ticks():
    """Empties `recent_ticks()`."""
    with _recent_lock:
        _recent_ticks.clear()


def env_enabled():
    """True when CLOUD_TPU_REQTRACE asks for request tracing."""
    value = os.environ.get("CLOUD_TPU_REQTRACE", "")
    return value.strip().lower() not in _TRUTHY_OFF


def default_path():
    base = (os.environ.get("CLOUD_TPU_REQTRACE_DIR")
            or os.environ.get("CLOUD_TPU_TELEMETRY_DIR")
            or os.getcwd())
    return os.path.join(base, "reqtrace.jsonl")


def _process_index():
    env = os.environ.get("CLOUD_TPU_PROCESS_INDEX")
    if env is not None:
        try:
            return int(env)
        except ValueError:
            pass
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return jax.process_index()
        except Exception:
            pass
    return 0


class RequestTracer:
    """Buffered JSONL emitter for request lifecycle events.

    Thread-safe; shared by the Scheduler's admission and tick threads.
    Never spawns threads of its own — the env-unset pin in CI asserts
    both zero events and zero threads.
    """

    def __init__(self, path=None, tick_every=None, flush_every=64):
        self.path = path or default_path()
        if tick_every is None:
            raw = os.environ.get("CLOUD_TPU_REQTRACE_TICK_EVERY", "")
            try:
                tick_every = int(raw)
            except ValueError:
                tick_every = DEFAULT_TICK_EVERY
        self.tick_every = max(1, int(tick_every))
        self._flush_every = max(1, int(flush_every))
        self._lock = threading.Lock()
        self._buffer = []
        self._next_rid = 0
        self._emitted = 0
        self._host = socket.gethostname()
        self._pid = os.getpid()
        self._process_index = _process_index()
        if not storage.is_gcs_path(self.path):
            parent = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(parent, exist_ok=True)

    def new_request(self):
        """Allocates a process-unique request id ("r000042")."""
        with self._lock:
            rid = "r%06d" % self._next_rid
            self._next_rid += 1
        return rid

    def emit(self, rid, event, **fields):
        """Records one lifecycle event and makes a terminal one
        durable. ``rid=None`` marks a global (request-independent)
        event such as prefix_evict."""
        self.record(rid, event, **fields)
        if event in ("complete", "fail", "shed"):
            self.flush()

    def record(self, rid, event, **fields):
        """Buffers one lifecycle event; the file is appended to only
        when the buffer fills (the scheduler's marks come here, so a
        tick never waits for a terminal event's write)."""
        payload = {"rid": rid, "event": event}
        payload.update(fields)
        record = {
            "time": time.time(),
            "monotonic": time.monotonic(),
            "host": self._host,
            "pid": self._pid,
            "process_index": self._process_index,
            "kind": "reqtrace",
            "payload": payload,
        }
        line = json.dumps(record, sort_keys=True) + "\n"
        with self._lock:
            self._buffer.append(line)
            self._emitted += 1
            if len(self._buffer) >= self._flush_every:
                self._flush_locked()

    def events_emitted(self):
        with self._lock:
            return self._emitted

    def _flush_locked(self):
        if not self._buffer:
            return
        data = "".join(self._buffer).encode("utf-8")
        self._buffer = []
        storage.append_bytes(self.path, data)

    def flush(self):
        with self._lock:
            self._flush_locked()

    def close(self):
        self.flush()


def install(path=None, tick_every=None):
    """Installs (or replaces) the ambient tracer and returns it."""
    global _tracer
    with _lock:
        previous, _tracer = _tracer, RequestTracer(path=path,
                                                   tick_every=tick_every)
    if previous is not None:
        previous.flush()
    return _tracer


def uninstall():
    """Flushes and removes the ambient tracer; returns it (or None)."""
    global _tracer
    with _lock:
        previous, _tracer = _tracer, None
    if previous is not None:
        previous.flush()
    return previous


def get():
    """The ambient tracer, or None when tracing is off."""
    return _tracer


def maybe_enable():
    """Scheduler.start() seam: returns the installed tracer; installs
    one from the environment when CLOUD_TPU_REQTRACE is set; otherwise
    returns None without touching the filesystem."""
    if _tracer is not None:
        return _tracer
    if not env_enabled():
        return None
    return install()


__all__ = [
    "DEFAULT_TICK_EVERY",
    "RECENT_CAP",
    "RequestRecord",
    "RequestTracer",
    "TICKS_CAP",
    "TickRecord",
    "clear",
    "clear_ticks",
    "default_path",
    "env_enabled",
    "get",
    "install",
    "maybe_enable",
    "new_rid",
    "new_server",
    "publish",
    "publish_tick",
    "recent",
    "recent_ticks",
    "uninstall",
]
