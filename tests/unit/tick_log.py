"""What a Scheduler's tick thread does to the device, in the order it
does it: a log for the tests of the one-deep tick pipeline
(test_serving.py, test_autoscale.py, test_serve_chaos.py). Below it,
`PrefillLog`: the same for the admission thread's one-deep prefill
pipeline.

Entries, appended by wrappers round the calls themselves:

    ("dispatch", n)      `engine.tick()` returned tick n's tokens
    ("fetch", n)         the blocking `runtime.device_fetch` of tick n
    ("drain", flying)    `_drain_tick` was called; `flying` says
                         whether a tick was in flight
    ("nap", timeout)     the tick thread's wait (0.005: a `tick_pace`
                         nap, 0.05: `tick_idle`)
    ("resize", new)      `engine.resize(new, perm)`
    ("chaos", kind)      `_apply_chaos(event)`
"""

import contextlib
import threading


class TickLog:

    def __init__(self, sched, monkeypatch):
        from cloud_tpu.parallel import runtime
        self.sched = sched
        self.entries = []
        self._lock = threading.Lock()
        self._ticks = {}     # id(out) -> n, for the ticks in flight
        self._keep = []      # the outs themselves: ids stay unique
        self.on_dispatch = None   # called with n on the tick thread
        engine = sched.engine
        tick, resize = engine.tick, engine.resize
        fetch = runtime.device_fetch
        drain, apply_chaos = sched._drain_tick, sched._apply_chaos
        wait = sched._wake.wait

        def logged_tick():
            out = tick()
            n = len(self._keep)
            self._keep.append(out)
            self._ticks[id(out)] = n
            self._add("dispatch", n)
            if self.on_dispatch is not None:
                self.on_dispatch(n)
            return out

        def logged_fetch(tree):
            # The tick's read-back is (tokens, counters); a prefill
            # fetches its first token alone.
            if (isinstance(tree, tuple) and len(tree) == 2
                    and id(tree[0]) in self._ticks):
                self._add("fetch", self._ticks.pop(id(tree[0])))
            return fetch(tree)

        def logged_drain():
            self._add("drain", sched._flight is not None)
            return drain()

        def logged_wait(timeout=None):
            self._add("nap", timeout)
            return wait(timeout=timeout)

        def logged_resize(new_slots, perm):
            self._add("resize", int(new_slots))
            return resize(new_slots, perm)

        def logged_chaos(event):
            self._add("chaos", event.kind)
            return apply_chaos(event)

        monkeypatch.setattr(engine, "tick", logged_tick)
        monkeypatch.setattr(runtime, "device_fetch", logged_fetch)
        monkeypatch.setattr(sched, "_drain_tick", logged_drain)
        monkeypatch.setattr(sched._wake, "wait", logged_wait)
        monkeypatch.setattr(engine, "resize", logged_resize)
        monkeypatch.setattr(sched, "_apply_chaos", logged_chaos)

    def _add(self, *entry):
        with self._lock:
            self.entries.append(entry)

    def mark(self):
        with self._lock:
            return len(self.entries)

    def since(self, mark=0):
        with self._lock:
            return list(self.entries[mark:])


def check_order(entries):
    """Holds a log (one that starts and ends with nothing in flight)
    to the pipeline's rules and returns `(ticks, overlapped)`: how
    many ticks it dispatched, and how many of them while the tick
    before was still unfetched.

    - ticks are fetched in the order dispatched, each once;
    - at most one tick is unfetched when another is dispatched, and
      then the older one is fetched next: dispatch n+1 precedes
      fetch n, nothing else comes between;
    - a nap, an idle wait, a resize and a chaos event find nothing in
      flight."""
    flying = []          # dispatched, not fetched
    ticks = overlapped = 0
    last = None
    for entry in entries:
        kind = entry[0]
        if kind == "dispatch":
            assert len(flying) <= 1, (entry, flying)
            if flying:
                overlapped += 1
            flying.append(entry[1])
            ticks += 1
        elif kind == "fetch":
            assert flying and flying[0] == entry[1], (entry, flying)
            flying.pop(0)
        elif kind in ("nap", "resize", "chaos"):
            assert not flying, (entry, flying)
        if len(flying) == 2:
            # Only between dispatch n+1 and fetch n.
            assert kind == "dispatch", (last, entry)
        last = entry
    assert not flying, flying
    return ticks, overlapped


class PrefillLog:
    """What a Scheduler's admission thread does to the device, and what
    it waits for, in order: a log for the tests of the one-deep
    prefill pipeline (test_serving.py, test_serve_chaos.py).

        ("dispatch", n)      `engine.prefill_dispatch` returned prefill n
        ("fetch", n)         `engine.prefill_finish` of prefill n: the
                             blocking fetch of its first token
        ("wait", "queue")    a blocking `_admit_q.get`
        ("wait", "pages")    a `pool.reserve` that may wait (timeout > 0)
                             on the admission thread
        ("wait", "hit")      a hit ticket made for the tick thread

    `hold()` parks the admission thread at its next blocking `get`
    until the block ends, so that what a test submits meanwhile is
    taken off the queue as one run of windows."""

    THREAD = "graftserve-prefill"

    def __init__(self, sched, monkeypatch):
        from cloud_tpu.serving import scheduler as scheduler_lib
        self.sched = sched
        self.entries = []
        self.rids = []           # prefill n's request id
        self._lock = threading.Lock()
        self._flights = {}       # id(flight) -> n
        self._keep = []
        self._open = threading.Event()
        self._open.set()
        self._parked = threading.Event()
        self.on_dispatch = None  # called with n on the admission thread
        engine, pool, admit_q = sched.engine, sched.pool, sched._admit_q
        dispatch, finish = engine.prefill_dispatch, engine.prefill_finish
        reserve, get = pool.reserve, admit_q.get
        ticket = scheduler_lib._HitTicket

        def logged_dispatch(*args, **kwargs):
            flight = dispatch(*args, **kwargs)
            n = len(self._keep)
            self._keep.append(flight)
            self._flights[id(flight)] = n
            self.rids.append(kwargs.get("rid"))
            self._add("dispatch", n)
            if self.on_dispatch is not None:
                self.on_dispatch(n)
            return flight

        def logged_finish(flight, rid=None):
            # `engine.prefill()` finishes its own dispatch, unlogged.
            if id(flight) in self._flights:
                self._add("fetch", self._flights.pop(id(flight)))
            return finish(flight, rid=rid)

        def logged_reserve(n, timeout=None):
            if (threading.current_thread().name == self.THREAD
                    and (timeout is None or timeout > 0)):
                self._add("wait", "pages")
            return reserve(n, timeout=timeout)

        def logged_get(block=True, timeout=None):
            if block and threading.current_thread().name == self.THREAD:
                self._add("wait", "queue")
                if not self._open.is_set():
                    self._parked.set()
                    self._open.wait(timeout=60)
            return get(block=block, timeout=timeout)

        class LoggedTicket(ticket):
            # A class, not a wrapper: the tick thread tells its ready
            # items apart with `isinstance`.
            __slots__ = ()

            def __init__(item, *args, **kwargs):
                if threading.current_thread().name == self.THREAD:
                    self._add("wait", "hit")
                super().__init__(*args, **kwargs)

        monkeypatch.setattr(engine, "prefill_dispatch", logged_dispatch)
        monkeypatch.setattr(engine, "prefill_finish", logged_finish)
        monkeypatch.setattr(pool, "reserve", logged_reserve)
        monkeypatch.setattr(admit_q, "get", logged_get)
        monkeypatch.setattr(scheduler_lib, "_HitTicket", LoggedTicket)

    _add = TickLog._add
    mark = TickLog.mark
    since = TickLog.since

    @contextlib.contextmanager
    def hold(self):
        self._parked.clear()
        self._open.clear()
        try:
            assert self._parked.wait(timeout=60), \
                "the admission thread never came back to its queue"
            yield
        finally:
            self._open.set()


def check_prefill_order(entries):
    """Holds a `PrefillLog` (one that starts and ends with nothing in
    flight) to the pipeline's rules and returns `(prefills,
    overlapped)`: how many were dispatched, and how many of them while
    the one before was still unfetched.

    - prefills are fetched in the order dispatched, each once;
    - at most one is unfetched when another is dispatched, and then
      the older one is fetched next: dispatch n+1, fetch n, nothing
      between;
    - whatever the thread waits for that is not the device (an empty
      queue, pages, the tick thread taking a hit) finds nothing in
      flight."""
    flying = []
    prefills = overlapped = 0
    last = None
    for entry in entries:
        kind = entry[0]
        if kind == "dispatch":
            assert len(flying) <= 1, (entry, flying)
            if flying:
                overlapped += 1
            flying.append(entry[1])
            prefills += 1
        elif kind == "fetch":
            assert flying and flying[0] == entry[1], (entry, flying)
            flying.pop(0)
        elif kind == "wait":
            assert not flying, (entry, flying)
        if len(flying) == 2:
            assert kind == "dispatch", (last, entry)
        last = entry
    assert not flying, flying
    return prefills, overlapped
