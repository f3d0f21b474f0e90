"""The profiler's trace: taking a short steady slice of the window, and the
reduction from its events to busy time, idle gaps and per-name device time.

The reduction works on plain arrays (`reduce_events`), so it is checked on a
small synthetic trace in tier-1; `load_xplane` turns the profiler's
`.xplane.pb` into those arrays with nothing but JAX.
"""

import dataclasses
import glob
import os
import shutil
import threading

import numpy as np

from cellbench import harness

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_HOST_EVENT_NS = 20_000


@dataclasses.dataclass
class Events:
    """Events of one line: parallel arrays, times in ns."""
    names: list
    start: np.ndarray
    dur: np.ndarray

    @classmethod
    def of(cls, triples):
        triples = sorted(triples, key=lambda t: t[1])
        return cls([t[0] for t in triples],
                   np.asarray([t[1] for t in triples], float),
                   np.asarray([t[2] for t in triples], float))


def short_name(text):
    """The trace names a device op by its whole HLO instruction; the short
    name is what stands before ` = `, without the `%`."""
    return text.split(" = ", 1)[0].lstrip("%")[:80]


def merged(start, dur):
    """Union of intervals as (starts, ends), sorted and disjoint."""
    if len(start) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(start)
    s, e = start[order], (start + dur)[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    firsts = np.flatnonzero(new)
    ends = np.append(run_end[firsts[1:] - 1], run_end[-1])
    return s[firsts], ends


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                    # mean over the chips used
    op_seconds: dict                 # device op name -> seconds, mean over chips
    op_counts: dict
    modules: Events                  # programs on the first device
    gaps: list                       # (name, seconds), longest first
    op_text: dict = None             # short name -> the trace's full text

    def breakdown(self):
        top = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}

    def module_gaps_s(self, match=lambda name: True):
        """Idle seconds between consecutive device programs that `match`."""
        keep = [i for i, n in enumerate(self.modules.names) if match(n)]
        if len(keep) < 2:
            return []
        s, d = self.modules.start[keep], self.modules.dur[keep]
        return list(np.maximum(s[1:] - (s[:-1] + d[:-1]), 0.0) / 1e9)


def reduce_events(device_ops, device_modules, host_events, window_ns):
    """`device_ops`: one Events per chip (its op line). `device_modules`: the
    first chip's program line. `host_events`: Events of all host threads."""
    chips = max(len(device_ops), 1)
    busy, seconds, counts, gaps, texts = 0.0, {}, {}, [], {}
    for i, ev in enumerate(device_ops):
        s, e = merged(ev.start, ev.dur)
        busy += float(np.sum(e - s))
        for text, d in zip(ev.names, ev.dur):
            name = short_name(text)
            texts.setdefault(name, text[:2000])
            seconds[name] = seconds.get(name, 0.0) + d / 1e9 / chips
            counts[name] = counts.get(name, 0) + 1
        if i == 0 and len(s) > 1:
            lengths = s[1:] - e[:-1]
            for j in np.argsort(-lengths)[:10]:
                if lengths[j] <= 0:
                    break
                gaps.append((_host_in(host_events, e[j], s[j + 1]),
                             float(lengths[j]) / 1e9))
    return Reduced(window_s=window_ns / 1e9, busy_s=busy / 1e9 / chips,
                   op_seconds=seconds, op_counts=counts, modules=device_modules,
                   gaps=gaps, op_text=texts)


def _host_in(host, lo, hi):
    """The host event that overlaps [lo, hi) most, by name."""
    if host is None or len(host.start) == 0:
        return "no host event"
    overlap = np.minimum(host.start + host.dur, hi) - np.maximum(host.start, lo)
    best = int(np.argmax(overlap))
    return host.names[best] if overlap[best] > 0 else "no host event"


def load_xplane(path):
    """(device_ops, device_modules, host_events, window_ns)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops, modules, host = [], None, []
    lo, hi = np.inf, -np.inf
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            triples = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            if not triples:
                continue
            lo = min(lo, min(t[1] for t in triples))
            hi = max(hi, max(t[1] + t[2] for t in triples))
            if is_device and line.name == OPS_LINE:
                ops.append(Events.of(triples))
            elif is_device and line.name == MODULES_LINE and modules is None:
                modules = Events.of(triples)
            elif not is_device and plane.name.startswith("/host:"):
                host.extend(t for t in triples if t[2] >= MIN_HOST_EVENT_NS)
    if modules is None:
        modules = Events.of([])
    return ops, modules, Events.of(host), max(hi - lo, 0.0)


class Slice:
    """Traces `trace_for_s` seconds of the window, `trace_after_s` in, from a
    timer thread, so the window's own loop is not touched."""

    def __init__(self, run, mix):
        self.enabled = bool(run.trace)
        self.after = float(mix.get("trace_after_s", 3.0))
        self.length = float(mix.get("trace_for_s", 2.0))
        self.dir = os.path.join(harness.OUT_DIR, "trace_" + run.cell.name)
        self._lock = threading.Lock()
        self._state = "idle"
        self._timers = []

    def arm(self, t0):
        if not self.enabled:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self._timer(self.after, self._start)

    def _timer(self, delay, fn):
        t = threading.Timer(delay, fn)
        t.daemon = True
        self._timers.append(t)
        t.start()

    def _start(self):
        import jax

        with self._lock:
            if self._state != "idle":
                return
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self._state = "tracing"
        self._timer(self.length, self._stop)

    def _stop(self):
        import jax

        with self._lock:
            if self._state == "tracing":
                jax.profiler.stop_trace()
                self._state = "done"

    def close(self):
        """Ends a slice that the window's end overtook. Call before reading."""
        for t in self._timers:
            t.cancel()
        with self._lock:
            if self._state == "idle":
                self._state = "closed"
        self._stop()
        for t in self._timers:
            t.join(timeout=120)

    def reduced(self, chips):
        if not self.enabled or self._state != "done":
            return None
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not found:
            return None
        ops, modules, host, window_ns = load_xplane(found[0])
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduce_events(ops[:chips], modules, host, window_ns)
