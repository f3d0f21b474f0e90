"""The program's own record of each decode tick of the window
(`cloud_tpu.serving.reqtrace.recent_ticks()`: kept in memory, bounded,
outliving the Scheduler), and the account of the window's time made from it,
for the readers of tick-side per-layer metrics.

A tick's record holds when its tokens were on the host (`t_fetched`) and what
the engine dispatched to the device between the tick before and it
(`dispatched`: notes of program, time, rows), with the naps and idle waits the
tick thread took meanwhile. The window is the request records' own: from the
least `t_submit` to the greatest `t_done`; its ticks are those fetched inside
it. Interval n runs from `max(t_fetched[n-1], window start)` to `t_fetched[n]`
and is what the device was given to do ahead of tick n, then tick n. From the
intervals the window is accounted whole:

- the clean period: the median interval of ticks that overlapped the tick
  before, with nothing but a `serve_tick` noted in them and in both
  neighbours, no nap, no idle wait: the device's tick period;
- prefill time: in an interval that holds a prefill's note (whole or chunk) in
  its own tick's record or in the next tick's (a note is written after its
  dispatch has returned, so it may land one tick late), the time from the
  first such note, or from the interval's start if that is later, to its end,
  less one clean period;
- wait time: what else an interval holds beyond the clean period where its
  tick carries a nap or an idle wait;
- tick time: ticks x the clean period.

What is left of the window is inserts, evictions, slow commits and whatever
has no name yet.

Nothing is read unless the request records passed their own check and the
window's tick records number the ticks the driver counted to within
`COUNT_SLACK` (a tick in flight at either end), so a mismatch (another
server's records, a ring that overflowed, a program without the record) shows
as a missing metric and never as a wrong one.
"""

import dataclasses
import statistics

from cellbench import request_records

TICK = "serve_tick"
WHOLE_PREFILL = "serve_prefill"
PREFILLS = (WHOLE_PREFILL, "serve_prefill_chunk")
COUNT_SLACK = 2
# A clean tick and both its neighbours.
MIN_TICKS = 3


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    ticks: list          # tick records fetched in [t0, t1], oldest first

    @property
    def seconds(self):
        return self.t1 - self.t0

    def notes(self, names):
        """The window's dispatch notes under `names`, each once."""
        return [n for t in self.ticks for n in t.dispatched
                if n.name in names and self.t0 <= n.t <= self.t1]


def window(observed):
    requests = request_records.finished(observed)
    if requests is None:
        return None
    try:
        from cloud_tpu.serving import reqtrace
    except ImportError:
        return None
    recent_ticks = getattr(reqtrace, "recent_ticks", None)
    if recent_ticks is None:
        return None
    t0 = min(r.t_submit for r in requests)
    t1 = max(r.t_done for r in requests)
    ticks = [t for t in recent_ticks() if t0 <= t.t_fetched <= t1]
    counted = observed.get("counters", {}).get("ticks")
    if (counted is None or abs(len(ticks) - counted) > COUNT_SLACK
            or len(ticks) < MIN_TICKS):
        return None
    return Window(t0, t1, ticks)


def _quiet(tick):
    return (not tick.naps and not tick.idle_s
            and all(n.name == TICK for n in tick.dispatched))


def intervals(win):
    """(start, end) a tick of the window: from `max(t_fetched[n-1], window
    start)` to `t_fetched[n]`."""
    fetched = [t.t_fetched for t in win.ticks]
    return list(zip([win.t0] + fetched[:-1], fetched))


def clean_period(win):
    """Seconds, or None where the window has no clean tick."""
    ticks, spans = win.ticks, intervals(win)
    clean = [spans[n][1] - spans[n][0] for n in range(1, len(ticks) - 1)
             if ticks[n].overlapped
             and all(_quiet(t) for t in ticks[n - 1:n + 2])]
    return statistics.median(clean) if clean else None


@dataclasses.dataclass
class Account:
    """The window's seconds by what the device was given to do."""
    window_s: float
    clean_s: float       # the clean tick period
    ticks: int
    prefill_s: float
    prefills: int        # prefill programs noted, a chunk counting as one
    wait_s: float

    @property
    def tick_s(self):
        return self.ticks * self.clean_s

    @property
    def left_s(self):
        return self.window_s - self.tick_s - self.prefill_s - self.wait_s

    def share_pct(self, seconds):
        return 100.0 * seconds / self.window_s


def account(observed):
    win = window(observed)
    if win is None:
        return None
    clean = clean_period(win)
    if clean is None:
        return None
    prefill_s = wait_s = 0.0
    for n, (start, end) in enumerate(intervals(win)):
        noted = [note.t for t in win.ticks[n:n + 2] for note in t.dispatched
                 if note.name in PREFILLS and note.t >= win.t0]
        taken = (max(end - max(start, min(noted)) - clean, 0.0)
                 if noted else 0.0)
        prefill_s += taken
        if win.ticks[n].naps or win.ticks[n].idle_s:
            wait_s += max(end - start - clean - taken, 0.0)
    return Account(win.seconds, clean, len(win.ticks), prefill_s,
                   len(win.notes(PREFILLS)), wait_s)


def positive(value):
    """A reader's value, or None where it is not above 0: a share that is
    nothing is left out of the line."""
    return value if value > 0 else None
