"""graftwatch: fleet liveness + hang flight recorder.

The reference framework's whole value is watching a remote cloud job
you can't ssh into (CAIP submit + the Stackdriver exporter); without
this layer a hung `fit()` dies only at an outer timeout with nothing
saying WHERE it hung. graftwatch is the fleet-health layer over
graftscope:

- a **heartbeat watchdog**: the Trainer's step loop beats a monitor
  thread; when no step (or boundary) progress arrives within the stall
  deadline, the monitor snapshots every thread's stack, writes a
  `blackbox.json` flight-recorder artifact, and converts the hang into
  a typed `runtime.BackendUnavailable` delivered to the training thread
  within seconds — not a 30-minute outer timeout. It starts no device
  probe: the chip belongs to this process, so a child could not open
  it and every stall would read as a dead backend;
- **liveness gauges**: while watching, every poll tick exports
  `cloud_tpu_watch_alive` / `cloud_tpu_watch_heartbeat_age_seconds` /
  `cloud_tpu_watch_last_step_age_seconds` / `cloud_tpu_watch_last_step`
  through the graftscope registry (when telemetry is enabled), so a
  fleet collector can see a straggler BEFORE it becomes a corpse;
- a **flight recorder**: `write_blackbox()` dumps all-thread stacks
  (structured + a raw `faulthandler` section), the graftscope span
  tail, the transfer/compile counter snapshots, any graftsan site
  table, and the tail of the JSONL job-event log — every hang or crash
  leaves a diagnosable artifact.

Zero-cost discipline (the graftsan/graftscope seam contract): nothing
is installed unless `CLOUD_TPU_WATCH` asks for it — no thread, no
hook; `heartbeat()`/`notify_step()` are one global load + None check
when disabled, and with the env unset `Trainer.fit()` installs zero
watch machinery (test-pinned).

Delivery semantics, honestly stated: the stall error is delivered via
`PyThreadState_SetAsyncExc`, which interrupts Python-level stalls (a
dispatch spinning in a retry loop, a feeder deadlock) within one
bytecode boundary. A thread wedged inside a single C call (a truly
hung XLA dispatch) cannot be interrupted from userspace — for that
case the guarantee is the ARTIFACT (blackbox + gauges + job event),
plus the opt-in `CLOUD_TPU_WATCH_FATAL=1` escalation: one full
deadline after the stall fired with still no heartbeat, the process
exits 70 so the fleet scheduler can reschedule in seconds instead of
waiting out the outer timeout.

Env contract:
    CLOUD_TPU_WATCH                  1|on -> Trainer entry points watch
    CLOUD_TPU_WATCH_DEADLINE         stall deadline, seconds (60)
    CLOUD_TPU_WATCH_STARTUP_DEADLINE pre-first-step deadline (600 —
                                     cold compiles are not stalls)
    CLOUD_TPU_WATCH_INTERVAL         monitor poll period (deadline/4,
                                     capped at 5s)
    CLOUD_TPU_WATCH_DIR              blackbox.json directory (default
                                     CLOUD_TPU_TELEMETRY_DIR, then
                                     ./telemetry)
    CLOUD_TPU_WATCH_FATAL            1 -> exit(70) one deadline after
                                     an undeliverable stall error
"""

import contextlib
import ctypes
import faulthandler
import json
import logging
import os
import socket
import sys
import tempfile
import threading
import time
import traceback

from cloud_tpu.monitoring import spans
from cloud_tpu.parallel import runtime

logger = logging.getLogger("cloud_tpu")

__all__ = ["Watchdog", "write_blackbox", "install", "uninstall",
           "current", "enabled", "env_enabled", "env_scope",
           "heartbeat", "notify_step", "notify_reentry", "check",
           "rewatch"]

#: Spans / job events kept in the blackbox tail.
BLACKBOX_SPAN_TAIL = 100
BLACKBOX_EVENT_TAIL = 25

_EXIT_FATAL = 70


def _env_float(key, default):
    try:
        return float(os.environ.get(key, default))
    except (TypeError, ValueError):
        return default


def env_enabled():
    """The CLOUD_TPU_WATCH env contract (same truthiness grammar as
    CLOUD_TPU_TELEMETRY / CLOUD_TPU_SANITIZE)."""
    value = os.environ.get("CLOUD_TPU_WATCH", "").strip().lower()
    return value not in ("", "0", "off", "false", "none")


def _process_index():
    """This process's index: the CLOUD_TPU_PROCESS_ID env contract
    first, a jax that is ALREADY imported second, else 0 — never an
    import, so the disabled path stays jax-free."""
    value = os.environ.get("CLOUD_TPU_PROCESS_ID")
    if value is not None:
        try:
            return int(value)
        except ValueError:
            return 0
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return int(jax.process_index())
        except Exception:
            return 0
    return 0


def _async_raise(tid, exc_type):
    """Schedules `exc_type` in thread `tid` (CPython only). Returns
    True when exactly one thread was targeted."""
    set_async = getattr(ctypes.pythonapi, "PyThreadState_SetAsyncExc",
                        None)
    if set_async is None:
        return False
    res = set_async(ctypes.c_ulong(tid), ctypes.py_object(exc_type))
    if res > 1:  # never happens for a valid ident; undo per the docs
        set_async(ctypes.c_ulong(tid), None)
        return False
    return res == 1


def _thread_stacks(stuck_tid=None):
    """Structured all-thread stacks from sys._current_frames()."""
    threads = {t.ident: t for t in threading.enumerate()}
    entries = []
    for tid, frame in sys._current_frames().items():
        thread = threads.get(tid)
        stack = [{"file": f.filename, "line": f.lineno,
                  "function": f.name, "code": f.line or ""}
                 for f in traceback.extract_stack(frame)]
        entries.append({
            "tid": tid,
            "name": thread.name if thread is not None
            else "thread-{}".format(tid),
            "daemon": bool(thread.daemon) if thread is not None else None,
            "stuck": tid == stuck_tid,
            "stack": stack,
        })
    # Stuck thread first: the artifact's reader wants the culprit on
    # top, not buried under daemon helpers.
    entries.sort(key=lambda e: (not e["stuck"], e["name"]))
    return entries


def _faulthandler_text():
    """The raw faulthandler all-thread dump (the signal-safe truth the
    structured stacks are derived next to, kept verbatim because it is
    the format every postmortem tool already reads)."""
    try:
        with tempfile.TemporaryFile(mode="w+") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            return f.read()
    except Exception:
        return None


def _sanitizer_sites():
    """Any stacked graftsan observer's site table (duck-typed off the
    runtime observer stack, the JsonlExporter recipe)."""
    for observer in runtime.observers():
        site_counts = getattr(observer, "site_counts", None)
        if callable(site_counts):
            try:
                return site_counts()
            except Exception:
                return None
    return None


def _job_events_tail(limit=BLACKBOX_EVENT_TAIL):
    """Last `limit` parseable records of the JSONL job-event log
    (CLOUD_TPU_EVENT_LOG), reading only the file's final 64KB so a
    week-long log costs nothing. Torn lines are skipped — this runs
    while a writer may be mid-append."""
    path = os.environ.get("CLOUD_TPU_EVENT_LOG")
    if not path:
        return []
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - 65536))
            data = f.read().decode("utf-8", errors="replace")
    except OSError:
        return []
    lines = data.splitlines()
    if size > 65536 and lines:
        lines = lines[1:]  # first line may be torn by the seek
    records = []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            records.append(json.loads(line))
        except ValueError:
            continue
    return records[-limit:]


def write_blackbox(path, reason, stuck_tid=None, last_step=None,
                   last_step_age=None, heartbeat_age=None,
                   error=None, stacks=None):
    """Writes the flight-recorder artifact to `path` (atomic
    tmp+rename) and returns the path.

    The artifact answers the questions a dead job can't: WHERE every
    thread was (structured stacks + raw faulthandler text, stuck
    thread first), WHAT the runtime had done (transfer/compile counter
    snapshots, graftsan site table), WHAT the host was doing around
    the incident (graftscope span tail), and WHAT the job had reported
    (JSONL event-log tail). Collection is best-effort per section — a
    failing source yields a null field, never a missing artifact.
    """
    record = {
        "format": "cloud_tpu.blackbox.v1",
        "reason": reason,
        "time": time.time(),
        "monotonic": time.monotonic(),
        "host": socket.gethostname(),
        "pid": os.getpid(),
        "process_index": _process_index(),
        "last_step": last_step,
        "last_step_age_seconds": last_step_age,
        "heartbeat_age_seconds": heartbeat_age,
        "error": error,
        "threads": stacks if stacks is not None
        else _thread_stacks(stuck_tid),
        "faulthandler": _faulthandler_text(),
        "transfer_stats": runtime.transfer_stats(),
        "compile_stats": runtime.compile_stats(),
        "sanitizer_sites": _sanitizer_sites(),
        "job_events_tail": _job_events_tail(),
    }
    tracer = spans.current_tracer()
    if tracer is not None:
        events = tracer.events()[-BLACKBOX_SPAN_TAIL:]
        record["spans_tail"] = [
            {"name": name, "tid": tid, "t0_ns": t0, "dur_ns": dur}
            for name, tid, t0, dur in events]
        record["spans_dropped"] = tracer.dropped()
    else:
        record["spans_tail"] = []
        record["spans_dropped"] = 0
    directory = os.path.dirname(path)
    if directory:
        os.makedirs(directory, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


class Watchdog:
    """Heartbeat monitor: stall detection, blackbox dump, typed error.

    The training thread (whoever calls `start()`) beats via
    `beat()`/`notify_step()`; a daemon monitor thread polls the beat
    age. Before the first completed step the startup deadline applies
    (a cold compile is not a stall); after it, the stall deadline.
    On stall the monitor — running OUTSIDE the hung thread — captures
    stacks, writes `blackbox.json`, logs a `graftwatch` job event, and schedules a
    `runtime.BackendUnavailable` in the watched thread. The incident
    LATCHES: once fired, `check()` raises the pending error even if a
    glacial step eventually completes — a deadline sized below the
    slowest legitimate step is a config bug worth dying loudly on.
    """

    def __init__(self, stall_deadline=None, startup_deadline=None,
                 poll_interval=None, out_dir=None, fatal=None):
        if stall_deadline is None:
            stall_deadline = _env_float("CLOUD_TPU_WATCH_DEADLINE", 60.0)
        if startup_deadline is None:
            startup_deadline = _env_float(
                "CLOUD_TPU_WATCH_STARTUP_DEADLINE",
                max(600.0, stall_deadline))
        if poll_interval is None:
            poll_interval = _env_float(
                "CLOUD_TPU_WATCH_INTERVAL",
                min(max(stall_deadline / 4.0, 0.05), 5.0))
        if out_dir is None:
            out_dir = (os.environ.get("CLOUD_TPU_WATCH_DIR")
                       or os.environ.get("CLOUD_TPU_TELEMETRY_DIR")
                       or os.path.join(os.getcwd(), "telemetry"))
        if fatal is None:
            fatal = os.environ.get("CLOUD_TPU_WATCH_FATAL", "") == "1"
        self.stall_deadline = float(stall_deadline)
        self.startup_deadline = float(startup_deadline)
        self.poll_interval = float(poll_interval)
        self.out_dir = str(out_dir)
        self.fatal = bool(fatal)
        self.blackbox_path = os.path.join(self.out_dir, "blackbox.json")
        # Beat state: plain attribute writes (atomic under the GIL) so
        # a beat from the hot loop takes no lock.
        now = time.monotonic()
        self._last_beat = now
        self._last_step_time = now
        self._step_count = 0
        # True until the first completed step of the CURRENT (re)entry
        # into the watched scope: the generous startup deadline covers
        # compile/restore; the tight stall deadline takes over once
        # steps flow. `notify_reentry` re-arms it so a graftguard
        # resume replaying restore+rebuild isn't judged by the step
        # deadline (ISSUE 9 satellite: STARTUP_DEADLINE per (re)entry,
        # not only the first).
        self._in_startup = True
        self._started = now
        self._watched_tid = None
        self._pending = None
        self._fired = False
        self._fired_at = None
        self._async_delivered = False
        self._stalls = 0
        self._stop = threading.Event()
        self._thread = None
        self._crash_dumped = False
        self._step_exported = False

    # -- the watched side ----------------------------------------------

    def start(self, watched_tid=None):
        """Starts the monitor thread, watching `watched_tid` (default:
        the calling thread). Idempotent."""
        if self._thread is not None:
            return self
        if watched_tid is None:
            watched_tid = threading.get_ident()
        self._watched_tid = watched_tid
        now = time.monotonic()
        self._last_beat = now
        self._last_step_time = now
        self._in_startup = True
        self._started = now
        self._stop.clear()
        self._step_exported = False
        self._thread = threading.Thread(
            target=self._run, name="cloud-tpu-watchdog", daemon=True)
        self._thread.start()
        return self

    def stop(self):
        """Stops the monitor thread (joined; idempotent)."""
        thread, self._thread = self._thread, None
        if thread is not None:
            self._stop.set()
            thread.join(timeout=10)

    def beat(self):
        """One liveness heartbeat (boundary work, eval batches)."""
        self._last_beat = time.monotonic()

    def rewatch(self, tid=None):
        """Re-aims the async-raise target at `tid` (default: the
        calling thread) and beats. A loop that adopts an installed
        watchdog — graftserve's tick thread — calls this once so a
        stall interrupts the thread that is actually stuck, not
        whichever thread ran install()."""
        self._watched_tid = (threading.get_ident() if tid is None
                             else tid)
        self._last_beat = time.monotonic()

    def notify_step(self, step=None):
        """One COMPLETED train step: beats and advances the step
        census the blackbox reports as `last_step`."""
        now = time.monotonic()
        if step is not None:
            self._step_count = int(step)
        else:
            self._step_count += 1
        self._in_startup = False
        self._last_step_time = now
        self._last_beat = now
        if not self._step_exported:
            # The watch scope wraps the telemetry scope, so the
            # registry wasn't active yet at start(); the first
            # completed step is the earliest deterministic moment it
            # is. One-time, so runs shorter than the poll interval
            # still stamp `alive` for the fleet collector.
            self._step_exported = True
            self._export_gauges(now, 0.0)

    def check(self):
        """Raises the pending BackendUnavailable, if a stall fired.
        The deterministic delivery point for threads the async raise
        could not reach (called at scope exit and safe anywhere)."""
        pending = self._pending
        if pending is not None and not self._async_delivered:
            self._pending = None
            raise pending

    def notify_reentry(self):
        """Re-arms the watchdog for a fresh (re)entry into the watched
        scope — graftguard calls this before every resume attempt.

        Resets the beat clocks and clears any latched stall so the
        generous STARTUP deadline (not the tight stall deadline)
        governs until the resumed run completes its first step: the
        re-entry legitimately spends that window on restore, rebuild,
        and (cold-cache worst case) recompile.
        """
        now = time.monotonic()
        self._last_beat = now
        self._last_step_time = now
        self._in_startup = True
        self._pending = None
        self._fired = False
        self._fired_at = None
        self._async_delivered = False
        self._crash_dumped = False

    def take_pending(self):
        """Removes and returns the pending error (or None) — the scope
        wrapper swaps the bare async-raised class for this rich
        instance."""
        pending, self._pending = self._pending, None
        return pending

    @property
    def last_step(self):
        return self._step_count

    @property
    def stalls(self):
        return self._stalls

    @property
    def fired(self):
        return self._fired

    def record_crash(self, exc):
        """Writes a crash blackbox for an exception escaping the
        watched scope (once per incident; a stall that already dumped
        does not get overwritten by its own propagating error)."""
        if self._fired or self._crash_dumped:
            return None
        self._crash_dumped = True
        now = time.monotonic()
        try:
            return write_blackbox(
                self.blackbox_path,
                "crash",
                stuck_tid=self._watched_tid,
                last_step=self._step_count,
                last_step_age=now - self._last_step_time,
                heartbeat_age=now - self._last_beat,
                error="{}: {}".format(type(exc).__name__, exc))
        except Exception:
            logger.exception("graftwatch: crash blackbox write failed")
            return None

    # -- the monitor side ----------------------------------------------

    def _run(self):
        while not self._stop.wait(self.poll_interval):
            now = time.monotonic()
            beat_age = now - self._last_beat
            self._export_gauges(now, beat_age)
            if self._fired:
                if (self.fatal and self._fired_at is not None
                        and now - self._fired_at > self.stall_deadline
                        and time.monotonic() - self._last_beat
                        > self.stall_deadline):
                    # The error could not be delivered and the thread
                    # never recovered: the artifact is on disk, exit
                    # loudly so the scheduler reschedules in seconds.
                    logger.error(
                        "graftwatch: stall error undeliverable for "
                        "%.0fs past the deadline; exiting %d "
                        "(CLOUD_TPU_WATCH_FATAL=1).",
                        now - self._fired_at, _EXIT_FATAL)
                    os._exit(_EXIT_FATAL)
                continue
            deadline = (self.startup_deadline if self._in_startup
                        else self.stall_deadline)
            if beat_age > deadline:
                self._on_stall(beat_age, deadline)

    def _export_gauges(self, now, beat_age):
        """Liveness gauges through the graftscope registry, when a
        telemetry session is active (sys.modules.get: watching must
        not IMPORT telemetry into a process that never enabled it)."""
        telemetry = sys.modules.get("cloud_tpu.monitoring.telemetry")
        if telemetry is None:
            return
        try:
            tele = telemetry.get()
            if tele is None or not tele.active:
                return
            reg = tele.registry
            reg.gauge("cloud_tpu_watch_alive").set(
                0.0 if self._fired else 1.0)
            reg.gauge("cloud_tpu_watch_heartbeat_age_seconds").set(
                beat_age)
            reg.gauge("cloud_tpu_watch_last_step_age_seconds").set(
                now - self._last_step_time)
            reg.gauge("cloud_tpu_watch_last_step").set(self._step_count)
        except Exception:  # a metrics sink must never kill the monitor
            logger.debug("graftwatch gauge export failed", exc_info=True)

    def _on_stall(self, beat_age, deadline):
        step_age = time.monotonic() - self._last_step_time
        # Stacks FIRST (closest to the stall), then the artifact. The
        # stacks say whether the watched thread sits in a dispatch or
        # in host code; no second process is asked, because this one
        # holds the chip.
        stacks = _thread_stacks(self._watched_tid)
        message = (
            "No training progress for {:.1f}s (deadline {:.1f}s; last "
            "completed step {}, {:.1f}s ago). Flight recorder: "
            "{}".format(beat_age, deadline, self._step_count, step_age,
                        self.blackbox_path))
        path = None
        try:
            path = write_blackbox(
                self.blackbox_path, "stall",
                stuck_tid=self._watched_tid,
                last_step=self._step_count,
                last_step_age=step_age, heartbeat_age=beat_age,
                error=message, stacks=stacks)
        except Exception:
            logger.exception("graftwatch: blackbox write failed")
        try:
            from cloud_tpu.utils import events
            events.log_job_event("graftwatch", {
                "event": "stall", "heartbeat_age_seconds": beat_age,
                "deadline_seconds": deadline,
                "last_step": self._step_count,
                "blackbox": path})
        except Exception:
            logger.debug("graftwatch job event failed", exc_info=True)
        error = runtime.BackendUnavailable(
            message, deadline=deadline, blackbox=path)
        # Pending BEFORE the latch flips: anyone who observes
        # `fired` must be able to collect the error via check()/
        # take_pending(). (_run is the only caller, so there is no
        # re-entry hazard in latching last.)
        self._pending = error
        self._stalls += 1
        self._fired = True
        self._fired_at = time.monotonic()
        logger.error("graftwatch: %s", message)
        if self._watched_tid is not None:
            self._async_delivered = _async_raise(
                self._watched_tid, runtime.BackendUnavailable)


# -- module seam (the None-check discipline) ----------------------------

_watchdog = None


def install(**kwargs):
    """Installs (and starts) the ambient watchdog. Idempotent when one
    is already running and no kwargs are given."""
    global _watchdog
    if _watchdog is None:
        _watchdog = Watchdog(**kwargs).start()
    return _watchdog


def uninstall():
    """Stops and removes the ambient watchdog (returns it, or None)."""
    global _watchdog
    previous, _watchdog = _watchdog, None
    if previous is not None:
        previous.stop()
    return previous


def current():
    return _watchdog


def enabled():
    return _watchdog is not None


def heartbeat():
    """One liveness beat (boundary/eval work). One global load + None
    check when disabled."""
    w = _watchdog
    if w is not None:
        w.beat()


def notify_step(step=None):
    """One completed train step. One global load + None check when
    disabled."""
    w = _watchdog
    if w is not None:
        w.notify_step(step)


def check():
    """Raises a pending stall error, if the watchdog latched one."""
    w = _watchdog
    if w is not None:
        w.check()


def rewatch(tid=None):
    """Hands the installed watchdog to the calling thread (async-raise
    target). No-op when disabled."""
    w = _watchdog
    if w is not None:
        w.rewatch(tid)


def notify_reentry():
    """Re-arms the installed watchdog for a resume attempt (startup
    deadline + cleared stall latch). No-op when disabled."""
    w = _watchdog
    if w is not None:
        w.notify_reentry()


@contextlib.contextmanager
def env_scope():
    """Trainer entry-point scope: installs the watchdog when
    CLOUD_TPU_WATCH asks for it, enables faulthandler (a hard crash
    dumps all threads to stderr), swaps the bare async-raised
    BackendUnavailable class for the rich latched instance, writes a
    crash blackbox for any other escaping exception, and tears the
    watchdog down on exit. Nested entry points (fit's validation
    evaluate) see the already-installed watchdog and change nothing.
    """
    if not env_enabled():
        yield None
        return
    if _watchdog is not None:  # nested entry point: ride the outer one
        yield _watchdog
        return
    try:
        faulthandler.enable()
    except Exception:  # exotic platforms without stderr fds
        pass
    w = install()
    try:
        try:
            yield w
            w.check()
        except runtime.BackendUnavailable as e:
            pending = w.take_pending()
            if pending is not None and pending is not e:
                raise pending from e
            raise
        except BaseException as e:
            w.record_crash(e)
            raise
    finally:
        uninstall()
