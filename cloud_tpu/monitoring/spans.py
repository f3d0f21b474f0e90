"""graftscope span tracer: nested, thread-aware host wall-time spans.

The jax profiler answers "what did the DEVICE do"; nothing answered
"where did the HOST's step wall time go" — data wait vs dispatch vs the
coalesced D2H fetch vs checkpoint snapshot. This module is that layer:
monotonic-ns spans recorded per thread into one bounded in-process
buffer, exported as Chrome trace-event JSON (the `{"traceEvents": []}`
format Perfetto and chrome://tracing load directly). Nesting needs no
parent pointers: complete ("ph":"X") events on one thread nest by time
containment, exactly how the viewers render them.

Two sinks behind one call. `span()`, `begin()`/`end()` and
`trace_steps()` always open a `jax.profiler.TraceAnnotation`: it costs
a flag test (under half a microsecond) while no profile is being
captured, and while one is (`monitoring.profiler.trace`) the span lands
in the `.xplane.pb` on the profiler's clock, beside the device's ops,
with its keyword ids (`rid=`) as the event's stats. The second sink is
the module-level `SpanTracer`: None until `install()`, one global load
+ None check when absent. `jax` is imported on first use, so this
module stays importable without it (the annotation is then a no-op).

The tables below are the contract: docs (monitoring/README.md,
serving/README.md, PERF.md section 3), the telemetry histograms
(`telemetry.SPAN_HISTOGRAMS`), the benchmark's readers and
`cellbench/tools/spans.py` key on these names, and
tests/unit/test_span_names.py holds the code to them. `names(section)`
parses them.

A span that belongs to one request carries its `rid=`, one that
belongs to one decode tick its `tick=` (the tick's `seq`): the table
says which, in brackets, and in a capture the id joins the span, on
the profiler's clock, to the record of the same request or tick
("Records", below).

Spans:

    step                  one epoch's step-loop section
    boundary              one epoch's end-of-epoch host work
    train_step            one step: data wait + dispatch + log append
                          (under a profile the feeder's last, empty
                          `next()` of an epoch shows as one more)
    data_wait             blocking on the input feeder inside a step
    dispatch              the jitted step-executable call
    d2h_fetch             a coalesced device->host readback
    checkpoint_snapshot   the donation-safe host copy before a save
    async_reader_drain    the off-thread metric fetch
    decode                one generate()/beam/speculative call
    tick_admit            tick thread, before a dispatch: resize, one
                          prefill chunk, inserts of ready requests
                          (the slot_insert dispatches), behind the
                          tick in flight
    tick_pace             one 5 ms nap of the tick thread, taken
                          because an admission is in flight and a slot
                          is free (nothing is in flight during it)
    tick_idle             the tick thread's wait (up to 50 ms) with no
                          slot occupied (nothing in flight)
    serve_tick            one turn of the serving hot loop: the
                          dispatch of tick n+1, then the d2h fetch of
                          tick n where one was in flight (the first
                          tick after a drain: the dispatch alone)
                          (tick of the one dispatched)
    tick_dispatch         inside serve_tick: the `engine.tick()` call
                          and the start of its tokens' copy to the
                          host (tick)
    tick_fetch            the blocking fetch of a tick's tokens: of
                          the tick BEFORE the one just dispatched,
                          inside serve_tick; of a tick that is
                          drained (before a nap, an idle wait, a
                          resize, a chaos event, close), on its own
                          (tick of the one fetched)
    tick_commit           after a tick_fetch, outside serve_tick: the
                          fetched tick's tokens to their requests,
                          completions, eviction (while the next tick
                          is on the device, unless drained) (tick of
                          the one committed)
    admit                 one request's turn in its admission window:
                          probe, decision, reservation, the dispatch
                          of its prefill and, where one was in flight,
                          the fetch of the prefill BEFORE it (rid)
    admit_reserve         inside admit: the page-reservation rounds
                          (rid)
    serve_prefill         one serving prefill (rid). On the admission
                          thread, which keeps one prefill in flight:
                          as far as the dispatch (prefill_host,
                          prefill_dispatch). On the tick thread (a
                          prefix hit, a requeue): gather + dense
                          prefill + first-token fetch
    serve_prefill_chunk   one chunk of a chunked prefill (rid)
    prefill_host          inside serve_prefill, once: array prep, the
                          key schedule (host arithmetic), the dense
                          cache, a hit's gather (rid)
    prefill_dispatch      inside serve_prefill: the jitted prefill
                          call and the start of the first token's
                          copy to the host (rid)
    prefill_fetch         the blocking fetch of a prefill's first
                          token, the TTFT point (rid of the prefill
                          fetched): inside the NEXT request's admit,
                          outside its serve_prefill; on its own where
                          the prefill in flight is collected (an empty
                          queue, a reservation that waits, a hit
                          handed over, close); inside serve_prefill
                          on the tick thread

Each `pl.pallas_call` passes one of these as `name=` (a constant
beside the call), and the trace's op text carries it.

Kernels:

    flash_fwd               ops/attention.py forward: one call a layer,
                            one grid step a kv head and live span pair
                            (`ops.attention.flash_plan` has the tiles,
                            spans and step counts of a shape)
    flash_bwd_dq            ops/attention.py backward, dq (as above)
    flash_bwd_dkv           ops/attention.py backward, dk and dv (as
                            above, the group's heads summed inside)
    fused_swiglu_fwd        ops/fused_mlp.py forward
    fused_rmsnorm           ops/fused_norm.py, no residual
    fused_rmsnorm_residual  ops/fused_norm.py, residual add fused
    paged_decode            ops/paged_attention.py decode walk, a full
                            layer's (every live page of a slot)
    paged_decode_window     the same walk over a window layer's band
                            (from the slot's first live page), and
                            over an EVA layer's one run of live rows:
                            the summaries of the windows behind the
                            query, then its own window's ring rows
    ssm_decode_update       ops/ssm.py, a Mamba-2 layer's decode step:
                            every slot's recurrent state read once and
                            written once in place, one call a layer

An expert layer's parts (constants in models/moe.py) and a Mamba-2
layer's (models/mamba2.py) run under `jax.named_scope`s of these
names, inside whatever program holds the layer (`jit_serve_tick`, `jit_serve_prefill`, `jit_train_step`): every
op a part lowers to carries the name in its `op_name`, and the grouped
products of the routed experts are XLA:TPU's own kernel, whose
instructions the trace names `ragged-dot*`. Where the routed experts
run batched over the held experts (models/moe.py `batched_over_held`:
a decode tick whose rows touch every held expert) there is no such
kernel: the products are XLA's own fusions.

Scopes:

    moe_router            scores over all experts, the top-k choice
                          and the gates (float32)
    moe_routed_experts    sort of the chosen pairs held here, the
                          grouped products, the weighted sum back to
                          tokens; or the batched products over the
                          held experts and the gates' sum
    moe_shared_expert     the always-on expert's MLP
    moe_latent_down       a latent expert layer: the projection to the
                          latent width, before the routed experts
    moe_latent_up         and back from it, after their weighted sum
    ssm_in_proj           a Mamba-2 layer: the projection to z, xBC, dt
    ssm_conv              the causal depthwise convolution and its
                          window's update
    ssm_scan              the recurrence: the chunked scan of a
                          sequence, or the tick's `ssm_decode_update`
    ssm_gate_norm         the gate by silu(z) and the grouped RMSNorm
    ssm_out_proj          the projection back to the model's width

An expert model's tick also returns counters, which `Scheduler.stats()`
sums over ticks and expert layers (fetched with the tick's tokens, in
the same read-back); a model with recurrent (Mamba-2) layers adds a
counter of its own to the same read-back, and a gauge. A model whose
slots keep a ring and summary rows (EVA attention, ops/eva.py) is
counted on the host, from each occupied slot's depth (the rows a query
attends follow from it alone); its prefill's window chunks run under
the span `serve_prefill_chunk`.

Counters:

    moe_pairs_routed      (token, choice) pairs of the active slots
    moe_pairs_held        those whose expert is held here
    moe_pairs_dense       (token, held expert) products of the ticks
                          whose expert layers ran batched over the
                          held experts; 0 where they ran grouped
    moe_experts_touched   held experts with at least one pair, summed
                          a layer and tick
    moe_expert_load       pairs a held expert (a vector)
    ssm_state_bytes       bytes of recurrent state resident beside the
                          page pool, all slots (a gauge, not a sum)
    ssm_slot_steps        slots advanced x recurrent layers, summed
                          over ticks (each is one state read and
                          written)
    eva_rows_read         rows of both kinds the ticks' queries
                          attended, summed over slots and ticks, a
                          layer counted once (`kv_live_tokens` is the
                          same sum for such a model, and
                          `kv_walked_tokens` the rows the walk fetched)
    eva_summary_rows_read the summary rows among them
    eva_windows_closed    window ends met, by ticks and by prefill
                          chunks (a dict of the two)
    eva_cache_bytes       bytes of ring and summary pages held by
                          requests (a gauge, not a sum)

The hot loops' jitted functions are named so (a constant beside the
jit), and the trace's program line reads `jit_<name>`. The serving
engine notes each of its dispatches under the same name in its
dispatch log (`DecodeEngine.take_dispatched()`), which holds no other
names.

Programs:

    train_step           training/trainer.py, one optimizer step
    serve_tick           serving/engine.py, one decode tick over all
                         slots
    serve_prefill        serving/engine.py, one dense prefill + first
                         token: a whole prompt, a hit's suffix, or
                         the last chunk of a chunked prefill
    slot_insert          serving/engine.py, a prefill scattered into
                         a slot
    slot_evict           serving/engine.py, finished slots' rows
                         zeroed
    serve_prefill_chunk  serving/engine.py, a prefill that keeps the
                         cache and samples nothing: a chunk of a
                         chunked prefill but the last, and a draft
                         model's prefill. The dispatch log notes
                         EVERY chunk under this name, the last too
    prefix_gather        serving/engine.py, a prefix hit's pages
                         copied from the pool into its dense cache
    slot_resize          serving/engine.py, the slots' rows moved to
                         another rung of the ladder
    page_snapshot        serving/engine.py, a finished request's
                         pages gathered for the host tier
    page_promote         serving/engine.py, host-tier pages written
                         back into the pool
    cache_zero           models/decoding.py, a zeroed dense cache for
                         a prefill: a parked one zeroed in place, or
                         a fresh one (two programs, one name)

Records. Two always-on records in memory, both in serving/reqtrace.py,
both on `time.monotonic()`, neither written anywhere: one
`RequestRecord` a request (its boundaries, whose phases tile its
latency; `reqtrace.recent()`; its JSONL export merges into the same
Perfetto view via `monitoring/collect.py --serve`), and one
`TickRecord` a decode tick (`reqtrace.recent_ticks()`): its dispatch,
fetch and commit times, what it advanced, and `dispatched`, the
engine's dispatch log since the tick before: the programs above that
shared the device with it, whichever thread sent them, with the naps
and idle waits the tick thread took meanwhile. The benchmark's
`window_*_share_pct.serve`, `tick_period_clean_ms.serve`,
`prefill_cost_ms.serve`, `*_overlap_share_pct.serve` and
`kv_walk_live_share_pct.serve` are read from it.
"""

import json
import os
import socket
import sys
import threading
import time

__all__ = ["SpanTracer", "install", "uninstall", "current_tracer",
           "enabled", "span", "begin", "end", "complete", "trace_steps",
           "names"]

#: Hard cap on buffered span events; beyond it new events are counted
#: as dropped instead of growing the host heap without bound (a week of
#: steps would otherwise OOM the host before anyone looked at a trace).
_DEFAULT_MAX_EVENTS = 500_000


def names(section):
    """The names in one table of this module's docstring ("Spans",
    "Kernels", "Scopes", "Counters" or "Programs"), in order."""
    out, inside = [], False
    for line in __doc__.splitlines():
        if not line.startswith(" "):
            if line:
                inside = line == section + ":"
            continue
        if inside and line.startswith("    ") and line[4] != " ":
            out.append(line.split()[0])
    return tuple(out)


class _NoAnnotation:
    """Stands in for `jax.profiler.TraceAnnotation` where jax cannot be
    imported."""

    __slots__ = ()

    def __init__(self, name, **ids):
        pass

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_annotation_cls = None


def _annotation():
    """`jax.profiler.TraceAnnotation`, imported on first use."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation
            _annotation_cls = TraceAnnotation
        except ImportError:
            _annotation_cls = _NoAnnotation
    return _annotation_cls


def _process_identity():
    """This process's (index, label) for trace metadata.

    Index comes from the CLOUD_TPU_PROCESS_ID env contract first, then
    from a jax that is ALREADY imported (`sys.modules.get` — this
    module stays stdlib-only and must never pull jax in), else 0. The
    label is what Perfetto shows on the process lane.
    """
    index = 0
    value = os.environ.get("CLOUD_TPU_PROCESS_ID")
    if value is not None:
        try:
            index = int(value)
        except ValueError:
            index = 0
    else:
        jax = sys.modules.get("jax")
        if jax is not None:
            try:
                index = int(jax.process_index())
            except Exception:
                index = 0
    label = "{}/p{} (pid {})".format(
        socket.gethostname(), index, os.getpid())
    return index, label


class _Span:
    """Context manager recording one complete event on exit, inside an
    optional profiler annotation."""

    __slots__ = ("_tracer", "_name", "_t0", "_annotation")

    def __init__(self, tracer, name, annotation=None):
        self._tracer = tracer
        self._name = name
        self._t0 = 0
        self._annotation = annotation

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        t0 = self._t0
        self._tracer.complete(self._name, t0,
                              time.monotonic_ns() - t0)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


class SpanTracer:
    """Bounded buffer of (name, tid, t0_ns, dur_ns) span events.

    Thread-safe: spans arrive from the training thread, the async
    metric reader, and the checkpoint worker concurrently; one lock
    guards the buffer and the listener list. Listeners fire on every
    span completion (under the lock, so keep them cheap — the
    telemetry registry's histogram observe is a dict update) and feed
    the step-latency/data-wait/dispatch distributions without a second
    timing source.
    """

    def __init__(self, max_events=_DEFAULT_MAX_EVENTS):
        self._lock = threading.Lock()
        self._events = []
        self._max_events = int(max_events)
        self._dropped = 0
        self._listeners = []
        # Trace epoch: event timestamps export relative to install time
        # so the Chrome trace starts near t=0 instead of host-uptime ns.
        self._epoch_ns = time.monotonic_ns()

    def add_listener(self, fn):
        """Registers `fn(name, t0_ns, dur_ns, tid)` on span completion."""
        with self._lock:
            self._listeners.append(fn)

    def span(self, name):
        """Context manager recording one span around its body."""
        return _Span(self, name)

    def complete(self, name, t0_ns, dur_ns):
        """Records one already-measured span (begin/end style)."""
        tid = threading.get_ident()
        with self._lock:
            if len(self._events) < self._max_events:
                self._events.append((name, tid, t0_ns, dur_ns))
            else:
                self._dropped += 1
            listeners = tuple(self._listeners)
        for fn in listeners:
            try:
                fn(name, t0_ns, dur_ns, tid)
            except Exception:
                pass  # a metrics sink must never break the traced code

    def events(self):
        """Snapshot of buffered (name, tid, t0_ns, dur_ns) tuples."""
        with self._lock:
            return list(self._events)

    def dropped(self):
        """Events discarded after the buffer cap was reached."""
        with self._lock:
            return self._dropped

    def chrome_trace(self):
        """The buffered spans as a Chrome trace-event JSON object.

        Complete events ("ph":"X", microsecond ts/dur) on per-thread
        tracks; Perfetto nests them by time containment. Thread names
        ride as metadata events so tracks read "cloud-tpu-metric-
        reader" instead of a bare tid. The pid is this PROCESS's index
        (CLOUD_TPU_PROCESS_ID / jax.process_index, not a hardcoded 1),
        with process_name/process_sort_index metadata naming the lane
        "host/pN (pid OSPID)" — so per-host traces merged by the fleet
        collector land on distinct, labeled lanes instead of colliding.
        """
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
            epoch = self._epoch_ns
        names = {t.ident: t.name for t in threading.enumerate()}
        process_index, process_label = _process_identity()
        trace_events = [
            {"ph": "M", "pid": process_index, "tid": 0,
             "name": "process_name",
             "args": {"name": process_label}},
            {"ph": "M", "pid": process_index, "tid": 0,
             "name": "process_sort_index",
             "args": {"sort_index": process_index}},
        ]
        for tid in sorted({tid for _, tid, _, _ in events}):
            trace_events.append({
                "ph": "M", "pid": process_index, "tid": tid,
                "name": "thread_name",
                "args": {"name": names.get(tid, "thread-{}".format(tid))},
            })
        for name, tid, t0_ns, dur_ns in events:
            trace_events.append({
                "ph": "X", "pid": process_index, "tid": tid, "name": name,
                "ts": (t0_ns - epoch) / 1e3,
                "dur": dur_ns / 1e3,
            })
        trace = {"traceEvents": trace_events,
                 "displayTimeUnit": "ms"}
        if dropped:
            trace["metadata"] = {"dropped_events": dropped}
        return trace

    def write(self, path):
        """Writes `chrome_trace()` as JSON to `path`."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


# -- module seam (the None-check discipline) ----------------------------

_tracer = None


def install(tracer=None):
    """Installs `tracer` (default: a fresh SpanTracer) as the ambient
    tracer and returns it. Idempotent when one is already installed and
    no explicit tracer is given."""
    global _tracer
    if tracer is None:
        if _tracer is None:
            _tracer = SpanTracer()
    else:
        _tracer = tracer
    return _tracer


def uninstall():
    """Removes the ambient tracer (returns it, or None)."""
    global _tracer
    previous, _tracer = _tracer, None
    return previous


def current_tracer():
    return _tracer


def enabled():
    return _tracer is not None


def _annotate(name, ids):
    """A profiler annotation carrying the ids that are not None."""
    if ids:
        ids = {k: v for k, v in ids.items() if v is not None}
    return _annotation()(name, **ids)


def span(name, **ids):
    """Context manager round one section: a profiler annotation
    carrying `ids` (`rid=`; a None is left out), which also records
    into the tracer when one is installed."""
    annotation = _annotate(name, ids)
    tracer = _tracer
    if tracer is None:
        return annotation
    return _Span(tracer, name, annotation)


def begin(name, **ids):
    """Begin handle for code that cannot use `with` (loop phases);
    pass it to `end()` on the same thread."""
    annotation = _annotate(name, ids)
    annotation.__enter__()
    return (name, time.monotonic_ns(), annotation)


def end(handle):
    """Completes a `begin()` handle (no-op for None); returns the
    span's ns."""
    if handle is None:
        return None
    name, t0, annotation = handle
    dur = time.monotonic_ns() - t0
    annotation.__exit__(None, None, None)
    tracer = _tracer
    if tracer is not None:
        tracer.complete(name, t0, dur)
    return dur


def complete(name, t0_ns, dur_ns):
    """Records an already-measured span into the ambient tracer. It
    cannot reach the profile (an annotation is opened, not back-dated):
    sections of the program use `span()`."""
    tracer = _tracer
    if tracer is not None:
        tracer.complete(name, t0_ns, dur_ns)


def trace_steps(iterable, step_name="train_step",
                wait_name="data_wait"):
    """Wraps a step feeder so every iteration becomes a `train_step`
    span containing a `data_wait` span.

    The generator protocol gives the exact cut points for free:
    `data_wait` covers blocking on the upstream feeder (`next(it)`),
    and the `train_step` span closes when the CONSUMER asks for the
    next item — i.e. after its dispatch + log-append body ran — so
    consecutive train_step spans tile the loop's wall time. A consumer
    `break` raises GeneratorExit at the yield; the finally completes
    the in-flight span before the generator closes.

    Always on: nothing tells Python whether a profile is being
    captured, and a step costs two annotation pairs (about a
    microsecond).
    """
    annotate = _annotation()
    it = iter(iterable)
    while True:
        step = begin(step_name)
        _, t0, step_annotation = step
        wait = annotate(wait_name)
        wait.__enter__()
        try:
            item = next(it)
        except StopIteration:
            # Nothing to record; only the annotations are closed.
            wait.__exit__(None, None, None)
            step_annotation.__exit__(None, None, None)
            return
        wait.__exit__(None, None, None)
        complete(wait_name, t0, time.monotonic_ns() - t0)
        try:
            yield item
        finally:
            end(step)
