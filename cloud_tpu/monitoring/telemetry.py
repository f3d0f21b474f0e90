"""graftscope: unified telemetry — metrics registry + lifecycle.

PRs 1-5 left the runtime with raw counters (`runtime.transfer_stats` /
`compile_stats`), a JSONL event log, the graftsan observer seam, and
jax-profiler wrappers — numbers, but no layer that turns them into
answerable questions ("where did this step's 40 ms go?", "what is
decode p99?"). This module is that layer:

- a **metrics registry**: Counter / Gauge / Histogram (exponential
  buckets with p50/p95/p99 readout) under one lock-per-metric design;
- **adapters**: a runtime observer (stacked NEXT TO graftsan through
  the widened `runtime.add_observer` seam) turns every H2D/D2H/compile
  record into counter movement; a span listener turns every completed
  graftscope span (monitoring/spans.py) into a latency observation —
  step latency, data wait, dispatch, D2H fetch — and `generate()` /
  beam / speculative feed a per-token decode-latency histogram (the
  precursor to serving p99); an MFU gauge derives model-flops-per-step
  (jit cost analysis) / chip peak;
- **lifecycle**: `CLOUD_TPU_TELEMETRY=1` makes Trainer entry points
  run under `env_scope()` — ambient enablement on first entry, a
  bounded-queue background flush (monitoring/export.py) per epoch, and
  a blocking flush at scope exit so `<dir>/trace.json`,
  `<dir>/metrics.prom` and `<dir>/telemetry.jsonl` are on disk when
  fit() returns.

Zero-cost discipline: with telemetry off nothing is installed — no
runtime observer, no span tracer, no thread; every integration point
is a None/env check (the graftsan seam contract, unchanged).

Env contract:
    CLOUD_TPU_TELEMETRY        1|on  -> Trainer entry points enable
    CLOUD_TPU_TELEMETRY_DIR    output directory (default ./telemetry)

The MFU gauge divides by the chip's published peak, looked up in
`PEAK_TFLOPS` by the `device_kind` JAX reports. A CPU run has no such
gauge; an accelerator missing from the table is an error, never a
default.
"""

import bisect
import contextlib
import logging
import os
import threading

from cloud_tpu.monitoring import spans
from cloud_tpu.parallel import runtime

logger = logging.getLogger("cloud_tpu")

__all__ = ["Counter", "Gauge", "Histogram", "Registry", "Telemetry",
           "PEAK_TFLOPS", "peak_tflops", "enable", "disable", "get",
           "enabled", "env_enabled", "env_scope"]

#: Published bf16 peak of ONE chip in TFLOP/s, keyed by the
#: `device_kind` JAX reports — the MFU gauge's denominator. v5e: 197
#: (Google Cloud documentation, "TPU v5e"). Add a kind together with
#: its source.
PEAK_TFLOPS = {
    "TPU v5 lite": 197.0,
}


def peak_tflops(device_kind):
    """The published bf16 peak for `device_kind`; raises for a kind
    that is not in `PEAK_TFLOPS` rather than measuring utilization
    against another chip's peak."""
    try:
        return PEAK_TFLOPS[device_kind]
    except KeyError:
        raise ValueError(
            "No published peak for device_kind {!r}; add it to "
            "cloud_tpu.monitoring.telemetry.PEAK_TFLOPS with its "
            "source (known: {}).".format(
                device_kind, sorted(PEAK_TFLOPS))) from None

#: Span name -> histogram metric fed by the span listener.
SPAN_HISTOGRAMS = {
    "train_step": "cloud_tpu_step_latency_seconds",
    "data_wait": "cloud_tpu_data_wait_seconds",
    "dispatch": "cloud_tpu_dispatch_seconds",
    "d2h_fetch": "cloud_tpu_d2h_fetch_seconds",
    "checkpoint_snapshot": "cloud_tpu_checkpoint_snapshot_seconds",
    "async_reader_drain": "cloud_tpu_async_reader_drain_seconds",
    "decode": "cloud_tpu_decode_seconds",
    "serve_prefill": "cloud_tpu_serve_prefill_seconds",
    "serve_tick": "cloud_tpu_serve_tick_wall_seconds",
}

DECODE_TOKEN_HISTOGRAM = "cloud_tpu_decode_token_latency_seconds"
MFU_GAUGE = "cloud_tpu_mfu_pct_peak"

#: graftserve (serving/scheduler.py) metric names. The scheduler feeds
#: these through `telemetry.get().registry` under the same
#: zero-cost-when-off discipline as the decode hooks.
SERVE_REQUESTS_TOTAL = "cloud_tpu_serve_requests_total"
SERVE_TOKENS_TOTAL = "cloud_tpu_serve_tokens_total"
SERVE_REQUESTS_PER_SEC = "cloud_tpu_serve_requests_per_sec"
SERVE_QUEUE_DEPTH = "cloud_tpu_serve_queue_depth"
SERVE_ACTIVE_SLOTS = "cloud_tpu_serve_active_slots"
SERVE_TTFT_HISTOGRAM = "cloud_tpu_serve_ttft_seconds"
SERVE_TOKEN_HISTOGRAM = "cloud_tpu_serve_token_latency_seconds"
#: graftlens (PR 13) latency decomposition: queue wait (submit ->
#: admission pop) and KV-page reservation blocking time were previously
#: folded into TTFT; splitting them out is the direct input ROADMAP
#: item 4's predicted-TTFT admission needs, and the waiter gauge makes
#: PagePool backpressure visible instead of masquerading as prefill.
SERVE_QUEUE_WAIT_HISTOGRAM = "cloud_tpu_serve_queue_wait_seconds"
SERVE_RESERVE_WAIT_HISTOGRAM = "cloud_tpu_serve_reserve_wait_seconds"
SERVE_RESERVE_WAITERS = "cloud_tpu_serve_reserve_waiters"

#: graftshare (prefix cache + CoW pages + tick speculation) names.
#: Split TTFT: requests whose prompt hit the radix prefix cache prefill
#: only their suffix, so their TTFT distribution is a different
#: population from misses — one merged histogram would hide the win.
SERVE_TTFT_HIT_HISTOGRAM = "cloud_tpu_serve_ttft_hit_seconds"
SERVE_TTFT_MISS_HISTOGRAM = "cloud_tpu_serve_ttft_miss_seconds"
SERVE_PREFIX_HIT_RATE = "cloud_tpu_serve_prefix_hit_rate"
SERVE_PREFIX_PAGES_HELD = "cloud_tpu_serve_prefix_pages_held"
SERVE_PREFIX_EVICTIONS = "cloud_tpu_serve_prefix_evictions_total"
SERVE_PAGES_FREE = "cloud_tpu_serve_pages_free"
SERVE_PAGES_SHARED = "cloud_tpu_serve_pages_shared"
SERVE_COW_COPIES = "cloud_tpu_serve_cow_copies_total"
#: Accepted-token rate per verification round (accepted/proposed in
#: [0, 1]), shared by `generate_speculative` and the serving tick's
#: per-slot speculation (models/speculative.py observe_accept_rate).
SERVE_SPEC_ACCEPT_HISTOGRAM = "cloud_tpu_serve_spec_accepted_rate"

#: graftstorm (serving chaos) names. Fault/requeue/shed counters label
#: by taxonomy kind / shed reason via the `%s` suffix (the single-
#: registry renderer has no label support — the KERNEL gauge idiom).
#: The predicted-TTFT gauge is the admission controller's latest
#: estimate: what the NEXT admitted request is expected to wait.
SERVE_FAULTS_TOTAL = "cloud_tpu_serve_faults_total_%s"
SERVE_REQUEUES_TOTAL = "cloud_tpu_serve_requeues_total"
SERVE_SHED_TOTAL = "cloud_tpu_serve_shed_total_%s"
SERVE_PREDICTED_TTFT = "cloud_tpu_serve_predicted_ttft"
#: Always-on host prefill-latency histogram: the predicted-TTFT model
#: needs a live prefill estimate even when telemetry export is off.
SERVE_PREFILL_HISTOGRAM = "cloud_tpu_serve_prefill_seconds"

#: Chunked prefill (ROADMAP item 4 tail). Per-CHUNK prefill latency
#: replaces the whole-prefill p50 in the admission model when chunking
#: is on; the decode-gap histogram is the tick-to-tick commit interval
#: active slots actually experience (the p99 the interleave protects —
#: tick COMPUTE time alone cannot see a stalled tick loop). The pages
#: gauge counts pages reserved for prefills still in flight.
SERVE_PREFILL_CHUNK_HISTOGRAM = "cloud_tpu_serve_prefill_chunk_seconds"
SERVE_PREFILL_CHUNKS_TOTAL = "cloud_tpu_serve_prefill_chunks_total"
SERVE_DECODE_GAP_HISTOGRAM = "cloud_tpu_serve_decode_gap_seconds"
SERVE_PAGES_PREFILLING = "cloud_tpu_serve_pages_prefilling"

#: graftpack (ROADMAP item 3) names: the KV memory hierarchy. The
#: bytes gauge labels by tier via the `%s` suffix (hbm = pages the
#: pool holds x page_hbm_bytes, host = pages the host tier holds at
#: the same per-page cost); capacity-sessions is how many FULL-length
#: sequences the pool can hold resident at once — the gauge the int8
#: page mode exists to raise. Demote/promote counters accrue in PAGES
#: moved; digest failures count promote-time tree_digest mismatches
#: (typed HostTierCorrupt, entry dropped, request re-prefills).
SERVE_KV_BYTES = "cloud_tpu_serve_kv_bytes_%s"
SERVE_KV_CAPACITY_SESSIONS = "cloud_tpu_serve_kv_capacity_sessions"
SERVE_HOST_TIER_PAGES = "cloud_tpu_serve_host_tier_pages"
SERVE_PAGE_DEMOTES_TOTAL = "cloud_tpu_serve_page_demotes_total"
SERVE_PAGE_PROMOTES_TOTAL = "cloud_tpu_serve_page_promotes_total"
SERVE_DIGEST_FAILURES_TOTAL = "cloud_tpu_serve_digest_failures_total"

#: graftflex (elastic tick geometry) names. The slot-count gauge is
#: the CURRENT ladder rung; the resize counter labels by direction
#: (grow/shrink) via the `%s` suffix; the per-tick latency histogram
#: labels by the slot count the tick ran at — one histogram per rung,
#: so a goodput A/B never averages a 4-wide tick against a 32-wide
#: one (the mixed-width trap the geometry stamp closes).
SERVE_SLOT_COUNT = "cloud_tpu_serve_slot_count"
SERVE_RESIZES_TOTAL = "cloud_tpu_serve_resizes_total_%s"
SERVE_TICK_SECONDS = "cloud_tpu_serve_tick_seconds_slots_%s"

#: graftsweep (tuner/sweep.py) names. Counters accrue across every
#: sweep a process runs; the gauges hold the LATEST sweep's values.
#: `_warm_trials_total` counts reused-Trainer trials that finished
#: with zero new compiles — the shared-warm-cache win, pinned.
SWEEP_TRIALS_TOTAL = "cloud_tpu_sweep_trials_total"
SWEEP_TRIALS_PRUNED_TOTAL = "cloud_tpu_sweep_trials_pruned_total"
SWEEP_TRIALS_FAILED_TOTAL = "cloud_tpu_sweep_trials_failed_total"
SWEEP_FAULTS_TOTAL = "cloud_tpu_sweep_faults_total"
SWEEP_RESUMES_TOTAL = "cloud_tpu_sweep_resumes_total"
SWEEP_WARM_TRIALS_TOTAL = "cloud_tpu_sweep_warm_trials_total"
SWEEP_BEST_SCORE = "cloud_tpu_sweep_best_score"
SWEEP_COMPILE_SECONDS = "cloud_tpu_sweep_compile_seconds"


class Counter:
    """Monotonic counter (int)."""

    __slots__ = ("name", "_mu", "_value")

    def __init__(self, name):
        self.name = name
        self._mu = threading.Lock()
        self._value = 0

    def inc(self, delta=1):
        with self._mu:
            self._value += int(delta)

    @property
    def value(self):
        with self._mu:
            return self._value


class Gauge:
    """Last-write-wins float."""

    __slots__ = ("name", "_mu", "_value")

    def __init__(self, name):
        self.name = name
        self._mu = threading.Lock()
        self._value = 0.0

    def set(self, value):
        with self._mu:
            self._value = float(value)

    @property
    def value(self):
        with self._mu:
            return self._value


class Histogram:
    """Exponential-bucket histogram with percentile readout.

    Bucket upper bounds are `start * factor**i` for i in [0, buckets);
    observations above the last bound land in the +Inf bucket. The
    defaults (1 µs .. ~72 min at factor 2) cover every latency this
    framework measures — a step dispatch, a host round trip, a cold
    compile — at ≤2x relative bucket error, which is what a p99 read
    off bucket interpolation inherits.
    """

    __slots__ = ("name", "_mu", "bounds", "_counts", "_sum", "_count",
                 "_max")

    def __init__(self, name, start=1e-6, factor=2.0, buckets=32):
        self.name = name
        self._mu = threading.Lock()
        bounds = []
        bound = float(start)
        for _ in range(int(buckets)):
            bounds.append(bound)
            bound *= float(factor)
        self.bounds = bounds
        self._counts = [0] * (len(bounds) + 1)  # [+Inf overflow last]
        self._sum = 0.0
        self._count = 0
        self._max = 0.0

    def observe(self, value, count=1):
        """Records `count` observations of `value` (a batched decode
        records its per-token latency once per generated token)."""
        value = float(value)
        idx = bisect.bisect_left(self.bounds, value)
        with self._mu:
            self._counts[idx] += count
            self._sum += value * count
            self._count += count
            if value > self._max:
                self._max = value

    @property
    def count(self):
        with self._mu:
            return self._count

    @property
    def sum(self):
        with self._mu:
            return self._sum

    def percentile(self, p):
        """Approximate p-th percentile (0-100) by linear interpolation
        inside the bucket holding that rank; 0.0 when empty. The +Inf
        bucket reports the largest observed value."""
        with self._mu:
            counts = list(self._counts)
            total = self._count
            largest = self._max
        if total <= 0:
            return 0.0
        rank = (p / 100.0) * total
        cumulative = 0
        for idx, bucket_count in enumerate(counts):
            previous = cumulative
            cumulative += bucket_count
            if cumulative >= rank and bucket_count:
                if idx >= len(self.bounds):
                    return largest
                upper = self.bounds[idx]
                lower = self.bounds[idx - 1] if idx else 0.0
                fraction = (rank - previous) / bucket_count
                return lower + (upper - lower) * min(fraction, 1.0)
        return largest

    def snapshot(self):
        with self._mu:
            counts = list(self._counts)
            total = self._count
            value_sum = self._sum
        return {
            "bounds": list(self.bounds),
            "counts": counts,
            "count": total,
            "sum": value_sum,
            "p50": self.percentile(50),
            "p95": self.percentile(95),
            "p99": self.percentile(99),
        }


class Registry:
    """Name-keyed metric store; get-or-create accessors."""

    def __init__(self):
        self._mu = threading.Lock()
        self._counters = {}
        self._gauges = {}
        self._histograms = {}

    def counter(self, name):
        with self._mu:
            metric = self._counters.get(name)
            if metric is None:
                metric = self._counters[name] = Counter(name)
            return metric

    def gauge(self, name):
        with self._mu:
            metric = self._gauges.get(name)
            if metric is None:
                metric = self._gauges[name] = Gauge(name)
            return metric

    def histogram(self, name, **kwargs):
        with self._mu:
            metric = self._histograms.get(name)
            if metric is None:
                metric = self._histograms[name] = Histogram(name,
                                                            **kwargs)
            return metric

    def snapshot(self):
        """Plain-data view for exporters: {"counters": {name: int},
        "gauges": {name: float}, "histograms": {name: {...}}}."""
        with self._mu:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            histograms = dict(self._histograms)
        return {
            "counters": {n: c.value for n, c in counters.items()},
            "gauges": {n: g.value for n, g in gauges.items()},
            "histograms": {n: h.snapshot()
                           for n, h in histograms.items()},
        }


class _RuntimeObserver:
    """The adapter on the widened runtime observer seam: every
    transfer/compile record becomes counter movement. Stacks with a
    graftsan Sanitizer through `runtime.add_observer` fanout."""

    def __init__(self, registry):
        self._h2d_transfers = registry.counter(
            "cloud_tpu_h2d_transfers_total")
        self._h2d_bytes = registry.counter("cloud_tpu_h2d_bytes_total")
        self._d2h_fetches = registry.counter(
            "cloud_tpu_d2h_fetches_total")
        self._d2h_bytes = registry.counter("cloud_tpu_d2h_bytes_total")
        self._traces = registry.counter("cloud_tpu_traces_total")
        self._compiles = registry.counter("cloud_tpu_compiles_total")
        self._cache_hits = registry.counter(
            "cloud_tpu_compile_cache_hits_total")
        self._cache_misses = registry.counter(
            "cloud_tpu_compile_cache_misses_total")

    def on_h2d(self, transfers, nbytes):
        self._h2d_transfers.inc(transfers)
        self._h2d_bytes.inc(nbytes)

    def on_d2h(self, nbytes, tree):
        self._d2h_fetches.inc(1)
        self._d2h_bytes.inc(nbytes)

    def on_compile(self, n_traces, n_compiles, cache_hits):
        self._traces.inc(n_traces)
        self._compiles.inc(n_compiles)
        self._cache_hits.inc(cache_hits)

    def on_cache_miss(self):
        self._cache_misses.inc(1)

    def on_epoch(self, epoch):
        pass

    def on_donation(self, args):
        pass


class Telemetry:
    """One enabled telemetry session: registry + tracer + exporters.

    Use the module-level `enable()`/`env_scope()` for the ambient
    singleton; direct construction is for tests that want an isolated
    instance.
    """

    def __init__(self, out_dir, peak_tflops=None):
        self.out_dir = str(out_dir)
        self.registry = Registry()
        self.tracer = None
        # None = look the device up on first use (tests pass a value).
        self._peak_tflops = peak_tflops
        self._observer = None
        self._worker = None
        self._exporters = ()
        self._step_flops = None
        self._active = False

    # -- lifecycle -----------------------------------------------------

    def enable(self):
        """Installs the span tracer + runtime observer and starts the
        background flush worker. Idempotent."""
        if self._active:
            return self
        os.makedirs(self.out_dir, exist_ok=True)
        self.tracer = spans.install()
        self.tracer.add_listener(self._on_span)
        self._observer = _RuntimeObserver(self.registry)
        runtime.add_observer(self._observer)
        # The headline histograms exist from t=0 (a textfile scrape
        # between enable and the first epoch still sees them); the MFU
        # gauge appears with its first value, on a chip with a peak.
        self.registry.histogram("cloud_tpu_step_latency_seconds")
        self.registry.histogram(DECODE_TOKEN_HISTOGRAM)
        from cloud_tpu.monitoring import export
        self._exporters = export.default_exporters(self.out_dir)
        self._worker = export.FlushWorker(self._do_flush)
        self._active = True
        return self

    def disable(self):
        """Final flush, then tears every hook down. Idempotent."""
        if not self._active:
            return
        self._active = False
        worker, self._worker = self._worker, None
        if worker is not None:
            worker.close(flush=True)
        if self._observer is not None:
            runtime.remove_observer(self._observer)
            self._observer = None
        spans.uninstall()

    @property
    def active(self):
        return self._active

    @property
    def peak_flops(self):
        """The chip's peak FLOP/s, or None on the CPU (which has no
        utilization gauges)."""
        if self._peak_tflops is None:
            import jax

            device = jax.devices()[0]
            if device.platform == "cpu":
                return None
            self._peak_tflops = peak_tflops(device.device_kind)
        return self._peak_tflops * 1e12

    # -- adapters ------------------------------------------------------

    def _on_span(self, name, t0_ns, dur_ns, tid):
        metric = SPAN_HISTOGRAMS.get(name)
        if metric is not None:
            self.registry.histogram(metric).observe(dur_ns / 1e9)

    def set_step_flops(self, flops):
        """Model flops for ONE train step (jit cost analysis), the MFU
        numerator. 0/None disables the gauge update."""
        self._step_flops = float(flops) if flops else None

    @property
    def step_flops(self):
        return self._step_flops

    def record_epoch(self, steps, examples, elapsed_secs):
        """Per-epoch rollup from the Trainer boundary: throughput
        counters, the MFU gauge, and one (lossy, non-blocking) flush."""
        if steps > 0:
            self.registry.counter("cloud_tpu_training_steps_total").inc(
                steps)
            self.registry.counter(
                "cloud_tpu_training_examples_total").inc(examples)
            elapsed_secs = max(float(elapsed_secs), 1e-9)
            self.registry.gauge("cloud_tpu_steps_per_sec").set(
                steps / elapsed_secs)
            peak = self.peak_flops if self._step_flops else None
            if peak:
                flops_per_sec = self._step_flops * steps / elapsed_secs
                self.registry.gauge(MFU_GAUGE).set(
                    100.0 * flops_per_sec / peak)
        self.flush()

    def observe_decode(self, n_tokens, elapsed_secs):
        """Per-token decode latency: one observation per generated
        token at the call's mean per-token latency (all tokens of one
        scan share their dispatch's wall time)."""
        n_tokens = int(n_tokens)
        if n_tokens <= 0:
            return
        self.registry.histogram(DECODE_TOKEN_HISTOGRAM).observe(
            float(elapsed_secs) / n_tokens, count=n_tokens)

    # -- export --------------------------------------------------------

    def flush(self, wait=False):
        """Requests an export pass on the background worker. Non-wait
        requests are lossy when one is already queued (coalesced);
        wait=True blocks until a full pass completed."""
        worker = self._worker
        if worker is None:
            self._do_flush()
            return
        worker.request(wait=wait)

    def _do_flush(self):
        for exporter in self._exporters:
            try:
                exporter.export(self)
            except Exception:
                logger.debug("telemetry exporter %r failed",
                             exporter, exc_info=True)


# -- ambient singleton + env contract -----------------------------------

_telemetry = None
_enable_lock = threading.Lock()


def env_enabled():
    """The CLOUD_TPU_TELEMETRY env contract (same truthiness grammar
    as CLOUD_TPU_SANITIZE)."""
    value = os.environ.get("CLOUD_TPU_TELEMETRY", "").strip().lower()
    return value not in ("", "0", "off", "false", "none")


def enable(out_dir=None):
    """Enables the ambient telemetry singleton (idempotent). `out_dir`
    defaults to CLOUD_TPU_TELEMETRY_DIR, then ./telemetry."""
    global _telemetry
    with _enable_lock:
        if _telemetry is None:
            if out_dir is None:
                out_dir = (os.environ.get("CLOUD_TPU_TELEMETRY_DIR")
                           or os.path.join(os.getcwd(), "telemetry"))
            _telemetry = Telemetry(out_dir)
        return _telemetry.enable()


def disable():
    """Tears the ambient singleton down (test isolation)."""
    global _telemetry
    with _enable_lock:
        tele, _telemetry = _telemetry, None
    if tele is not None:
        tele.disable()


def get():
    """The ambient Telemetry, or None when disabled."""
    return _telemetry


def enabled():
    return _telemetry is not None and _telemetry.active


@contextlib.contextmanager
def env_scope():
    """Library entry-point scope (Trainer.fit/evaluate): enables the
    ambient singleton when CLOUD_TPU_TELEMETRY asks for it, and
    guarantees a completed (blocking) flush at scope exit so the
    trace/textfile artifacts exist the moment the entry point returns.
    Enablement is ambient, not scoped — nested fits reuse the same
    session and tear nothing down (use `disable()` for that)."""
    if not env_enabled():
        yield None
        return
    tele = enable()
    try:
        yield tele
    finally:
        tele.flush(wait=True)
