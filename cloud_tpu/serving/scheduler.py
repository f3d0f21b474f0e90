"""graftserve request scheduler: admission, batching, backpressure.

Two threads around a `DecodeEngine`:

- the ADMISSION thread pops submitted requests from a bounded queue in
  FCFS windows, orders each window longest-RADIX-match-first (requests
  whose prompts share the most already-cached pages admit first — they
  are the cheapest TTFT and keep hot prefixes hot; ties fall back to
  longest-prefill-first), reserves KV pages for cache MISSES (BLOCKING
  when the pool is exhausted — backpressure, never OOM; a blocked
  reservation applies LRU eviction pressure to the prefix cache), and
  runs miss prefills off the tick's critical path. It keeps ONE
  prefill in flight: request n+1 is marked, reserved and dispatched
  before prefill n's first token is fetched (`_prefill_loop`), and
  whatever it waits for that is not the device (an empty queue, pages
  only ticks can free, a hit handed over, close) first fetches the
  prefill in flight;
- the TICK thread owns the engine's device state: it admits prefix-
  cache HITS (the hit prefill gathers from the engine's live pool
  cache, which every tick donates — only the tick thread may read it),
  inserts ready prefills into free slots, advances all active slots
  (one committed token per tick, or up to spec_k + 1 with speculative
  decode), fetches the tick output (the serving loop's single counted
  d2h round trip), completes/evicts finished slots, and returns their
  pages. It keeps ONE tick in flight: tick n+1 is dispatched before
  tick n's tokens are fetched, so the commit, the admissions and the
  next dispatch run while the device works (`_tick_loop`); whatever
  needs the slots' exact state (a nap, an idle wait, a resize, a chaos
  event, close) first fetches and commits the tick in flight.

Prefix sharing (graftshare): every inserted prompt's full pages are
registered in a radix trie (serving/prefixcache.py). A later request
whose prompt shares a prefix maps those pages into its own page table
(pool-refcounted, copy-on-write on divergence) and prefills only its
suffix — TTFT O(prompt) -> O(suffix). The trie's HBM budget is enforced
by LRU eviction of pages no in-flight request holds.

Liveness rides graftwatch: the tick thread beats the installed watchdog
every iteration and polls `watch.check()`, so a stuck tick surfaces as
the watchdog's typed fault (graftwatch blackbox + `BackendUnavailable`)
instead of a silent hang. Throughput/latency ride graftscope: requests
and tokens totals, queue-depth and active-slots gauges, pool/prefix
gauges, and TTFT histograms split by hit/miss (p50/p95/p99 via the
registry snapshot).

Phase labels: the tick thread runs under `runtime.set_phase
("serve_tick")`, the admission thread under "serve_prefill" — distinct
from the training "step" phase, so graftsan GS001 (d2h-in-step-loop)
correctly treats the per-tick fetch as a sanctioned, attributed read.

Request records (graftlens): every request gets a rid at submit() and
a `reqtrace.RequestRecord` that the scheduler's one marking call
(`_mark`) stamps at each boundary it passes — submit, dequeued (its
window popped), admit (its own turn in the window), reserved, first
(token on the host), insert, each later token's commit, done — always,
in memory, clock reads only. The phases queue + window + reserve +
prefill add up to `ServeResult.ttft_s` exactly (both are computed from
the record, which is `ServeResult.trace`), and the last 4096 finished
records are `reqtrace.recent()`. With `CLOUD_TPU_REQTRACE=1` the same
marks are exported as typed reqtrace JSONL events (serving/reqtrace.py):
submitted -> queued -> radix_probe -> pages_reserved -> prefill ->
slot_insert -> tick_commit* -> complete | fail, buffered and written
when the buffer fills and at close(); with the env unset no tracer is
installed: no events, no file, no threads. The sections of both threads
are graftscope spans (`monitoring/spans.py` has the table), which a
profile capture shows beside the device's ops, those of one request
under its rid, those of one tick under its `tick=`. Beside the record a
request the tick thread keeps a record a TICK (`reqtrace.TickRecord`,
made in `_dispatch_tick`, published in `_commit_tick`; the last 16 384
are `reqtrace.recent_ticks()`): its dispatch, fetch and commit times,
what it advanced, and what the engine dispatched to the device between
the tick before and it (`DecodeEngine.take_dispatched()`), with the
naps and idle waits taken meanwhile: what shared the device with each
tick, over the whole of a window. Queue-wait and
page-reservation-wait histograms are
host-side and always on (warm-reset like TTFT), feeding `stats()` and
ROADMAP item 4's predicted-TTFT admission.

Fault handling (graftstorm): chaos serving injections (analysis/
chaos.py SERVE_KINDS, tick-indexed) are consumed at the top of every
tick iteration. A faulted slot drains through the same fixed-shape
evict scatter finished slots use — the persistent tick never stops —
its pages return to the pool exactly once (prefix-trie references
survive untouched), and its request re-enters the tick thread's ready
deque as a typed requeue: re-prefill from retained prompt + tokens
generated so far, with the slot's ORIGINAL rng schedule re-based via
the engine's `key_override` so the continuation completes bit-identical
to an uninterrupted decode (graftguard's resume discipline, per slot).
A `prefill_fail` releases any reserved pages and retries — transient,
never lost. SLO-aware admission: with `CLOUD_TPU_SERVE_SLO_TTFT` set
(or the `slo_ttft` ctor arg), the admission thread predicts each
candidate's TTFT from the live queue-wait/prefill histograms plus pool
occupancy, and sheds (typed `ServeShed`) or defers
(`CLOUD_TPU_SERVE_SHED=defer`) work it cannot serve within SLO instead
of plain-FCFS admitting it.

Chunked prefill (ROADMAP item 4 tail): with `prefill_chunk=` (or
`CLOUD_TPU_SERVE_PREFILL_CHUNK`) set to a pow2 chunk width, prefills
run as `engine.ChunkedPrefill` continuations interleaved with the
decode tick — at most ONE chunk dispatched per tick-loop iteration, so
a 4k-token arrival costs every resident slot one chunk of extra
tick-to-tick latency instead of the whole prefill. All three prefill
classes chunk (miss, prefix hit via the gather offset, requeue via
key_override), outputs stay bit-identical (the tail chunk runs the
SAME sampling executable a whole prefill of that suffix would), chaos
`prefill_fail` lands on chunk boundaries with completed chunks
retained, and the admission model swaps the whole-prefill p50 for a
per-chunk histogram. The decode-gap histogram (commit-to-commit
interval over active slots) is the p99 this interleave protects —
tick COMPUTE time alone cannot see a tick loop stalled behind a
monolithic prefill.

Elastic tick geometry (graftflex): with a slot-count ladder configured
(`ladder=`, or pow2 rungs derived from `CLOUD_TPU_SERVE_SLOTS_MIN` /
`CLOUD_TPU_SERVE_SLOTS_MAX`), the tick's batch width follows offered
load through pre-warmed per-rung executables: a full rung with waiting
work grows to the next rung at the SAME tick boundary (a slammed
replica widens instead of shedding), a rung whose live set fits the
next rung down shrinks after `resize_quiet_ticks` consecutive quiet
boundaries (hysteresis — oscillating load never flaps). Page tables
are pool-indexed, so a resize gathers slot ROWS only (rng schedules,
eos latches, spec state ride along bit-identical); KV pages never
move, and warmup walks every rung so steady state stays at zero new
traces. Every per-tick stat stamps its geometry, and the admission
predictor can be replaced by an offline model fit from the reqtrace
corpus (`python -m cloud_tpu.serving.admission fit`, loaded via
`CLOUD_TPU_SERVE_ADMISSION_MODEL` at start()).
"""

import collections
import dataclasses
import os
import queue
import threading
import time
from concurrent.futures import Future
from typing import Optional, Sequence

import jax
import numpy as np

from cloud_tpu.monitoring import spans
from cloud_tpu.ops.paged_attention import (copies_pages, group_pages,
                                         mla_group_pages, walked_tokens)
from cloud_tpu.parallel import runtime
from cloud_tpu.serving import reqtrace
from cloud_tpu.serving.engine import (DecodeEngine, attention_shape,
                                      host_prng_key)
from cloud_tpu.serving.faults import (HostTierCorrupt, PoolSqueezed,
                                      PrefillFailed, ServeShed,
                                      SlotEvicted, SlotHang, fault_kind)
from cloud_tpu.serving.kvpool import (HostPageTier, PagePool,
                                      RingSummaryPagePool)
from cloud_tpu.serving.prefixcache import PrefixCache

#: pool_squeeze hold window: confiscated pages return after this many
#: ticks OR this much wall time, whichever first — the wall-clock bound
#: keeps a squeeze from deadlocking a pool so starved that no slot is
#: active and ticks stop advancing.
SQUEEZE_HOLD_TICKS = 8
SQUEEZE_HOLD_S = 2.0

_OFF_VALUES = ("", "0", "off", "false", "none")

#: A block-diffusion model's counters in `stats()` (table in
#: monitoring/spans.py), each summed over the ticks.
DIFFUSION_COUNTERS = ("diffusion_slot_steps", "diffusion_tokens_committed",
                      "diffusion_blocks_closed", "diffusion_rows_computed")


@dataclasses.dataclass
class ServeRequest:
    """One decode request. Semantics (and output) match
    `generate(model, params, prompt[None], max_new_tokens,
    rng=PRNGKey(rng_seed), ...)` exactly — the determinism contract,
    regardless of prefix sharing or speculation."""
    prompt: Sequence[int]
    max_new_tokens: int
    temperature: float = 1.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token: Optional[int] = None
    rng_seed: int = 0
    # A block-diffusion model's (`models.sdar.SdarLM`): the denoising
    # steps a block of this request takes, 1 to B (None = B). Fewer
    # steps commit more tokens a step: speed against quality.
    denoising_steps: Optional[int] = None


@dataclasses.dataclass
class ServeResult:
    """A completed request: `tokens` is prompt + continuation, the
    `generate()` row contract. `prefix_len` is the token count served
    from the prefix cache (0 = cold prefill). `trace` is the request's
    `reqtrace.RequestRecord`: `ttft_s` and `latency_s` are computed
    from it, and its `phases()` add up to them. `commit_steps` (a
    block-diffusion model's request; None otherwise): for each new
    token the step of its block at which it was committed, from 0, as
    `models.sdar.generate` returns them."""
    tokens: np.ndarray
    ttft_s: float
    latency_s: float
    prefix_len: int = 0
    trace: Optional[reqtrace.RequestRecord] = None
    commit_steps: Optional[np.ndarray] = None


class _Slot:
    __slots__ = ("request", "pages", "emitted", "future", "rec",
                 "prefix_len", "trace_ticks", "step_keys",
                 "result_prefix_len", "committed", "block_steps")

    def __init__(self, request, pages, future, rec, prefix_len):
        self.request = request
        self.pages = pages
        self.emitted = []
        self.future = future
        self.rec = rec  # the request's reqtrace.RequestRecord
        self.prefix_len = prefix_len
        self.trace_ticks = 0  # ticks since the last tick_commit event
        # Retained per-slot rng schedule (the PrefillResult's host
        # uint32[max_new_cap-1, 2] array): a fault after n emitted
        # tokens re-bases the continuation onto rows n-1 (its prefill
        # key) and n.. (its tick schedule) — graftstorm bit-identity.
        self.step_keys = None
        # prefix_len the final ServeResult reports: survives requeue
        # (the continuation cold-prefills, but the REQUEST's cache-hit
        # status is a property of its original admission).
        self.result_prefix_len = prefix_len
        # A block-diffusion request's new tokens as they are committed,
        # out of order inside a block: position -> (token, the step of
        # its block), and the steps its open block has had. `emitted`
        # takes a token when it and every one before it are committed.
        self.committed = {}
        self.block_steps = 0


class _Flight:
    """A tick on the device whose tokens the host has not read: what
    its dispatch has to remember, because by the time it is committed
    the engine holds the next tick's counters and a slot that finished
    in the tick before may hold another request."""
    __slots__ = ("out", "counters", "record", "slots")

    def __init__(self, out, counters, record, slots):
        self.out = out              # device tokens (`engine.tick()`)
        self.counters = counters    # `engine.tick_counters`, this tick's
        self.record = record        # its `reqtrace.TickRecord`
        self.slots = slots          # slot -> _Slot it ran with (a copy)


class _ReadyItem:
    """A miss-path prefill waiting for a free slot (admission thread
    already ran the prefill and holds the reserved pages)."""
    __slots__ = ("request", "result", "pages", "future", "rec")

    def __init__(self, request, result, pages, future, rec):
        self.request = request
        self.result = result
        self.pages = pages
        self.future = future
        self.rec = rec


class _MissFlight:
    """A miss whose whole-prompt prefill is on the device and whose
    first token the host has not read (admission thread only): the
    twin of `_Flight`, one thread over."""
    __slots__ = ("request", "flight", "pages", "future", "rec")

    def __init__(self, request, flight, pages, future, rec):
        self.request = request
        self.flight = flight        # `engine.PrefillFlight`
        self.pages = pages
        self.future = future
        self.rec = rec


class _HitTicket:
    """A prefix-cache hit waiting for the tick thread: no pages, no
    prefill yet — the hit prefill must read the engine's live pool
    cache, which only the tick thread may touch."""
    __slots__ = ("request", "future", "rec", "t_reserve0")

    def __init__(self, request, future, rec):
        self.request = request
        self.future = future
        self.rec = rec
        # First reservation attempt: a page-starved hit retries across
        # _insert_ready passes, so the cumulative reserve wait must
        # survive the ticket being re-queued.
        self.t_reserve0 = None


class _RequeueItem:
    """A faulted request re-entering the tick thread's ready deque
    (graftstorm). `request` is the CONTINUATION: original prompt +
    tokens generated so far, max_new reduced by the same count — so
    prompt + emitted at completion reassembles the original row.
    `key`/`rest` are the original schedule rows the continuation's
    prefill and ticks must consume (engine.prefill key_override)."""
    __slots__ = ("request", "key", "rest", "future", "rec",
                 "result_prefix_len")

    def __init__(self, request, key, rest, future, rec,
                 result_prefix_len):
        self.request = request
        self.key = key
        self.rest = rest
        self.future = future
        self.rec = rec
        self.result_prefix_len = result_prefix_len


class _ChunkItem:
    """An in-flight chunked prefill on the tick thread's interleave
    queue: the `engine.ChunkedPrefill` continuation plus everything
    needed to insert (or complete) it when the tail chunk lands.
    `kind` selects the insert variant — "miss" (admission-thread
    reservation, registers in the trie), "hit" (shared + fresh pages,
    CoW partial page, registers), "requeue" (key-override
    continuation: the record keeps the original TTFT, no register)."""
    __slots__ = ("kind", "request", "chunked", "pages", "shared",
                 "fresh", "partial_page", "partial_len", "prefix_len",
                 "result_prefix_len", "future", "rec", "result",
                 "t_prefill0", "counts_pending", "hold_released")

    def __init__(self, kind, request, chunked, future, rec, pages=(),
                 shared=(), fresh=(), partial_page=None, partial_len=0,
                 prefix_len=0, result_prefix_len=0):
        self.kind = kind
        self.request = request
        self.chunked = chunked
        self.pages = list(pages)
        self.shared = list(shared)
        self.fresh = list(fresh)
        self.partial_page = partial_page
        self.partial_len = partial_len
        self.prefix_len = prefix_len
        self.result_prefix_len = result_prefix_len
        self.future = future
        self.rec = rec
        self.result = None       # PrefillResult once the tail chunk ran
        self.t_prefill0 = None   # first chunk dispatch (prefill span)
        self.counts_pending = (kind != "requeue"
                               and request.max_new_tokens > 1)
        self.hold_released = False

    def pages_held(self):
        """Pages the eventual _Slot owns (the CoW partial page is
        freed at insert, never carried into the slot)."""
        if self.kind == "hit":
            return self.shared + self.fresh
        return list(self.pages)

    def all_pages(self):
        """Every page to free if the item dies before insert."""
        held = self.pages_held()
        if self.kind == "hit" and self.partial_len:
            held = held + [self.partial_page]
        return held


def _registry():
    """graftscope registry when telemetry is enabled, else None — the
    decode hooks' zero-cost-when-off discipline."""
    import sys
    telemetry = sys.modules.get("cloud_tpu.monitoring.telemetry")
    if telemetry is None:
        return None
    tele = telemetry.get()
    if tele is None or not tele.active:
        return None
    return tele.registry


class Scheduler:
    """Continuous-batching front door. `submit()` from any thread;
    results come back as futures resolving to `ServeResult`."""

    def __init__(self, model, params, slots=4, page_size=16,
                 num_pages=None, max_new_cap=None, max_queue=64,
                 admission_window=8, strict_no_retrace=False,
                 prefix_cache=True, prefix_cache_pages=None,
                 draft_model=None, draft_params=None, spec_k=0,
                 slo_ttft=None, shed_policy=None, prefill_chunk=None,
                 kv_dtype=None, host_tier=None, host_tier_pages=None,
                 ladder=None, slots_min=None, slots_max=None,
                 resize_quiet_ticks=32, admission_model=None):
        # -- graftflex: elastic tick geometry -------------------------
        # The ladder is the pow2 set of pre-warmed slot counts the tick
        # may resize between. Explicit `ladder=` wins; otherwise the
        # CLOUD_TPU_SERVE_SLOTS_MIN/_MAX knobs (or ctor args) derive
        # the pow2 rungs in [min, max]; otherwise the geometry is fixed
        # at `slots` (exactly the pre-graftflex engine).
        if slots_min is None:
            env = os.environ.get("CLOUD_TPU_SERVE_SLOTS_MIN",
                                 "").strip()
            slots_min = int(env) if env else None
        if slots_max is None:
            env = os.environ.get("CLOUD_TPU_SERVE_SLOTS_MAX",
                                 "").strip()
            slots_max = int(env) if env else None
        if ladder is None and (slots_min is not None
                               or slots_max is not None):
            lo = int(slots_min if slots_min is not None else 1)
            hi = int(slots_max if slots_max is not None
                     else max(slots, lo))
            if lo < 1 or hi < lo:
                raise ValueError(
                    "need 1 <= slots_min <= slots_max; got min={} "
                    "max={}.".format(lo, hi))
            rungs, w = set(), 1
            while w <= hi:
                if w >= lo:
                    rungs.add(w)
                w *= 2
            ladder = tuple(sorted(rungs | {int(slots)}))
        # The rows a slot keeps where they are not one a token (the
        # model's class says so; `DecodeEngine.layout`).
        layout = getattr(model, "layout", None)
        if num_pages is None:
            # Default: every slot of the WIDEST rung can hold a
            # full-length sequence, plus scratch — paging then bounds
            # fragmentation, not memory, and a grow never needs new
            # pages (the pool serves every geometry).
            widest = max(ladder) if ladder else slots
            rows = layout.rows if layout is not None else model.max_seq_len
            num_pages = widest * (rows // page_size) + 1
        # -- graftpack: KV page dtype + host page tier ----------------
        if kv_dtype is None:
            kv_dtype = os.environ.get("CLOUD_TPU_SERVE_KV_DTYPE",
                                      "").strip().lower()
        if kv_dtype in _OFF_VALUES:
            kv_dtype = ""
        if kv_dtype not in ("", "int8"):
            raise ValueError(
                "kv_dtype must be '' or 'int8'; got {!r}.".format(
                    kv_dtype))
        self.kv_dtype = kv_dtype
        if host_tier is None:
            env = os.environ.get("CLOUD_TPU_SERVE_HOST_TIER",
                                 "").strip().lower()
            host_tier = env not in _OFF_VALUES
        if getattr(model, "block_length", 0):
            # A model that generates by denoising blocks (its class
            # says so): a prompt's last partial block is run again with
            # the tokens generated beside it, so no page of a request
            # is final when its prefill ends, and one tick commits a
            # block's rows out of order. No trie; no chunks.
            prefix_cache = False
            if host_tier:
                raise NotImplementedError(
                    "host_tier is not served for a block-diffusion "
                    "model ({}).".format(type(model).__name__))
            if prefill_chunk:
                raise NotImplementedError(
                    "prefill_chunk is not served for a block-diffusion "
                    "model ({}): it is prefilled whole.".format(
                        type(model).__name__))
            prefill_chunk = 0
        if getattr(model, "state_layers", 0):
            # A model with recurrent layers (its class says so): a
            # page holds keys and values, not the state those layers
            # had after a prefix, so nothing can be reused from pages
            # until state snapshots exist. No trie: no request is
            # probed, registered or counted as a hit.
            prefix_cache = False
            if host_tier:
                raise NotImplementedError(
                    "host_tier is not served for a model with "
                    "recurrent layers ({}): a demoted page cannot "
                    "give their state back.".format(
                        type(model).__name__))
        if layout is not None:
            # A model whose slots keep a ring and summary rows (its
            # class says so): a ring page is overwritten when the next
            # window begins and a summary row stands for one request's
            # own chunk, so no page outlives or is shared beyond its
            # request. No trie, as above.
            prefix_cache = False
            if host_tier:
                raise NotImplementedError(
                    "host_tier is not served for a model whose slots "
                    "keep a ring and summary rows ({}): its pages are "
                    "overwritten in place.".format(type(model).__name__))
        if host_tier:
            if draft_model is not None and spec_k > 0:
                raise ValueError(
                    "host_tier is incompatible with speculative decode "
                    "(the verify window transiently writes past the "
                    "committed history a demote key would stamp).")
            if not prefix_cache:
                raise ValueError(
                    "host_tier requires prefix_cache=True (promote "
                    "rides the hit-admission path and registers its "
                    "pages in the trie).")
        self.engine = DecodeEngine(model, params, slots, page_size,
                                   num_pages, max_new_cap=max_new_cap,
                                   draft_model=draft_model,
                                   draft_params=draft_params,
                                   spec_k=spec_k, page_dtype=kv_dtype,
                                   ladder=ladder)
        if layout is not None:
            self.pool = RingSummaryPagePool(
                layout, num_pages, page_size,
                page_bytes=self.engine.page_hbm_bytes())
        else:
            self.pool = PagePool(
                num_pages, page_size, self.engine.pages_per_slot,
                page_dtype=kv_dtype,
                page_bytes=self.engine.page_hbm_bytes(),
                state_bytes=self.engine.state_hbm_bytes())
        self.host_tier = None
        if host_tier:
            if host_tier_pages is None:
                env = os.environ.get("CLOUD_TPU_SERVE_HOST_TIER_PAGES",
                                     "").strip()
                # Default: 4x the device pool — a host tier exists to
                # be much larger than HBM.
                host_tier_pages = int(env) if env else 4 * num_pages
            self.host_tier = HostPageTier(host_tier_pages, page_size)
        # prefix_cache_pages is the trie's HBM budget (None = half the
        # pool — see PrefixCache); prefix_cache=False disables sharing
        # entirely (every request cold-prefills, the A/B baseline).
        self.trie = (PrefixCache(self.pool, max_pages=prefix_cache_pages)
                     if prefix_cache else None)
        self.strict_no_retrace = bool(strict_no_retrace)
        self._admission_window = int(admission_window)
        self._admit_q = queue.Queue(maxsize=max_queue)
        self._ready = collections.deque()
        self._ready_lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._failure = None
        self._slots = [None] * self.engine.slots
        self._free_slots = list(range(self.engine.slots))
        self._started = False
        self._t_start = None
        self._completed = 0
        self._tokens_out = 0
        self._ticks = 0
        self._hits = 0
        self._misses = 0
        self._prefix_tokens_served = 0
        self._accepted_draft_tokens = 0
        self._proposed_draft_tokens = 0
        # Requests admitted but not yet slot-resident. While > 0 and
        # slots are free, the tick loop briefly yields so inserts land
        # before the next tick — a tick advancing 2 of 8 slots costs
        # the same device work as a full one (the batch-synchronous
        # waste this engine exists to avoid).
        self._pending_inserts = 0
        # 5 ms naps the tick thread took for the sake of an admission
        # in flight (each is a `tick_pace` span).
        self._tick_paces = 0
        # The tick on the device whose tokens are not fetched yet (tick
        # thread only; other threads read it to wait for None), and
        # the ticks that were dispatched while the one before them was
        # still unfetched: beside `ticks`, the share of ticks for
        # which the device did not wait for the host.
        self._flight = None
        self._ticks_overlapped = 0
        # The tick record (`reqtrace.TickRecord`, one a tick): the next
        # tick's ordinal, never reset, and what the tick thread did
        # instead of dispatching since the last dispatch.
        self._tick_seq = 0
        self._naps_since = 0
        self._idle_since = 0.0
        # The same one thread over: the miss whose prefill is on the
        # device with its first token unread (admission thread only),
        # and the prefills dispatched while the one before them was
        # still unfetched.
        self._miss_flight = None
        self._prefills_overlapped = 0
        # Running sums over ticks and occupied slots: the keys a slot
        # attends to, and the keys the paged kernel's walk fetches for
        # that depth (whole groups of `_kv_group` pages, or whole pages
        # where the kernel copies its live pages itself; the kernel's
        # own arithmetic, at the unsharded width). live / walked is
        # the share of the walk that is not dead. A verify window
        # reaches `_kv_reach` keys past the plain tick's.
        m = self.engine.model
        heads, kv_heads, head_dim = attention_shape(m)
        self._kv_reach = self.engine.spec_k if self.engine.spec_on else 0
        if self.engine.block:
            # A block tick's rows reach to the end of the open block.
            self._kv_reach = self.engine.block - 1
        if getattr(m, "latent_row", 0):
            # One latent row a token, walked by `paged_mla_decode`.
            self._kv_group = mla_group_pages(page_size,
                                             self.engine.pages_per_slot)
        else:
            itemsize = (1 if kv_dtype == "int8"
                        else np.dtype(m.compute_dtype).itemsize)
            width = kv_heads * head_dim
            self._kv_group = (
                1 if copies_pages(page_size, width, itemsize)
                else group_pages(page_size, heads, width, itemsize,
                                 self._kv_reach + 1,
                                 self.engine.pages_per_slot))
        self._kv_live_tokens = 0
        self._kv_walked_tokens = 0
        # A ring-and-summaries model's counters, summed over ticks and
        # occupied slots (layers counted once): the rows a tick
        # attended and the summary rows among them (`kv_live_tokens`
        # and `kv_walked_tokens` count rows for such a model), and the
        # window ends met by ticks and by prefill chunks.
        self._eva_rows_read = 0
        self._eva_summary_rows_read = 0
        self._eva_windows_closed = {"ticks": 0, "prefills": 0}
        # A latent-attention model's counters (`DeepseekLM`): the
        # latent rows its ticks read, summed over ticks, occupied slots
        # and layers (each row is read once, for scores and values
        # alike), and the suffix tokens its prefills ran in each form
        # (`DeepseekLM.latent_absorbed` of the prefill's bucket).
        self._latent_layers = (m.num_layers
                               if getattr(m, "latent_row", 0) else 0)
        self._mla_rows_read = 0
        self._mla_prefill_tokens = {"expanded": 0, "absorbed": 0}
        # An expert model's counters, summed over ticks and expert
        # layers (`moe.MOE_STATS`): (token, choice) pairs of active
        # slots, those whose expert is held here, held experts that
        # got at least one pair (a layer and tick), the pairs a held
        # expert, and the (token, held expert) products of the ticks
        # whose expert layers ran batched over the held experts (0
        # where they ran grouped: `moe.batched_over_held`). They come
        # back with each tick's tokens.
        self._moe_pairs_routed = 0
        self._moe_pairs_held = 0
        self._moe_pairs_dense = 0
        self._moe_experts_touched = 0
        self._moe_expert_load = None
        # A recurrent model's counter, summed over ticks: slots
        # advanced x state layers (each is one state read and written).
        self._ssm_slot_steps = 0
        # A block-diffusion model's counters, summed over ticks
        # (`DecodeEngine._block_tick_impl`): denoising steps of slots,
        # tokens they committed, blocks closed by a clean pass, and the
        # query rows the ticks ran (B a live slot: masked, committed
        # and clean rows alike).
        self._diffusion = dict.fromkeys(DIFFUSION_COUNTERS, 0)
        from cloud_tpu.monitoring.telemetry import Histogram
        self._ttft_hist = Histogram("ttft")
        self._ttft_hit_hist = Histogram("ttft_hit")
        self._ttft_miss_hist = Histogram("ttft_miss")
        self._token_hist = Histogram("token_latency")
        self._queue_wait_hist = Histogram("queue_wait")
        self._reserve_wait_hist = Histogram("reserve_wait")
        # Host prefill-latency histogram: always on (like queue wait),
        # because the predicted-TTFT admission model samples its p50
        # even when telemetry export is off.
        self._prefill_hist = Histogram("prefill")
        # graftlens JSONL export of the request records; installed at
        # start() when CLOUD_TPU_REQTRACE asks for it, else stays None
        # (zero events, zero file). The records themselves are always
        # kept (reqtrace.recent()), under this server's ordinal.
        self._trace = None
        self._server = 0
        self._trace_suppress = False  # warmup traffic is not traced
        # -- graftstorm: SLO-aware admission + chaos state ------------
        if slo_ttft is None:
            env = os.environ.get("CLOUD_TPU_SERVE_SLO_TTFT", "").strip()
            slo_ttft = float(env) if env else None
        self._slo_ttft = slo_ttft
        if shed_policy is None:
            shed_policy = os.environ.get("CLOUD_TPU_SERVE_SHED", "shed")
        shed_policy = str(shed_policy).strip().lower()
        if shed_policy in _OFF_VALUES:
            shed_policy = "off"
        elif shed_policy != "defer":
            shed_policy = "shed"
        self._shed_policy = shed_policy
        self._defer_max = 2
        self._fault_counts = {}
        self._requeues = 0
        self._shed_counts = {}
        self._last_predicted_ttft = None
        self._chaos_lock = threading.Lock()
        self._prefill_fail_armed = 0
        # Squeezed page holds: (pages, release_tick, release_deadline).
        self._squeezed = []
        # -- chunked prefill: budgeted tick interleave ----------------
        if prefill_chunk is None:
            env = os.environ.get("CLOUD_TPU_SERVE_PREFILL_CHUNK",
                                 "").strip().lower()
            prefill_chunk = 0 if env in _OFF_VALUES else int(env)
        prefill_chunk = int(prefill_chunk)
        if layout is not None:
            # Prefilled a window at a time, whatever was asked: each
            # chunk attends `[summaries so far | its own window]` and
            # leaves summaries and a partial window, never a dense
            # cache.
            prefill_chunk = layout.window
        if prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0 (0 = off); "
                             "got {}.".format(prefill_chunk))
        if prefill_chunk:
            if prefill_chunk & (prefill_chunk - 1):
                raise ValueError(
                    "prefill_chunk must be a power of two (the tail "
                    "bucket family only telescopes then); got "
                    "{}.".format(prefill_chunk))
            if prefill_chunk > model.max_seq_len:
                raise ValueError(
                    "prefill_chunk ({}) exceeds max_seq_len "
                    "({}).".format(prefill_chunk, model.max_seq_len))
        self._prefill_chunk = prefill_chunk or None
        # In-flight ChunkedPrefill continuations, oldest first. Guarded
        # by _ready_lock: the admission thread appends, the tick thread
        # pops/re-queues — at most ONE chunk dispatched per tick.
        self._chunks = collections.deque()
        # How many _pending_inserts are chunk items only THIS loop can
        # advance — excluded from the skip-yield, else the tick loop
        # would sleep waiting on work it alone performs.
        self._chunk_accounted = 0
        self._chunks_dispatched = 0
        self._t_last_commit = None
        # Per-chunk dispatch latency (feeds the chunked admission
        # model) and commit-to-commit decode gap (the p99 the
        # interleave protects; tick COMPUTE time cannot see a loop
        # stalled behind a monolithic prefill).
        self._prefill_chunk_hist = Histogram("prefill_chunk")
        self._decode_gap_hist = Histogram("decode_gap")
        # -- graftflex: resize policy + per-geometry stats ------------
        # Hysteresis: grow fires eagerly (full rung + waiting work at a
        # tick boundary); shrink only after this many consecutive quiet
        # boundaries, so oscillating load never flaps the geometry.
        self._resize_quiet_ticks = int(resize_quiet_ticks)
        if self._resize_quiet_ticks < 1:
            raise ValueError("resize_quiet_ticks must be >= 1; got "
                             "{}.".format(resize_quiet_ticks))
        self._quiet_ticks = 0
        self._resize_counts = {"grow": 0, "shrink": 0}
        self._resize_events = []
        # (new_slots, reason) queued for the tick thread's next
        # boundary — the warmup ladder walk and tests use this hook;
        # the load-adaptive policy calls the same machinery.
        self._requested_resize = None
        # Per-geometry rollups: every per-tick stat stamps the rung it
        # ran under, so A/B comparisons never mix widths silently.
        self._geom_stats = {}
        # -- graftflex: learned admission predictor -------------------
        self._admission_model_path = admission_model
        self._admission_model = None
        self._admission_model_error = None
        self._admission_model_hits = 0

    def _geom(self, slots=None):
        """The per-geometry stats record for `slots` (default: the
        current rung), created on first touch."""
        slots = int(self.engine.slots if slots is None else slots)
        g = self._geom_stats.get(slots)
        if g is None:
            g = {"ticks": 0, "active_sum": 0}
            self._geom_stats[slots] = g
        return g

    # -- lifecycle ----------------------------------------------------

    def start(self):
        if self._started:
            return self
        self._started = True
        self._trace = reqtrace.maybe_enable()
        self._server = reqtrace.new_server()
        self._load_admission_model()
        self._t_start = time.monotonic()
        self._prefill_thread = threading.Thread(
            target=self._prefill_loop, name="graftserve-prefill",
            daemon=True)
        self._tick_thread = threading.Thread(
            target=self._tick_loop, name="graftserve-tick", daemon=True)
        self._prefill_thread.start()
        self._tick_thread.start()
        return self

    def close(self):
        """Stops both threads; pending/queued requests fail with a
        RuntimeError (or the loop's typed fault, if one fired)."""
        if not self._started:
            return
        self._stop.set()
        self.pool.close()
        self._wake.set()
        self._prefill_thread.join(timeout=30)
        self._tick_thread.join(timeout=30)
        self._release_squeezes(force=True)
        error = self._failure or RuntimeError("scheduler closed")
        self._fail_pending(error)
        if self._trace is not None:
            self._trace.flush()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.close()

    def _load_admission_model(self):
        """Loads the offline-fit admission predictor (ctor arg, else
        `CLOUD_TPU_SERVE_ADMISSION_MODEL`). Absent or unreadable models
        fall back to the live-histogram heuristic — the predictor is an
        accuracy upgrade, never an availability dependency."""
        path = self._admission_model_path
        if path is None:
            path = os.environ.get("CLOUD_TPU_SERVE_ADMISSION_MODEL",
                                  "").strip() or None
        if not path:
            return
        self._admission_model_path = path
        from cloud_tpu.serving import admission
        try:
            self._admission_model = admission.load_model(path)
        except (OSError, ValueError, KeyError) as exc:
            self._admission_model = None
            self._admission_model_error = "{}: {}".format(
                type(exc).__name__, exc)

    # -- graftflex: elastic tick geometry -----------------------------

    @staticmethod
    def resize_decision(ladder, slots, active, waiting, quiet_ticks,
                        quiet_threshold):
        """Pure hysteresis policy, one call per tick boundary. Returns
        `(target_rung_or_None, quiet_ticks')`.

        GROW (eager, the high watermark): the current rung is full AND
        work is waiting — a slammed replica widens instead of shedding,
        immediately. SHRINK (lazy): the active set fits the next rung
        down and nothing waits, for `quiet_threshold` CONSECUTIVE
        boundaries — any burst in between resets the counter, so
        oscillating load holds the wide geometry instead of flapping.
        """
        idx = ladder.index(slots)
        if waiting > 0 and active >= slots and idx + 1 < len(ladder):
            return ladder[idx + 1], 0
        if idx > 0 and waiting == 0 and active <= ladder[idx - 1]:
            quiet_ticks += 1
            if quiet_ticks >= quiet_threshold:
                return ladder[idx - 1], 0
            return None, quiet_ticks
        return None, 0

    def request_resize(self, new_slots, reason="manual", wait=True,
                       timeout=60.0):
        """Queues a resize to ladder rung `new_slots` for the tick
        thread's next boundary (resizes NEVER happen mid-tick). The
        warmup ladder walk and tests drive this; live traffic resizes
        through the same `_resize_to` via the hysteresis policy. With
        `wait`, blocks until the engine reports the new geometry."""
        new_slots = int(new_slots)
        if new_slots not in self.engine.ladder:
            raise ValueError(
                "resize target {} is not a ladder rung {}.".format(
                    new_slots, self.engine.ladder))
        self._requested_resize = (new_slots, reason)
        self._wake.set()
        if not wait:
            return
        deadline = time.monotonic() + timeout
        while self.engine.slots != new_slots:
            if self._failure is not None:
                raise self._failure
            if self._stop.is_set():
                raise RuntimeError("scheduler closed during resize")
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    "resize to {} slots not applied within {}s".format(
                        new_slots, timeout))
            time.sleep(0.002)

    def _maybe_resize(self):
        """Tick-boundary resize hook (tick thread only). Forced
        requests (warmup walk, tests) apply first — retried until the
        occupancy fits; then the hysteresis policy reads live
        occupancy + waiting-work depth. Policy resizes are disabled
        during warmup so the ladder walk owns the geometry."""
        forced = self._requested_resize
        if forced is not None:
            target, reason = forced
            if (target == self.engine.slots
                    or self._resize_to(target, reason)):
                self._requested_resize = None
            return
        if len(self.engine.ladder) <= 1 or self._trace_suppress:
            return
        active = sum(s is not None for s in self._slots)
        # _pending_inserts counts admitted-but-not-resident requests
        # (it decrements at insert), so queue depth + pending is the
        # work a wider tick could be serving right now.
        waiting = self._admit_q.qsize() + self._pending_inserts
        target, self._quiet_ticks = self.resize_decision(
            self.engine.ladder, self.engine.slots, active, waiting,
            self._quiet_ticks, self._resize_quiet_ticks)
        if target is not None:
            self._resize_to(
                target,
                "grow" if target > self.engine.slots else "shrink")

    def _resize_to(self, new_slots, reason):
        """Moves the geometry to `new_slots` one ADJACENT rung at a
        time. Only adjacent (old, new) pairs are pre-warmed by the
        ladder walk — the policy never jumps rungs, so warming the
        O(n^2) pair matrix for the sake of manual/forced jumps would
        buy nothing but compile time. Decomposing keeps every forced
        jump on warmed executables too. Returns False when the live
        set does not fit `new_slots` (the caller retries after
        drains); occupancy cannot change between steps because the
        whole walk runs inside one tick boundary on the tick thread.
        The geometry moves only with nothing in flight: the tick on
        the device is fetched and committed first, so the rows the
        gather migrates are the rows the host knows."""
        ladder = self.engine.ladder
        if self.engine.slots != new_slots:
            self._drain_tick()
        while self.engine.slots != new_slots:
            idx = ladder.index(self.engine.slots)
            step = (ladder[idx + 1] if new_slots > self.engine.slots
                    else ladder[idx - 1])
            if not self._resize_step(step, reason):
                return False
        return True

    def _resize_step(self, new_slots, reason):
        """Applies one resize at the current tick boundary: in-flight
        slots migrate (grow keeps indices; shrink compacts the live
        rows into the low indices), the engine gathers the geometry-
        bound rows under the same perm (bit-identity: rng schedules,
        eos latches, spec state ride along), and the pool is untouched
        — pages never move. Returns False when the live set does not
        fit `new_slots` (the caller retries after drains)."""
        old = self.engine.slots
        occupied = [i for i, s in enumerate(self._slots)
                    if s is not None]
        if len(occupied) > new_slots:
            return False
        if new_slots >= old:
            perm = list(range(old)) + [-1] * (new_slots - old)
        else:
            perm = occupied + [-1] * (new_slots - len(occupied))
        self.engine.resize(new_slots, perm)
        states = self._slots
        self._slots = [states[p] if p >= 0 else None for p in perm]
        self._free_slots = [i for i, s in enumerate(self._slots)
                            if s is None]
        direction = "grow" if new_slots > old else "shrink"
        self._resize_counts[direction] += 1
        self._quiet_ticks = 0
        # Decode gaps never straddle a geometry change — the next
        # commit starts a fresh interval stamped with the new rung.
        self._t_last_commit = None
        event = {"from": old, "to": new_slots, "reason": reason,
                 "tick": self._ticks}
        self._resize_events.append(event)
        trace = self._trace
        if trace is not None and not self._trace_suppress:
            trace.record(None, "resize", **event)
        reg = _registry()
        if reg is not None:
            from cloud_tpu.monitoring import telemetry
            reg.counter(telemetry.SERVE_RESIZES_TOTAL % direction).inc()
            reg.gauge(telemetry.SERVE_SLOT_COUNT).set(new_slots)
        return True

    # -- submission ---------------------------------------------------

    def submit(self, request, timeout=None):
        """Admits one request; returns a Future[ServeResult]. Blocks
        (then raises queue.Full) when the bounded admission queue is
        full — backpressure, by design, reaches the caller."""
        if self._failure is not None:
            raise self._failure
        self._validate(request)
        future = Future()
        # Warm-up requests are synthetic: they get a record (the
        # result's times come from it) but no rid, so they reach
        # neither recent() nor the JSONL nor the spans' ids.
        rid = None
        if not self._trace_suppress:
            rid = (self._trace.new_request() if self._trace is not None
                   else reqtrace.new_rid())
        rec = reqtrace.RequestRecord(
            rid, self._server, len(request.prompt),
            int(request.max_new_tokens), time.monotonic())
        self._trace_emit(rid, "submitted", prompt_len=rec.prompt_len,
                         max_new=rec.max_new_tokens)
        if request.max_new_tokens == 0:
            for boundary in ("dequeued", "admit", "reserved", "first",
                             "insert"):
                self._mark(rec, boundary, rec.t_submit, event=False)
            self._mark(rec, "done", rec.t_submit, ttft_s=0.0,
                       latency_s=0.0, tokens=0, prefix_len=0)
            future.set_result(ServeResult(
                tokens=np.asarray(request.prompt, np.int32),
                ttft_s=0.0, latency_s=0.0, trace=rec))
            return future
        if self._slotted(request):
            self._pending_inserts += 1
        try:
            self._admit_q.put((request, future, rec, {"defers": 0}),
                              timeout=timeout)
        except queue.Full:
            if self._slotted(request):
                self._pending_inserts -= 1
            self._trace_emit(rid, "fail", error="queue.Full: admission "
                             "queue full (load shed)")
            raise
        self._observe_queue()
        return future

    def _slotted(self, request):
        """Whether the request takes pages and a decode slot: every
        request of a block-diffusion model (its prefill samples
        nothing), else one of more than one new token (the prefill
        samples the first)."""
        if self.engine.block:
            return request.max_new_tokens > 0
        return request.max_new_tokens > 1

    def _spec_slack(self):
        """Positions a request may write past its last new token: a
        verify window's drafts, or the rest of a block-diffusion
        request's last block (pages are reserved in whole blocks)."""
        if self.engine.block:
            return self.engine.block - 1
        return self.engine.spec_k if self.engine.spec_on else 0

    def _validate(self, request):
        model = self.engine.model
        prompt_len = len(request.prompt)
        if prompt_len < 1:
            raise ValueError("prompt must be non-empty.")
        if request.max_new_tokens < 0:
            raise ValueError("max_new_tokens must be >= 0.")
        if prompt_len + request.max_new_tokens > model.max_seq_len:
            raise ValueError(
                "prompt ({}) + max_new_tokens ({}) exceeds max_seq_len "
                "{}.".format(prompt_len, request.max_new_tokens,
                             model.max_seq_len))
        if (self.engine.spec_on and request.max_new_tokens > 1
                and prompt_len + request.max_new_tokens - 1
                + self.engine.spec_k > model.max_seq_len):
            # The verify window transiently writes up to spec_k draft
            # positions past the last committed token.
            raise ValueError(
                "prompt ({}) + max_new_tokens ({}) - 1 + spec_k ({}) "
                "exceeds max_seq_len {} (speculative verify "
                "headroom).".format(prompt_len, request.max_new_tokens,
                                    self.engine.spec_k,
                                    model.max_seq_len))
        if request.max_new_tokens > self.engine.max_new_cap:
            raise ValueError(
                "max_new_tokens ({}) exceeds the engine's max_new_cap "
                "({}).".format(request.max_new_tokens,
                               self.engine.max_new_cap))
        block = self.engine.block
        if block:
            steps = request.denoising_steps or block
            end = -(-(prompt_len + request.max_new_tokens) // block) * block
            if (prompt_len < block or not 1 <= steps <= block
                    or end > model.max_seq_len or request.temperature
                    or request.eos_token is not None
                    or request.max_new_tokens < 1):
                raise ValueError(
                    "a block-diffusion request has a prompt of at least "
                    "one block ({} tokens), 1 <= denoising_steps <= {}, "
                    "at least one new token, its last block inside "
                    "max_seq_len {}, temperature 0 and no eos_token (the "
                    "block tick is greedy and stops at its length).".format(
                        block, block, model.max_seq_len))
        elif request.denoising_steps is not None:
            raise ValueError(
                "denoising_steps is a block-diffusion model's; {} "
                "decodes a token a tick.".format(type(model).__name__))
        if request.top_k is not None and not (
                1 <= request.top_k <= model.vocab_size):
            raise ValueError("top_k must be in [1, vocab_size={}]; got "
                             "{}.".format(model.vocab_size,
                                          request.top_k))
        if request.top_p is not None and not (
                0.0 < request.top_p <= 1.0):
            raise ValueError("top_p must be in (0, 1]; got {}.".format(
                request.top_p))
        if self._slotted(request):
            # Raises when no reservation could EVER satisfy it.
            need = self.pool.pages_needed(prompt_len,
                                          request.max_new_tokens,
                                          slack=self._spec_slack())
            if need > self.pool.capacity:
                raise ValueError(
                    "request needs {} pages; the pool has {} "
                    "allocatable.".format(need, self.pool.capacity))

    def _bucket(self, request):
        from cloud_tpu.models.decoding import bucket_length
        return bucket_length(len(request.prompt),
                             self.engine.max_seq_len)

    def _probe(self, request):
        if self.trie is None or request.max_new_tokens <= 1:
            return 0
        prompt = [int(t) for t in request.prompt]
        matched = self.trie.probe(prompt)
        if self.host_tier is not None:
            # A host-only match must route through the hit path too:
            # the promote executable touches the live cache, which only
            # the tick thread may write.
            matched = max(matched, self.host_tier.probe(prompt))
        return matched

    @staticmethod
    def _sampling(request):
        return {
            "temperature": float(request.temperature),
            "top_k": None if request.top_k is None
            else int(request.top_k),
            "top_p": None if request.top_p is None
            else float(request.top_p),
            "eos_token": None if request.eos_token is None
            else int(request.eos_token),
            "denoising_steps": request.denoising_steps,
        }

    # -- admission/prefill thread -------------------------------------

    def _prefill_loop(self):
        """The admission thread: a pipeline of depth one between the
        host and the device, the twin of `_tick_loop`'s. A whole-prompt
        miss is marked, decided, reserved and DISPATCHED, and only then
        is the prefill before it fetched, marked `first` and handed to
        the tick thread (`_admit_one`): the next request's host work
        runs while a prefill is on the device, which needs none of it.
        The prefill in flight is collected (`_collect_prefill`) before
        the thread waits for anything but the device: an empty queue,
        a reservation only ticks can satisfy, a hit handed to the tick
        thread, the end of the loop. So a first token never waits for
        an arrival. (With `prefill_chunk` set every miss is the tick
        thread's, a chunk at a time, and nothing is ever in flight
        here.)"""
        runtime.set_phase("serve_prefill")
        try:
            while not self._stop.is_set():
                self._admit_window(self._next_window())
        finally:
            self._collect_prefill()

    def _admit_window(self, window):
        # Longest-radix-match-first within the FCFS window, then
        # longest-prefill-first (stable sort: ties stay FCFS). Hits
        # admit cheapest and re-touch their prefix before LRU
        # pressure can evict it; among misses, big prefills hold
        # their slot longest, so starting them earliest minimizes
        # tail latency.
        window.sort(key=lambda item: (-self._probe(item[0]),
                                      -self._bucket(item[0])))
        admitted = 0
        for request, future, rec, meta in window:
            if self._stop.is_set():
                return
            # Its own turn begins: the window's requests are taken
            # one after another, so the time since `dequeued` is
            # the wait for the turns ahead of it.
            self._mark(rec, "admit")
            with spans.span("admit", rid=rec.rid):
                admitted += self._admit_turn(request, future, rec,
                                             meta, admitted)

    def _admit_turn(self, request, future, rec, meta, admitted):
        """One request's turn in its window: decision, then admission.
        Returns 1 when it was admitted, 0 when deferred or shed."""
        verdict, reason, predicted = self._admission_decision(
            request, rec.t_submit, admitted, meta)
        if verdict == "defer":
            meta["defers"] += 1
            try:
                self._admit_q.put_nowait((request, future, rec, meta))
                self._observe_queue()
                return 0
            except queue.Full:
                verdict, reason = "shed", "queue_full"
        if verdict == "shed":
            self._shed(request, future, rec.rid, reason, predicted)
            return 0
        try:
            self._admit_one(request, future, rec)
        except BaseException as exc:  # noqa: BLE001
            self._fail_admission(request, future, rec, exc)
        return 1

    def _fail_admission(self, request, future, rec, exc):
        if self._slotted(request):
            self._pending_inserts -= 1
        self._trace_fail(rec.rid, exc)
        future.set_exception(exc)

    def _next_window(self):
        window = []
        try:
            window.append(self._admit_q.get_nowait())
        except queue.Empty:
            # Nothing to run ahead with: the prefill in flight is
            # fetched before the thread waits for an arrival.
            self._collect_prefill()
            try:
                window.append(self._admit_q.get(timeout=0.05))
            except queue.Empty:
                return window
        while len(window) < self._admission_window:
            try:
                window.append(self._admit_q.get_nowait())
            except queue.Empty:
                break
        # Queue wait ends when the admission thread pops the window:
        # submit -> here is pure queueing, the first segment of the
        # request waterfall and the predicted-TTFT admission input.
        now = time.monotonic()
        reg = _registry()
        for _, _, rec, _ in window:
            wait = max(now - rec.t_submit, 0.0)
            self._queue_wait_hist.observe(wait)
            if reg is not None:
                from cloud_tpu.monitoring import telemetry
                reg.histogram(
                    telemetry.SERVE_QUEUE_WAIT_HISTOGRAM).observe(wait)
            self._mark(rec, "dequeued", now, wait_s=wait)
        self._observe_queue()
        return window

    def _reserve_blocking(self, request, rec):
        """The admission thread's reservation for a miss: rounds of
        `_reserve_with_pressure` until the pool gives the pages
        (blocking is the backpressure). Marks `reserved`; a request
        that needs no pages passes straight through. Returns the pages,
        or None when the scheduler closed meanwhile."""
        if not self._slotted(request):
            self._mark(rec, "reserved", event=False)
            return []
        need = self.pool.pages_needed(len(request.prompt),
                                      request.max_new_tokens,
                                      slack=self._spec_slack())
        t_reserve0 = time.monotonic()
        with spans.span("admit_reserve", rid=rec.rid):
            # What is free now, after LRU pressure where that is
            # short; beyond it only ticks free pages, and the prefill
            # in flight is not left waiting for them.
            pages = self._reserve_with_pressure(need, timeout=0)
            if pages is None and self.trie is not None:
                pages = self.pool.reserve(need, timeout=0)
            if pages is None:
                self._collect_prefill()
            while pages is None and not self._stop.is_set():
                pages = self._reserve_with_pressure(need, timeout=0.2)
        if pages is None:
            return None
        now = time.monotonic()
        self._observe_reserve_wait(now - t_reserve0)
        self._mark(rec, "reserved", now, pages=len(pages),
                   wait_s=now - t_reserve0)
        return pages

    def _fail_closed(self, future, rec):
        """Shutdown overtook an admission blocked on the pool."""
        self._pending_inserts -= 1
        error = RuntimeError("scheduler closed")
        self._trace_fail(rec.rid, error)
        future.set_exception(error)

    def _reserve_with_pressure(self, need, timeout):
        """One blocking-reserve round; a failed round applies LRU
        eviction pressure to the prefix cache (pages only the trie
        holds are reclaimable) before the caller retries."""
        pages = self.pool.reserve(need, timeout=timeout)
        if pages is None and self.trie is not None:
            self.trie.evict(need)
        return pages

    # -- SLO-aware admission (graftstorm) -----------------------------

    def _predict_ttft(self, request, t_submit, position, now=None):
        """TTFT estimate for a candidate at admission time: queue wait
        already accrued + serialization behind the `position` requests
        admitted ahead of it this window + its own prefill (live p50 of
        the always-on host histogram) + expected page-reservation wait
        (reserve-wait p95) when the pool cannot satisfy it right now.
        All inputs are live histograms, so the estimate tracks the
        current regime instead of a configured constant — unless a
        graftflex admission model is loaded, in which case the offline
        per-phase quantile regressions (fit on the reqtrace corpus's
        exact ground truth) replace the histogram percentiles, with
        the live histograms as fallback for any phase the model cannot
        cover."""
        now = time.monotonic() if now is None else now
        accrued = max(now - t_submit, 0.0)
        model = self._admission_model
        if model is not None:
            pool_short = False
            if self._slotted(request):
                need = self.pool.pages_needed(
                    len(request.prompt), request.max_new_tokens,
                    slack=self._spec_slack())
                pool_short = self.pool.available() < need
            predicted = model.predict_ttft(
                accrued=accrued, position=position,
                bucket=self._bucket(request),
                prompt_len=len(request.prompt),
                n_chunks=(self._n_chunks(len(request.prompt))
                          if self._prefill_chunk is not None else None),
                pool_short=pool_short)
            if predicted is not None:
                self._admission_model_hits += 1
                return predicted
        if self._prefill_chunk is not None:
            # Chunk granularity: the candidate costs n_chunks chunk
            # dispatches, interleaved one per tick, and each request
            # admitted ahead of it serializes at least one chunk before
            # the candidate's first. A whole-prefill p50 would be
            # bimodal junk here — short and 4k prompts now differ only
            # in chunk COUNT, not per-dispatch latency.
            chunk_p50 = self._prefill_chunk_hist.percentile(50)
            tick_p50 = self._token_hist.percentile(50)
            n = self._n_chunks(len(request.prompt))
            predicted = (accrued + position * chunk_p50 + n * chunk_p50
                         + max(n - 1, 0) * tick_p50)
        else:
            prefill_p50 = self._prefill_hist.percentile(50)
            predicted = accrued + (position + 1) * prefill_p50
        if self._slotted(request):
            need = self.pool.pages_needed(len(request.prompt),
                                          request.max_new_tokens,
                                          slack=self._spec_slack())
            if self.pool.available() < need:
                predicted += self._reserve_wait_hist.percentile(95)
        return predicted

    def _admission_decision(self, request, t_submit, position, meta,
                            now=None):
        """(verdict, reason, predicted_ttft) for one candidate:
        "admit" when the SLO policy is off or the prediction fits,
        "defer" (policy=defer, bounded retries, SLO not yet blown) to
        re-queue behind fresh arrivals, else "shed"."""
        if (self._slo_ttft is None or self._shed_policy == "off"
                or self._trace_suppress):
            return ("admit", None, None)
        now = time.monotonic() if now is None else now
        predicted = self._predict_ttft(request, t_submit, position,
                                       now=now)
        self._last_predicted_ttft = predicted
        reg = _registry()
        if reg is not None:
            from cloud_tpu.monitoring import telemetry
            reg.gauge(telemetry.SERVE_PREDICTED_TTFT).set(predicted)
        if predicted <= self._slo_ttft:
            return ("admit", None, predicted)
        accrued = now - t_submit
        if accrued > self._slo_ttft:
            return ("shed", "expired", predicted)
        if (self._shed_policy == "defer"
                and meta.get("defers", 0) < self._defer_max):
            return ("defer", "predicted", predicted)
        reason = "deferred" if meta.get("defers", 0) else "predicted"
        return ("shed", reason, predicted)

    def _shed(self, request, future, rid, reason, predicted):
        """Refuses one candidate by policy: typed ServeShed to the
        caller, `shed` terminal trace event, census counters."""
        if self._slotted(request):
            self._pending_inserts -= 1
        self._shed_counts[reason] = self._shed_counts.get(reason, 0) + 1
        reg = _registry()
        if reg is not None:
            from cloud_tpu.monitoring import telemetry
            reg.counter(telemetry.SERVE_SHED_TOTAL % reason).inc()
        self._trace_emit(rid, "shed", reason=reason,
                         predicted_ttft=predicted)
        future.set_exception(ServeShed(
            "admission shed ({}): predicted TTFT {:.3f}s > SLO {:.3f}s"
            .format(reason, -1.0 if predicted is None else predicted,
                    self._slo_ttft),
            reason=reason, predicted_ttft=predicted,
            slo_ttft=self._slo_ttft))

    def _admit_one(self, request, future, rec):
        sampling = self._sampling(request)
        matched = self._probe(request)
        self._trace_emit(rec.rid, "radix_probe", hit=matched > 0,
                         matched_tokens=int(matched))
        if request.max_new_tokens > 1 and matched > 0:
            # Prefix-cache hit: hand the whole admission to the tick
            # thread — the gather-prefill reads the engine's live pool
            # cache, which every tick donates, so no other thread may
            # read it concurrently. The miss in flight goes first, as
            # its turn came first.
            self._collect_prefill()
            rec.path = "hit"
            with self._ready_lock:
                self._ready.append(_HitTicket(request, future, rec))
            self._wake.set()
            return
        if self._prefill_chunk is not None:
            self._admit_miss_chunked(request, future, rec, sampling)
            return
        while True:
            # Re-entered on a transient PrefillFailed: the reservation
            # is released and retaken, so the retry re-queues behind
            # live backpressure instead of squatting on pages.
            pages = self._reserve_blocking(request, rec)
            if pages is None:  # shutdown while blocked on the pool
                self._fail_closed(future, rec)
                return
            try:
                self._chaos_prefill()
                flight = self.engine.prefill_dispatch(
                    np.asarray(request.prompt, np.int32),
                    request.max_new_tokens,
                    host_prng_key(request.rng_seed), sampling,
                    rid=rec.rid,
                    overlapped=self._miss_flight is not None)
            except PrefillFailed as exc:
                if pages:
                    self.pool.free(pages)
                self._note_fault(exc, rid=rec.rid, slot=None)
                self._note_requeue(rec.rid, tokens_done=0)
                continue
            except BaseException:
                if pages:
                    self.pool.free(pages)
                raise
            break
        # This prefill is on the device; now the one before it.
        behind, self._miss_flight = self._miss_flight, _MissFlight(
            request, flight, pages, future, rec)
        if behind is not None:
            self._prefills_overlapped += 1
            self._finish_miss(behind)

    def _collect_prefill(self):
        """Fetches the prefill in flight, if there is one (admission
        thread only), and hands its request on."""
        behind, self._miss_flight = self._miss_flight, None
        if behind is not None:
            self._finish_miss(behind)

    def _finish_miss(self, item):
        """The fetch of a dispatched miss's first token (the TTFT
        point), then the hand-over to the tick thread. A failure that
        surfaces here is the failure of `item`'s request, not of the
        one whose turn it is."""
        request, future, rec = item.request, item.future, item.rec
        try:
            result = self.engine.prefill_finish(item.flight, rid=rec.rid)
        except BaseException as exc:  # noqa: BLE001
            if item.pages:
                self.pool.free(item.pages)
            self._fail_admission(request, future, rec, exc)
            return
        if self.engine.block:
            # No token yet: the first is out when the ticks have
            # committed it (`_distribute_block`).
            rec.bucket = int(result.bucket)
            self._observe_prefill(time.monotonic() - rec.t_reserved)
            with self._ready_lock:
                self._ready.append(_ReadyItem(request, result, item.pages,
                                              future, rec))
            self._wake.set()
            return
        self._first_token(rec, result, hit=False)
        if request.max_new_tokens == 1:
            # Completes at prefill: no slot, no pages, no tick.
            self.engine.release_prefill(result)
            self._mark(rec, "insert", rec.t_first, event=False)
            self._complete(request, future, rec, [result.first_token],
                           prefix_len=0)
            return
        with self._ready_lock:
            self._ready.append(_ReadyItem(request, result, item.pages,
                                          future, rec))
        self._wake.set()

    def _first_token(self, rec, result, hit, prefix_len=0, t0=None,
                     **fields):
        """The TTFT point of a request admitted for the first time: its
        prefill returned with the first token on the host. `t0` is
        where the prefill began (default: the reservation)."""
        now = time.monotonic()
        dur = now - (rec.t_reserved if t0 is None else t0)
        rec.bucket = int(result.bucket)
        if self._latent_layers:
            form = ("absorbed"
                    if self.engine.model.latent_absorbed(rec.bucket)
                    else "expanded")
            self._mla_prefill_tokens[form] += (result.prompt_len
                                               - int(prefix_len))
        self._mark(rec, "first", now, bucket=rec.bucket,
                   prefix_len=int(prefix_len), dur_s=dur, **fields)
        self._record_ttft(rec.ttft_s, hit=hit)
        self._observe_prefill(dur)

    def _record_ttft(self, ttft, hit):
        self._ttft_hist.observe(ttft)
        (self._ttft_hit_hist if hit else self._ttft_miss_hist).observe(
            ttft)
        if hit:
            self._hits += 1
        else:
            self._misses += 1
        reg = _registry()
        if reg is not None:
            from cloud_tpu.monitoring import telemetry
            reg.histogram(telemetry.SERVE_TTFT_HISTOGRAM).observe(ttft)
            name = (telemetry.SERVE_TTFT_HIT_HISTOGRAM if hit
                    else telemetry.SERVE_TTFT_MISS_HISTOGRAM)
            reg.histogram(name).observe(ttft)
            total = self._hits + self._misses
            reg.gauge(telemetry.SERVE_PREFIX_HIT_RATE).set(
                self._hits / total if total else 0.0)

    # -- chunked prefill: tick-interleaved continuations --------------

    def _n_chunks(self, n_suffix):
        """Chunk count for an `n_suffix`-token prefill at the
        configured chunk size (1 when chunking is off)."""
        if self._prefill_chunk is None or n_suffix <= 0:
            return 1
        return (n_suffix - 1) // self._prefill_chunk + 1

    def _admit_miss_chunked(self, request, future, rec, sampling):
        """Miss admission with chunking on: reserve pages here (same
        blocking backpressure as the whole-prefill path), then hand the
        request to the tick thread as a ChunkedPrefill continuation —
        the admission thread never touches the device, so a long
        prompt cannot monopolize the chip between ticks. Chaos
        `prefill_fail` moves to chunk dispatch."""
        rec.path = "chunked"
        pages = self._reserve_blocking(request, rec)
        if pages is None:  # shutdown while blocked on the pool
            self._fail_closed(future, rec)
            return
        chunked = self.engine.prefill_chunks(
            np.asarray(request.prompt, np.int32),
            request.max_new_tokens, host_prng_key(request.rng_seed),
            sampling, self._prefill_chunk, rid=rec.rid)
        self._enqueue_chunk_item(_ChunkItem(
            "miss", request, chunked, future, rec, pages=pages))

    def _enqueue_chunk_item(self, item):
        self.pool.note_prefill_hold(len(item.all_pages()))
        with self._ready_lock:
            if item.counts_pending:
                self._chunk_accounted += 1
            self._chunks.append(item)
        self._wake.set()

    def _release_chunk_hold(self, item):
        if not item.hold_released:
            item.hold_released = True
            self.pool.note_prefill_release(len(item.all_pages()))

    def _fail_chunk_item(self, item, error):
        """Drains one chunk item on failure/shutdown: caches park,
        pages free (exactly once), the future fails, and the pending-
        insert accounting unwinds."""
        try:
            item.chunked.abandon()
        except Exception:  # noqa: BLE001 — drain is best-effort
            pass
        if item.result is not None:
            try:
                self.engine.release_prefill(item.result)
            except Exception:  # noqa: BLE001
                pass
            item.result = None
        self._release_chunk_hold(item)
        pages = item.all_pages()
        if pages:
            self.pool.free(pages)
        with self._ready_lock:
            if item.counts_pending:
                self._chunk_accounted -= 1
        if item.counts_pending:
            self._pending_inserts -= 1
        if not item.future.done():
            self._trace_fail(item.rec.rid, error)
            item.future.set_exception(error)

    def _step_chunks(self):
        """Budgeted interleave: dispatch at most ONE prefill chunk per
        tick-loop iteration, oldest continuation first. Chaos
        `prefill_fail` is consumed at the chunk boundary — the faulted
        dispatch counts a fault + requeue but the continuation keeps
        its already-computed chunks (retained progress; the retry costs
        one tick, not a re-prefill). The tail chunk records TTFT and
        moves the item to the ready deque for slot insertion (or
        completes outright when max_new == 1). Returns True when a
        chunk was dispatched so the idle branch can drain continuations
        back-to-back instead of sleeping."""
        with self._ready_lock:
            if not self._chunks:
                return False
            item = self._chunks.popleft()
        if self._stop.is_set():
            self._fail_chunk_item(
                item, self._failure or RuntimeError("scheduler closed"))
            return False
        with self._chaos_lock:
            armed = self._prefill_fail_armed > 0
            if armed:
                self._prefill_fail_armed -= 1
        if armed:
            self._note_fault(
                PrefillFailed("graftchaos: injected prefill_fail"),
                rid=item.rec.rid, slot=None)
            self._note_requeue(item.rec.rid, tokens_done=0)
            with self._ready_lock:
                self._chunks.appendleft(item)
            return True
        if item.t_prefill0 is None:
            item.t_prefill0 = time.monotonic()
        i = item.chunked.chunks_done
        t0 = time.monotonic()
        try:
            result = item.chunked.step()
        except BaseException as exc:  # noqa: BLE001
            self._fail_chunk_item(item, exc)
            raise
        dur = time.monotonic() - t0
        self._chunks_dispatched += 1
        if self.engine.layout is not None:
            # A chunk that fills its window ends it (the tail's may
            # not).
            self._eva_windows_closed["prefills"] += (
                item.chunked.chunk_tokens(i) == self.engine.layout.window)
        self._observe_prefill_chunk(dur)
        self._trace_emit(item.rec.rid, "prefill_chunk", i=int(i),
                         n=int(item.chunked.n_chunks),
                         tokens=int(item.chunked.chunk_tokens(i)),
                         dur_s=dur)
        if result is None:
            with self._ready_lock:
                self._chunks.appendleft(item)
            return True
        item.result = result
        rec = item.rec
        if item.kind == "requeue":
            self._later_prefill(rec, result, item.t_prefill0,
                                chunks=int(item.chunked.n_chunks))
        else:
            self._first_token(rec, result, hit=item.kind == "hit",
                              prefix_len=item.prefix_len,
                              t0=item.t_prefill0,
                              chunks=int(item.chunked.n_chunks))
        if item.kind == "hit":
            self._prefix_tokens_served += item.prefix_len
        if item.request.max_new_tokens == 1:
            # Completes at prefill: no slot, no pages, no tick.
            self.engine.release_prefill(result)
            item.result = None
            self._release_chunk_hold(item)
            if item.kind != "requeue":
                self._mark(rec, "insert", rec.t_first, event=False)
            self._complete(item.request, item.future, rec,
                           [result.first_token],
                           prefix_len=item.result_prefix_len)
            return True
        with self._ready_lock:
            self._ready.append(item)
        return True

    def _insert_chunk_item(self, item):
        """Slot insertion for a completed chunked prefill (the tail
        chunk already ran): the kind-specific page-vector split and
        bookkeeping of the three unchunked insert paths, unified."""
        if self._stop.is_set():
            self._fail_chunk_item(
                item, self._failure or RuntimeError("scheduler closed"))
            return
        held = item.pages_held()
        slot = self._free_slots.pop()
        state = _Slot(item.request, held, item.future, item.rec,
                      prefix_len=item.prefix_len)
        state.result_prefix_len = item.result_prefix_len
        state.emitted.append(item.result.first_token)
        state.step_keys = item.result.step_keys
        self._slots[slot] = state
        page_vec = self.pool.page_vec(held)
        if item.kind == "hit":
            # Shared pages are immutable: route their scatter entries
            # to scratch, reconstruct divergence into fresh pages.
            scatter_vec = self.pool.page_vec(
                [0] * len(item.shared) + list(item.fresh))
        else:
            scatter_vec = page_vec
        self.engine.insert(slot, item.result, page_vec, scatter_vec,
                           self._sampling(item.request))
        item.result = None
        self._inserted(item.rec, slot, first=item.kind != "requeue")
        if item.kind == "hit" and item.partial_len:
            # The divergent page was reconstructed into a fresh page by
            # the insert scatter — device-side copy-on-write done.
            self.pool.note_cow()
            self.pool.free([item.partial_page])
        self._release_chunk_hold(item)
        if item.kind != "requeue":
            self._register(item.request, held)
        if item.counts_pending:
            self._pending_inserts -= 1
            with self._ready_lock:
                self._chunk_accounted -= 1
        self._observe_gauges()

    def _observe_prefill_chunk(self, dur):
        self._prefill_chunk_hist.observe(dur)
        reg = _registry()
        if reg is not None:
            from cloud_tpu.monitoring import telemetry
            reg.histogram(
                telemetry.SERVE_PREFILL_CHUNK_HISTOGRAM).observe(dur)
            reg.counter(telemetry.SERVE_PREFILL_CHUNKS_TOTAL).inc()

    def _observe_decode_gap(self, gap, n_active):
        if n_active <= 0:
            return
        self._decode_gap_hist.observe(gap, count=n_active)
        reg = _registry()
        if reg is not None:
            from cloud_tpu.monitoring import telemetry
            reg.histogram(
                telemetry.SERVE_DECODE_GAP_HISTOGRAM).observe(
                    gap, count=n_active)

    # -- graftstorm: chaos + slot fault recovery ----------------------

    def _chaos_prefill(self):
        """Every prefill dispatch passes here first, so an armed chaos
        `prefill_fail` hits whichever thread prefills next (admission
        thread for misses, tick thread for hits/requeues), before
        anything of it is on the device."""
        with self._chaos_lock:
            armed = self._prefill_fail_armed > 0
            if armed:
                self._prefill_fail_armed -= 1
        if armed:
            raise PrefillFailed("graftchaos: injected prefill_fail")

    def _engine_prefill(self, *args, **kwargs):
        """The tick thread's prefill: dispatch and fetch back to back
        (it reads the pool cache the next tick donates)."""
        self._chaos_prefill()
        return self.engine.prefill(*args, **kwargs)

    def _chaos_pre_tick(self):
        """Tick-loop chaos hook: returns squeezed pages whose hold
        expired, then consumes due serving injections. Warm-up traffic
        is exempt (the tick counter resets after warmup, so configured
        ticks index post-warmup traffic only)."""
        self._release_squeezes()
        if self._trace_suppress:
            return
        from cloud_tpu.analysis import chaos
        plan = chaos.active_plan()
        if plan is None:
            return
        # Events are indexed by the ticks that have RUN: the one in
        # flight counts, and is committed before a fault reads a
        # slot's `emitted`.
        due = plan.pre_tick(self._ticks + (self._flight is not None))
        if due:
            self._drain_tick()
        for event in due:
            self._apply_chaos(event)

    def _apply_chaos(self, event):
        if event.kind == "prefill_fail":
            with self._chaos_lock:
                self._prefill_fail_armed += 1
            return
        if event.kind == "pool_squeeze":
            n = 1 if event.arg is None else int(event.arg)
            pages = self.pool.squeeze(n)
            self._note_fault(PoolSqueezed(
                "graftchaos: squeezed {} page(s) at tick {}".format(
                    len(pages), self._ticks)))
            if pages:
                self._squeezed.append(
                    (pages, self._ticks + SQUEEZE_HOLD_TICKS,
                     time.monotonic() + SQUEEZE_HOLD_S))
            return
        victim = None
        if event.kind == "slot_evict" and event.arg is not None:
            idx = int(event.arg)
            if 0 <= idx < len(self._slots) and \
                    self._slots[idx] is not None:
                victim = idx
        else:
            for idx, state in enumerate(self._slots):
                if state is not None:
                    victim = idx
                    break
        if victim is None:
            # Nothing in flight to fault — the one-shot still fired
            # (logged by the plan), the injection is a no-op.
            return
        cls = SlotHang if event.kind == "slot_hang" else SlotEvicted
        self._fault_slot(victim, self._slots[victim], cls(
            "graftchaos: {} slot {} at tick {}".format(
                event.kind, victim, self._ticks)))

    def _release_squeezes(self, force=False):
        if not self._squeezed:
            return
        now = time.monotonic()
        keep = []
        for pages, release_tick, deadline in self._squeezed:
            if force or self._ticks >= release_tick or now >= deadline:
                self.pool.free(pages)
            else:
                keep.append((pages, release_tick, deadline))
        self._squeezed = keep

    def _fault_slot(self, slot, state, fault):
        """Slot-level fault recovery: drain the victim through the
        SAME fixed-shape evict scatter finished slots use (the
        persistent tick never stops), return its pages exactly once
        (prefix-trie references survive untouched), and requeue its
        request with retained progress."""
        self._note_fault(fault, rid=state.rec.rid, slot=slot)
        evict_mask = np.zeros((self.engine.slots,), bool)
        evict_mask[slot] = True
        self.engine.evict(evict_mask)
        self._slots[slot] = None
        self._free_slots.append(slot)
        self.pool.free(state.pages)
        if self.engine.block:
            # No continuation re-enters a block mid-way: the request
            # fails with the fault.
            self._trace_fail(state.rec.rid, fault)
            state.future.set_exception(fault)
        else:
            self._requeue_slot(state)
        self._observe_gauges()

    def _requeue_slot(self, state):
        """Builds the typed continuation: original prompt + emitted
        tokens become the new prompt, max_new shrinks by the same
        count, and the ORIGINAL schedule rows n-1 / n.. ride along as
        the engine's key_override — so the continuation's first token
        samples with exactly the key the uninterrupted run would have
        consumed (bit-identity). Front of the ready deque: a faulted
        request has already waited once."""
        request = state.request
        emitted = [int(t) for t in state.emitted]
        n = len(emitted)
        eos = request.eos_token
        if eos is not None and eos in emitted:
            # eos already latched: the remaining decode is pure eos
            # replay, which _complete's fill reproduces on host.
            done = emitted[:emitted.index(eos) + 1]
            self._complete(request, state.future, state.rec, done,
                           prefix_len=state.result_prefix_len)
            return
        if n >= request.max_new_tokens:
            self._complete(request, state.future, state.rec, emitted,
                           prefix_len=state.result_prefix_len)
            return
        self._note_requeue(state.rec.rid, tokens_done=n)
        cont = dataclasses.replace(
            request,
            prompt=[int(t) for t in request.prompt] + emitted,
            max_new_tokens=request.max_new_tokens - n)
        item = _RequeueItem(
            cont, np.array(state.step_keys[n - 1], np.uint32),
            np.array(state.step_keys[n:], np.uint32),
            state.future, state.rec, state.result_prefix_len)
        with self._ready_lock:
            self._ready.appendleft(item)
        self._wake.set()

    def _note_fault(self, fault, rid=None, slot=None):
        kind = fault_kind(fault)
        self._fault_counts[kind] = self._fault_counts.get(kind, 0) + 1
        reg = _registry()
        if reg is not None:
            from cloud_tpu.monitoring import telemetry
            reg.counter(telemetry.SERVE_FAULTS_TOTAL % kind).inc()
        if rid is not None:
            self._trace_emit(rid, "slot_fault", kind=kind, slot=slot)

    def _note_requeue(self, rid, tokens_done):
        self._requeues += 1
        reg = _registry()
        if reg is not None:
            from cloud_tpu.monitoring import telemetry
            reg.counter(telemetry.SERVE_REQUEUES_TOTAL).inc()
        self._trace_emit(rid, "requeue", tokens_done=int(tokens_done))

    def _observe_prefill(self, dur):
        self._prefill_hist.observe(dur)
        reg = _registry()
        if reg is not None:
            from cloud_tpu.monitoring import telemetry
            reg.histogram(
                telemetry.SERVE_PREFILL_HISTOGRAM).observe(dur)

    def _later_prefill(self, rec, result, t0, **fields):
        """A requeued request's re-prefill returned: its token is a
        LATER token of the request (the TTFT point stays where the
        first admission put it), so it joins `token_times`."""
        now = time.monotonic()
        rec.path = "requeue"
        rec.token_times.append(now)
        self._observe_prefill(now - t0)
        self._trace_emit(rec.rid, "prefill", bucket=int(result.bucket),
                         prefix_len=0, dur_s=now - t0, **fields)

    def _inserted(self, rec, slot, first=True):
        """The request was written into decode slot `slot`; only its
        first insertion is the record's `t_insert`."""
        if first:
            self._mark(rec, "insert", slot=slot)
        else:
            self._trace_emit(rec.rid, "slot_insert", slot=slot)

    # -- tick thread --------------------------------------------------

    def _tick_loop(self):
        """The tick thread: a pipeline of depth one between the host
        and the device. In steady state an iteration admits
        (`tick_admit`), dispatches tick n+1, and only then fetches and
        commits tick n — so commit, admissions and the next dispatch
        run while a tick is on the device, which needs none of them:
        `cur_tok`, the step counters, the eos latch and the key
        schedule live in `ctl`, and a tick retires the slots it
        finishes (`DecodeEngine.tick`). Inserts and evictions are
        dispatched behind the tick in flight and ordered with it by
        the donated `cache` / `ctl`. Any iteration that does not
        dispatch (no slot occupied, a pacing nap) and anything that
        reasons about exact slot state (resize, chaos, close) first
        fetches and commits the tick in flight (`_drain_tick`)."""
        runtime.set_phase("serve_tick")
        from cloud_tpu.monitoring import watch
        # Adopt an installed graftwatch: the tick thread becomes the
        # beat source AND the async-raise target, so a stuck tick is
        # the thread the stall fault interrupts (typed
        # BackendUnavailable + blackbox), not a silent hang.
        watch.rewatch()
        skips = 0
        try:
            while not self._stop.is_set():
                if watch.enabled():
                    watch.heartbeat()
                    watch.check()
                self._chaos_pre_tick()
                with spans.span("tick_admit"):
                    # Tick boundary: the only point the geometry may
                    # move — never mid-tick, never from another thread.
                    self._maybe_resize()
                    stepped = self._step_chunks()
                    self._insert_ready()
                if not any(s is not None for s in self._slots):
                    self._drain_tick()
                    self._t_last_commit = None
                    if stepped:
                        # A continuation advanced and nothing decodes:
                        # drain chunks back-to-back, no idle sleep.
                        continue
                    t_idle = time.monotonic()
                    with spans.span("tick_idle"):
                        woke = self._wake.wait(timeout=0.05)
                    self._idle_since += time.monotonic() - t_idle
                    if woke:
                        self._wake.clear()
                    continue
                if (self._free_slots
                        # A stale read only mis-times one 5 ms pacing
                        # nap; correctness never depends on it.
                        and self._pending_inserts > self._chunk_accounted  # graftlint: unlocked-ok
                        and skips < 40):
                    # Admissions are in flight on OTHER threads and
                    # slots are open: yield briefly so the insert lands
                    # before the next tick. The skip cap bounds the
                    # stall when an admission is itself blocked on
                    # pages only ticks can free. In-flight chunked
                    # prefills are excluded — only this loop advances
                    # them, so waiting on them would stall every
                    # resident slot for nothing. A finished tick's
                    # tokens are never held behind a nap.
                    self._drain_tick()
                    skips += 1
                    self._tick_paces += 1
                    self._naps_since += 1
                    with spans.span("tick_pace"):
                        self._wake.wait(timeout=0.005)
                    self._wake.clear()
                    continue
                skips = 0
                with spans.span("serve_tick", tick=self._tick_seq):
                    behind, self._flight = (self._flight,
                                            self._dispatch_tick())
                    fetched = (None if behind is None
                               else self._fetch_tick(behind))
                if behind is not None:
                    self._ticks_overlapped += 1
                    self._commit_tick(behind, *fetched)
            self._drain_tick()
        except BaseException as exc:  # noqa: BLE001
            self._failure = exc
            self._stop.set()
            self.pool.close()
            self._fail_pending(exc)

    def _dispatch_tick(self):
        """Puts one tick on the device and returns what its commit
        will need. The copy of its tokens (and of an expert model's
        counters) to the host is started here, so that the transfer is
        queued ahead of whatever program is dispatched next. The
        tick's record starts here: it takes the engine's dispatch log
        (what went to the device since the tick before, this tick's
        own note not yet among it) and the naps and idle time since."""
        dispatched = self.engine.take_dispatched()
        record = reqtrace.TickRecord(
            self._tick_seq, self._server, self.engine.slots,
            time.monotonic(), overlapped=self._flight is not None,
            dispatched=dispatched, naps=self._naps_since,
            idle_s=self._idle_since)
        self._tick_seq += 1
        self._naps_since, self._idle_since = 0, 0.0
        with spans.span("tick_dispatch", tick=record.seq):
            out = self.engine.tick()
            # The next tick overwrites the attribute.
            counters = self.engine.tick_counters
            for leaf in jax.tree_util.tree_leaves((out, counters)):
                leaf.copy_to_host_async()
        return _Flight(out, counters, record, list(self._slots))

    def _fetch_tick(self, flight):
        """Blocks until `flight`'s tokens are on the host: the serving
        loop's one counted read-back a tick."""
        flight.record.t_fetch0 = time.monotonic()
        with spans.span("tick_fetch", tick=flight.record.seq):
            fetched, counters = runtime.device_fetch(
                (flight.out, flight.counters))
        return fetched, counters, time.monotonic()

    def _commit_tick(self, flight, fetched, counters, t_commit):
        """Hands a fetched tick's tokens to its requests. `elapsed` is
        what the token cost a slot: commit to commit while the
        pipeline is full, dispatch to commit for the first tick after
        a drain."""
        self._ticks += 1
        # The slots this tick advanced: those it was dispatched with,
        # less the ones the tick before it had finished (row s of a
        # tick dispatched blind belongs neither to the request that
        # left slot s nor to the one inserted there since).
        live = [(slot, state) for slot, state in enumerate(flight.slots)
                if state is not None and self._slots[slot] is state]
        record = flight.record
        t_from = record.t_dispatch
        if self._t_last_commit is not None:
            t_from = max(t_from, self._t_last_commit)
            self._observe_decode_gap(t_commit - self._t_last_commit,
                                     len(live))
        self._t_last_commit = t_commit
        kv_live, kv_walked = self._kv_live_tokens, self._kv_walked_tokens
        mla_rows = self._mla_rows_read
        with spans.span("tick_commit", tick=record.seq):
            # The counters first: a request this tick completes sees
            # them in `stats()`.
            if counters:
                self._count_tick(counters)
            self._distribute(live, fetched, t_commit - t_from, t_commit)
        record.t_fetched = t_commit
        record.live = len(live)
        record.kv_live = self._kv_live_tokens - kv_live
        record.kv_walked = self._kv_walked_tokens - kv_walked
        record.mla_rows = self._mla_rows_read - mla_rows
        if self.engine.block:
            record.committed = int(counters["diffusion_tokens_committed"])
            record.rows = int(counters["diffusion_rows_computed"])
        record.t_committed = time.monotonic()
        reqtrace.publish_tick(record)
        if self.strict_no_retrace:
            self.engine.check_no_retrace()

    def _drain_tick(self):
        """Fetches and commits the tick in flight, if there is one
        (tick thread only). After it the host's view of every slot is
        exact: nothing is on the device that it has not read."""
        flight = self._flight
        if flight is not None:
            self._commit_tick(flight, *self._fetch_tick(flight))
            self._flight = None

    def _settle(self, timeout=60.0):
        """Another thread's wait for the tick thread to have drained
        its pipeline: with no request in flight the last tick
        dispatched (blind, behind the one that finished the last slot)
        is fetched within an iteration."""
        deadline = time.monotonic() + timeout
        while (self._flight is not None and time.monotonic() < deadline
               and self._tick_thread.is_alive()):
            time.sleep(0.001)

    def _insert_ready(self):
        # Hit tickets blocked on page reservation are stashed and
        # restored at the front afterwards: a page-starved hit must not
        # head-of-line-block ready misses (whose pages are already
        # reserved — inserting them is what eventually frees pages).
        blocked = []
        try:
            while self._free_slots:
                with self._ready_lock:
                    if not self._ready:
                        return
                    item = self._ready.popleft()
                if isinstance(item, _HitTicket):
                    with spans.span("admit", rid=item.rec.rid):
                        admitted = self._admit_hit(item)
                    if not admitted:
                        blocked.append(item)
                    continue
                if isinstance(item, _RequeueItem):
                    if not self._insert_requeue(item):
                        blocked.append(item)
                    continue
                if isinstance(item, _ChunkItem):
                    self._insert_chunk_item(item)
                    continue
                self._insert_miss_item(item)
        finally:
            if blocked:
                with self._ready_lock:
                    self._ready.extendleft(reversed(blocked))

    def _insert_miss_item(self, item):
        slot = self._free_slots.pop()
        state = _Slot(item.request, item.pages, item.future, item.rec,
                      prefix_len=0)
        if item.result.first_token is not None:
            state.emitted.append(item.result.first_token)
        state.step_keys = item.result.step_keys
        self._slots[slot] = state
        vec = self.pool.page_vec(item.pages)
        self.engine.insert(slot, item.result, vec, vec,
                           self._sampling(item.request))
        if not self.engine.block:
            # A block-diffusion request is marked inserted with its
            # first token (`_release`): its prefill phase holds the
            # ticks that committed it.
            self._inserted(item.rec, slot)
        self._register(item.request, item.pages)
        self._pending_inserts -= 1
        self._observe_gauges()

    def _insert_requeue(self, item):
        """Tick-thread re-admission of a faulted request's continuation:
        reserve (non-blocking — a starved requeue stays queued), cold
        re-prefill under the key_override schedule, insert. No new TTFT
        observation — the request's TTFT happened at its ORIGINAL
        prefill and stays in its record. Returns False when pages are
        not available yet."""
        request, rec = item.request, item.rec
        if self._stop.is_set():
            if not item.future.done():
                error = (self._failure
                         or RuntimeError("scheduler closed"))
                self._trace_fail(rec.rid, error)
                item.future.set_exception(error)
            return True
        key_override = (item.key, item.rest)
        pages = []
        if request.max_new_tokens > 1:
            need = self.pool.pages_needed(len(request.prompt),
                                          request.max_new_tokens,
                                          slack=self._spec_slack())
            pages = self._reserve_with_pressure(need, timeout=0.01)
            if pages is None:
                return False
            self._trace_emit(rec.rid, "pages_reserved",
                             pages=len(pages), wait_s=0.0)
        if self._prefill_chunk is not None:
            chunked = self.engine.prefill_chunks(
                np.asarray(request.prompt, np.int32),
                request.max_new_tokens,
                host_prng_key(request.rng_seed),
                self._sampling(request), self._prefill_chunk,
                key_override=key_override, rid=rec.rid)
            self._enqueue_chunk_item(_ChunkItem(
                "requeue", request, chunked, item.future, rec,
                pages=pages,
                result_prefix_len=item.result_prefix_len))
            return True
        t_prefill0 = time.monotonic()
        try:
            result = self._engine_prefill(
                np.asarray(request.prompt, np.int32),
                request.max_new_tokens,
                host_prng_key(request.rng_seed),
                self._sampling(request), key_override=key_override,
                rid=rec.rid)
        except PrefillFailed as exc:
            if pages:
                self.pool.free(pages)
            self._note_fault(exc, rid=rec.rid, slot=None)
            return False
        except BaseException:
            if pages:
                self.pool.free(pages)
            raise
        if request.max_new_tokens == 1:
            # Single remaining token: completes at prefill, no slot.
            rec.path = "requeue"
            rec.token_times.append(time.monotonic())
            self.engine.release_prefill(result)
            self._complete(request, item.future, rec,
                           [result.first_token],
                           prefix_len=item.result_prefix_len)
            return True
        self._later_prefill(rec, result, t_prefill0)
        slot = self._free_slots.pop()
        state = _Slot(request, pages, item.future, rec, prefix_len=0)
        state.result_prefix_len = item.result_prefix_len
        state.emitted.append(result.first_token)
        state.step_keys = result.step_keys
        self._slots[slot] = state
        vec = self.pool.page_vec(pages)
        self.engine.insert(slot, result, vec, vec,
                           self._sampling(request))
        self._inserted(rec, slot, first=False)
        self._observe_gauges()
        return True

    def _admit_hit(self, ticket):
        """Tick-thread admission of a prefix-cache hit: match (taking
        pool refs), trim the match until the padded suffix fits the
        cache, reserve fresh pages for the unshared tail, gather-prefill
        the suffix, insert, register. Returns False (nothing consumed)
        when fresh pages cannot be reserved yet."""
        from cloud_tpu.models.decoding import bucket_length

        request, rec = ticket.request, ticket.rec
        if self._stop.is_set():
            self._pending_inserts -= 1
            if not ticket.future.done():
                error = (self._failure
                         or RuntimeError("scheduler closed"))
                self._trace_fail(rec.rid, error)
                ticket.future.set_exception(error)
            return True
        prompt = [int(t) for t in request.prompt]
        prompt_len = len(prompt)
        page = self.pool.page_size
        total = self.pool.pages_needed(prompt_len,
                                       request.max_new_tokens,
                                       slack=self._spec_slack())
        match = self.trie.match(prompt)
        shared = list(match.pages)
        partial_page = match.partial_page
        partial_len = match.partial_len
        prefix_len = match.prefix_len
        # Trim until prefix + pow2(suffix) fits max_seq_len: drop the
        # partial first, then whole pages (each dropped page's ref goes
        # straight back).
        while prefix_len and (prefix_len + bucket_length(
                prompt_len - prefix_len, self.engine.max_seq_len)
                > self.engine.max_seq_len):
            if partial_len:
                self.pool.free([partial_page])
                partial_page, partial_len = None, 0
            else:
                self.pool.free([shared.pop()])
            prefix_len = len(shared) * page + partial_len
        shared, partial_page, partial_len, prefix_len = \
            self._host_extend(ticket, prompt, prompt_len, shared,
                              partial_page, partial_len, prefix_len)
        held = shared + ([partial_page] if partial_len else [])
        if prefix_len == 0:
            # Evicted (or trimmed away) between probe and match: it is
            # a plain miss now — run it here; the tick thread is also
            # allowed to prefill.
            if held:
                self.pool.free(held)
            return self._admit_miss_on_tick(ticket, total)
        fresh = self._reserve_on_tick(ticket, total - len(shared))
        if fresh is None:
            self.pool.free(held)
            return False
        if self._prefill_chunk is not None:
            # The gather runs lazily at the first chunk step (tick
            # thread — safe); the held refs keep the prefix pages'
            # content live until then.
            chunked = self.engine.prefill_chunks(
                np.asarray(prompt, np.int32), request.max_new_tokens,
                host_prng_key(request.rng_seed),
                self._sampling(request), self._prefill_chunk,
                prefix_len=prefix_len,
                gather_vec=self.pool.page_vec(held), rid=rec.rid)
            self._enqueue_chunk_item(_ChunkItem(
                "hit", request, chunked, ticket.future, rec,
                shared=shared, fresh=fresh, partial_page=partial_page,
                partial_len=partial_len, prefix_len=prefix_len,
                result_prefix_len=prefix_len))
            return True
        try:
            result = self._engine_prefill(
                np.asarray(prompt, np.int32), request.max_new_tokens,
                host_prng_key(request.rng_seed),
                self._sampling(request), prefix_len=prefix_len,
                gather_vec=self.pool.page_vec(held), rid=rec.rid)
        except PrefillFailed as exc:
            self.pool.free(held + fresh)
            self._note_fault(exc, rid=rec.rid, slot=None)
            self._note_requeue(rec.rid, tokens_done=0)
            return False
        except BaseException:
            self.pool.free(held + fresh)
            raise
        self._first_token(rec, result, hit=True, prefix_len=prefix_len)
        self._prefix_tokens_served += prefix_len
        slot = self._free_slots.pop()
        state = _Slot(request, shared + fresh, ticket.future, rec,
                      prefix_len=prefix_len)
        state.emitted.append(result.first_token)
        state.step_keys = result.step_keys
        self._slots[slot] = state
        page_vec = self.pool.page_vec(shared + fresh)
        scatter_vec = self.pool.page_vec([0] * len(shared) + fresh)
        self.engine.insert(slot, result, page_vec, scatter_vec,
                           self._sampling(request))
        self._inserted(rec, slot)
        if partial_len:
            # The divergent page was reconstructed into its fresh page
            # by the insert scatter — the device-side copy-on-write.
            self.pool.note_cow()
            self.pool.free([partial_page])
        self._register(request, shared + fresh)
        self._pending_inserts -= 1
        self._observe_gauges()
        return True

    def _reserve_on_tick(self, ticket, need):
        """One short reservation round for a ticket the tick thread
        admits (the tick must not block): the pages and the `reserved`
        mark, or None when the pool has none yet (the ticket stays
        queued and its wait keeps running)."""
        rec = ticket.rec
        if ticket.t_reserve0 is None:
            ticket.t_reserve0 = time.monotonic()
        with spans.span("admit_reserve", rid=rec.rid):
            pages = self._reserve_with_pressure(need, timeout=0.01)
        if pages is None:
            return None
        now = time.monotonic()
        self._observe_reserve_wait(now - ticket.t_reserve0)
        self._mark(rec, "reserved", now, pages=len(pages),
                   wait_s=now - ticket.t_reserve0)
        return pages

    def _admit_miss_on_tick(self, ticket, need):
        """Fallback when a probed hit vanished before `match`: admit it
        as a miss without bouncing back to the admission thread."""
        request, rec = ticket.request, ticket.rec
        rec.path = "miss_on_tick"
        pages = self._reserve_on_tick(ticket, need)
        if pages is None:
            return False
        if self._prefill_chunk is not None:
            chunked = self.engine.prefill_chunks(
                np.asarray(request.prompt, np.int32),
                request.max_new_tokens,
                host_prng_key(request.rng_seed),
                self._sampling(request), self._prefill_chunk,
                rid=rec.rid)
            self._enqueue_chunk_item(_ChunkItem(
                "miss", request, chunked, ticket.future, rec,
                pages=pages))
            return True
        try:
            result = self._engine_prefill(
                np.asarray(request.prompt, np.int32),
                request.max_new_tokens,
                host_prng_key(request.rng_seed),
                self._sampling(request), rid=rec.rid)
        except PrefillFailed as exc:
            self.pool.free(pages)
            self._note_fault(exc, rid=rec.rid, slot=None)
            self._note_requeue(rec.rid, tokens_done=0)
            return False
        except BaseException:
            self.pool.free(pages)
            raise
        self._first_token(rec, result, hit=False)
        slot = self._free_slots.pop()
        state = _Slot(request, pages, ticket.future, rec, prefix_len=0)
        state.emitted.append(result.first_token)
        state.step_keys = result.step_keys
        self._slots[slot] = state
        vec = self.pool.page_vec(pages)
        self.engine.insert(slot, result, vec, vec,
                           self._sampling(request))
        self._inserted(rec, slot)
        self._register(request, pages)
        self._pending_inserts -= 1
        self._observe_gauges()
        return True

    def _register(self, request, pages):
        """Indexes the inserted request's full prompt pages (tick
        thread, right after insert: the pages are populated and
        immutable from here — decode writes start past the prompt)."""
        if self.trie is None or request.max_new_tokens <= 1:
            return
        self.trie.register([int(t) for t in request.prompt], pages)

    # -- graftpack: host page tier demote/promote ---------------------

    def _host_extend(self, ticket, prompt, prompt_len, shared,
                     partial_page, partial_len, prefix_len):
        """Promote: extend the trie's device-resident prefix with
        host-tier pages from a completed earlier turn. Finds the
        longest host entry strictly past the trie match (page-aligned,
        leaving >= 1 suffix token, and fitting the same
        prefix+pow2(suffix) constraint the trim loop enforces),
        verifies its tree_digest (mismatch -> typed HostTierCorrupt,
        entry dropped, the trie prefix alone carries on — corrupt
        pages are never mapped), reserves the extension pages
        NON-BLOCKING (promotion is an optimization; a starved pool
        falls back to re-prefilling the tail), and runs the engine's
        fixed-shape promote scatter. The extension pages ride the hit
        flow as extra `shared` pages: the insert scatter routes them
        to scratch, `_register` indexes them, refcounts balance
        exactly like trie-matched pages. Tick thread only."""
        tier = self.host_tier
        if tier is None:
            return shared, partial_page, partial_len, prefix_len
        from cloud_tpu.models.decoding import bucket_length
        from cloud_tpu.training.checkpoint import tree_digest
        page = self.pool.page_size
        n_t = len(shared)
        n_h = 0
        for n in range((prompt_len - 1) // page, n_t, -1):
            if (n * page + bucket_length(prompt_len - n * page,
                                         self.engine.max_seq_len)
                    > self.engine.max_seq_len):
                continue
            if tier.contains(prompt[:n * page]):
                n_h = n
                break
        if n_h == 0:
            return shared, partial_page, partial_len, prefix_len
        entry = tier.get(prompt, n_h)
        if entry is None:  # concurrently evicted between probe and get
            return shared, partial_page, partial_len, prefix_len
        if tree_digest(entry["pages"]) != entry["digest"]:
            tier.note_digest_failure()
            tier.drop(prompt, n_h)
            self._note_fault(HostTierCorrupt(
                "host-tier digest mismatch at {} pages; entry dropped, "
                "falling back to re-prefill.".format(n_h)),
                rid=ticket.rec.rid, slot=None)
            reg = _registry()
            if reg is not None:
                from cloud_tpu.monitoring import telemetry
                reg.counter(telemetry.SERVE_DIGEST_FAILURES_TOTAL).inc()
            return shared, partial_page, partial_len, prefix_len
        # Plain non-blocking reserve — no trie eviction pressure; a
        # promote must never evict device-resident prefixes to make
        # room for itself.
        ext = self.pool.reserve(n_h - n_t, timeout=0.01)
        if ext is None:
            return shared, partial_page, partial_len, prefix_len
        if partial_len:
            # The promoted prefix covers (and extends past) the
            # divergent partial page — drop its ref, no CoW needed.
            self.pool.free([partial_page])
            partial_page, partial_len = None, 0
        self.engine.promote_pages(entry["pages"], shared + ext,
                                  n_skip=n_t)
        tier.note_promote()
        self._trace_emit(ticket.rec.rid, "page_promote", pages=len(ext),
                         prefix_len=n_h * page)
        reg = _registry()
        if reg is not None:
            from cloud_tpu.monitoring import telemetry
            reg.counter(telemetry.SERVE_PAGE_PROMOTES_TOTAL).inc(
                len(ext))
        return shared + ext, None, 0, n_h * page

    def _maybe_demote(self, state):
        """Demote: at turn completion, snapshot the slot's full
        written pages to the host tier keyed by their token history,
        so the NEXT conversation turn (prompt = this turn's prompt +
        continuation) promotes them back instead of re-prefilling.
        Tick thread, BEFORE the pages return to the pool — the
        snapshot executable reads the live cache."""
        tier = self.host_tier
        request = state.request
        if tier is None or request.max_new_tokens <= 1:
            return
        from cloud_tpu.training.checkpoint import tree_digest
        emitted = [int(t)
                   for t in state.emitted[:request.max_new_tokens]]
        full = [int(t) for t in request.prompt] + emitted
        # The final sampled token was never written to the cache.
        written = len(full) - 1
        n_full = written // self.pool.page_size
        if n_full < 1 or n_full > len(state.pages):
            return
        key = full[:n_full * self.pool.page_size]
        if tier.contains(key):
            return
        host_tree = self.engine.snapshot_pages(state.pages[:n_full])
        if not tier.put(key, host_tree, n_full,
                        tree_digest(host_tree)):
            return  # oversized for the tier budget — refused, not LRUed
        self._trace_emit(state.rec.rid, "page_demote", pages=n_full,
                         tokens=len(key))
        reg = _registry()
        if reg is not None:
            from cloud_tpu.monitoring import telemetry
            reg.counter(telemetry.SERVE_PAGE_DEMOTES_TOTAL).inc(n_full)

    def _distribute(self, live, fetched, elapsed, t_commit):
        """`live`: the (slot, state) pairs the tick advanced, as
        `_commit_tick` reads them from the dispatch's snapshot."""
        n_active = len(live)
        if n_active:
            self._token_hist.observe(elapsed, count=n_active)
            # Geometry stamp: occupancy rolls up under the rung this
            # tick RAN at, never a mixed aggregate (a tick's times by
            # rung: its `reqtrace.TickRecord` carries `slots`).
            g = self._geom()
            g["ticks"] += 1
            g["active_sum"] += n_active
            # What this tick's attention read: the token it consumed
            # sits at prompt + emitted - 1, so that many keys and
            # itself.
            layout = self.engine.layout
            for slot, state in live:
                depth = (len(state.request.prompt)
                         + len(state.emitted) + self._kv_reach)
                if self.engine.block:
                    # The open block's end: its first position and B.
                    depth = int(fetched[2 * self.engine.block + 2][slot]
                                ) + self._kv_reach + 1
                if layout is not None:
                    # The token consumed sits at depth - 1: the rows
                    # of both kinds it attends, and the groups the
                    # walk fetches from its first live row to its own.
                    summaries, ring = layout.rows_read(depth - 1)
                    self._eva_rows_read += summaries + ring
                    self._eva_summary_rows_read += summaries
                    self._eva_windows_closed["ticks"] += (
                        depth % layout.window == 0)
                    self._kv_live_tokens += summaries + ring
                    self._kv_walked_tokens += layout.rows_walked(
                        depth - 1, self._kv_group * self.pool.page_size)
                    continue
                self._kv_live_tokens += depth
                self._kv_walked_tokens += walked_tokens(
                    depth, self.pool.page_size, self._kv_group)
                self._mla_rows_read += depth * self._latent_layers
            reg = _registry()
            if reg is not None:
                from cloud_tpu.monitoring import telemetry
                reg.histogram(telemetry.SERVE_TOKEN_HISTOGRAM).observe(
                    elapsed, count=n_active)
                reg.histogram(
                    telemetry.SERVE_TICK_SECONDS
                    % self.engine.slots).observe(elapsed)
        if self.engine.block:
            self._distribute_block(live, fetched, t_commit)
        elif self.engine.spec_on:
            self._distribute_spec(live, fetched, t_commit)
        else:
            self._distribute_plain(live, fetched, t_commit)
        trace = self._trace
        if trace is not None:
            # Batched tick commits: one event per tick_every ticks per
            # surviving slot (finished slots emit `complete` instead),
            # carrying committed-token progress and batch occupancy —
            # the slot-occupancy timeline without per-token event cost.
            every = trace.tick_every
            for slot, state in live:
                if (self._slots[slot] is not state
                        or state.rec.rid is None):
                    continue
                state.trace_ticks += 1
                if state.trace_ticks >= every:
                    state.trace_ticks = 0
                    trace.record(state.rec.rid, "tick_commit",
                               tokens_committed=len(state.emitted),
                               active_slots=n_active,
                               ticks=self._ticks,
                               slots=self.engine.slots)

    def _count_tick(self, counters):
        self._ssm_slot_steps += int(counters.get("ssm_slot_steps", 0))
        for name in DIFFUSION_COUNTERS:
            self._diffusion[name] += int(counters.get(name, 0))
        if "pairs_routed" not in counters:
            return
        self._moe_pairs_routed += int(counters["pairs_routed"])
        self._moe_pairs_held += int(counters["pairs_held"])
        self._moe_pairs_dense += int(counters["pairs_dense"])
        self._moe_experts_touched += int(counters["experts_touched"])
        load = np.asarray(counters["expert_load"], np.int64)
        self._moe_expert_load = (load if self._moe_expert_load is None
                                 else self._moe_expert_load + load)

    def _distribute_plain(self, live, fetched, t_commit):
        tokens_row, finished_row = fetched[0], fetched[1]
        evict_mask = np.zeros((self.engine.slots,), bool)
        for slot, state in live:
            state.emitted.append(int(tokens_row[slot]))
            state.rec.token_times.append(t_commit)
            if finished_row[slot]:
                self._finish_slot(slot, state, evict_mask)
        if evict_mask.any():
            self.engine.evict(evict_mask)
            self._observe_gauges()

    def _distribute_spec(self, live, fetched, t_commit):
        from cloud_tpu.models.speculative import observe_accept_rate

        k = self.engine.spec_k
        count_row = fetched[k + 1]
        finished_row = fetched[k + 2]
        accept_row = fetched[k + 3]
        evict_mask = np.zeros((self.engine.slots,), bool)
        for slot, state in live:
            c = int(count_row[slot])
            state.emitted.extend(
                int(fetched[j][slot]) for j in range(c))
            state.rec.token_times.extend([t_commit] * c)
            n_acc = int(accept_row[slot])
            if n_acc >= 0:
                self._accepted_draft_tokens += n_acc
                self._proposed_draft_tokens += k
                observe_accept_rate(n_acc, k)
            if finished_row[slot]:
                self._finish_slot(slot, state, evict_mask)
        if evict_mask.any():
            self.engine.evict(evict_mask)
            self._observe_gauges()

    def _distribute_block(self, live, fetched, t_commit):
        """A block tick's commits (`DecodeEngine.tick`'s out-array):
        each slot's committed rows go to their positions, and a token
        is released to its request once it and every position before
        it are committed (`_release`)."""
        b = self.engine.block
        count_row, finished_row, start_row = fetched[2 * b:2 * b + 3]
        evict_mask = np.zeros((self.engine.slots,), bool)
        for slot, state in live:
            c = int(count_row[slot])
            if c == 0:
                state.block_steps = 0       # a clean pass: the block closed
            else:
                start = int(start_row[slot])
                for j in range(c):
                    state.committed[start + int(fetched[b + j][slot])] = (
                        int(fetched[j][slot]), state.block_steps)
                state.block_steps += 1
                self._release(slot, state, t_commit)
            if finished_row[slot]:
                self._finish_slot(slot, state, evict_mask)
        if evict_mask.any():
            self.engine.evict(evict_mask)
            self._observe_gauges()

    def _release(self, slot, state, t_commit):
        """Hands a block-diffusion request the tokens that are now
        committed in order: its first is the TTFT point (and its
        insertion is marked there too), each later one joins
        `token_times`."""
        request, rec = state.request, state.rec
        at = len(request.prompt) + len(state.emitted)
        while (len(state.emitted) < request.max_new_tokens
               and at in state.committed):
            state.emitted.append(state.committed[at][0])
            if len(state.emitted) == 1:
                self._mark(rec, "first", t_commit, bucket=rec.bucket,
                           prefix_len=0)
                self._mark(rec, "insert", t_commit, slot=slot)
                self._record_ttft(rec.ttft_s, hit=False)
            else:
                rec.token_times.append(t_commit)
            at += 1

    def _finish_slot(self, slot, state, evict_mask):
        evict_mask[slot] = True
        self._slots[slot] = None
        self._free_slots.append(slot)
        self._maybe_demote(state)
        self.pool.free(state.pages)
        steps = None
        if self.engine.block:
            first = len(state.request.prompt)
            steps = np.asarray(
                [state.committed[p][1] for p in range(
                    first, first + state.request.max_new_tokens)],
                np.int32)
        self._complete(state.request, state.future, state.rec,
                       state.emitted,
                       prefix_len=state.result_prefix_len,
                       commit_steps=steps)

    def _complete(self, request, future, rec, emitted, prefix_len,
                  commit_steps=None):
        # A speculative tick can overshoot max_new_tokens by up to
        # spec_k accepted tokens — the greedy chain is identical, so
        # truncation is exact.
        emitted = emitted[:request.max_new_tokens]
        # Early-eos eviction: generate() keeps emitting eos after done,
        # so the bit-identical fill is pure host work.
        if len(emitted) < request.max_new_tokens:
            emitted = emitted + [request.eos_token] * (
                request.max_new_tokens - len(emitted))
        tokens = np.concatenate([
            np.asarray(request.prompt, np.int32),
            np.asarray(emitted, np.int32)])
        # The record keeps one time a token the device produced: a
        # speculative overshoot goes, the host's eos fill has none.
        rec.new_tokens = min(1 + len(rec.token_times),
                             rec.max_new_tokens)
        del rec.token_times[rec.new_tokens - 1:]
        rec.prefix_len = int(prefix_len)
        now = time.monotonic()
        ttft, latency = rec.ttft_s, now - rec.t_submit
        self._completed += 1
        self._tokens_out += request.max_new_tokens
        reg = _registry()
        if reg is not None:
            from cloud_tpu.monitoring import telemetry
            reg.counter(telemetry.SERVE_REQUESTS_TOTAL).inc()
            reg.counter(telemetry.SERVE_TOKENS_TOTAL).inc(
                request.max_new_tokens)
            wall = max(time.monotonic() - self._t_start, 1e-9)
            reg.gauge(telemetry.SERVE_REQUESTS_PER_SEC).set(
                self._completed / wall)
        self._mark(rec, "done", now, ttft_s=ttft, latency_s=latency,
                   tokens=int(request.max_new_tokens),
                   prefix_len=rec.prefix_len)
        if rec.rid is not None:
            reqtrace.publish(rec)
        future.set_result(ServeResult(tokens=tokens, ttft_s=ttft,
                                      latency_s=latency,
                                      prefix_len=prefix_len, trace=rec,
                                      commit_steps=commit_steps))

    # -- shared helpers -----------------------------------------------

    #: The JSONL event each boundary of the record exports as (the
    #: `admit` boundary is the record's alone).
    _BOUNDARY_EVENTS = {"dequeued": "queued", "reserved": "pages_reserved",
                        "first": "prefill", "insert": "slot_insert",
                        "done": "complete"}

    def _mark(self, rec, boundary, now=None, event=True, **fields):
        """The one marking call: stamps `rec.t_<boundary>` and, where a
        JSONL tracer is installed, exports the boundary's event with
        `fields`. `event=False` marks a boundary the path passes
        without work (an empty phase), which the JSONL never showed."""
        setattr(rec, "t_" + boundary,
                time.monotonic() if now is None else now)
        if event and boundary in self._BOUNDARY_EVENTS:
            self._trace_emit(rec.rid, self._BOUNDARY_EVENTS[boundary],
                             **fields)

    def _trace_emit(self, rid, event, **fields):
        trace = self._trace
        if trace is not None and rid is not None:
            # Buffered: the file is written when the buffer fills and
            # at close(), never for the sake of one event.
            trace.record(rid, event, **fields)

    def _trace_fail(self, rid, error):
        self._trace_emit(rid, "fail", error="{}: {}".format(
            type(error).__name__, str(error)[:200]))

    def _observe_reserve_wait(self, wait):
        self._reserve_wait_hist.observe(wait)
        reg = _registry()
        if reg is not None:
            from cloud_tpu.monitoring import telemetry
            reg.histogram(
                telemetry.SERVE_RESERVE_WAIT_HISTOGRAM).observe(wait)

    def _observe_queue(self):
        reg = _registry()
        if reg is not None:
            from cloud_tpu.monitoring import telemetry
            reg.gauge(telemetry.SERVE_QUEUE_DEPTH).set(
                self._admit_q.qsize())

    def _observe_gauges(self):
        reg = _registry()
        if reg is None:
            return
        from cloud_tpu.monitoring import telemetry
        reg.gauge(telemetry.SERVE_ACTIVE_SLOTS).set(
            sum(s is not None for s in self._slots))
        reg.gauge(telemetry.SERVE_SLOT_COUNT).set(self.engine.slots)
        reg.gauge(telemetry.SERVE_QUEUE_DEPTH).set(
            self._admit_q.qsize())
        pstats = self.pool.pool_stats()
        reg.gauge(telemetry.SERVE_PAGES_FREE).set(pstats["pages_free"])
        reg.gauge(telemetry.SERVE_PAGES_SHARED).set(
            pstats["pages_shared"])
        reg.gauge(telemetry.SERVE_COW_COPIES).set(pstats["cow_copies"])
        reg.gauge(telemetry.SERVE_RESERVE_WAITERS).set(
            pstats["reserve_waiters"])
        reg.gauge(telemetry.SERVE_PAGES_PREFILLING).set(
            pstats["pages_prefilling"])
        reg.gauge(telemetry.SERVE_KV_BYTES % "hbm").set(
            pstats["kv_bytes_held"])
        reg.gauge(telemetry.SERVE_KV_CAPACITY_SESSIONS).set(
            self.pool.capacity // self.engine.pages_per_slot)
        if self.host_tier is not None:
            hstats = self.host_tier.stats()
            reg.gauge(telemetry.SERVE_HOST_TIER_PAGES).set(
                hstats["pages"])
            reg.gauge(telemetry.SERVE_KV_BYTES % "host").set(
                hstats["pages"] * self.pool.page_bytes)
        if self.trie is not None:
            tstats = self.trie.stats()
            reg.gauge(telemetry.SERVE_PREFIX_PAGES_HELD).set(
                tstats["pages_held"])
            reg.gauge(telemetry.SERVE_PREFIX_EVICTIONS).set(
                tstats["evictions"])

    def _fail_pending(self, error):
        with self._ready_lock:
            ready, self._ready = list(self._ready), collections.deque()
            chunks, self._chunks = (list(self._chunks),
                                    collections.deque())
        for item in ready:
            if isinstance(item, _ChunkItem):
                chunks.append(item)
                continue
            if isinstance(item, _ReadyItem) and item.pages:
                self.pool.free(item.pages)
            if not item.future.done():
                self._trace_fail(item.rec.rid, error)
                item.future.set_exception(error)
        for item in chunks:
            self._fail_chunk_item(item, error)
        self._pending_inserts = 0
        with self._ready_lock:
            self._chunk_accounted = 0
        for slot, state in enumerate(self._slots):
            if state is not None:
                if state.pages:
                    self.pool.free(state.pages)
                if not state.future.done():
                    self._trace_fail(state.rec.rid, error)
                    state.future.set_exception(error)
            self._slots[slot] = None
        while True:
            try:
                _, future, rec, _ = self._admit_q.get_nowait()
            except queue.Empty:
                break
            if not future.done():
                self._trace_fail(rec.rid, error)
                future.set_exception(error)

    # -- invariants ---------------------------------------------------

    def assert_drained(self, clear_prefix=False):
        """Refcount leak detector. With no in-flight work, every held
        pool page must be exactly one trie reference (refcount 1, page
        indexed); with `clear_prefix` the trie is dropped first and the
        pool must be FULLY free. Raises RuntimeError on any leak."""
        busy = (any(s is not None for s in self._slots)
                or self._pending_inserts > 0 or self._admit_q.qsize())
        with self._ready_lock:
            busy = busy or bool(self._ready) or bool(self._chunks)
        if busy:
            raise RuntimeError(
                "assert_drained called with requests in flight.")
        self._settle()
        if clear_prefix and self.trie is not None:
            self.trie.clear()
        held = self.pool.leak_report()
        trie_pages = (set(self.trie.held_pages())
                      if self.trie is not None else set())
        leaked = {p: r for p, r in held.items()
                  if p not in trie_pages or r != 1}
        if leaked:
            raise RuntimeError(
                "page refcount leak (page -> holders, beyond the "
                "prefix index): {}".format(leaked))
        if len(held) != len(trie_pages):
            raise RuntimeError(
                "prefix index holds {} pages but the pool records {} "
                "held.".format(len(trie_pages), len(held)))

    # -- warm-up + stats ----------------------------------------------

    def warmup(self, buckets, sampling_configs=((),), max_new=3):
        """Compiles the whole serving surface for `buckets` x sampling
        configs: per-bucket prefill (full and short lengths), insert,
        tick, evict, and the cache-reuse re-zero. Two sequential waves
        so the second wave's prefills acquire parked caches (compiling
        the in-place zero executable). With the prefix cache on, every
        pow2 width up to the largest bucket is warmed too (a hit's
        SUFFIX can land in any of them) and a shared-prefix trio
        compiles the gather + copy-on-write path; the trie is cleared
        afterwards so warm-up leaves no cached state. Call
        `engine.mark_warm()` is implicit — after warmup the retrace
        sentinel is armed."""
        from cloud_tpu.models.decoding import bucket_length

        # Warm-up requests are synthetic: stamp no rids, emit no trace
        # events and keep no records, so every lifecycle in the JSONL
        # and in reqtrace.recent() is real traffic and the zero-orphans
        # CI assertion stays meaningful.
        self._trace_suppress = True
        vocab = self.engine.model.vocab_size
        configs = []
        for cfg in sampling_configs:
            merged = dict(temperature=0.0, top_k=None, top_p=None,
                          eos_token=None)
            merged.update(dict(cfg))
            configs.append(merged)
        widths = set(buckets)
        block = self.engine.block
        if block:
            # A block-diffusion prefill runs at the bucket of the
            # prompt's whole blocks: a prompt whose last block is
            # partial may fall in the bucket below its own.
            widths = {w for b in widths for w in (b, b // 2) if w >= block}
        if self.engine.layout is not None:
            # One chunk executable and one tail executable serve every
            # prompt length (the chunk path below warms both): the
            # widths asked for name no program of such a model.
            widths = set()
        if self.trie is not None and buckets:
            w = 1
            while w <= max(buckets):
                widths.add(w)
                w *= 2
        # Distinct first tokens keep warm-up prompts from prefix-
        # matching EACH OTHER — a warm-up hit would compile its suffix
        # bucket instead of the width it was meant to compile.
        combo = 0
        # Widest buckets can't host a full-length prompt AND max_new
        # decode positions — cap warm-up lengths so the request
        # validates; bucket_length() still maps the capped length to
        # the intended width.
        cap = self.engine.max_seq_len - max_new - self._spec_slack()
        chunk_lengths = []
        if self.engine.layout is not None:
            # A whole window and a one-token tail where a request may
            # be that long, else the tail alone (no prompt then
            # reaches a second chunk).
            chunk_lengths = [min(self._prefill_chunk + 1, cap)]
        elif self._prefill_chunk is not None:
            # Drive the chunk + tail-bucket surface: length C + t has
            # exactly one full chunk and a t-token tail, so the set
            # {C + t : t pow2 <= C} compiles the fixed-chunk executable
            # and EVERY tail bucket per sampling config. Steady state
            # then stays at zero new traces regardless of prompt
            # length — any n decomposes into full chunks + one of
            # these tails.
            t = 1
            while t <= self._prefill_chunk:
                if self._prefill_chunk + t <= cap:
                    chunk_lengths.append(self._prefill_chunk + t)
                t *= 2
        for _ in range(2):
            futures = []
            for bucket in sorted(widths):
                lengths = {min(bucket, cap), min(max(bucket - 1, 1), cap)}
                if block:
                    lengths = {min(bucket, cap)}
                for length in sorted(lengths):
                    whole = length // block * block if block else length
                    if whole < 1 or bucket_length(
                            whole, self.engine.max_seq_len) != bucket:
                        continue
                    for cfg in configs:
                        first = 2 + combo % max(vocab - 2, 1)
                        combo += 1
                        futures.append(self.submit(ServeRequest(
                            prompt=[first] + [1] * (length - 1),
                            max_new_tokens=max_new, **cfg)))
            for length in chunk_lengths:
                for cfg in configs:
                    first = 2 + combo % max(vocab - 2, 1)
                    combo += 1
                    futures.append(self.submit(ServeRequest(
                        prompt=[first] + [1] * (length - 1),
                        max_new_tokens=max_new, **cfg)))
            for future in futures:
                future.result(timeout=600)
        if self.trie is not None:
            self._warm_prefix_path(configs[0])
            if self.host_tier is not None:
                self._warm_host_tier(configs[0])
                self.host_tier.clear()
                self.host_tier.reset_stats()
            self.trie.clear()
            self.trie.reset_stats()
        self._warm_ladder(configs[0], max_new)
        # The tick dispatched behind the last finish is still counted
        # as warm-up.
        self._settle()
        self.engine.mark_warm()
        self._trace_suppress = False
        # Warm-up TTFTs are compile times; restart the host-side stats
        # so `stats()` describes warm traffic only.
        from cloud_tpu.monitoring.telemetry import Histogram
        self._ttft_hist = Histogram("ttft")
        self._ttft_hit_hist = Histogram("ttft_hit")
        self._ttft_miss_hist = Histogram("ttft_miss")
        self._token_hist = Histogram("token_latency")
        self._queue_wait_hist = Histogram("queue_wait")
        self._reserve_wait_hist = Histogram("reserve_wait")
        self._prefill_hist = Histogram("prefill")
        self._prefill_chunk_hist = Histogram("prefill_chunk")
        self._decode_gap_hist = Histogram("decode_gap")
        self._chunks_dispatched = 0
        self._tick_paces = 0
        self._ticks_overlapped = 0
        self._prefills_overlapped = 0
        self._kv_live_tokens = 0
        self._kv_walked_tokens = 0
        self._eva_rows_read = 0
        self._eva_summary_rows_read = 0
        self._eva_windows_closed = {"ticks": 0, "prefills": 0}
        self._mla_rows_read = 0
        self._mla_prefill_tokens = {"expanded": 0, "absorbed": 0}
        self._moe_pairs_routed = 0
        self._moe_pairs_held = 0
        self._moe_pairs_dense = 0
        self._moe_experts_touched = 0
        self._moe_expert_load = None
        self._ssm_slot_steps = 0
        self._diffusion = dict.fromkeys(DIFFUSION_COUNTERS, 0)
        self._t_last_commit = None
        self._completed = 0
        self._tokens_out = 0
        self._ticks = 0
        self._hits = 0
        self._misses = 0
        self._prefix_tokens_served = 0
        self._accepted_draft_tokens = 0
        self._proposed_draft_tokens = 0
        self._resize_counts = {"grow": 0, "shrink": 0}
        self._resize_events = []
        self._quiet_ticks = 0
        self._geom_stats = {}
        self._admission_model_hits = 0
        self._t_start = time.monotonic()

    def _warm_ladder(self, cfg, max_new):
        """graftflex ladder walk: visits every rung (start -> min ->
        max -> start, one rung per step) so EACH adjacent resize pair
        compiles in BOTH directions, and runs a small decode wave the
        first time a rung is visited — tick/insert/evict trace per
        slot count, so steady-state traffic on any rung, with policy
        resizes in between, stays at zero new traces. The walk ends
        back on the starting rung. Prefill executables are dense
        [1, L] and geometry-free; the main waves already warmed them.
        """
        ladder = self.engine.ladder
        if len(ladder) <= 1:
            return
        start = self.engine.slots
        idx = ladder.index(start)
        targets = (list(ladder[:idx][::-1])       # start -> min
                   + list(ladder)                 # min -> max
                   + list(ladder[idx:-1][::-1]))  # max -> start
        vocab = self.engine.model.vocab_size
        visited = {start}
        combo = 0
        for rung in targets:
            if rung == self.engine.slots:
                continue
            self.request_resize(rung, reason="warmup", timeout=600)
            if rung in visited:
                continue
            visited.add(rung)
            futures = []
            for _ in range(2):
                first = 2 + combo % max(vocab - 2, 1)
                combo += 1
                futures.append(self.submit(ServeRequest(
                    prompt=[first], max_new_tokens=max_new, **cfg)))
            for future in futures:
                future.result(timeout=600)

    def _warm_prefix_path(self, cfg):
        """Shared-prefix trio: a miss that registers a page, a mid-page
        divergence (gather + CoW reconstruction), and a clean full-page
        hit — compiles the gather executables (target and draft trees)
        and exercises the hit insert before the sentinel arms."""
        page = self.pool.page_size
        vocab = self.engine.model.vocab_size
        base_len = page + page // 2
        if (page < 4 or vocab < 4 or base_len + 2 + self._spec_slack()
                > self.engine.max_seq_len):
            return
        base = [1] * base_len
        prompts = [
            base,                                       # miss, registers
            base[:(3 * page) // 4] + [2] * (base_len - (3 * page) // 4),
            base[:page] + [3] * (base_len - page),      # full-page hit
        ]
        for prompt in prompts:
            self.submit(ServeRequest(prompt=prompt, max_new_tokens=2,
                                     **cfg)).result(timeout=600)

    def _warm_host_tier(self, cfg):
        """graftpack pair: a turn that completes and demotes two full
        pages (compiling the snapshot executable), then its next turn,
        whose admission finds the host entry past the one-page trie
        prefix and promotes (compiling the promote scatter and the
        wider-prefix gather) — so steady-state offload traffic stays
        at zero new traces. Both executables are fixed-shape, so one
        compile each covers every page count."""
        page = self.pool.page_size
        vocab = self.engine.model.vocab_size
        if (page < 2 or vocab < 5
                or page + 2 > self.engine.max_new_cap
                or 2 * page + 5 + self._spec_slack()
                > self.engine.max_seq_len):
            return
        first = self.submit(ServeRequest(
            prompt=[4] * page, max_new_tokens=page + 2,
            **cfg)).result(timeout=600)
        turn2 = [int(t) for t in first.tokens] + [2]
        self.submit(ServeRequest(prompt=turn2, max_new_tokens=2,
                                 **cfg)).result(timeout=600)

    def stats(self):
        """Host-side rollup (works with telemetry off)."""
        wall = max(time.monotonic() - (self._t_start or
                                       time.monotonic()), 1e-9)
        lookups = self._hits + self._misses
        proposed = self._proposed_draft_tokens
        pool = self.pool.pool_stats()
        out = {
            "requests_completed": self._completed,
            "tokens_emitted": self._tokens_out,
            "ticks": self._ticks,
            "tick_paces": self._tick_paces,
            "ticks_overlapped": self._ticks_overlapped,
            "prefills_overlapped": self._prefills_overlapped,
            "kv_live_tokens": self._kv_live_tokens,
            "kv_walked_tokens": self._kv_walked_tokens,
            "moe_pairs_routed": self._moe_pairs_routed,
            "moe_pairs_held": self._moe_pairs_held,
            "moe_pairs_dense": self._moe_pairs_dense,
            "moe_experts_touched": self._moe_experts_touched,
            "moe_expert_load": ([] if self._moe_expert_load is None
                                else self._moe_expert_load.tolist()),
            "ssm_state_bytes": self.pool.state_bytes,
            "ssm_slot_steps": self._ssm_slot_steps,
            **self._diffusion,
            "eva_rows_read": self._eva_rows_read,
            "eva_summary_rows_read": self._eva_summary_rows_read,
            "eva_windows_closed": dict(self._eva_windows_closed),
            "eva_cache_bytes": (pool["kv_bytes_held"]
                                if self.engine.layout is not None else 0),
            "mla_rows_read": self._mla_rows_read,
            "mla_prefill_tokens_expanded":
                self._mla_prefill_tokens["expanded"],
            "mla_prefill_tokens_absorbed":
                self._mla_prefill_tokens["absorbed"],
            "weight_bytes_given": self.engine.weight_bytes_given,
            "weight_bytes_served": self.engine.weight_bytes_served,
            "elapsed_seconds": wall,
            "requests_per_sec": self._completed / wall,
            "tokens_per_sec": self._tokens_out / wall,
            "ttft": self._ttft_hist.snapshot(),
            "ttft_hit": self._ttft_hit_hist.snapshot(),
            "ttft_miss": self._ttft_miss_hist.snapshot(),
            "token_latency": self._token_hist.snapshot(),
            "queue_wait": self._queue_wait_hist.snapshot(),
            "reserve_wait": self._reserve_wait_hist.snapshot(),
            "prefill": self._prefill_hist.snapshot(),
            "prefill_chunk": self._prefill_chunk_hist.snapshot(),
            "decode_gap": self._decode_gap_hist.snapshot(),
            "prefill_chunks_dispatched": self._chunks_dispatched,
            "prefill_chunk_size": self._prefill_chunk or 0,
            "queue_depth": self._admit_q.qsize(),
            "faults": dict(self._fault_counts),
            "requeues": self._requeues,
            "shed": dict(self._shed_counts),
            "predicted_ttft": self._last_predicted_ttft,
            "slo_ttft": self._slo_ttft,
            "shed_policy": self._shed_policy,
            "prefix_hits": self._hits,
            "prefix_misses": self._misses,
            "prefix_hit_rate": self._hits / lookups if lookups else 0.0,
            "prefix_tokens_served": self._prefix_tokens_served,
            "pool": pool,
            "spec_accept_rate": (self._accepted_draft_tokens / proposed
                                 if proposed else 0.0),
            "spec_accepted_tokens": self._accepted_draft_tokens,
            "spec_proposed_tokens": proposed,
        }
        # graftflex geometry rollup: the current rung, the ladder, the
        # resize census, and ticks and occupancy split by the geometry
        # they ran under (a tick's times by rung: `reqtrace.
        # recent_ticks()`, whose records carry `slots`).
        geoms = {}
        for s, g in sorted(self._geom_stats.items()):
            geoms[str(s)] = {
                "ticks": g["ticks"],
                "occupancy_mean": (g["active_sum"] / g["ticks"]
                                   if g["ticks"] else 0.0),
            }
        out["geometry"] = {
            "slots": self.engine.slots,
            "ladder": list(self.engine.ladder),
            "resizes": dict(self._resize_counts),
            "resize_events": list(self._resize_events),
            "per_geometry": geoms,
        }
        out["admission_predictor"] = {
            "loaded": self._admission_model is not None,
            "path": self._admission_model_path,
            "error": self._admission_model_error,
            "predictions": self._admission_model_hits,
        }
        # graftpack KV hierarchy rollup: dtype-aware byte accounting
        # plus the demote/promote census, mirrored from the host tier.
        hstats = (self.host_tier.stats() if self.host_tier is not None
                  else None)
        out["kv"] = {
            "page_dtype": self.kv_dtype,
            "page_bytes": self.pool.page_bytes,
            "capacity_sessions": (self.pool.capacity
                                  // self.engine.pages_per_slot),
            "host_tier_pages": hstats["pages"] if hstats else 0,
            "page_demotes": hstats["demotes"] if hstats else 0,
            "page_promotes": hstats["promotes"] if hstats else 0,
            "digest_failures": (hstats["digest_failures"]
                                if hstats else 0),
        }
        if hstats is not None:
            out["host_tier"] = hstats
        if self.trie is not None:
            out["prefix_cache"] = self.trie.stats()
        return out


__all__ = ["ServeRequest", "ServeResult", "Scheduler"]
