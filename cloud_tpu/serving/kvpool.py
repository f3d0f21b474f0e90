"""Paged KV-cache pool: host-side physical page accounting.

The physical pages themselves live in HBM as flax cache variables of the
paged decoder (`key_pages`/`value_pages` `[num_pages, page_size, H*D]`
per attention layer — models/transformer.py `_paged_decode_attention`).
This module owns the other half of the design: WHICH physical pages each
request holds. Reservation happens at admission (before any HBM is
touched for the request), so exhaustion surfaces as scheduler
backpressure — a blocked reserve — never as an OOM or a reshape/retrace
of the pool executable. The device side only ever sees page-id ARRAYS
(page-table rows), so allocation and free are in-graph index updates on
executables of fixed shape.

Page 0 is the scratch page: it is never handed out, and every freed or
never-filled page-table entry points at it. Inactive slots write their
(masked, never-attended) tick garbage there, which is what makes
cross-request leakage structurally impossible — a slot's table can only
reference pages reserved for it, or scratch.

Pages are REFERENCE COUNTED so multiple holders can map the same
physical page (the radix prefix cache shares populated prompt pages
across requests — serving/prefixcache.py). `reserve` hands out fresh
pages at refcount 1; `share` adds a holder to an already-allocated page;
`free` drops one holder and only recycles the page when the last holder
lets go. A shared page is immutable by convention: the holder that needs
to write past it makes a copy-on-write page first (the engine's insert
scatter routes shared entries to scratch and reconstructs divergent
content into fresh pages), and `note_cow` keeps the count for
`pool_stats`.

Two kinds of page for a model whose slots keep a ring and summary rows
(`RingSummaryPagePool`, `ops/eva.py`): a request holds the pages of the
current window's ring, which it overwrites in place window after
window, and the summary pages of every window it begins. Both are pages
of the one pool and count alike in every total; neither is ever shared.
"""

import threading

import numpy as np


class PagePool:
    """Refcounting free-list allocator over `num_pages` physical pages.

    Thread-safe; `reserve` blocks (condition wait) until enough pages
    are free, which is the backpressure primitive the scheduler builds
    on. All bookkeeping is host-side python — the device never sees
    this object, only the page-id vectors it emits.
    """

    def __init__(self, num_pages, page_size, pages_per_slot,
                 page_dtype="", page_bytes=0, state_bytes=0):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is the "
                             "scratch page); got {}.".format(num_pages))
        if page_size < 1 or pages_per_slot < 1:
            raise ValueError("page_size and pages_per_slot must be "
                             ">= 1.")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.pages_per_slot = int(pages_per_slot)
        # Byte accounting for the KV-hierarchy gauges: page_dtype is
        # the storage dtype name ("" = the engine compute dtype,
        # "int8" = graftpack quantized pages) and page_bytes the HBM
        # bytes ONE physical page costs summed over every attention
        # layer (K + V + scale sidecars). Zero when the engine doesn't
        # wire it (pool used standalone in tests).
        self.page_dtype = str(page_dtype)
        self.page_bytes = int(page_bytes)
        # What a model with recurrent layers keeps beside the pages: a
        # fixed-size state a slot and layer, resident for every slot
        # whether occupied or not (engine.state_hbm_bytes). No page
        # accounts for it; the byte totals below add it.
        self.state_bytes = int(state_bytes)
        self._cond = threading.Condition()
        # LIFO free list: recently-freed pages are re-handed first
        # (warm in whatever cache hierarchy the backend keeps).
        self._free = list(range(1, self.num_pages))
        self._refs = {}  # page id -> holder count, allocated pages only
        self._cow_copies = 0
        self._reserve_waiters = 0
        self._prefilling = 0
        self._closed = False

    @property
    def capacity(self):
        """Allocatable pages (scratch excluded)."""
        return self.num_pages - 1

    def available(self):
        with self._cond:
            return len(self._free)

    def pages_needed(self, prompt_tokens, max_new_tokens, slack=0):
        """Pages a request holds for its lifetime: one slot writes
        `prompt + max_new - 1` cache positions (the final sampled token
        is returned but never written back). `slack` adds positions the
        slot may transiently overshoot into — the speculative tick
        writes up to `spec_k` draft positions past the last committed
        token before rewinding."""
        tokens = prompt_tokens + max(int(max_new_tokens) - 1, 0) + slack
        need = -(-tokens // self.page_size)  # ceil
        if need > self.pages_per_slot:
            raise ValueError(
                "request needs {} pages but a slot addresses only {} "
                "({} tokens / page_size {}).".format(
                    need, self.pages_per_slot,
                    self.pages_per_slot * self.page_size,
                    self.page_size))
        return need

    def reserve(self, n, timeout=None):
        """Takes `n` pages off the free list, blocking until available.

        Returns the list of page ids (each at refcount 1), or None on
        timeout/close. A request for more than `capacity` pages raises
        immediately — waiting could never succeed (the deadlock the
        scheduler's submit-time validation also rejects).
        """
        n = int(n)
        if n == 0:
            return []
        if n > self.capacity:
            raise ValueError(
                "cannot reserve {} pages from a pool of {} allocatable "
                "pages.".format(n, self.capacity))
        with self._cond:
            # The waiter count only becomes observable while wait_for
            # actually releases the lock, so the gauge reads as "threads
            # currently blocked on page reservation" — live backpressure.
            self._reserve_waiters += 1
            try:
                ok = self._cond.wait_for(
                    lambda: self._closed or len(self._free) >= n,
                    timeout=timeout)
            finally:
                self._reserve_waiters -= 1
            if self._closed or not ok:
                return None
            pages = [self._free.pop() for _ in range(n)]
            for pid in pages:
                self._refs[pid] = 1
            return pages

    def share(self, page_ids):
        """Adds one holder to each already-allocated page (prefix-cache
        hit: a new request maps populated pages into its table)."""
        with self._cond:
            for pid in page_ids:
                pid = int(pid)
                if pid not in self._refs:
                    raise ValueError(
                        "cannot share unallocated page {}.".format(pid))
                self._refs[pid] += 1

    def refcount(self, page_id):
        """Current holder count for a page (0 when free)."""
        with self._cond:
            return self._refs.get(int(page_id), 0)

    def free(self, page_ids):
        """Drops one holder per page; recycles pages whose last holder
        let go and wakes blocked reservers."""
        if not page_ids:
            return
        with self._cond:
            recycled = False
            for pid in page_ids:
                pid = int(pid)
                if not 1 <= pid < self.num_pages:
                    raise ValueError(
                        "page id {} outside pool [1, {}).".format(
                            pid, self.num_pages))
                refs = self._refs.get(pid, 0)
                if refs <= 0:
                    raise ValueError(
                        "double free of page {}.".format(pid))
                if refs == 1:
                    del self._refs[pid]
                    self._free.append(pid)
                    recycled = True
                else:
                    self._refs[pid] = refs - 1
            if recycled:
                self._cond.notify_all()

    def reserve_waiters(self):
        """Threads currently blocked inside reserve() (backpressure)."""
        with self._cond:
            return self._reserve_waiters

    def squeeze(self, n):
        """Confiscates up to `n` FREE pages immediately (no blocking, a
        partial take is fine) — the chaos `pool_squeeze` primitive: a
        noisy neighbor claiming HBM that admission backpressure must
        absorb. The taken pages are ordinary refcount-1 allocations, so
        returning them is a plain free() and the leak detector treats a
        squeeze holder like any other."""
        n = int(n)
        with self._cond:
            take = min(n, len(self._free))
            pages = [self._free.pop() for _ in range(take)]
            for pid in pages:
                self._refs[pid] = 1
            return pages

    def note_cow(self, n=1):
        """Counts a copy-on-write page reconstruction (telemetry)."""
        with self._cond:
            self._cow_copies += int(n)

    def note_prefill_hold(self, n):
        """Marks `n` already-reserved pages as held by an in-flight
        (chunked) prefill — occupancy accounting only, no allocation.
        A multi-chunk prefill holds its pages for several ticks before
        its slot insert, so `pages_prefilling` splits `pages_held`
        into decoding vs still-prefilling for the SERVE_* gauges."""
        with self._cond:
            self._prefilling += int(n)

    def note_prefill_release(self, n):
        """Drops `n` pages from the prefill-hold count (the prefill
        inserted, failed, or was drained — the pages themselves move
        or free separately)."""
        with self._cond:
            self._prefilling -= int(n)
            if self._prefilling < 0:
                raise ValueError(
                    "prefill-hold underflow: released more prefilling "
                    "pages than held.")

    def pool_stats(self):
        """Point-in-time accounting: free/held/shared page counts, CoW
        copies since construction, and a holder-count histogram
        ({refcount: pages}) — the raw material for the SERVE_* gauges
        and the refcount leak detector."""
        with self._cond:
            hist = {}
            for refs in self._refs.values():
                hist[refs] = hist.get(refs, 0) + 1
            return {
                "pages_free": len(self._free),
                "pages_held": len(self._refs),
                "pages_shared": sum(1 for r in self._refs.values()
                                    if r >= 2),
                "pages_prefilling": self._prefilling,
                "cow_copies": self._cow_copies,
                "reserve_waiters": self._reserve_waiters,
                "refcount_hist": hist,
                "page_dtype": self.page_dtype,
                "kv_bytes_held": len(self._refs) * self.page_bytes,
                "kv_bytes_total": self.capacity * self.page_bytes,
                "state_bytes": self.state_bytes,
                "cache_bytes_total": (self.capacity * self.page_bytes
                                      + self.state_bytes),
            }

    def leak_report(self):
        """Pages still held, with holder counts. A drained scheduler
        (all requests complete, prefix cache cleared) must see {} here
        — anything else is a refcount leak."""
        with self._cond:
            return dict(self._refs)

    def close(self):
        """Unblocks every waiting reserve with None (shutdown path)."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def page_vec(self, page_ids):
        """A full-width page-table row for `page_ids`: the reserved ids
        in logical order, scratch (0) beyond them. Fixed [pages_per_slot]
        shape keeps the insert executable monomorphic."""
        vec = np.zeros((self.pages_per_slot,), np.int32)
        vec[:len(page_ids)] = page_ids
        return vec


class RingSummaryPagePool(PagePool):
    """The pool of a model whose slots keep a ring of the current
    window's rows and one summary row per chunk behind it
    (`ops.eva.EvaLayout`; `pages_per_slot` is the layout's). A request
    holds ring pages for at most one window, however long it is, and
    summary pages for every window it begins; where each goes in the
    slot's page table is the layout's. Everything else (reservation,
    backpressure, refcounts of 1, byte totals, the leak report) is the
    base pool's: both kinds are pages like any other."""

    def __init__(self, layout, num_pages, page_size, page_bytes=0):
        super().__init__(num_pages, page_size, layout.rows // page_size,
                         page_bytes=page_bytes)
        self.layout = layout

    def pages_needed(self, prompt_tokens, max_new_tokens, slack=0):
        """Ring pages + summary pages a request holds for its life
        (`prompt + max_new - 1` positions written):
        `ceil(min(tokens, window) / page_size)` of the ring, and a
        window's summary pages for each window begun."""
        tokens = prompt_tokens + max(int(max_new_tokens) - 1, 0) + slack
        if tokens > self.layout.max_seq_len:
            ring, summaries = self.layout.pages(self.layout.max_seq_len,
                                                self.page_size)
            raise ValueError(
                "request writes {} positions but a slot addresses {} "
                "ring pages (a window of {} tokens) + {} summary pages "
                "(one row per {} tokens of {}), page_size {}.".format(
                    tokens, ring, self.layout.window, summaries,
                    self.layout.chunk, self.layout.max_seq_len,
                    self.page_size))
        return sum(self.layout.pages(tokens, self.page_size))

    def page_vec(self, page_ids):
        """The slot's page-table row: ring pages at the ring's rows,
        summary pages below them (the count says which are which),
        scratch elsewhere."""
        return self.layout.page_vec(page_ids, self.page_size)

    def pool_stats(self):
        out = super().pool_stats()
        ring, summaries = self.layout.pages(self.layout.max_seq_len,
                                            self.page_size)
        out["page_kinds"] = {"ring_pages_per_slot": ring,
                             "summary_pages_per_slot": summaries}
        return out


class HostPageTier:
    """Host-RAM second tier of the KV page hierarchy (graftpack).

    Holds page-granular KV snapshots of completed conversation turns,
    keyed by the token prefix they encode, so the NEXT turn's admission
    can promote them back with a few H2D page copies instead of
    re-prefilling the whole history. This turns the prefix cache into a
    session store that survives pool pressure: trie eviction may drop
    the device pages, the host copy persists.

    An entry is `{key: token tuple (page-aligned prefix), pages: the
    engine's host-side page pytree snapshot (numpy; per-layer K/V page
    blocks + scale sidecars in int8 mode), n_pages, digest}`. The
    digest is `checkpoint.tree_digest` over the snapshot at demote
    time; promote recomputes it and a mismatch is a typed
    `HostTierCorrupt` fault — the entry is dropped and admission falls
    back to re-prefill, never serving corrupt pages.

    Budgeted in PAGES with LRU eviction (a host tier exists to be much
    larger than HBM, but smoke rigs still need determinism). All
    host-side python, thread-safe; the device is only ever touched by
    the engine's fixed-shape promote executable.
    """

    def __init__(self, max_pages, page_size):
        if max_pages < 1:
            raise ValueError("max_pages must be >= 1; got {}.".format(
                max_pages))
        self.max_pages = int(max_pages)
        self.page_size = int(page_size)
        self._lock = threading.Lock()
        self._entries = {}   # key tuple -> entry dict
        self._clock = 0
        self.demotes = 0
        self.promotes = 0
        self.digest_failures = 0
        self.evictions = 0

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def held_pages(self):
        with self._lock:
            return sum(e["n_pages"] for e in self._entries.values())

    def contains(self, tokens):
        """True when an entry for exactly this page-aligned prefix
        exists (cheap pre-snapshot dedup check)."""
        with self._lock:
            return tuple(tokens) in self._entries

    def put(self, tokens, pages, n_pages, digest):
        """Demotes a snapshot: `tokens` is the page-aligned token
        prefix the pages encode (len == n_pages * page_size), `pages`
        the host pytree, `digest` its tree_digest stamp. Evicts LRU
        entries to stay under the page budget; an oversized snapshot
        is refused (False) rather than thrashing the whole tier."""
        key = tuple(int(t) for t in tokens)
        if len(key) != n_pages * self.page_size:
            raise ValueError(
                "demote key must be page-aligned: {} tokens vs {} "
                "pages of {}.".format(len(key), n_pages,
                                      self.page_size))
        if n_pages > self.max_pages:
            return False
        with self._lock:
            held = sum(e["n_pages"] for e in self._entries.values())
            if key in self._entries:
                held -= self._entries[key]["n_pages"]
            while held + n_pages > self.max_pages and self._entries:
                lru = min(self._entries,
                          key=lambda k: self._entries[k]["stamp"])
                held -= self._entries[lru]["n_pages"]
                del self._entries[lru]
                self.evictions += 1
            self._clock += 1
            self._entries[key] = {"pages": pages, "n_pages": n_pages,
                                  "digest": digest,
                                  "stamp": self._clock}
            self.demotes += 1
            return True

    def probe(self, tokens):
        """Longest page-aligned prefix of `tokens` with a host entry,
        in TOKENS (0 = none). Side-effect-free and cheap — one dict
        probe per page boundary, longest first — so admission can rank
        by it like the trie's probe."""
        limit = (len(tokens) - 1) // self.page_size
        key = tuple(int(t) for t in tokens[:limit * self.page_size])
        with self._lock:
            for n in range(limit, 0, -1):
                if key[:n * self.page_size] in self._entries:
                    return n * self.page_size
        return 0

    def get(self, tokens, n_pages):
        """The entry for exactly `tokens[:n_pages * page_size]`, LRU-
        refreshed, or None. Digest verification is the CALLER's step
        (scheduler promote) so the failure is typed and counted there."""
        key = tuple(int(t) for t in tokens[:n_pages * self.page_size])
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._clock += 1
                entry["stamp"] = self._clock
            return entry

    def drop(self, tokens, n_pages):
        """Removes one entry (digest mismatch / explicit invalidation)."""
        key = tuple(int(t) for t in tokens[:n_pages * self.page_size])
        with self._lock:
            self._entries.pop(key, None)

    def note_promote(self):
        with self._lock:
            self.promotes += 1

    def note_digest_failure(self):
        with self._lock:
            self.digest_failures += 1

    def clear(self):
        with self._lock:
            self._entries.clear()

    def reset_stats(self):
        with self._lock:
            self.demotes = 0
            self.promotes = 0
            self.digest_failures = 0
            self.evictions = 0

    def stats(self):
        with self._lock:
            return {
                "entries": len(self._entries),
                "pages": sum(e["n_pages"]
                             for e in self._entries.values()),
                "max_pages": self.max_pages,
                "demotes": self.demotes,
                "promotes": self.promotes,
                "digest_failures": self.digest_failures,
                "evictions": self.evictions,
            }


__all__ = ["PagePool", "RingSummaryPagePool", "HostPageTier"]
