"""graftsan: runtime sanitizer with per-source-line attribution.

The static rules (GL001-GL009) predict runtime pathology from the AST;
the counters in `cloud_tpu.parallel.runtime` measure it. This module is
the bridge: under `sanitize()` every transfer/compile record and every
`jax.random` key consumption is attributed to the source line that
caused it, aggregated per line, and checked against the same invariants
the static rules encode — so a `d2h_fetches` regression arrives as
"trainer.py:2134 fetched inside the step loop", not a bare number.

Violations (ids mirror the GL numbering, GS-prefixed):

- GS001 d2h-in-step-loop — a device->host fetch while the recording
  thread's phase label is "step" (the Trainer marks its epoch step
  loops; boundary/async-reader/checkpoint fetches are sanctioned).
- GS002 retrace-after-warm — a trace recorded in the step phase after
  the first epoch finished: the runtime dual of GL002, attributing the
  retrace the Trainer's sentinel can only count.
- GS003 rng-key-reuse — a key with bit-identical contents consumed by
  two `jax.random` calls (the runtime dual of GL004/GL008). `fold_in`,
  `PRNGKey` and `key` are deliberately not watched: deriving fresh
  keys from a base key is the sanctioned pattern (e.g. the per-epoch
  `fold_in(PRNGKey(seed), epoch)` shuffle keys in training/data.py).
- GS004 donated-buffer-access — a fetch touched an array previously
  donated to an `instrumented_jit(donate_argnums=...)` call, tracked
  by weakref identity. jax's own failure for this is a bare "Array
  has been deleted" with no hint of WHERE the donation happened (and
  on backends that ignore donation there is no failure at all, just a
  silent portability bug); the finding carries the donation site.
- GS005 retrace-attribution — the runtime dual of GL010. When a trace
  fires after warmup (after `runtime.notify_warm_mark()` — the serving
  engine's `mark_warm()` — or after epoch 1), the InstrumentedJit
  diffs the offending call's aval signature against the warm table and
  its trace history and the finding names the exact leaf whose avals
  moved: "args[1]['page_table'] widened int32[4,16] -> int32[8,16]",
  attributed to the dispatching call site. Warmup traces are expected
  and record nothing.
- GS006 mesh-drift — the runtime dual of the graftmesh rules
  (GL014-GL018). Every InstrumentedJit records the concrete mesh +
  input shardings at the first observed dispatch per executable (aval
  signature); a later dispatch whose shardings differ means the jit
  boundary is silently resharding — a device transfer per call that no
  counter otherwise names — and the finding carries the exact leaf and
  BOTH layouts: "args[0] moved NamedSharding(..., PartitionSpec()) ->
  NamedSharding(..., PartitionSpec('dp',))". Unlike GS005 this arms
  immediately (the baseline IS the first dispatch), so it fires during
  warmup too — drift there costs the same transfer.

Enablement is scoped, never ambient: `with sanitize(mode="warn"):`
installs the runtime observer and the `jax.random` watchers and tears
both down on exit — with no active scope there are ZERO hooks: the
observer seam is a None check and `jax.random` holds its original
functions. `CLOUD_TPU_SANITIZE=1|warn|strict` asks the Trainer to wrap
each `fit()`/`evaluate()` in such a scope (`env_scope()`).

Findings are emitted through `utils/events.log_job_event` (JSONL, kind
"graftsan") and escalate like the preflight lint: warn logs, strict
raises `GraftsanError` at scope exit.
"""

import contextlib
import functools
import logging
import os
import sys
import threading

from cloud_tpu.parallel import runtime
from cloud_tpu.utils import events

logger = logging.getLogger("cloud_tpu")

#: Violation id -> (title, message template).
VIOLATIONS = {
    "GS001": ("d2h-in-step-loop",
              "device->host fetch ({} bytes) inside the step loop at "
              "{} — every such fetch is a blocking round trip per step; "
              "coalesce into the epoch-boundary fetch"),
    "GS002": ("retrace-after-warm",
              "{} new trace(s) after epoch 1 at {} — the steady state "
              "should be fully warm; suspect a ragged tail batch, "
              "dtype drift, or a Python-value argument"),
    "GS003": ("rng-key-reuse",
              "RNG key with identical bits consumed twice: first at "
              "{}, again at {} — both draws see the same randomness; "
              "split and consume each subkey once"),
    "GS004": ("donated-buffer-access",
              "fetched an array that was donated at {} — donation "
              "invalidated that buffer; keep the jitted result (or "
              "drop the argument from donate_argnums) instead of "
              "re-reading the donated input"),
    "GS005": ("retrace-attribution",
              "post-warmup retrace of `{}` at {}: {} — the signature "
              "leaf(s) named moved between calls; pin the leaf's "
              "shape/dtype, pre-warm the new geometry, or drop a dead "
              "leaf from the signature (graftlint GL010)"),
    "GS006": ("mesh-drift",
              "input sharding of `{}` drifted at {}: {} — the jit "
              "boundary is silently resharding that leaf (a device "
              "transfer per dispatch); device_put the input into the "
              "first-dispatch layout once upstream, or make the new "
              "layout the one the executable is compiled for "
              "(graftmesh GL014-GL018)"),
}

#: jax.random functions whose first argument is a key they consume.
#: Creators (PRNGKey/key) and derivers (fold_in) are excluded — see
#: the module docstring.
_WATCHED_RANDOM = ("normal", "uniform", "bernoulli", "split",
                   "categorical", "randint", "permutation", "choice",
                   "gumbel", "truncated_normal", "exponential",
                   "shuffle", "laplace", "beta", "gamma", "poisson",
                   "dirichlet", "multivariate_normal")

_THIS_FILE = os.path.abspath(__file__)
_RUNTIME_FILE = os.path.abspath(runtime.__file__)
_SKIP_MARKERS = ("site-packages", "dist-packages",
                 os.sep + "jax" + os.sep, "importlib", "<frozen")


class GraftsanError(RuntimeError):
    """Raised at `sanitize(mode="strict")` scope exit when the run
    produced sanitizer findings. The message lists every finding with
    its attributed site."""


def _attribution_site(skip=2):
    """(path, line, function) of the innermost frame that is user or
    framework code — sanitizer/runtime internals, jax, and stdlib
    import machinery are walked past. Falls back to "<unknown>" when
    every frame is infrastructure (e.g. a pure-jax-internal event)."""
    try:
        frame = sys._getframe(skip)
    except ValueError:  # shallower stack than `skip`
        return "<unknown>", 0, "?"
    while frame is not None:
        path = frame.f_code.co_filename
        if not _is_infrastructure(path):
            return path, frame.f_lineno, frame.f_code.co_name
        frame = frame.f_back
    return "<unknown>", 0, "?"


def _is_infrastructure(path):
    abspath = os.path.abspath(path)
    if abspath in (_THIS_FILE, _RUNTIME_FILE):
        return True
    return any(marker in path for marker in _SKIP_MARKERS)


def _format_site(site):
    return "{}:{}".format(site[0], site[1])


def _key_fingerprint(key):
    """Canonical bytes of a PRNG key's bit content, or None for values
    we must not (tracers) or cannot (exotic dtypes) inspect. Typed key
    arrays go through `jax.random.key_data`; raw uint32 keys through
    numpy."""
    import jax
    import numpy as np

    if isinstance(key, jax.core.Tracer):
        return None
    try:
        data = key
        if getattr(getattr(key, "dtype", None), "name", "").startswith(
                "key"):
            data = jax.random.key_data(key)
        arr = np.asarray(data)
    except Exception:
        return None
    if arr.dtype.kind not in "ui":
        return None
    return arr.tobytes()


class Sanitizer:
    """The observer `sanitize()` installs into the runtime seam.

    All state is guarded by one lock: events arrive from the training
    thread, the async metric-reader thread, and the checkpoint worker
    concurrently. Attribution walks the recording thread's own stack,
    so each event lands on the line that caused it regardless of which
    thread recorded."""

    def __init__(self, mode="warn", event_log=None):
        self.mode = mode
        self.event_log = event_log
        self._lock = threading.Lock()
        #: (path, line) -> {"d2h"/"h2d"/"traces"/"compiles"/
        #: "cache_hits"/"cache_misses"/"key_uses": count}
        self._site_counts = {}
        self._findings = []
        self._finding_index = {}   # (rule, site-string) -> finding
        self._epochs_done = 0
        self._warm_marked = False  # notify_warm_mark() arms GS005
        self._seen_keys = {}       # fingerprint -> first-use site str
        self._donated = {}         # id(array) -> (weakref, site str)

    # -- runtime observer interface ------------------------------------

    def on_d2h(self, nbytes, tree):
        site = _attribution_site()
        with self._lock:
            self._bump(site, "d2h")
            if runtime.current_phase() == "step":
                self._violation(
                    "GS001", site,
                    VIOLATIONS["GS001"][1].format(
                        nbytes, _format_site(site)))
            self._check_donated(tree, site)

    def on_h2d(self, transfers, nbytes):
        site = _attribution_site()
        with self._lock:
            self._bump(site, "h2d", transfers)

    def on_compile(self, n_traces, n_compiles, cache_hits):
        site = _attribution_site()
        with self._lock:
            self._bump(site, "traces", n_traces)
            self._bump(site, "compiles", n_compiles)
            self._bump(site, "cache_hits", cache_hits)
            if (n_traces and self._epochs_done >= 1
                    and runtime.current_phase() == "step"):
                self._violation(
                    "GS002", site,
                    VIOLATIONS["GS002"][1].format(
                        n_traces, _format_site(site)))

    def on_cache_miss(self):
        site = _attribution_site()
        with self._lock:
            self._bump(site, "cache_misses")

    def on_epoch(self, epoch):
        with self._lock:
            self._epochs_done = max(self._epochs_done, epoch + 1)

    def on_warm_mark(self):
        """Arms GS005: every executable the workload needs is compiled
        (the serving engine's `mark_warm()`), so any later trace is a
        bug with a name."""
        with self._lock:
            self._warm_marked = True

    def on_retrace(self, label, diffs):
        """One attributed retrace from an InstrumentedJit. `diffs` is
        a tuple of (leaf path, old aval, new aval) naming what moved,
        or None when no prior signature shared the call's tree shape.
        Silent until armed — warmup traces are the expected cost of
        building the warm table, not findings."""
        site = _attribution_site()
        with self._lock:
            if not (self._warm_marked or self._epochs_done >= 1):
                return
            if diffs:
                detail = "; ".join(
                    "{} widened {} -> {}".format(path, old, new)
                    for path, old, new in diffs)
            else:
                detail = ("new call structure (no prior signature "
                          "with this tree shape to diff)")
            self._violation(
                "GS005", site,
                VIOLATIONS["GS005"][1].format(
                    label, _format_site(site), detail))

    def on_mesh_drift(self, label, drifts):
        """One attributed jit-boundary resharding from an
        InstrumentedJit (GS006). `drifts` is a tuple of (leaf path,
        sharding at first dispatch, sharding now). No warm gate:
        unlike a retrace, the baseline is by definition the first
        dispatch, so every drift is a real extra transfer."""
        site = _attribution_site()
        with self._lock:
            detail = "; ".join(
                "{} moved {} -> {}".format(path, old, new)
                for path, old, new in drifts)
            self._violation(
                "GS006", site,
                VIOLATIONS["GS006"][1].format(
                    label, _format_site(site), detail))

    def on_donation(self, args):
        import jax
        import weakref

        site = _attribution_site()
        site_str = _format_site(site)
        with self._lock:
            # Prune dead entries so id() recycling cannot mis-attribute
            # a fresh array to a long-freed donation.
            dead = [k for k, (ref, _) in self._donated.items()
                    if ref() is None]
            for k in dead:
                del self._donated[k]
            for leaf in jax.tree_util.tree_leaves(args):
                if isinstance(leaf, jax.Array):
                    try:
                        self._donated[id(leaf)] = (weakref.ref(leaf),
                                                   site_str)
                    except TypeError:  # pragma: no cover - no weakref
                        pass

    # -- jax.random watcher interface ----------------------------------

    def on_key_use(self, key):
        fingerprint = _key_fingerprint(key)
        if fingerprint is None:
            return
        site = _attribution_site()
        with self._lock:
            self._bump(site, "key_uses")
            first = self._seen_keys.get(fingerprint)
            if first is None:
                self._seen_keys[fingerprint] = _format_site(site)
            else:
                self._violation(
                    "GS003", site,
                    VIOLATIONS["GS003"][1].format(
                        first, _format_site(site)))

    # -- bookkeeping ---------------------------------------------------

    def _bump(self, site, kind, count=1):
        if not count:
            return
        bucket = self._site_counts.setdefault((site[0], site[1]), {})
        bucket[kind] = bucket.get(kind, 0) + count

    def _check_donated(self, tree, site):
        import jax

        for leaf in jax.tree_util.tree_leaves(tree):
            if not isinstance(leaf, jax.Array):
                continue
            entry = self._donated.get(id(leaf))
            if entry is not None and entry[0]() is leaf:
                self._violation(
                    "GS004", site,
                    VIOLATIONS["GS004"][1].format(entry[1]))

    def _violation(self, rule, site, message):
        # Already holding self._lock. Dedupe per (rule, line): steady
        # repetition raises the count, not the noise.
        key = (rule, _format_site(site))
        existing = self._finding_index.get(key)
        if existing is not None:
            existing["count"] += 1
            return
        finding = {"rule": rule, "title": VIOLATIONS[rule][0],
                   "path": site[0], "line": site[1],
                   "message": message, "count": 1}
        self._finding_index[key] = finding
        self._findings.append(finding)
        if self.mode == "warn":
            logger.warning("graftsan %s %s: %s", rule,
                           VIOLATIONS[rule][0], message)

    # -- results -------------------------------------------------------

    def findings(self):
        """Copies of the accumulated findings (thread-safe snapshot)."""
        with self._lock:
            return [dict(f) for f in self._findings]

    def site_counts(self):
        """{"path:line": {kind: count}} aggregate attribution table."""
        with self._lock:
            return {_format_site(site): dict(counts)
                    for site, counts in self._site_counts.items()}

    def finalize(self):
        """Emits the JSONL event and escalates per mode. Called by
        `sanitize()` at scope exit (after hooks are removed)."""
        findings = self.findings()
        events.log_job_event(
            "graftsan",
            {"mode": self.mode, "findings": findings,
             "site_counts": self.site_counts()},
            path=self.event_log)
        if self.mode == "strict" and findings:
            raise GraftsanError(
                "graftsan: {} finding(s) in strict mode:\n{}".format(
                    len(findings),
                    "\n".join("  {} {} {}:{} {}".format(
                        f["rule"], f["title"], f["path"], f["line"],
                        f["message"]) for f in findings)))


# -- jax.random watchers ------------------------------------------------


def _install_random_watchers(san):
    """Wraps the consuming jax.random functions to report first-arg
    key fingerprints. Returns {name: original} for teardown."""
    import jax

    originals = {}
    for name in _WATCHED_RANDOM:
        original = getattr(jax.random, name, None)
        if original is None:
            continue

        def _make(fn):
            @functools.wraps(fn)
            def _watched(key, *args, **kwargs):
                san.on_key_use(key)
                return fn(key, *args, **kwargs)
            _watched.__graftsan_original__ = fn
            return _watched

        originals[name] = original
        setattr(jax.random, name, _make(original))
    return originals


def _remove_random_watchers(originals):
    import jax

    for name, original in originals.items():
        setattr(jax.random, name, original)


def random_watchers_installed():
    """True when any jax.random function is currently wrapped — the
    "zero hooks when disabled" invariant's introspection point."""
    import jax

    return any(
        hasattr(getattr(jax.random, name, None),
                "__graftsan_original__")
        for name in _WATCHED_RANDOM)


# -- public entry points ------------------------------------------------


@contextlib.contextmanager
def sanitize(mode="warn", event_log=None):
    """Scoped runtime sanitizing: observer + jax.random watchers.

    Args:
        mode: "warn" logs each finding as it first occurs and reports
            all of them at exit; "strict" additionally raises
            `GraftsanError` at scope exit when any finding accumulated.
        event_log: Optional JSONL path for the "graftsan" job event;
            defaults to the CLOUD_TPU_EVENT_LOG env contract (see
            `utils.events.log_job_event`).

    Yields:
        The `Sanitizer`, for introspection (`findings()`,
        `site_counts()`) while the scope is live.
    """
    if mode not in ("warn", "strict"):
        raise ValueError(
            "Invalid graftsan mode {!r}. Expected \"warn\" or "
            "\"strict\".".format(mode))
    san = Sanitizer(mode=mode, event_log=event_log)
    # add/remove (not set/restore): the sanitizer STACKS with other
    # runtime observers — graftscope telemetry keeps counting while a
    # sanitize scope is live, and vice versa.
    runtime.add_observer(san)
    originals = _install_random_watchers(san)
    try:
        yield san
    finally:
        _remove_random_watchers(originals)
        runtime.remove_observer(san)
        san.finalize()


def env_mode():
    """The CLOUD_TPU_SANITIZE env contract -> None | "warn" | "strict".

    Unset / "0" / "off" / "false" disable; "strict" escalates; any
    other truthy value (the documented spelling is "1" or "warn")
    means warn.
    """
    value = os.environ.get("CLOUD_TPU_SANITIZE", "").strip().lower()
    if value in ("", "0", "off", "false", "none"):
        return None
    return "strict" if value == "strict" else "warn"


def env_scope():
    """A context manager for library entry points (Trainer.fit/
    evaluate): a real `sanitize()` scope when CLOUD_TPU_SANITIZE asks
    for one and no sanitizer is already active, else a no-op. Nested
    fits under an explicit `sanitize()` reuse the outer scope instead
    of stacking. Only SANITIZERS suppress: another observer kind on
    the seam (graftscope telemetry) must not swallow the env ask."""
    mode = env_mode()
    if mode is None or any(isinstance(obs, Sanitizer)
                           for obs in runtime.observers()):
        return contextlib.nullcontext()
    return sanitize(mode=mode)
