"""Ambient distribution runtime for TPU-native execution.

This is the TPU-native replacement for the reference's ambient strategy
mechanism (`tf.distribute.experimental_set_strategy(strategy)`, reference
core/preprocess.py:148-149) and its TPU bootstrap dance (the 40x10s
`TPU_CONFIG`-polling `TPUClusterResolver`, reference
core/preprocess.py:215-262). On TPU-VMs the chips are local devices, so
bootstrap collapses to a bounded wait on `jax.devices()`; multi-host pods
bootstrap through `jax.distributed.initialize` driven by an env-var
contract (the analogue of the reference's `TF_CONFIG`/`TPU_CONFIG`
injection, reference core/deploy.py:159-161).

The initialized context — a `jax.sharding.Mesh` plus the strategy name —
is ambient: `cloud_tpu.training.Trainer` and the `run()`-generated runner
scripts pick it up via `global_mesh()` without user code changes.

Env contract (set by the deployer on every remote process):
    CLOUD_TPU_COORDINATOR_ADDRESS  host:port of process 0
    CLOUD_TPU_NUM_PROCESSES        total process count
    CLOUD_TPU_PROCESS_ID           this process's index
    CLOUD_TPU_RUNNING_REMOTELY     guard consumed by `run.remote()`
    CLOUD_TPU_MESH                 optional mesh layout, e.g.
                                   "dp:-1,tp:2" (-1 = infer from device
                                   count); lets a launched job request
                                   tensor/sequence/expert axes without
                                   code changes
"""

import logging
import os
import threading
import time

logger = logging.getLogger("cloud_tpu")

# Known strategy names, selected by the strategy compiler
# (cloud_tpu/core/preprocess.py) from the cluster shape.
STRATEGIES = ("one_device", "mirrored", "multi_worker", "tpu_slice",
              "tpu_pod", "multi_slice")

_context = None


class DistributionContext:
    """The ambient distribution state: strategy name + device mesh."""

    def __init__(self, strategy, mesh):
        self.strategy = strategy
        self.mesh = mesh

    @property
    def num_devices(self):
        return self.mesh.devices.size

    def __repr__(self):
        return "DistributionContext(strategy={!r}, mesh_shape={})".format(
            self.strategy, dict(self.mesh.shape))


def _wait_for_devices(min_devices=1, retries=40, retry_interval_secs=10.0):
    """Bounded wait for accelerator availability.

    Parity with the reference's TPU-provisioning wait
    (core/preprocess.py:238-261: 40 retries x 10s), collapsed to a local
    device query because TPU-VM chips are local.
    """
    import jax

    last_err = None
    for attempt in range(retries):
        try:
            devices = jax.devices()
            if len(devices) >= min_devices:
                return devices
        except RuntimeError as e:  # backend not ready yet
            last_err = e
        if attempt < retries - 1:
            time.sleep(retry_interval_secs)
    raise RuntimeError(
        "Accelerator devices did not become available after {} attempts "
        "({}s apart). Last error: {}".format(
            retries, retry_interval_secs, last_err))


def initialize(strategy="tpu_slice",
               axis_names=None,
               mesh_shape=None,
               dcn_mesh_shape=None,
               coordinator_address=None,
               num_processes=None,
               process_id=None,
               devices=None,
               retries=40,
               retry_interval_secs=10.0):
    """Initializes the ambient distribution context.

    Args:
        strategy: One of `STRATEGIES`. Multi-process strategies
            ("multi_worker", "tpu_pod") run `jax.distributed.initialize`
            first, using the env contract when args are not given.
        axis_names: Mesh axis names. Default (None) is the CLOUD_TPU_MESH
            env layout when set, else a pure data-parallel 1D mesh
            ("dp",); pass e.g. ("dp", "tp") with `mesh_shape` for hybrid
            layouts (explicit args always beat the env).
        mesh_shape: Optional tuple of ints matching `axis_names`. Default:
            all devices on the first axis. For "multi_slice" this is the
            PER-SLICE (ICI) shape; the full mesh axis sizes are
            elementwise `dcn_mesh_shape * mesh_shape`.
        dcn_mesh_shape: ("multi_slice" only) how each axis spans slices
            over DCN; same length as axis_names. Default: all slices on
            the first (data) axis — dp gradient reductions cross DCN,
            tp/sp/pp collectives stay on intra-slice ICI, the standard
            multi-slice layout. Slices are identified by the devices'
            `slice_index` (fallback for simulation: contiguous groups of
            CLOUD_TPU_NUM_SLICES equal chunks).
        coordinator_address / num_processes / process_id: Multi-process
            bootstrap parameters; default to the CLOUD_TPU_* env contract.
        devices: Explicit device list (tests); default `jax.devices()`
            after a bounded availability wait.
        retries / retry_interval_secs: Device-wait bounds (reference
            parity: 40 x 10s).

    Returns:
        The installed `DistributionContext`.
    """
    global _context
    if strategy not in STRATEGIES:
        raise ValueError(
            "Unknown strategy {!r}. Expected one of {}.".format(
                strategy, STRATEGIES))

    # Launch-time mesh layout via env contract: only when the caller did
    # not pass an explicit layout (generated runners pass neither).
    env_mesh = os.environ.get("CLOUD_TPU_MESH")
    if axis_names is None and mesh_shape is None and env_mesh:
        axis_names, mesh_shape = _parse_mesh_env(env_mesh)
    elif axis_names is None:
        axis_names = ("dp",)

    if strategy in ("multi_worker", "tpu_pod", "multi_slice"):
        _maybe_init_distributed(coordinator_address, num_processes,
                                process_id)

    import jax
    from jax.sharding import Mesh
    import numpy as np

    if devices is None:
        if strategy == "one_device":
            devices = _wait_for_devices(1, retries, retry_interval_secs)[:1]
        else:
            devices = _wait_for_devices(1, retries, retry_interval_secs)

    if strategy == "multi_slice":
        device_array = _hybrid_device_array(devices, axis_names,
                                            mesh_shape, dcn_mesh_shape)
    else:
        device_array = np.asarray(devices)
        mesh_shape = _infer_mesh_shape(mesh_shape, device_array.size)
        if mesh_shape is not None:
            if len(mesh_shape) != len(axis_names):
                raise ValueError(
                    "mesh_shape {} does not match axis_names {}.".format(
                        mesh_shape, axis_names))
            device_array = device_array.reshape(mesh_shape)
        else:
            device_array = device_array.reshape(
                (device_array.size,) + (1,) * (len(axis_names) - 1))

    mesh = Mesh(device_array, axis_names)
    _context = DistributionContext(strategy, mesh)
    logger.info("cloud_tpu runtime initialized: %r", _context)
    return _context


def _infer_mesh_shape(mesh_shape, total):
    """Resolves one -1 entry against `total` devices (env-contract
    layouts like "dp:-1,tp:2" leave the data axis inferred)."""
    if mesh_shape is None or -1 not in mesh_shape:
        return mesh_shape
    known = 1
    for dim in mesh_shape:
        if dim != -1:
            known *= dim
    if known <= 0 or mesh_shape.count(-1) != 1 or total % known:
        raise ValueError(
            "Cannot infer mesh_shape {} for {} devices.".format(
                mesh_shape, total))
    return tuple(total // known if d == -1 else d for d in mesh_shape)


def _group_by_slice(devices):
    """Devices grouped by TPU slice.

    Real multi-slice platforms expose `slice_index` per device; when
    absent (CPU simulation, single slice), CLOUD_TPU_NUM_SLICES splits
    the flat list into contiguous equal chunks so the layout logic can
    be exercised anywhere.
    """
    groups = {}
    for d in devices:
        idx = getattr(d, "slice_index", None)
        if idx is None:
            break
        groups.setdefault(idx, []).append(d)
    else:
        if len(groups) > 1:
            return [groups[k] for k in sorted(groups)]
    n = int(os.environ.get("CLOUD_TPU_NUM_SLICES", "1"))
    if n <= 1:
        return [list(devices)]
    if len(devices) % n:
        raise ValueError(
            "CLOUD_TPU_NUM_SLICES={} does not divide {} devices.".format(
                n, len(devices)))
    per = len(devices) // n
    return [list(devices[i * per:(i + 1) * per]) for i in range(n)]


def _hybrid_device_array(devices, axis_names, ici_shape, dcn_shape):
    """DCN x ICI hybrid mesh layout (the multi-slice analogue of
    jax.experimental.mesh_utils.create_hybrid_device_mesh, built
    directly from the slice grouping so it also works on simulated
    slices).

    Each mesh axis k has size dcn[k] * ici[k]; devices are arranged so
    that moving along an axis inside one ICI block stays within a
    slice (fast ICI hops) and the dcn factor strides across slices
    (DCN hops). With the default dcn = (num_slices, 1, ...), dp spans
    slices and every other axis is slice-local.
    """
    import numpy as np

    groups = _group_by_slice(devices)
    num_slices = len(groups)
    per_slice = len(groups[0])
    if any(len(g) != per_slice for g in groups):
        raise ValueError("Slices are unequal: {}.".format(
            [len(g) for g in groups]))
    rank = len(axis_names)
    if dcn_shape is None:
        dcn_shape = (num_slices,) + (1,) * (rank - 1)
    if len(dcn_shape) != rank:
        raise ValueError(
            "dcn_mesh_shape {} does not match axis_names {}.".format(
                dcn_shape, axis_names))
    dcn_total = int(np.prod(dcn_shape))
    if dcn_total != num_slices:
        raise ValueError(
            "dcn_mesh_shape {} needs {} slices; found {}.".format(
                dcn_shape, dcn_total, num_slices))
    if ici_shape is None:
        ici_shape = (per_slice,) + (1,) * (rank - 1)
    if len(ici_shape) != rank:
        raise ValueError(
            "mesh_shape {} does not match axis_names {}.".format(
                ici_shape, axis_names))
    # Env-contract layouts leave one dim inferred ("dp:-1,tp:2"); for
    # multi_slice the per-slice device count is the inference base.
    ici_shape = _infer_mesh_shape(tuple(ici_shape), per_slice)
    if int(np.prod(ici_shape)) != per_slice:
        raise ValueError(
            "Per-slice mesh_shape {} needs {} devices; each slice has "
            "{}.".format(ici_shape, int(np.prod(ici_shape)), per_slice))

    # [dcn0, dcn1, ..., ici0, ici1, ...] -> interleave -> combined.
    arr = np.array([np.array(g).reshape(ici_shape) for g in groups])
    arr = arr.reshape(tuple(dcn_shape) + tuple(ici_shape))
    order = []
    for k in range(rank):
        order.extend([k, rank + k])
    arr = np.transpose(arr, order)
    return arr.reshape(tuple(d * i for d, i in zip(dcn_shape, ici_shape)))


def _maybe_init_distributed(coordinator_address, num_processes, process_id):
    """Runs `jax.distributed.initialize` from args or the env contract."""
    import jax

    coordinator_address = coordinator_address or os.environ.get(
        "CLOUD_TPU_COORDINATOR_ADDRESS")
    if num_processes is None:
        num_processes = _env_int("CLOUD_TPU_NUM_PROCESSES")
    if process_id is None:
        process_id = _env_int("CLOUD_TPU_PROCESS_ID")

    if coordinator_address is None and num_processes in (None, 1):
        # Single-process "pod": legitimate in tests and on a single
        # TPU-VM; nothing to bootstrap.
        logger.info("No multi-process env contract found; running "
                    "single-process.")
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)


def _parse_mesh_env(value):
    """"dp:-1,tp:2" -> (("dp", "tp"), (-1, 2)). Shapeless entries
    ("dp,tp:2") default to -1 (inferred)."""
    names, shape = [], []
    for part in value.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, dim = part.partition(":")
        names.append(name.strip())
        shape.append(int(dim) if dim else -1)
    if not names:
        raise ValueError("Empty CLOUD_TPU_MESH value: {!r}".format(value))
    return tuple(names), tuple(shape)


def _env_int(name):
    value = os.environ.get(name)
    return int(value) if value is not None else None


class BackendUnavailable(RuntimeError):
    """Training stopped making progress on the accelerator.

    The typed form of a hang: graftwatch's stall handler
    (monitoring/watch.py) raises THIS in the watched thread within a
    bounded deadline — not a 30-minute outer timeout with no artifact.
    Carries the deadline that was exceeded and the flight-recorder path
    when one was written.

    `fault_kind` places it in graftguard's typed-fault taxonomy
    (training/resilience.py) — the retry loop classifies every caught
    fault by this attribute.
    """

    fault_kind = "backend_unavailable"

    def __init__(self, message="accelerator backend unavailable",
                 deadline=None, blackbox=None):
        super().__init__(message)
        self.deadline = deadline
        self.blackbox = blackbox


def is_initialized():
    return _context is not None


def context():
    if _context is None:
        raise RuntimeError(
            "cloud_tpu runtime is not initialized. Call "
            "cloud_tpu.parallel.runtime.initialize() first (the run() "
            "generated runner does this automatically).")
    return _context


def global_mesh():
    """The ambient mesh, or None when uninitialized (single-device ok)."""
    return _context.mesh if _context is not None else None


def reset():
    """Clears the ambient context (test isolation)."""
    global _context
    _context = None


# --------------------------------------------------------------------------
# Host<->device transfer observability.
#
# H2D: every feed-path entry point (sharding.shard_batch /
# make_global_batch, Trainer's no-mesh device_put branches,
# prefetch_to_device's default feed, and the DeviceResidentDataset
# one-time upload) records what it is about to move.
#
# D2H: every device->host readback goes through `device_fetch` (or calls
# `record_d2h` right before its own jax.device_get), so "the async host
# loop issues at most ONE fetch per logging interval" is a counted
# invariant, not a wall-clock inference. One `device_fetch` CALL counts
# as one fetch no matter how many leaves the tree has — coalescing N
# metric reads into one call is exactly the round-trip win the counter
# exists to pin.
#
# Tests assert transfer behavior from these counters
# instead of inferring it from wall clock — in particular that the
# device-resident pipeline does ZERO per-step H2D data transfers after
# its one-time upload, and that input_cast="bfloat16" halves the bytes
# on the wire.

_transfer_stats = {"h2d_transfers": 0, "h2d_bytes": 0,
                   "d2h_fetches": 0, "d2h_bytes": 0}

# --------------------------------------------------------------------------
# graftsan observer seam (cloud_tpu.analysis.sanitizer).
#
# The counters above say THAT a transfer/compile happened; the sanitizer
# wants to know WHERE. Rather than having the sanitizer monkeypatch the
# record_* functions (fragile against `from runtime import record_d2h`
# binding), each record site notifies a single module-level observer.
# When no observer is installed — the default, and the production state
# — the cost is one global load + None check per record call; nothing
# is wrapped, patched, or allocated.
#
# Phases are thread-local labels the Trainer (and its worker threads)
# publish so an observer can tell a step-loop fetch from a sanctioned
# boundary fetch: "step" inside the epoch step loop, "boundary" between
# epochs, "async_reader" / "checkpoint" on the worker threads. The
# label is advisory context for attribution, never control flow.

_observer = None
_observers = ()
_phase = threading.local()


class _FanoutObserver:
    """Dispatch target when more than one observer is installed
    (graftsan + graftscope stacking). Forwards each event to every
    target that implements it; a missing method on one target never
    hides the event from the others. Hot-path cost with a single
    observer is unchanged: the fanout only exists with >= 2."""

    __slots__ = ("targets",)

    def __init__(self, targets):
        self.targets = tuple(targets)

    def _fan(self, method, *args):
        for target in self.targets:
            fn = getattr(target, method, None)
            if fn is not None:
                fn(*args)

    def on_h2d(self, transfers, nbytes):
        self._fan("on_h2d", transfers, nbytes)

    def on_d2h(self, nbytes, tree):
        self._fan("on_d2h", nbytes, tree)

    def on_compile(self, n_traces, n_compiles, cache_hits):
        self._fan("on_compile", n_traces, n_compiles, cache_hits)

    def on_cache_miss(self):
        self._fan("on_cache_miss")

    def on_epoch(self, epoch):
        self._fan("on_epoch", epoch)

    def on_donation(self, args):
        self._fan("on_donation", args)

    def on_warm_mark(self):
        self._fan("on_warm_mark")

    def on_retrace(self, label, diffs):
        self._fan("on_retrace", label, diffs)

    def on_mesh_drift(self, label, drifts):
        self._fan("on_mesh_drift", label, drifts)


def _rebuild_dispatch():
    """Recomputes the fast dispatch target `_observer` from the
    installed set: None (record sites stay one None-check), the sole
    observer (direct calls, no indirection), or a fanout."""
    global _observer
    if not _observers:
        _observer = None
    elif len(_observers) == 1:
        _observer = _observers[0]
    else:
        _observer = _FanoutObserver(_observers)


def add_observer(observer):
    """Adds `observer` to the installed set (idempotent). Observers
    see `on_h2d(transfers, nbytes)`, `on_d2h(nbytes, tree)`,
    `on_compile(n_traces, n_compiles, cache_hits)`, `on_cache_miss()`,
    `on_epoch(epoch)`, `on_donation(args)`, `on_warm_mark()`,
    `on_retrace(label, diffs)`, `on_mesh_drift(label, drifts)` — all
    best-effort, called inline at record time on whatever thread
    recorded; any subset of those methods may be implemented when
    stacked. Returns `observer`."""
    global _observers
    if observer is not None and observer not in _observers:
        _observers = _observers + (observer,)
        _rebuild_dispatch()
    return observer


def remove_observer(observer):
    """Removes `observer` from the installed set (no-op if absent)."""
    global _observers
    if observer in _observers:
        _observers = tuple(o for o in _observers if o is not observer)
        _rebuild_dispatch()


def observers():
    """Snapshot of the installed observer set (install order)."""
    return _observers


def set_observer(observer):
    """Legacy single-observer API: replaces the WHOLE installed set
    with `observer` (or clears it for None). Returns the previous
    dispatch target so scoped installers can restore it. New code —
    anything that must coexist with another observer — uses
    `add_observer`/`remove_observer` instead."""
    global _observers
    previous = _observer
    _observers = (observer,) if observer is not None else ()
    _rebuild_dispatch()
    return previous


def get_observer():
    """The current dispatch target: None, the sole observer, or the
    internal fanout when several are stacked."""
    return _observer


def set_phase(name):
    """Sets this thread's phase label; returns the previous label."""
    previous = getattr(_phase, "name", None)
    _phase.name = name
    return previous


def current_phase():
    """This thread's phase label, or None when never set."""
    return getattr(_phase, "name", None)


def notify_epoch(epoch):
    """Tells the observer (if any) that epoch `epoch` just finished."""
    if _observer is not None:
        _observer.on_epoch(epoch)


def notify_warm_mark():
    """Tells the observer (if any) that warmup just finished — every
    executable the workload needs is compiled, so from here on a trace
    is a bug and `on_retrace` events carry blame (GS005). getattr-
    guarded: observers that predate the event simply never see it."""
    if _observer is not None:
        fn = getattr(_observer, "on_warm_mark", None)
        if fn is not None:
            fn()


def _notify_retrace(label, diffs):
    """Forwards one attributed retrace to the observer (if any)."""
    if _observer is not None:
        fn = getattr(_observer, "on_retrace", None)
        if fn is not None:
            fn(label, diffs)


def _notify_mesh_drift(label, drifts):
    """Forwards one attributed jit-boundary resharding to the observer
    (if any): `drifts` is a tuple of (leaf path, sharding at first
    dispatch, sharding now) — the GS006 mesh-drift event. getattr-
    guarded like on_warm_mark: observers that predate the event never
    see it."""
    if _observer is not None:
        fn = getattr(_observer, "on_mesh_drift", None)
        if fn is not None:
            fn(label, drifts)


def record_h2d(batch):
    """Counts the host->device bytes about to be transferred for `batch`.

    Only host-resident leaves count: a leaf that is already a `jax.Array`
    costs nothing to "transfer" again (device_put is a no-op or a
    device-to-device move), so it is skipped. Python scalars and lists are
    measured through `np.asarray`. Returns the byte count recorded, so the
    one-time resident upload can report its own size.
    """
    import jax
    import numpy as np

    transfers = 0
    total = 0
    for leaf in jax.tree_util.tree_leaves(batch):
        if isinstance(leaf, jax.Array):
            continue
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None:
            nbytes = np.asarray(leaf).nbytes
        transfers += 1
        total += int(nbytes)
    if transfers:
        _transfer_stats["h2d_transfers"] += transfers
        _transfer_stats["h2d_bytes"] += total
        if _observer is not None:
            _observer.on_h2d(transfers, total)
    return total


def record_d2h(tree):
    """Counts one device->host fetch about to be issued for `tree`.

    The unit is the ROUND TRIP, not the leaf: a coalesced
    `jax.device_get` of a whole metric pytree is one host round trip
    regardless of leaf count, so one call here increments
    `d2h_fetches` by exactly one. Bytes sum over the `jax.Array`
    leaves (host-resident leaves ride along for free — they are not
    fetched). A tree with no device leaves records nothing: there is
    no round trip to count. Returns the byte count recorded.
    """
    import jax

    total = 0
    device_leaves = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            device_leaves += 1
            total += int(leaf.nbytes)
    if device_leaves:
        _transfer_stats["d2h_fetches"] += 1
        _transfer_stats["d2h_bytes"] += total
        if _observer is not None:
            _observer.on_d2h(total, tree)
    return total


def device_fetch(tree):
    """The sanctioned instrumented readback: record, then device_get.

    All Trainer device->host reads route through here so the
    d2h counters stay an exhaustive census of fetch sites — and so one
    graftscope span ("d2h_fetch") times every round trip. Returns
    `jax.device_get(tree)` (host numpy leaves, same structure).
    """
    import jax

    record_d2h(tree)
    from cloud_tpu.monitoring import spans

    with spans.span("d2h_fetch"):
        return jax.device_get(tree)


def transfer_stats():
    """A snapshot of the process-wide transfer counters (H2D + D2H)."""
    return dict(_transfer_stats)


def reset_transfer_stats():
    """Zeroes all transfer counters (test isolation / warm-up
    barrier)."""
    for key in _transfer_stats:
        _transfer_stats[key] = 0


# --------------------------------------------------------------------------
# Compilation observability.
#
# The same doctrine as the transfer counters above, applied to the other
# uncounted wall-clock sink: trace + XLA compile. Every framework
# `jax.jit` site (Trainer steps, decode prefill/step, speculative round
# functions) goes through `instrumented_jit`, so "a steady-state epoch
# performs ZERO new traces/compiles" is a counted invariant a test can
# pin, not a wall-clock inference.
#
# n_traces  — times a wrapped function body was re-traced (bumped from
#             inside the traced body, so it fires exactly when jax
#             actually retraces: dispatch-cache misses and .lower()).
# n_compiles — executables built (dispatch-path misses + explicit AOT
#             `.compile()` calls).
# compile_seconds — wall seconds spent in calls that traced. On the
#             dispatch path this includes the first execution (jax
#             offers no clean split there); AOT `.compile()` timings are
#             pure compile.
# cache_hits — persistent-compile-cache hits (fed by the
#             `compile_cache` module's jax.monitoring listener).

_compile_stats = {"n_traces": 0, "n_compiles": 0,
                  "compile_seconds": 0.0, "cache_hits": 0}


class RetraceWarning(UserWarning):
    """A steady-state epoch compiled something new.

    Raised as a warning (opt-in: an exception) by the Trainer's retrace
    sentinel when `compile_stats()` moved during an epoch that should
    have been fully warm — the usual culprits are a ragged tail batch,
    a dtype drift in the input pipeline, or a new decode prompt length.
    """


def record_compile(n_traces=0, n_compiles=0, compile_seconds=0.0,
                   cache_hits=0):
    """Adds to the process-wide compile counters."""
    _compile_stats["n_traces"] += n_traces
    _compile_stats["n_compiles"] += n_compiles
    _compile_stats["compile_seconds"] += compile_seconds
    _compile_stats["cache_hits"] += cache_hits
    if _observer is not None and (n_traces or n_compiles or cache_hits):
        _observer.on_compile(n_traces, n_compiles, cache_hits)


def compile_stats():
    """A snapshot of the process-wide compile counters."""
    return dict(_compile_stats)


def reset_compile_stats():
    """Zeroes all compile counters (test isolation / warm-up
    barrier). Does NOT clear jax's own caches — an executable compiled
    before the reset stays warm, which is exactly what a steady-state
    invariant wants."""
    _compile_stats["n_traces"] = 0
    _compile_stats["n_compiles"] = 0
    _compile_stats["compile_seconds"] = 0.0
    _compile_stats["cache_hits"] = 0


def _aval_signature(args):
    """A hashable (treedef, leaf-aval) key for the warm-executable table.

    Returns None when any leaf lacks shape/dtype (python scalars,
    strings) — those calls fall back to the ordinary jit dispatch path
    rather than risking a wrong executable match.
    """
    import jax
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            return None
        sig.append((tuple(shape),
                    jax.dtypes.canonicalize_dtype(np.dtype(dtype))))
    return (treedef, tuple(sig))


class _InstrumentedLowered:
    """Proxy over `jax.stages.Lowered` that counts `.compile()`."""

    def __init__(self, lowered):
        self._lowered = lowered

    def compile(self, *args, **kwargs):
        t0 = time.perf_counter()
        compiled = self._lowered.compile(*args, **kwargs)
        record_compile(n_compiles=1,
                       compile_seconds=time.perf_counter() - t0)
        return compiled

    def __getattr__(self, name):
        return getattr(self._lowered, name)


class InstrumentedJit:
    """`jax.jit` with compile counting and an AOT warm-start table.

    Drop-in at call sites: `__call__` and `.lower()` mirror the jitted
    function. Tracing is detected from inside the traced body (a
    counter bump that only runs when jax actually retraces), so cached
    dispatches cost one integer compare and no counter traffic.

    `.warm(*specs)` AOT-compiles for the given `ShapeDtypeStruct`s (or
    example arrays) and installs the executable in a signature-keyed
    table that `__call__` consults first — a warmed call never enters
    jit dispatch at all, so step 1 after `Trainer.warmup()` runs
    trace-free. Signature mismatches (and executables whose sharding
    check rejects the actual args) fall back to the jit path; the warm
    table is an accelerator, never a correctness gate.
    """

    def __init__(self, fun, **jit_kwargs):
        import functools
        import jax

        self._fun = fun
        self._label = getattr(fun, "__name__", None) or repr(fun)
        self._trace_count = 0
        self._warm = {}
        # treedef -> leaf-aval tuple of the LAST traced call with that
        # structure. Written only when a trace actually fired (rare by
        # construction), read only to attribute the NEXT trace: the
        # diff against it names the exact leaf whose avals moved.
        self._sig_history = {}
        # aval signature -> per-leaf (path, sharding str) tuple of the
        # FIRST observed dispatch with that signature. Only populated
        # while an observer is installed (graftsan): a later dispatch
        # whose shardings differ is an implicit reshard at the jit
        # boundary, forwarded as the GS006 mesh-drift event.
        self._shard_baseline = {}
        # Donated positions, kept for the graftsan observer: donation
        # invalidates the caller's buffer, so the sanitizer tracks the
        # donated arrays (by weakref) to catch later reads of them.
        donate = jit_kwargs.get("donate_argnums")
        if donate is None:
            donate = ()
        elif isinstance(donate, int):
            donate = (donate,)
        self._donate_argnums = tuple(donate)
        # The warm table matches on positional avals only; static or
        # keyword-routed arguments would make the signature ambiguous.
        self._warmable = not any(
            jit_kwargs.get(k) for k in ("static_argnums", "static_argnames"))

        def _shim(*args, **kwargs):
            # Runs at TRACE time only: jax executes the python body
            # exactly when (re)tracing, which is the event we count.
            self._trace_count += 1
            record_compile(n_traces=1)
            return fun(*args, **kwargs)

        try:
            functools.update_wrapper(_shim, fun)
        except AttributeError:  # functools.partial etc.
            pass
        self._jitted = jax.jit(_shim, **jit_kwargs)

    @property
    def n_traces(self):
        """Times THIS wrapper's body was traced (per-site counter)."""
        return self._trace_count

    def __call__(self, *args, **kwargs):
        if _observer is not None and self._donate_argnums:
            _observer.on_donation(
                [args[i] for i in self._donate_argnums
                 if 0 <= i < len(args)])
        sig = None
        if (self._warm or _observer is not None) and not kwargs:
            sig = _aval_signature(args)
        if _observer is not None and sig is not None:
            self._check_mesh_drift(sig, args)
        if self._warm and sig is not None:
            compiled = self._warm.get(sig)
            if compiled is not None:
                try:
                    return compiled(*args)
                except Exception:
                    # Aval match but sharding/layout rejection: evict
                    # and let jit dispatch handle it from now on.
                    self._warm.pop(sig, None)
        before = self._trace_count
        t0 = time.perf_counter()
        out = self._jitted(*args, **kwargs)
        if self._trace_count != before:
            record_compile(n_compiles=1,
                           compile_seconds=time.perf_counter() - t0)
            if not kwargs:
                self._attribute_trace(args)
        return out

    def _check_mesh_drift(self, sig, args):
        """GS006: the first observed dispatch per aval signature
        records every jax.Array input leaf's concrete sharding (the
        mesh AND the spec, via its str form); a later dispatch with
        the same signature but different leaf shardings means the jit
        boundary is silently resharding — a device transfer per call —
        and the observer gets the exact leaves with both layouts.
        Runs only while an observer is installed, so the unobserved
        hot path never flattens shardings."""
        import jax

        try:
            flat, _ = jax.tree_util.tree_flatten_with_path(args)
            current = tuple(
                ("args" + jax.tree_util.keystr(path), str(leaf.sharding))
                for path, leaf in flat
                if isinstance(leaf, jax.Array))
        except Exception:
            return  # exotic leaves: attribution is best-effort
        baseline = self._shard_baseline.get(sig)
        if baseline is None:
            self._shard_baseline[sig] = current
            return
        if baseline == current:
            return
        base = dict(baseline)
        drifts = tuple(
            (path, base[path], sharding)
            for path, sharding in current
            if path in base and base[path] != sharding)
        if drifts:
            _notify_mesh_drift(self._label, drifts)

    def _attribute_trace(self, args):
        """Names the leaves that forced the trace that just fired.

        Diffs the call's aval signature against the closest previously
        seen signature of the same tree structure (warm table first,
        then the per-structure trace history) and forwards the diff to
        the observer as an `on_retrace` event — the GS005 runtime dual
        of graftlint GL010. Runs only on traced calls, so steady-state
        dispatch cost is untouched."""
        sig = _aval_signature(args)
        if sig is None:
            _notify_retrace(self._label, None)
            return
        treedef, leaves = sig
        diffs = None
        if _observer is not None:
            candidates = [s[1] for s in self._warm if s[0] == treedef]
            prior = self._sig_history.get(treedef)
            if prior is not None:
                candidates.append(prior)
            best = None
            for old in candidates:
                if len(old) != len(leaves):
                    continue
                changed = [i for i, (a, b) in enumerate(zip(old, leaves))
                           if a != b]
                if changed and (best is None or len(changed) < len(best[0])):
                    best = (changed, old)
            if best is not None:
                diffs = self._leaf_diffs(args, best[1], leaves, best[0])
            _notify_retrace(self._label, diffs)
        self._sig_history[treedef] = leaves

    @staticmethod
    def _leaf_diffs(args, old, new, changed):
        """[(leaf path, old aval, new aval), ...] with human names:
        `args[1]['page_table']` widened `int32[4,16]` -> `int32[8,16]`."""
        import jax

        flat, _ = jax.tree_util.tree_flatten_with_path(args)

        def aval(entry):
            shape, dtype = entry
            return "{}[{}]".format(dtype, ",".join(map(str, shape)))

        out = []
        for i in changed:
            path = ("args" + jax.tree_util.keystr(flat[i][0])
                    if i < len(flat) else "leaf {}".format(i))
            out.append((path, aval(old[i]), aval(new[i])))
        return tuple(out)

    def lower(self, *args, **kwargs):
        return _InstrumentedLowered(self._jitted.lower(*args, **kwargs))

    def warm(self, *specs):
        """AOT-compiles for `specs` (ShapeDtypeStructs or example
        arrays) and installs the executable in the warm table. Returns
        the `jax.stages.Compiled`. Idempotent per signature: a spec
        already warm returns its executable without re-lowering, so
        `warmup()` followed by `fit(warm_start=True)` compiles once."""
        sig = _aval_signature(specs) if self._warmable else None
        if sig is not None and sig in self._warm:
            return self._warm[sig]
        compiled = self.lower(*specs).compile()
        if sig is not None:
            self._warm[sig] = compiled
        return compiled

    def warm_signatures(self):
        """The aval signatures currently warm (introspection/tests)."""
        return tuple(self._warm)

    def clear_warm(self):
        self._warm.clear()

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def instrumented_jit(fun, **jit_kwargs):
    """`jax.jit` replacement that feeds `compile_stats()`.

    Usage matches jit: `instrumented_jit(f, donate_argnums=0)` or
    `@functools.partial(instrumented_jit, donate_argnums=1)`.
    """
    return InstrumentedJit(fun, **jit_kwargs)
