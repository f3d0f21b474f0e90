"""Input pipeline: batched, shuffled, host-sharded iteration.

The reference delegates input pipelines to `tf.data` and per-worker
auto-sharding inside `tf.distribute` (reference cloud_fit/client.py:151-189
ships datasets as serialized tf.functions). The TPU-native pipeline is a
small, dependency-free design: numpy-backed batching on the host, static
shapes for XLA (tail batch dropped or padded), and per-process sharding
for multi-host pods. Overlap of host batching with device compute comes
from JAX async dispatch: the Trainer never blocks on device values inside
the step loop, so batch i+1 is prepared while step i runs.
"""

import logging
import os

import numpy as np

import jax
import jax.numpy as jnp

from cloud_tpu.parallel import runtime as runtime_lib

logger = logging.getLogger("cloud_tpu")


def epoch_permutation(num_examples, seed, epoch):
    """The canonical per-epoch shuffle order, shared host/device.

    Both the host path (`ArrayDataset._epoch_order`) and the
    device-resident executable (`Trainer._make_resident_run`) draw their
    order from the same jax threefry stream:
    `permutation(fold_in(PRNGKey(seed), epoch), num_examples)`. threefry
    is bit-deterministic across backends, so `cache="device"` reproduces
    the host path's batches exactly at a fixed seed (pinned by
    tests/unit/test_resident_data.py). Computed on the CPU backend when
    one is available so host-side epoch prep never queues behind the
    accelerator's dispatch stream.
    """
    def _draw():
        key = jax.random.fold_in(jax.random.PRNGKey(seed), epoch)
        return np.asarray(jax.random.permutation(key, num_examples))

    try:
        cpu = jax.local_devices(backend="cpu")[0]
    except (RuntimeError, ValueError):
        return _draw()
    with jax.default_device(cpu):
        return _draw()


class ArrayDataset:
    """In-memory dataset of (features, labels) arrays.

    Args:
        x: Array or pytree of arrays with a common leading dimension.
        y: Optional array of labels (kept separate so loss/metric code can
            treat batches as (x, y) tuples).
        batch_size: Global batch size (across all processes/devices).
        shuffle: Reshuffle each epoch.
        seed: Shuffle seed (kept per-epoch deterministic so every process
            draws the same permutation — required for multi-host sharding
            to stay aligned).
        drop_remainder: Drop the tail batch (True keeps shapes static for
            XLA; False pads the tail by wrapping to the start).
        sample_weight: Optional [num_examples] per-example weights
            (the Keras `fit(sample_weight=)` contract); when set,
            batches are (x, y, w) triples and the Trainer weights the
            loss/metrics accordingly.
    """

    def __init__(self, x, y=None, batch_size=32, shuffle=False, seed=0,
                 drop_remainder=True, sample_weight=None):
        self.x = x
        # Keras accepts plain-list labels; indexing below needs arrays.
        self.y = None if y is None else np.asarray(y)
        y = self.y
        leaves = jax.tree_util.tree_leaves(x)
        if not leaves:
            raise ValueError("Empty dataset.")
        self.num_examples = leaves[0].shape[0]
        if y is not None and y.shape[0] != self.num_examples:
            raise ValueError(
                "x has {} examples but y has {}.".format(
                    self.num_examples, y.shape[0]))
        if sample_weight is not None:
            sample_weight = np.asarray(sample_weight, np.float32)
            if sample_weight.shape != (self.num_examples,):
                raise ValueError(
                    "sample_weight must be [num_examples]={}; got "
                    "shape {}.".format((self.num_examples,),
                                       sample_weight.shape))
        self.sample_weight = sample_weight
        if batch_size <= 0:
            raise ValueError("batch_size must be positive.")
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.drop_remainder = drop_remainder
        self._epoch = 0

    @property
    def steps_per_epoch(self):
        if self.drop_remainder:
            return self.num_examples // self.batch_size
        return -(-self.num_examples // self.batch_size)

    def _epoch_order(self):
        if self.shuffle:
            # Shared doctrine with the device-resident path: same seed,
            # same epoch -> same permutation on every process and on
            # either side of the wire (see epoch_permutation).
            return epoch_permutation(self.num_examples, self.seed,
                                     self._epoch)
        return np.arange(self.num_examples)

    def __iter__(self):
        """Yields global (x, y) numpy batches for one epoch."""
        return self.iter_from(0)

    def iter_from(self, start_step):
        """Yields one epoch's global batches starting at batch index
        `start_step` — the mid-epoch resume entry point (graftguard).

        The permutation is the SAME one `__iter__` would draw for this
        epoch (the threefry perm depends only on seed and the epoch
        counter), re-based by skipping the first `start_step` batches,
        so a resumed run continues the interrupted epoch's exact batch
        sequence. Epoch-counter semantics match `__iter__`: the counter
        advances at the first `next()`, not at generator creation.
        """
        order = self._epoch_order()
        self._epoch += 1
        steps = self.steps_per_epoch
        for step in range(int(start_step), steps):
            idx = order[step * self.batch_size:(step + 1) * self.batch_size]
            if len(idx) < self.batch_size:
                # Pad the tail by tiling the epoch order (robust even when
                # the whole dataset is smaller than one batch).
                idx = np.concatenate(
                    [idx, np.resize(order, self.batch_size - len(idx))])
            xb = jax.tree_util.tree_map(lambda a: a[idx], self.x)
            if self.sample_weight is not None:
                yield xb, (None if self.y is None else self.y[idx]), \
                    self.sample_weight[idx]
            elif self.y is None:
                yield xb
            else:
                yield xb, self.y[idx]

    def process_local_view(self, process_index=None, process_count=None,
                           start_step=0):
        """Returns this process's shard of each global batch.

        Multi-host feeding: every process iterates the same global order
        (same seed) and takes its contiguous slice of each batch; the
        slices are reassembled into a global array by
        `cloud_tpu.parallel.sharding.make_global_batch`. `start_step`
        re-bases the epoch mid-stream (see `iter_from`) — every process
        skips the same prefix, so the shards stay aligned on resume.
        """
        process_index = (jax.process_index()
                         if process_index is None else process_index)
        process_count = (jax.process_count()
                         if process_count is None else process_count)
        if self.batch_size % process_count:
            raise ValueError(
                "batch_size={} is not divisible by process_count={}.".format(
                    self.batch_size, process_count))
        shard = self.batch_size // process_count
        lo, hi = process_index * shard, (process_index + 1) * shard

        def _slices():
            for batch in self.iter_from(start_step):
                yield jax.tree_util.tree_map(lambda a: a[lo:hi], batch)
        return _slices()


class _LeafCast:
    """Per-leaf transfer decision. A plain object (not a registered
    pytree node) so a specs tree stays congruent with the feature tree
    under tree_map."""

    __slots__ = ("mode", "lo", "scale")

    def __init__(self, mode, lo=None, scale=None):
        self.mode = mode  # "keep" | "bf16" | "uint8"
        self.lo = lo
        self.scale = scale


class InputCast:
    """A narrow-on-the-wire transfer policy for feature batches.

    The host narrows features before the H2D copy (`host_cast`); the
    jitted train step widens them back to float32 as its first op
    (`widen`), so the model always computes in its own dtype and only
    the wire pays the narrow format:

    - "bfloat16": float leaves cross as bf16 — 2x fewer bytes, ~3
      decimal digits of mantissa, parameterless (works on streams).
    - "uint8": float leaves cross as affine-quantized uint8 — 4x fewer
      bytes; lo/scale are computed once from the full arrays, so this
      policy needs an `ArrayDataset`. Data already on a 255-point grid
      (images) round-trips exactly.

    Integer/bool leaves are never touched. Build instances through
    `make_input_cast`.
    """

    def __init__(self, name, specs):
        self.name = name
        self._specs = specs

    @property
    def cache_key(self):
        """Hashable identity for jit-closure caches: `widen` is baked
        into the compiled step, so steps must be cached per-policy."""
        return (self.name,) + tuple(
            (s.mode, s.lo, s.scale)
            for s in jax.tree_util.tree_leaves(self._specs))

    def host_cast(self, x):
        """Narrows a host feature batch for the wire (numpy in/out)."""
        def leaf(a, spec):
            if spec.mode == "bf16":
                return np.asarray(a).astype(jnp.bfloat16)
            if spec.mode == "uint8":
                q = np.round(
                    (np.asarray(a, np.float32) - spec.lo) / spec.scale)
                return np.clip(q, 0, 255).astype(np.uint8)
            return a
        return jax.tree_util.tree_map(leaf, x, self._specs)

    def widen(self, x):
        """Inverse of `host_cast`, traceable inside the jitted step."""
        def leaf(a, spec):
            if spec.mode == "bf16":
                return a.astype(jnp.float32)
            if spec.mode == "uint8":
                return a.astype(jnp.float32) * spec.scale + spec.lo
            return a
        return jax.tree_util.tree_map(leaf, x, self._specs)

    def cast_nbytes(self, x):
        """Post-cast byte count of `x` (no materialization)."""
        def leaf(a, spec):
            if spec.mode == "bf16":
                return a.size * 2
            if spec.mode == "uint8":
                return int(a.size)
            return int(np.asarray(a).nbytes)
        return sum(jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(leaf, x, self._specs)))


def make_input_cast(policy, x):
    """Builds an `InputCast` for feature tree `x`.

    Args:
        policy: None/"none" (returns None), "bfloat16"/"bf16", "uint8",
            or an existing `InputCast` (passed through).
        x: The feature tree the policy will apply to — the full arrays
            for "uint8" (range calibration), any representative sample
            for "bfloat16".
    """
    if policy is None or policy == "none":
        return None
    if isinstance(policy, InputCast):
        return policy

    def _is_float(a):
        return np.issubdtype(np.asarray(a).dtype, np.floating)

    if policy in ("bfloat16", "bf16"):
        specs = jax.tree_util.tree_map(
            lambda a: _LeafCast("bf16" if _is_float(a)
                                and np.asarray(a).dtype.itemsize > 2
                                else "keep"), x)
        return InputCast("bfloat16", specs)
    if policy == "uint8":
        def spec(a):
            if not _is_float(a):
                return _LeafCast("keep")
            a = np.asarray(a)
            lo = float(a.min())
            hi = float(a.max())
            scale = (hi - lo) / 255.0 or 1.0
            return _LeafCast("uint8", lo=lo, scale=scale)
        return InputCast("uint8", jax.tree_util.tree_map(spec, x))
    raise ValueError(
        "Unknown input_cast {!r}; expected None, 'bfloat16' or "
        "'uint8'.".format(policy))


def _resident_hbm_budget():
    """Per-device byte budget for the resident upload.

    CLOUD_TPU_RESIDENT_HBM_BUDGET (bytes) overrides; otherwise 60% of
    the device's reported bytes_limit (leaving room for params, grads,
    moments and activations); None (no check) when the backend reports
    nothing, as the virtual-CPU test backend doesn't.
    """
    env = os.environ.get("CLOUD_TPU_RESIDENT_HBM_BUDGET")
    if env:
        try:
            return int(float(env))
        except ValueError:
            logger.warning("Ignoring malformed "
                           "CLOUD_TPU_RESIDENT_HBM_BUDGET=%r", env)
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:  # backend without memory introspection
        return None
    limit = stats.get("bytes_limit")
    return int(limit * 0.6) if limit else None


class DeviceResidentDataset:
    """An `ArrayDataset` uploaded to device HBM once.

    Steady-state training then does ZERO host->device data transfers:
    the Trainer's resident executable draws every batch in-graph from
    the uploaded arrays with a device-side per-epoch permutation
    (`epoch_permutation` doctrine) and `jnp.take` /
    `lax.dynamic_slice`. Construct through `build()`, which applies the
    HBM budget check and falls back (returns None, one-line warning)
    instead of raising; `__init__` raises on structural problems.

    Attributes:
        data: Device-resident feature tree shaped like the dataset's
            per-batch yields ((x, y, w), (x, y) or bare x) but with the
            full example dimension.
        sharding: Congruent tree of NamedShardings (None off-mesh):
            leaves divisible by the dp axis are sharded on examples,
            the rest replicated.
        policy: The `InputCast` applied on upload (features stay narrow
            in HBM; the resident step widens per batch), or None.
        upload_bytes: Host bytes moved by the one-time upload.
    """

    def __init__(self, dataset, input_cast=None, mesh=None):
        if not isinstance(dataset, ArrayDataset):
            raise TypeError(
                "DeviceResidentDataset needs an ArrayDataset (in-memory "
                "arrays); got {!r}.".format(type(dataset).__name__))
        if dataset.steps_per_epoch < 1:
            raise ValueError(
                "Dataset yields no full batch (num_examples={}, "
                "batch_size={}).".format(dataset.num_examples,
                                         dataset.batch_size))
        if (not dataset.drop_remainder
                and dataset.num_examples % dataset.batch_size):
            raise ValueError(
                "drop_remainder=False with a ragged tail pads batches on "
                "the host; the resident path cannot reproduce that "
                "in-graph.")
        # The live dataset, not a copy: the resident fit loop reads and
        # advances its `_epoch` counter so shuffled order stays in
        # lockstep with (and resumable by) the host path.
        self.source = dataset
        self.num_examples = dataset.num_examples
        self.batch_size = dataset.batch_size
        self.steps_per_epoch = dataset.steps_per_epoch
        self.shuffle = dataset.shuffle
        self.seed = dataset.seed
        self.policy = (input_cast if isinstance(input_cast, InputCast)
                       or input_cast is None
                       else make_input_cast(input_cast, dataset.x))

        x = dataset.x if self.policy is None else self.policy.host_cast(
            dataset.x)
        if dataset.sample_weight is not None:
            host = (x, dataset.y, dataset.sample_weight)
            self.kind = "xyw"
        elif dataset.y is None:
            host = x
            self.kind = "x"
        else:
            host = (x, dataset.y)
            self.kind = "xy"

        self.sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from cloud_tpu.parallel import sharding as sharding_lib

            dp = dict(mesh.shape).get(sharding_lib.DATA_AXIS, 1)

            def leaf_sharding(a):
                if dp > 1 and a.shape[0] % dp == 0:
                    return NamedSharding(mesh, P(sharding_lib.DATA_AXIS))
                return NamedSharding(mesh, P())

            self.sharding = jax.tree_util.tree_map(leaf_sharding, host)

        self.upload_bytes = runtime_lib.record_h2d(host)
        if self.sharding is None:
            self.data = jax.tree_util.tree_map(jax.device_put, host)
        elif jax.process_count() > 1:
            # Every process holds the full arrays (the ArrayDataset
            # multi-host contract: same global order everywhere), so
            # each can serve any addressable shard by plain indexing.
            self.data = jax.tree_util.tree_map(
                lambda a, s: jax.make_array_from_callback(
                    a.shape, s, lambda idx, a=a: a[idx]),
                host, self.sharding)
        else:
            self.data = jax.tree_util.tree_map(
                jax.device_put, host, self.sharding)

    @classmethod
    def build(cls, dataset, input_cast=None, mesh=None,
              budget_bytes=None):
        """Residency with graceful fallback.

        Returns a `DeviceResidentDataset`, or None after ONE warning
        line when the dataset can't live on device (not in-memory
        arrays, no full batch, host-padded ragged tail, or over the
        HBM budget) — the caller then streams from the host as usual.
        """
        def _fallback(why):
            logger.warning(
                "cache='device' unavailable (%s); streaming from "
                "host instead.", why)
            return None

        if not isinstance(dataset, ArrayDataset):
            return _fallback("needs in-memory arrays, got {}".format(
                type(dataset).__name__))
        if dataset.steps_per_epoch < 1:
            return _fallback("dataset smaller than one batch")
        if (not dataset.drop_remainder
                and dataset.num_examples % dataset.batch_size):
            return _fallback("ragged tail is host-padded")

        policy = (input_cast if isinstance(input_cast, InputCast)
                  or input_cast is None
                  else make_input_cast(input_cast, dataset.x))
        budget = (_resident_hbm_budget() if budget_bytes is None
                  else budget_bytes)
        if budget is not None:
            need = cls._per_device_bytes(dataset, policy, mesh)
            if need > budget:
                return _fallback(
                    "dataset needs {} bytes/device, budget {}".format(
                        need, budget))
        return cls(dataset, input_cast=policy, mesh=mesh)

    @staticmethod
    def _per_device_bytes(dataset, policy, mesh):
        """Worst-device resident footprint after the input cast."""
        dp = 1
        if mesh is not None:
            from cloud_tpu.parallel import sharding as sharding_lib

            dp = dict(mesh.shape).get(sharding_lib.DATA_AXIS, 1) or 1

        def nbytes(a, cast_bytes):
            a = np.asarray(a)
            per = cast_bytes if cast_bytes is not None else a.nbytes
            return per // dp if dp > 1 and a.shape[0] % dp == 0 else per

        total = 0
        if policy is not None:
            specs = policy._specs
            flat_x = jax.tree_util.tree_leaves(dataset.x)
            flat_s = jax.tree_util.tree_leaves(specs)
            for a, s in zip(flat_x, flat_s):
                a = np.asarray(a)
                if s.mode == "bf16":
                    per = a.size * 2
                elif s.mode == "uint8":
                    per = int(a.size)
                else:
                    per = None
                total += nbytes(a, per)
        else:
            for a in jax.tree_util.tree_leaves(dataset.x):
                total += nbytes(a, None)
        for extra in (dataset.y, dataset.sample_weight):
            if extra is not None:
                total += nbytes(extra, None)
        return total


def as_dataset(data, y=None, batch_size=32, **kwargs):
    """Coerces user input to a re-iterable dataset of batches.

    Accepts (in resolution order):
    - an `ArrayDataset` (used as-is);
    - raw arrays or an array pytree (dict, or list/tuple of arrays) —
      wrapped in an `ArrayDataset`; always the case when `y` is given;
    - a one-shot iterator/generator of batches — materialized into a list
      once so multi-epoch training sees every batch every epoch;
    - any other re-iterable of batches (used as-is, re-iterated per
      epoch).
    """
    if isinstance(data, ArrayDataset):
        return data
    if y is not None or hasattr(data, "shape") or isinstance(data, dict):
        return ArrayDataset(data, y, batch_size=batch_size, **kwargs)
    if isinstance(data, (list, tuple)):
        leaves = [e for e in data]
        if leaves and all(hasattr(e, "shape") for e in leaves):
            # Pytree-of-arrays (multi-input model), not a batch list.
            return ArrayDataset(data, y, batch_size=batch_size, **kwargs)
        return data
    if hasattr(data, "__next__"):
        return list(data)
    if hasattr(data, "__iter__"):
        return data
    return ArrayDataset(data, y, batch_size=batch_size, **kwargs)


class GeneratorDataset:
    """Streaming dataset from an iterator factory.

    For data too large for memory: `factory` must return a fresh
    iterator of batches (numpy arrays or (x, y) tuples, fixed shapes
    for XLA) each time it is called — once per epoch, plus once for the
    Trainer's build-time sample peek, so keep it side-effect free.
    `steps_per_epoch` bounds each epoch for non-terminating streams
    (Trainer.fit picks it up when its own steps_per_epoch is unset).

    cloud_fit ships this WITHOUT materializing the stream: a
    module-level `factory` travels as its dotted path plus
    `factory_kwargs` (JSON), and the remote worker rebuilds the dataset
    and pulls batches there (the JAX-native analogue of the reference
    shipping datasets as serialized tf.functions,
    reference cloud_fit/client.py:151-189).
    """

    def __init__(self, factory, steps_per_epoch=None,
                 factory_kwargs=None):
        if not callable(factory):
            raise TypeError("factory must be callable, got {!r}"
                            .format(type(factory)))
        self.factory = factory
        self.steps_per_epoch = steps_per_epoch
        self.factory_kwargs = dict(factory_kwargs or {})

    def __iter__(self):
        return iter(self.factory(**self.factory_kwargs))


class NpzShardDataset:
    """Batches from .npz shards already sitting on storage.

    The cloud_fit shard-manifest path: the client ships only the list
    of shard paths (JSON manifest); the worker streams each shard
    through the storage seam (local or gs://) per epoch — data that
    never fits one `np.asarray` crosses as references, not bytes.

    Each shard is an .npz with an `x` array (and optionally `y`),
    uniform across shards except possibly a short last shard. Batches
    of `batch_size` are cut per shard; a shard tail smaller than
    `batch_size` is dropped (static shapes for XLA) unless the shard
    yields no full batch at all, in which case it is yielded whole.
    """

    def __init__(self, shard_paths, batch_size=32):
        if not shard_paths:
            raise ValueError("shard_paths must be non-empty.")
        if batch_size <= 0:
            raise ValueError("batch_size must be positive.")
        self.shard_paths = [str(p) for p in shard_paths]
        self.batch_size = batch_size

    def __iter__(self):
        import io

        from cloud_tpu.utils import storage

        for path in self.shard_paths:
            arrays = np.load(io.BytesIO(storage.read_bytes(path)))
            x = arrays["x"]
            y = arrays["y"] if "y" in arrays.files else None
            n = x.shape[0]
            steps = n // self.batch_size
            if steps == 0:
                yield (x, y) if y is not None else x
                continue
            for i in range(steps):
                sl = slice(i * self.batch_size, (i + 1) * self.batch_size)
                if y is not None:
                    yield x[sl], y[sl]
                else:
                    yield x[sl]


class ThreadedDataset:
    """Pulls a wrapped dataset on a background thread through a bounded
    queue — the host-side complement of `prefetch_to_device`.

    Device prefetch overlaps the host->HBM copy with compute; this
    overlaps producing the batches themselves (augmentation, decoding,
    a slow generator) with training. Wrap any dataset/iterable whose
    per-batch host work is non-trivial:

        ds = ThreadedDataset(GeneratorDataset(factory), buffer_size=4)
        trainer.fit(ds, ...)

    Semantics: batch order is preserved; producer exceptions re-raise
    in the consumer; abandoning iteration mid-epoch (steps_per_epoch,
    early break) stops the producer thread promptly. `steps_per_epoch`
    and evaluate's exactness attributes are forwarded from the wrapped
    dataset.
    """

    _SENTINEL = object()

    def __init__(self, dataset, buffer_size=4):
        if buffer_size < 1:
            raise ValueError("buffer_size must be >= 1.")
        if hasattr(dataset, "__next__"):
            raise TypeError(
                "ThreadedDataset needs a re-iterable (multi-epoch "
                "training re-iterates per epoch; a one-shot iterator "
                "would be silently empty after epoch 1). Wrap the "
                "source in GeneratorDataset(factory) instead.")
        self.dataset = dataset
        self.buffer_size = buffer_size
        for attr in ("steps_per_epoch", "num_examples", "batch_size"):
            value = getattr(dataset, attr, None)
            if value is not None:
                setattr(self, attr, value)

    def __iter__(self):
        return self._threaded(self.dataset)

    def __getattr__(self, name):
        # Forward the multi-host protocol ONLY when the wrapped dataset
        # provides it: Trainer dispatches on hasattr(process_local_view),
        # and an unconditional method would make wrapping a plain
        # GeneratorDataset crash on pods instead of iterating normally.
        if name == "process_local_view" and hasattr(
                self.dataset, "process_local_view"):
            return lambda *a, **k: self._threaded(
                self.dataset.process_local_view(*a, **k))
        raise AttributeError(name)

    def _threaded(self, source):
        import queue as queue_lib
        import threading

        q = queue_lib.Queue(maxsize=self.buffer_size)
        stop = threading.Event()

        def _put(item):
            """put() that gives up when the consumer is gone."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue_lib.Full:
                    continue
            return False

        def producer():
            try:
                for item in source:
                    if not _put((None, item)):
                        return
                _put((None, self._SENTINEL))
            except BaseException as e:  # noqa: BLE001 - re-raised below
                _put((e, None))

        thread = threading.Thread(target=producer, daemon=True)
        thread.start()
        try:
            while True:
                err, item = q.get()
                if err is not None:
                    raise err
                if item is self._SENTINEL:
                    return
                yield item
        finally:
            # Deterministic shutdown: signal, then join — an abandoned
            # epoch (steps_per_epoch break) must not leave a producer
            # racing the next epoch's thread over the inner dataset.
            stop.set()
            thread.join(timeout=5.0)


def prefetch_to_device(iterator, size=2, sharding=None, feed=None,
                       limit=None):
    """Wraps a host batch iterator with device read-ahead.

    JAX async dispatch already overlaps host batching with device
    compute; explicit prefetch additionally overlaps the host->HBM copy
    of batch i+1 with step i, which matters when batches are large
    (images) relative to step time.

    Composes with the async host loop (trainer async_logging): this
    side keeps the H2D wire full while the background metric reader
    drains D2H — neither direction ever blocks the step dispatch, and
    both are counted in `runtime.transfer_stats()` (record_h2d here,
    record_d2h at every fetch site).

    Args:
        iterator: Host batch iterable.
        size: Read-ahead depth — `size` batches are queued on device
            ahead of the one being consumed (so up to size+1 alive;
            size=0 feeds synchronously, the minimal-HBM mode).
        sharding: Optional sharding for the default device_put feed.
        feed: Optional callable replacing the default device_put (e.g.
            a mesh-aware Trainer feed); its return value is yielded.
        limit: Bound pulls from the iterator BEFORE reading ahead —
            for steps_per_epoch over unbounded streams.
    """
    import collections
    import itertools

    if feed is None:
        def feed(batch):
            runtime_lib.record_h2d(batch)
            if sharding is None:
                return jax.device_put(batch)
            return jax.tree_util.tree_map(
                lambda a: jax.device_put(a, sharding), batch)

    it = iter(iterator)
    if limit is not None:
        it = itertools.islice(it, limit)
    if size <= 0:
        for batch in it:
            yield feed(batch)
        return

    queue = collections.deque()
    try:
        for _ in range(size):
            queue.append(feed(next(it)))
    except StopIteration:
        pass
    while queue:
        out = queue.popleft()
        try:
            queue.append(feed(next(it)))
        except StopIteration:
            pass
        yield out
