"""Persistent XLA compilation cache + AOT executable helpers.

The reference delegates compilation entirely to TF; a TPU-native stack
pays trace + XLA compile on every cold start. This module makes that
cost a managed resource in three pieces:

1. `enable()` — turns the persistent compile cache on for the process.
   WHERE it lives is decided outside the code: when
   `JAX_COMPILATION_CACHE_DIR` is set, JAX already has its directory
   and `enable()` sets none; otherwise it is `benchmarks/.jax_cache` in
   the checkout (git-ignored; the directory holds nothing else). One
   fixed path either way — the path is part of the cache key, so a
   directory that moves never hits.
2. Cache hit/miss stats — a `jax.monitoring` listener feeds persistent
   cache hits into `runtime.compile_stats()["cache_hits"]` so tests
   can assert "the second process compiled nothing" as a counted
   invariant (the same doctrine as `runtime.transfer_stats()`).
3. `serialize_executable` / `deserialize_executable` — thin wrappers
   over the JAX AOT serialization API for shipping a compiled step to
   another same-topology process (deploy-time warm start).
"""

import logging
import os
import pickle

logger = logging.getLogger("cloud_tpu")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "benchmarks", ".jax_cache")

_enabled = False             # also gates the hit/miss listener
_listener_installed = False
_event_stats = {"persistent_hits": 0, "persistent_misses": 0}


def enable(min_compile_time_secs=0.0):
    """Turns on the persistent compilation cache for this process.

    Args:
        min_compile_time_secs: Persist executables whose compile took at
            least this long. The default 0.0 persists everything, and
            the entry-size floor is lifted with it, so a small (CPU,
            test) executable still round-trips.

    Returns:
        The cache directory in effect.
    """
    global _enabled
    import jax
    from jax._src import compilation_cache as jax_cc

    if not os.environ.get(ENV_VAR):
        os.makedirs(CHECKOUT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax memoizes the is-the-cache-used decision per process at the
    # FIRST compile — enabling after anything has compiled would
    # otherwise be a silent no-op (no writes, no events).
    jax_cc.reset_cache()
    _enabled = True
    _install_listener()
    logger.info("Persistent compile cache enabled at %s", cache_dir())
    return cache_dir()


def disable():
    """Stops counting, and persisting where `enable()` chose the
    directory (test isolation). A directory that came from
    `JAX_COMPILATION_CACHE_DIR` is JAX's own and stays."""
    global _enabled
    if not _enabled:
        return
    _enabled = False
    if not os.environ.get(ENV_VAR):
        import jax
        from jax._src import compilation_cache as jax_cc

        jax.config.update("jax_compilation_cache_dir", None)
        jax_cc.reset_cache()


def is_enabled():
    return _enabled


def cache_dir():
    """The directory JAX persists to, or None before `enable()`."""
    if not _enabled:
        return None
    import jax
    return jax.config.jax_compilation_cache_dir


def _install_listener():
    """Registers the (idempotent, irrevocable) jax.monitoring hook.

    jax has no unregister API, so the listener is installed once and
    gated on `_enabled`; `disable()` just flips the gate.
    """
    global _listener_installed
    if _listener_installed:
        return
    from jax import monitoring

    def _on_event(event, **kwargs):
        if not _enabled:
            return
        if event == "/jax/compilation_cache/cache_hits":
            _event_stats["persistent_hits"] += 1
            from cloud_tpu.parallel import runtime
            runtime.record_compile(cache_hits=1)
        elif event == "/jax/compilation_cache/cache_misses":
            _event_stats["persistent_misses"] += 1
            # A miss is a compile-from-scratch the persistent cache
            # could not absorb; the graftsan observer attributes it to
            # the dispatch site (the hit path notifies through
            # record_compile above).
            from cloud_tpu.parallel import runtime
            observer = runtime.get_observer()
            if observer is not None:
                observer.on_cache_miss()

    monitoring.register_event_listener(_on_event)
    _listener_installed = True


def stats():
    """Persistent-cache event counts (process-wide, since enable())."""
    return dict(_event_stats)


def reset_stats():
    for key in _event_stats:
        _event_stats[key] = 0


# --------------------------------------------------------------------------
# AOT executable serialization (deploy-time warm start).

def serialize_executable(compiled):
    """Serializes a `jax.stages.Compiled` to a portable triple.

    Returns `(payload_bytes, in_tree, out_tree)` — exactly what
    `deserialize_executable` needs; the payload carries the ids of the
    devices the executable was compiled for. Raises whatever the JAX
    AOT API raises when the executable is not serializable on this
    backend.
    """
    from jax.experimental import serialize_executable as se
    payload, in_tree, out_tree = se.serialize(compiled)
    device_ids = [d.id for d in
                  compiled.runtime_executable().local_devices()]
    return pickle.dumps((device_ids, payload)), in_tree, out_tree


def deserialize_executable(triple):
    """Loads a `(payload, in_tree, out_tree)` triple back into a
    callable Compiled on the devices (by id) it was compiled for. Only
    valid on a same-topology process with the same jax/jaxlib."""
    import jax
    from jax.experimental import serialize_executable as se

    blob, in_tree, out_tree = triple
    # Only bytes this module wrote: `serialize_executable`'s payload.
    device_ids, payload = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


def save_executable(path, compiled):
    """Serializes `compiled` to `path` (pickle of the AOT triple)."""
    triple = serialize_executable(compiled)
    with open(path, "wb") as f:
        pickle.dump(triple, f)
    return path


def load_executable(path):
    """Loads an executable previously written by `save_executable`."""
    with open(path, "rb") as f:
        triple = pickle.load(f)
    return deserialize_executable(triple)
