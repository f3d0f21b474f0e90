"""Tracing/profiling subsystem: JAX profiler hooks.

The reference has no dedicated tracing subsystem (SURVEY §5 — its
closest analogues are TensorBoard event logs reused as a metric
transport and the 10s periodic metric exporter). The TPU-native build
gets a real one: thin, dependency-free wrappers over the JAX/XLA
profiler (device traces viewable in TensorBoard/Perfetto, with MXU
utilization and HBM analysis on TPU) plus a Trainer callback that
captures selected epochs, and step annotations that show up as named
spans in the trace.
"""

import contextlib
import logging

import jax

from cloud_tpu.training.callbacks import Callback


def start_server(port=9012):
    """Starts the profiler server for on-demand remote capture
    (`tensorboard --logdir` "capture profile" button or
    `jax.profiler.start_trace` from another process)."""
    return jax.profiler.start_server(port)


@contextlib.contextmanager
def trace(log_dir, host_tracer_level=2, python_tracer_level=1):
    """Context manager capturing a device+host trace into `log_dir`.

    The artifact lands under `<log_dir>/plugins/profile/<run>` in the
    TensorBoard profile-plugin layout.
    """
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    options.python_tracer_level = python_tracer_level
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name):
    """Named span inside a trace (shows as a labeled region); usable as
    decorator or context manager."""
    return jax.profiler.TraceAnnotation(name)


def device_memory_profile(path=None):
    """Snapshot of per-device memory (pprof format). Returns the bytes,
    and writes them to `path` when given."""
    data = jax.profiler.device_memory_profile()
    if path is not None:
        with open(path, "wb") as f:
            f.write(data)
    return data


class ProfilerCallback(Callback):
    """Traces selected training epochs into `log_dir`.

    By default profiles epoch 1 only (epoch 0 pays the jit compile, so
    its trace is mostly compilation): the standard "skip the warmup
    epoch" recipe.
    """

    def __init__(self, log_dir, epochs=(1,)):
        self.log_dir = log_dir
        self.epochs = set(epochs)
        self._active = False
        self._run_epochs = self.epochs

    def on_train_begin(self):
        # Per-run view: never mutate the configured epochs, so a reused
        # callback instance re-evaluates the fallback for each fit().
        self._run_epochs = self.epochs
        planned = getattr(self.trainer, "planned_epochs", None)
        start = getattr(self.trainer, "initial_epoch", 0)
        if planned is not None and not any(start <= e < planned
                                           for e in self.epochs):
            # E.g. the default epochs=(1,) with fit(epochs=1) (only
            # epoch 0 runs) or a resumed fit(initial_epoch=4) that
            # starts past every requested epoch. Trace the first epoch
            # THIS fit will actually run rather than silently producing
            # nothing.
            logging.getLogger("cloud_tpu").warning(
                "ProfilerCallback: none of the requested epochs %s will "
                "run (fit runs epochs [%d, %d)); profiling epoch %d "
                "instead.", sorted(self.epochs), start, planned, start)
            self._run_epochs = {start}

    def on_epoch_begin(self, epoch):
        if epoch in self._run_epochs and jax.process_index() == 0:
            jax.profiler.start_trace(self.log_dir)
            self._active = True

    def on_epoch_end(self, epoch, logs):
        if self._active:
            jax.profiler.stop_trace()
            self._active = False

    def on_train_end(self, history):
        if self._active:  # interrupted epoch (e.g. EarlyStopping)
            jax.profiler.stop_trace()
            self._active = False
