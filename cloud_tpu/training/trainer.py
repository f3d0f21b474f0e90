"""Trainer: a `model.fit`-style training loop, TPU-native.

The reference's training loop lives inside Keras under an ambient
`tf.distribute` strategy (reference core/preprocess.py:148-149,
cloud_fit/remote.py:84-128). This Trainer is the JAX equivalent: one
jitted train step over the ambient device mesh, parameters laid out by
explicit sharding rules (replicated for pure DP; XLA inserts the gradient
psum over ICI), batches sharded on the "dp" axis, buffers donated so the
optimizer update is in-place in HBM.

Works with any flax.linen Module, or any (init_fn, apply_fn) pair.

Example:
    trainer = Trainer(model=MLP(), optimizer=optax.adam(1e-3),
                      loss="sparse_categorical_crossentropy",
                      metrics=("accuracy",))
    history = trainer.fit(x_train, y_train, epochs=2, batch_size=128)
"""

import functools
import inspect
import itertools
import logging
import os
import sys
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax

from jax.sharding import NamedSharding, PartitionSpec as P

from cloud_tpu.monitoring import spans as spans_lib
from cloud_tpu.monitoring import watch as watch_lib
from cloud_tpu.parallel import runtime
from cloud_tpu.parallel import sharding as sharding_lib
from cloud_tpu.training import async_logs as async_logs_lib
from cloud_tpu.training import data as data_lib

logger = logging.getLogger("cloud_tpu")

#: The training loop's program, named by the function that is jitted
#: (`train_step` in `_make_train_step_body`): the trace's program line
#: reads `jit_train_step` (table in monitoring/spans.py).
TRAIN_STEP = "train_step"


def _env_sanitized(method):
    """Runs a Trainer entry point under a graftsan env scope.

    `CLOUD_TPU_SANITIZE=1|warn|strict` turns the wrapped call into a
    sanitized region (cloud_tpu.analysis.sanitizer): runtime transfer/
    compile records and jax.random key consumption are attributed to
    their call sites and checked against the step-loop invariants.
    Unset, the wrapper is a plain delegation — no import, no observer
    hook. Nested regions don't stack: a validation `evaluate` inside a
    sanitized `fit` sees the already-installed observer and no-ops.
    """
    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        if not os.environ.get("CLOUD_TPU_SANITIZE"):
            return method(self, *args, **kwargs)
        from cloud_tpu.analysis import sanitizer
        with sanitizer.env_scope():
            return method(self, *args, **kwargs)
    return wrapper


def _env_telemetry(method):
    """Runs a Trainer entry point under a graftscope telemetry scope.

    `CLOUD_TPU_TELEMETRY=1` enables the ambient telemetry session
    (span tracer + metrics registry + exporters, see
    cloud_tpu.monitoring.telemetry) and guarantees a completed flush
    when the entry point returns, so trace.json / metrics.prom exist
    the moment fit() does. Unset, the wrapper is a plain delegation —
    no import, no tracer, no observer hook (the graftsan zero-cost
    discipline). Stacks with `_env_sanitized`: both observers ride the
    widened runtime fanout seam.
    """
    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        if not os.environ.get("CLOUD_TPU_TELEMETRY"):
            return method(self, *args, **kwargs)
        from cloud_tpu.monitoring import telemetry
        with telemetry.env_scope():
            return method(self, *args, **kwargs)
    return wrapper


def _env_watched(method):
    """Runs a Trainer entry point under a graftwatch watchdog scope.

    `CLOUD_TPU_WATCH=1` installs the heartbeat watchdog
    (cloud_tpu.monitoring.watch): the step loop beats it, a monitor
    thread converts a stall past CLOUD_TPU_WATCH_DEADLINE into a typed
    `runtime.BackendUnavailable` plus a `blackbox.json` flight
    recorder, and liveness gauges ride the telemetry registry when one
    is active. Unset, the wrapper is a plain delegation — no import,
    no thread, no hook (the graftsan zero-cost discipline, test-
    pinned). Stacked OUTERMOST so a stall inside the telemetry scope
    still flushes artifacts on the way out, and so the crash blackbox
    sees the sanitizer/telemetry state before their teardown. A nested
    entry point (fit's validation evaluate) rides the outer watchdog.
    """
    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        if not os.environ.get("CLOUD_TPU_WATCH"):
            return method(self, *args, **kwargs)
        from cloud_tpu.monitoring import watch
        with watch.env_scope():
            return method(self, *args, **kwargs)
    return wrapper


# -- Losses (logits-in, per-example-loss-out) ---------------------------

def _sparse_categorical_crossentropy(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels)


def _categorical_crossentropy(logits, labels):
    return optax.softmax_cross_entropy(logits, labels)


def _binary_crossentropy(logits, labels):
    return optax.sigmoid_binary_cross_entropy(logits, labels)


def _mse(preds, targets):
    return jnp.mean(jnp.square(preds - targets),
                    axis=tuple(range(1, preds.ndim)))


def sparse_categorical_crossentropy(label_smoothing=0.0):
    """Loss factory: integer-label softmax CE with label smoothing.

    smoothing=0 is the registry default; >0 mixes the one-hot target
    with the uniform distribution (Keras `label_smoothing=` parity).
    """
    if not 0.0 <= label_smoothing < 1.0:
        raise ValueError("label_smoothing must be in [0, 1); got "
                         "{}.".format(label_smoothing))
    if not label_smoothing:
        return _sparse_categorical_crossentropy

    def loss(logits, labels):
        num_classes = logits.shape[-1]
        smoothed = optax.smooth_labels(
            jax.nn.one_hot(labels, num_classes), label_smoothing)
        return optax.softmax_cross_entropy(logits, smoothed)

    return loss


LOSSES = {
    "sparse_categorical_crossentropy": _sparse_categorical_crossentropy,
    "categorical_crossentropy": _categorical_crossentropy,
    "binary_crossentropy": _binary_crossentropy,
    "mse": _mse,
    "mean_squared_error": _mse,
}


def _accuracy(outputs, labels):
    """Per-example correctness (float). Mean-reduced by the train step;
    kept per-example so evaluate() can mask padded tail examples for
    exact example-weighted metrics."""
    preds = jnp.argmax(outputs, axis=-1)
    if labels.ndim == preds.ndim + 1:  # one-hot
        labels = jnp.argmax(labels, axis=-1)
    return (preds == labels).astype(jnp.float32)


def _top5_accuracy(outputs, labels):
    """Per-example top-5 hit rate (ImageNet's second headline metric).

    k clamps to the class count (Keras TopKCategoricalAccuracy
    behavior: fewer than 5 classes means every example hits)."""
    k = min(5, outputs.shape[-1])
    topk = jax.lax.top_k(outputs, k)[1]            # [B..., k]
    return jnp.any(topk == labels[..., None],
                   axis=-1).astype(jnp.float32)


def _mae_metric(outputs, labels):
    v = jnp.abs(outputs - labels)
    return v.reshape(v.shape[0], -1).mean(axis=1)


def _mse_metric(outputs, labels):
    v = jnp.square(outputs - labels)
    return v.reshape(v.shape[0], -1).mean(axis=1)


METRICS = {
    "accuracy": _accuracy,
    "top5_accuracy": _top5_accuracy,
    "mae": _mae_metric,
    "mean_absolute_error": _mae_metric,
    "mse": _mse_metric,
    "mean_squared_error": _mse_metric,
}

OPTIMIZERS = {
    "adam": lambda: optax.adam(1e-3),
    "adamw": lambda: optax.adamw(1e-3),
    "sgd": lambda: optax.sgd(1e-2, momentum=0.9),
    "rmsprop": lambda: optax.rmsprop(1e-3),
    "adagrad": lambda: optax.adagrad(1e-2),
    "adafactor": lambda: optax.adafactor(),  # the TPU LLM workhorse
    "lamb": lambda: optax.lamb(1e-3),
    "lion": lambda: optax.lion(1e-4),
}


def _per_example_view(v, batch_dim):
    """Collapse any non-batch dims (e.g. per-token losses) to one value
    per example so a per-example mask/weight applies cleanly."""
    v = jnp.asarray(v)
    if v.ndim > 1:
        return jnp.mean(v.reshape(batch_dim, -1), axis=1)
    return v


def _weighted_mean(v, weights):
    """sum(v*w) / sum(w), safe on all-zero weights.

    The tiny (1e-9, not 1.0) floor keeps the identity
    `weighted_mean * sum(w) == sum(v*w)` exact for ANY positive weight
    sum — evaluate() re-multiplies by sum(w) when aggregating across
    batches, so a 1.0 floor would silently scale batches whose total
    weight is below one. All-zero weights give 0, not nan.
    """
    return jnp.sum(v * weights) / jnp.maximum(jnp.sum(weights), 1e-9)


def _lead_count(batch):
    """The batch's leading (example) dimension, from its first shaped
    leaf — the host-side example count feeding and grouping key on."""
    lead = next((l for l in jax.tree_util.tree_leaves(batch)
                 if getattr(l, "shape", ())), None)
    return int(lead.shape[0]) if lead is not None else 0


def _emit_runtime_metrics(steps, examples, elapsed_secs):
    """Feeds the native metrics registry and ensures the periodic C++
    exporter is running (it refuses unless CLOUD_TPU_MONITORING_ENABLED
    is set) — once per epoch, off the hot loop."""
    if steps <= 0:
        return
    try:
        from cloud_tpu import monitoring
        monitoring.start_exporter()  # idempotent, env-gated
        monitoring.counter_increment(monitoring.TRAINING_STEPS, steps)
        monitoring.counter_increment(monitoring.TRAINING_EXAMPLES,
                                     examples)
        monitoring.histogram_observe(
            monitoring.STEP_TIME_HISTOGRAM,
            elapsed_secs / steps * 1e6,
            monitoring.STEP_TIME_BOUNDS)
    except Exception:  # monitoring must never break training
        logger.debug("metric emission failed", exc_info=True)


def _emit_telemetry_epoch(steps, examples, elapsed_secs):
    """Feeds the graftscope registry's per-epoch rollup (throughput
    counters + MFU gauge + one non-blocking flush). `sys.modules.get`
    keeps the disabled path import-free: if telemetry was never
    imported, it is certainly not enabled."""
    telemetry = sys.modules.get("cloud_tpu.monitoring.telemetry")
    if telemetry is None:
        return
    tele = telemetry.get()
    if tele is None or not tele.active:
        return
    try:
        tele.record_epoch(steps, examples, elapsed_secs)
    except Exception:  # telemetry must never break training
        logger.debug("telemetry epoch rollup failed", exc_info=True)


import typing


class ParamEmaState(typing.NamedTuple):
    """EMA shadow of the parameters.

    A DISTINCT node type (not a bare params-shaped subtree) so
    Trainer.build can recognize it structurally and keep the shadow in
    the PARAMETER layout — eval/predict substitute it straight into the
    params slot, so it must not pick up the ZeRO moment layout.
    """
    ema: typing.Any


def _trainable_labels(params, trainable):
    """"train"/"freeze" label per param leaf.

    trainable: regex (re.search over the same path strings
    param_sharding_rules match, e.g. "block_0/attention/query/kernel")
    or callable path_string -> bool.
    """
    import re

    if callable(trainable):
        matches = trainable
    else:
        pattern = re.compile(trainable)
        matches = lambda path: pattern.search(path) is not None
    return jax.tree_util.tree_map_with_path(
        lambda path, _: ("train"
                         if matches(sharding_lib.path_string(path))
                         else "freeze"),
        params)


def _freeze_untrainable(optimizer, trainable):
    """Wraps an optimizer so only `trainable`-matched params update.

    Frozen leaves get `optax.set_to_zero`, and `optax.multi_transform`'s
    masking means the wrapped optimizer allocates state (Adam moments
    etc.) ONLY for the trainable subset — frozen positions hold
    `optax.MaskedNode` placeholders (see build()'s masked-moment
    sharding).
    """
    return optax.multi_transform(
        {"train": optimizer, "freeze": optax.set_to_zero()},
        lambda params: _trainable_labels(params, trainable))


def _param_ema(decay):
    """optax transform tracking an EMA of the PARAMETERS.

    Chained AFTER the base optimizer: update() sees the pre-update
    params and the final updates, reconstructs the post-update params,
    and folds them into the shadow.
    """

    def init(params):
        # A REAL copy: jnp.asarray would alias the live param buffers,
        # and aliased leaves break the train step's state donation
        # (same buffer donated twice).
        return ParamEmaState(ema=jax.tree_util.tree_map(
            lambda p: jnp.array(p, copy=True), params))

    def update(updates, state, params=None):
        if params is None:
            raise ValueError("param_ema requires params in update().")
        new_params = optax.apply_updates(params, updates)
        ema = jax.tree_util.tree_map(
            lambda e, p: decay * e + (1.0 - decay) * p,
            state.ema, new_params)
        return updates, ParamEmaState(ema=ema)

    return optax.GradientTransformation(init, update)


class TrainState:
    """Step + params + optimizer state + auxiliary model variables
    (e.g. flax batch_stats), registered as a pytree."""

    def __init__(self, step, params, opt_state, rng, extra_vars=None):
        self.step = step
        self.params = params
        self.opt_state = opt_state
        self.rng = rng
        self.extra_vars = {} if extra_vars is None else extra_vars

    def tree_flatten(self):
        return (self.step, self.params, self.opt_state, self.rng,
                self.extra_vars), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


class Trainer:
    """Keras-`model.fit` parity on a JAX device mesh."""

    def __init__(self,
                 model,
                 optimizer="adam",
                 loss="sparse_categorical_crossentropy",
                 metrics=("accuracy",),
                 mesh=None,
                 param_sharding_rules=None,
                 train_kwargs=None,
                 eval_kwargs=None,
                 rng_keys=(),
                 seed=0,
                 aux_loss_weight=0.01,
                 gradient_accumulation_steps=1,
                 remat=False,
                 zero1=False,
                 fsdp=False,
                 ema_decay=None,
                 steps_per_execution=1,
                 trainable=None):
        """Constructor.

        Args:
            model: A flax.linen Module (init/apply), or a tuple
                (init_fn, apply_fn) with init_fn(rng, x)->params and
                apply_fn(params, x, **kwargs)->outputs.
            optimizer: optax `GradientTransformation` or a name in
                OPTIMIZERS.
            loss: callable(outputs, labels)->per-example loss, or a name
                in LOSSES.
            metrics: iterable of names in METRICS or callables
                (outputs, labels)->scalar.
            mesh: Device mesh; defaults to the ambient runtime mesh (or
                single-device execution when neither exists).
            param_sharding_rules: list of (path_regex, PartitionSpec) for
                model-parallel layouts; default replicates params (DP).
            train_kwargs: extra kwargs passed to apply during training
                (e.g. {"train": True} or {"deterministic": False}).
            eval_kwargs: extra kwargs for evaluation/prediction.
            rng_keys: names of per-step rngs to pass to flax apply (e.g.
                ("dropout",)).
            seed: PRNG seed.
            aux_loss_weight: Weight on auxiliary losses the model sows
                into the "losses" collection (e.g. MoE load-balancing
                loss; Switch-Transformer default 0.01).
            gradient_accumulation_steps: Accumulate gradients over N
                steps before applying the update (`optax.MultiSteps`) —
                N small device batches emulate one N-x-larger global
                batch when HBM cannot hold it.
            remat: Rematerialize the forward pass in backward
                (`jax.checkpoint`): trades recompute FLOPs for
                activation memory — the standard lever for long
                sequences / deep models on HBM-bound chips.
            zero1: Shard optimizer state (Adam moments etc.) over the
                data axis — ZeRO stage 1. Optimizer memory drops to
                O(1/|dp|) per device for one all-gather of the updates
                per step; parameters keep their layout. No-op without a
                mesh or a >1-sized "dp" axis.
            fsdp: Fully-shard parameters themselves over the data axis
                (ZeRO-3 style), on top of any param_sharding_rules; XLA
                all-gathers weights at use and reduce-scatters grads.
                Implies the zero1 moment layout (moments follow their
                params). No-op without a mesh or a >1-sized "dp" axis.
            steps_per_execution: Run N optimizer steps per XLA
                executable call (Keras `steps_per_execution`): fit
                stacks N host batches and a `lax.scan` executes them in
                ONE dispatch — the host-overhead amortizer for
                fast steps (each dispatch has a fixed host cost).
                Works on multi-host
                pods (local groups assemble into global stacked
                arrays); leftover/ragged batches run through the
                single-step path.
            trainable: Optional param-path regex (or callable
                path_string -> bool): only matching parameters receive
                optimizer updates; the rest are frozen — the
                fine-tuning lever for imported checkpoints (e.g.
                `trainable=r"lm_head|block_11"` trains the head and
                last block of an `import_hf_llama` model). Matching
                uses `re.search` on the same "block_0/attention/query/
                kernel" path strings as `param_sharding_rules`. Frozen
                parameters allocate NO optimizer state (`optax.
                multi_transform` masking), so Adam moments shrink to
                the trainable subset.
            ema_decay: Track an exponential moving average of the
                parameters (e.g. 0.999): `ema_params` exposes the
                shadow, and evaluate/predict take `use_ema=True` to
                run on it — the standard eval-quality lever for vision
                and diffusion training. The shadow lives in optimizer
                state (checkpointed, sharded like the params).
        """
        if hasattr(model, "init") and hasattr(model, "apply"):
            self._init_fn = model.init
            self._apply_fn = model.apply
            self._is_flax = True
        else:
            self._init_fn, self._apply_fn = model
            self._is_flax = False
        self.model = model

        # Original constructor specs are kept for cross-process shipping
        # (cloud_fit serializes names/callables, not optax closures).
        self.optimizer_spec = optimizer
        self.loss_spec = loss
        self.metric_specs = tuple(metrics)

        if isinstance(optimizer, str):
            optimizer = OPTIMIZERS[optimizer]()
        self.trainable = trainable
        if trainable is not None:
            optimizer = _freeze_untrainable(optimizer, trainable)
        self.ema_decay = ema_decay
        if ema_decay is not None:
            if not 0.0 < ema_decay < 1.0:
                raise ValueError(
                    "ema_decay must be in (0, 1); got {}.".format(
                        ema_decay))
            # Chained before any MultiSteps wrap so the shadow folds in
            # applied updates (zero updates on accumulation micro-steps
            # just decay toward unchanged params — harmless smoothing).
            optimizer = optax.chain(optimizer, _param_ema(ema_decay))
        self.steps_per_execution = int(steps_per_execution)
        if self.steps_per_execution < 1:
            raise ValueError(
                "steps_per_execution must be >= 1; got {}.".format(
                    steps_per_execution))
        self.gradient_accumulation_steps = int(gradient_accumulation_steps)
        if self.gradient_accumulation_steps > 1:
            optimizer = optax.MultiSteps(
                optimizer, every_k_schedule=self.gradient_accumulation_steps)
        self.optimizer = optimizer
        self.remat = bool(remat)
        self.zero1 = bool(zero1)
        self.fsdp = bool(fsdp)

        if loss is sparse_categorical_crossentropy:
            # The FACTORY, not a loss: Keras muscle memory makes
            # `loss=sparse_categorical_crossentropy` an easy slip that
            # would otherwise fail with an arity error deep inside the
            # jitted step.
            raise TypeError(
                "sparse_categorical_crossentropy is a factory — call it "
                "(e.g. loss=sparse_categorical_crossentropy(0.1)) or "
                "use the string 'sparse_categorical_crossentropy'.")
        self.loss_fn = LOSSES[loss] if isinstance(loss, str) else loss
        self.metric_fns = {}
        for m in metrics:
            if isinstance(m, str):
                self.metric_fns[m] = METRICS[m]
            else:
                self.metric_fns[getattr(m, "__name__", "metric")] = m

        self._mesh = mesh if mesh is not None else runtime.global_mesh()
        self.param_sharding_rules = param_sharding_rules
        self.train_kwargs = dict(train_kwargs or {})
        self.eval_kwargs = dict(eval_kwargs or {})
        self.rng_keys = tuple(rng_keys)
        self.seed = seed
        self.aux_loss_weight = aux_loss_weight
        self._sows_losses = False  # set by build() when the model sows

        self.state = None
        self._jit_train_step = None
        self._jit_eval_step = None
        self._scalar_unmasked_metrics = set()
        self._jit_predict_step = None
        self.stop_training = False  # set by callbacks (EarlyStopping)
        # Step-granular abort (preemption): checked between steps in the
        # fit loop — a plain host bool, so the check costs nothing and
        # never syncs the device. request_stop() sets it.
        self._abort_epoch = False
        # graftguard state: the live data-stream position (stamped into
        # checkpoint metadata by AutoCheckpoint/rescue saves), the armed
        # resume-latency probe, and the active chaos plan.
        self._data_progress = None
        self._resume_probe = None
        self._chaos = None

    # -- state construction --------------------------------------------

    def _apply(self, params, x, extra_vars=None, rngs=None, mutable=False,
               **kwargs):
        if self._is_flax:
            variables = dict({"params": params}, **(extra_vars or {}))
            extra = {}
            if rngs:
                extra["rngs"] = rngs
            if mutable:
                extra["mutable"] = mutable
            return self._apply_fn(variables, x, **extra, **kwargs)
        return self._apply_fn(params, x, **kwargs)

    def build(self, sample_x, variables=None):
        """Initializes parameters/optimizer state (lazily called by fit).

        variables: optional pre-trained variables to build FROM —
        e.g. the dict `models.import_hf_llama`/`import_hf_gpt2`/
        `import_hf_deepseek` return — instead of random init (the
        fine-tuning entry point; the Keras analogue of building a
        model with loaded weights). Provided collections override the
        freshly initialized ones per collection ({"params": ...} alone
        keeps fresh batch_stats etc.); params must match the model's
        structure and shapes exactly, checked loudly. Optimizer state,
        shardings, and trainable= masking are derived from the
        provided weights like any other build.
        """
        if self.state is not None:
            if variables is not None:
                # Returning the existing (possibly random-init) state
                # while the caller believes a checkpoint was loaded is
                # the silent-divergence failure mode this API exists
                # to avoid.
                raise RuntimeError(
                    "build(variables=...) called on an already-built "
                    "Trainer: the provided weights would be ignored. "
                    "Load weights before the first fit/evaluate/"
                    "predict/build call.")
            return self.state
        rng = jax.random.PRNGKey(self.seed)
        init_rng, state_rng = jax.random.split(rng)
        sample = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a[:1]), sample_x)
        init_kwargs = dict(self.train_kwargs)
        init_variables = self._init_fn(init_rng, sample, **init_kwargs)
        if variables is not None:
            if not (self._is_flax and "params" in init_variables):
                raise ValueError(
                    "build(variables=...) needs a flax model (the "
                    "(init_fn, apply_fn) path has no collections).")
            if "params" not in variables:
                raise ValueError(
                    "build(variables=...) must include a 'params' "
                    "collection (got {}).".format(sorted(variables)))
            init_shapes = jax.tree_util.tree_map(
                jnp.shape, init_variables["params"])
            try:
                given_shapes = jax.tree_util.tree_map(
                    jnp.shape, variables["params"])
                matches = init_shapes == given_shapes
            except ValueError:
                matches = False
            if not matches:
                raise ValueError(
                    "build(variables=...): provided params do not "
                    "match the model's structure/shapes — wrong "
                    "checkpoint for this model configuration?")
            init_variables = {**dict(init_variables), **dict(variables)}
        variables = init_variables
        if self._is_flax and "params" in variables:
            variables = dict(variables)
            params = variables.pop("params")
            # "losses" is a transient per-step collection (sown aux
            # losses, e.g. MoE load balancing), not persistent state.
            self._sows_losses = variables.pop("losses", None) is not None
            extra_vars = variables  # e.g. {"batch_stats": ...}
        else:
            params, extra_vars = variables, {}
        if self._mesh is not None:
            if self.fsdp:
                param_sharding = sharding_lib.fsdp_sharding(
                    params, self._mesh, rules=self.param_sharding_rules)
            else:
                param_sharding = sharding_lib.param_sharding(
                    params, self.param_sharding_rules, self._mesh)
            params = jax.tree_util.tree_map(
                lambda a, s: jax.device_put(a, s), params, param_sharding)
            # Optimizer-state layout: optax states embed params-shaped
            # subtrees (Adam moments) — those inherit the param sharding
            # (tp-sharded moments for tp-sharded params); everything else
            # (step counters) replicates. Structural substitution is used
            # because jnp.zeros_like in init has no data dependence on
            # params, so jit sharding propagation cannot infer this.
            abstract_opt = jax.eval_shape(self.optimizer.init, params)
            param_struct = jax.tree_util.tree_structure(params)
            # fsdp params are already dp-sharded, so moments inheriting
            # the param layout are ZeRO-sharded for free; zero1 adds the
            # dp moment layout without touching the params.
            moment_sharding = param_sharding
            if self.zero1 and not self.fsdp:
                moment_sharding = sharding_lib.zero1_opt_sharding(
                    params, param_sharding, self._mesh)

            # Trainable-subset masking (optax.multi_transform) swaps
            # frozen leaves for MaskedNode, so masked moments are NOT
            # params-shaped: recognize that structure too, or every
            # moment falls into the replicated fallback and the
            # zero1/fsdp/tp layouts silently vanish exactly for the
            # fine-tuning runs the feature targets.
            masked_struct = None
            if self.trainable is not None:
                labels = _trainable_labels(params, self.trainable)
                _mask_like = lambda tree: jax.tree_util.tree_map(
                    lambda lbl, leaf: (leaf if lbl == "train"
                                       else optax.MaskedNode()),
                    labels, tree)
                masked_struct = jax.tree_util.tree_structure(
                    _mask_like(params))
                masked_moment_sharding = _mask_like(moment_sharding)

            def _is_params_shaped(node):
                if isinstance(node, ParamEmaState):
                    return True
                struct = jax.tree_util.tree_structure(node)
                return (struct == param_struct
                        or (masked_struct is not None
                            and struct == masked_struct))

            def _subtree_sharding(node):
                if isinstance(node, ParamEmaState):
                    # The EMA shadow substitutes into the params slot at
                    # eval time, so it keeps the PARAM layout even under
                    # zero1 moment sharding.
                    return ParamEmaState(ema=param_sharding)
                if (masked_struct is not None
                        and jax.tree_util.tree_structure(node)
                        == masked_struct):
                    return masked_moment_sharding
                if _is_params_shaped(node):
                    return moment_sharding
                return jax.tree_util.tree_map(
                    lambda _: sharding_lib.replicated(self._mesh), node)

            opt_sharding = jax.tree_util.tree_map(
                _subtree_sharding, abstract_opt,
                is_leaf=_is_params_shaped)
            opt_state = runtime.instrumented_jit(
                self.optimizer.init, out_shardings=opt_sharding)(params)
            replicate_all = lambda tree: jax.tree_util.tree_map(
                lambda _: sharding_lib.replicated(self._mesh), tree)
            extra_vars = jax.tree_util.tree_map(
                lambda a: jax.device_put(
                    jnp.asarray(a), sharding_lib.replicated(self._mesh)),
                extra_vars)
            self._state_sharding = TrainState(
                sharding_lib.replicated(self._mesh),
                param_sharding,
                opt_sharding,
                sharding_lib.replicated(self._mesh),
                replicate_all(extra_vars))
            state = TrainState(
                jax.device_put(jnp.zeros((), jnp.int32),
                               sharding_lib.replicated(self._mesh)),
                params,
                opt_state,
                jax.device_put(state_rng,
                               sharding_lib.replicated(self._mesh)),
                extra_vars)
        else:
            opt_state = self.optimizer.init(params)
            self._state_sharding = None
            state = TrainState(jnp.zeros((), jnp.int32), params, opt_state,
                               state_rng, extra_vars)
        self.state = state
        return state

    # -- jitted steps ---------------------------------------------------

    @staticmethod
    def _batch_widener(policy, weighted):
        """In-graph inverse of the `input_cast` host narrowing: widens
        the features slot of a train batch back to float32 as the
        step's first op, so the model computes in its own dtype and
        only the wire (or resident HBM storage) pays the narrow
        format. None when no policy is active."""
        if policy is None:
            return None
        if weighted:
            def widen(batch):
                x, y, w = batch
                return (policy.widen(x), y, w)
        else:
            def widen(batch):
                x, y = batch
                return (policy.widen(x), y)
        return widen

    def _make_train_step_body(self, weighted=False, widen=None):
        """The raw (unjitted) train step closure — the single source of
        truth shared by the jitted single-step path, the
        steps_per_execution scan and the device-resident executable.

        weighted: batches are (x, y, sample_weight) triples — the
        loss is the weighted batch mean (Keras sum-over-batch-size
        semantics: mean(per_example * w)) and per-example metrics are
        weighted means (sum(v*w)/sum(w)).

        widen: optional in-graph batch transform (`_batch_widener`)
        restoring input_cast-narrowed features to float32."""
        metric_fns = self.metric_fns
        loss_fn = self.loss_fn
        optimizer = self.optimizer
        train_kwargs = self.train_kwargs
        train_mask_aware = {name: self._metric_accepts_mask(fn)
                            for name, fn in metric_fns.items()}
        rng_keys = self.rng_keys

        aux_loss_weight = self.aux_loss_weight
        sows_losses = self._sows_losses
        # Scalar metrics that can't take weights, recorded at trace
        # time (fit() checks after the first step on the weighted path).
        train_scalar_unmasked = self._train_scalar_unmasked = set()

        def train_step(state, batch):
            if widen is not None:
                batch = widen(batch)
            if weighted:
                x, y, w = batch
                w = w.astype(jnp.float32)
            else:
                x, y = batch
                w = None
            step_rng = jax.random.fold_in(state.rng, state.step)
            rngs = ({k: jax.random.fold_in(step_rng, i)
                     for i, k in enumerate(rng_keys)} or None)
            mutable = list(state.extra_vars.keys())
            if sows_losses:
                mutable = mutable + ["losses"]

            def compute_loss(params):
                if mutable:
                    outputs, new_vars = self._apply(
                        params, x, extra_vars=state.extra_vars, rngs=rngs,
                        mutable=mutable, **train_kwargs)
                else:
                    outputs = self._apply(params, x, rngs=rngs,
                                          **train_kwargs)
                    new_vars = state.extra_vars
                per_example = loss_fn(outputs, y)
                if w is not None:
                    # Weighted Keras semantics: collapse any non-batch
                    # dims per example, then mean(per_example * w)
                    # (sum-over-batch-size, NOT normalized by sum(w)).
                    per_example = _per_example_view(per_example,
                                                    w.shape[0]) * w
                loss = jnp.mean(per_example)
                new_vars = dict(new_vars)
                sown = new_vars.pop("losses", None)
                if sown is not None:
                    aux = sum(jnp.sum(jnp.asarray(l).astype(loss.dtype))
                              for l in jax.tree_util.tree_leaves(sown))
                    loss = loss + aux_loss_weight * aux
                return loss, (outputs, new_vars)

            if self.remat:
                # Recompute the forward in backward instead of keeping
                # activations: HBM for FLOPs.
                compute = jax.checkpoint(compute_loss)
            else:
                compute = compute_loss
            (loss, (outputs, new_vars)), grads = jax.value_and_grad(
                compute, has_aux=True)(state.params)
            if isinstance(optimizer, (optax.GradientTransformationExtraArgs,
                                      optax.MultiSteps)):
                # The extra-args protocol carries the step's loss to
                # loss-aware transforms (optax.contrib.reduce_on_plateau
                # chained after the base optimizer). In current optax
                # every built-in optimizer is ExtraArgs-typed and simply
                # ignores unknown extras, so this is the COMMON branch;
                # MultiSteps (grad accumulation) forwards **extra_args
                # to its inner chain. Only raw custom
                # GradientTransformations (e.g. _param_ema) take the
                # plain call below.
                updates, new_opt_state = optimizer.update(
                    grads, state.opt_state, state.params, value=loss)
            else:
                updates, new_opt_state = optimizer.update(
                    grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            new_state = TrainState(state.step + 1, new_params,
                                   new_opt_state, state.rng, new_vars)
            logs = {"loss": loss}
            for name, fn in metric_fns.items():
                # Mean-reduce: metric fns may return per-example values
                # (built-ins do) or a scalar; train logs are batch means
                # (weighted means under sample_weight). Mask-aware
                # metrics (fn(outputs, y, mask=...), the padded-eval
                # contract) get the weights as the mask — or all-ones,
                # train batches are never padded.
                lead = jax.tree_util.tree_leaves(outputs)[0].shape[0]
                mask = w if w is not None else jnp.ones((lead,),
                                                        jnp.float32)
                if train_mask_aware[name]:
                    # Same contract as eval: per-example returns get
                    # the weighted mean; scalars are already weighted.
                    v = jnp.asarray(fn(outputs, y, mask=mask))
                    if v.ndim >= 1:
                        logs[name] = _weighted_mean(
                            _per_example_view(v, lead), mask)
                    else:
                        logs[name] = v
                    continue
                v = jnp.asarray(fn(outputs, y))
                if v.ndim >= 1:
                    logs[name] = _weighted_mean(
                        _per_example_view(v, lead), mask)
                else:
                    # Scalar metric with no way to apply weights:
                    # recorded at trace time; fit() raises on the
                    # weighted path instead of logging an unweighted
                    # number (mirror of evaluate()'s guard).
                    if weighted:
                        train_scalar_unmasked.add(name)
                    logs[name] = jnp.mean(v)
            if weighted:
                # For exact epoch-level aggregation: per-batch weighted
                # means must be re-weighted by their batch weight sums
                # (a plain mean of ratios is biased when batch sums
                # differ). Stripped from user-facing logs in
                # _fit_epochs.
                logs["_batch_weight"] = jnp.sum(w)
            return new_state, logs

        return train_step

    def _make_train_step(self, weighted=False, widen=None):
        train_step = self._make_train_step_body(weighted=weighted,
                                                widen=widen)
        if self._mesh is None:
            return runtime.instrumented_jit(train_step, donate_argnums=0)
        batch_sharding = sharding_lib.batch_sharding(self._mesh)
        batch_in = ((batch_sharding,) * 3 if weighted
                    else (batch_sharding, batch_sharding))
        return runtime.instrumented_jit(
            train_step,
            in_shardings=(self._state_sharding, batch_in),
            out_shardings=(self._state_sharding, None),
            donate_argnums=0)

    @staticmethod
    def _reduce_scan_logs(logs_seq):
        """Group-level aggregation of scanned per-step logs ([num_steps]
        leaves) — shared by the steps_per_execution executable and the
        device-resident executable.

        Weighted groups: each step's metric is a weighted mean over
        that step's batch; the group value re-weights by the per-step
        weight sums (same identity the epoch aggregation uses). Loss
        keeps sum-over-batch-size semantics (plain mean)."""
        if "_batch_weight" in logs_seq:
            ws = logs_seq["_batch_weight"]
            logs = {}
            for k, v in logs_seq.items():
                if k == "_batch_weight":
                    continue
                logs[k] = (jnp.mean(v) if k == "loss"
                           else _weighted_mean(v, ws))
            logs["_batch_weight"] = jnp.sum(ws)
            return logs
        return {k: jnp.mean(v) for k, v in logs_seq.items()}

    def _make_multi_train_step(self, num_steps, weighted=False,
                               widen=None):
        """ONE XLA executable running `num_steps` optimizer steps via
        `lax.scan` over a leading step axis of stacked batches
        ([num_steps, B, ...] leaves) — Keras `steps_per_execution`,
        TPU-first: the per-step host dispatch cost amortizes across
        the whole group, and
        XLA can overlap the next step's transfers with compute.

        Returns (state, logs) with each log the mean over the group
        (weighted runs also return summed "_batch_weight" so epoch
        aggregation stays exact).
        """
        del num_steps  # shape comes from the stacked batch leaves
        inner = self._make_train_step_body(weighted=weighted,
                                           widen=widen)

        def multi_step(state, batches):
            def body(s, batch):
                s, logs = inner(s, batch)
                return s, logs

            state, logs_seq = jax.lax.scan(body, state, batches)
            return state, self._reduce_scan_logs(logs_seq)

        if self._mesh is None:
            return runtime.instrumented_jit(multi_step, donate_argnums=0)
        batch_sharding = sharding_lib.batch_sharding(self._mesh)
        stacked = NamedSharding(
            self._mesh, P(None, *batch_sharding.spec))
        batch_in = ((stacked,) * 3 if weighted
                    else (stacked, stacked))
        return runtime.instrumented_jit(
            multi_step,
            in_shardings=(self._state_sharding, batch_in),
            out_shardings=(self._state_sharding, None),
            donate_argnums=0)

    def _make_resident_run(self, num_steps, steps_per_epoch, resident,
                           weighted):
        """ONE XLA executable advancing `num_steps` optimizer steps
        with ALL data already in HBM (`DeviceResidentDataset`).

        The within-epoch position is derived in-graph from
        `state.step` relative to `base_step` (the step counter at
        epoch entry); the epoch index arrives as `epoch_idx`. Both are
        device scalars, so a call never syncs the host. `epoch_idx` is
        kept in lockstep with the source dataset's `_epoch` counter by
        the fit loop — the host path's shape-inference peek consumes
        one epoch of that counter, and matching it here is what makes
        shuffled resident batches bit-identical to the host path's.
        Shuffled runs rebuild the epoch's permutation with the exact
        `epoch_permutation` doctrine the host path uses (threefry is
        bit-deterministic across backends), then draw each batch with
        `dynamic_slice` of the permutation + `jnp.take`; unshuffled
        runs are a contiguous `dynamic_slice` of the data. The fit
        loop guarantees a call never straddles an epoch boundary (the
        permutation is computed once per call).

        Executables are cached per geometry (`_resident_run_cache`):
        a re-entrant fit over the same dataset — graftguard's warm
        resume, or back-to-back fits — reuses the compiled run instead
        of re-tracing, which is what keeps a resumed resident fit at
        zero new compiles (the retrace sentinel's invariant).
        """
        key = (num_steps, steps_per_epoch, resident.batch_size,
               resident.num_examples, resident.shuffle, resident.seed,
               resident.kind, weighted,
               None if resident.policy is None
               else resident.policy.cache_key)
        cache = getattr(self, "_resident_run_cache", None)
        if cache is None:
            cache = self._resident_run_cache = {}
        cached = cache.get(key)
        if cached is not None:
            run, scalar_set = cached
            # Restore the build-time scalar-metric set: the fit loop's
            # first-step guard reads whatever the (cached) build saw.
            self._train_scalar_unmasked = scalar_set
            return run
        inner = self._make_train_step_body(
            weighted=weighted,
            widen=self._batch_widener(resident.policy, weighted))
        batch_size = resident.batch_size
        num_examples = resident.num_examples
        shuffle = resident.shuffle
        seed = resident.seed

        def run(state, data, base_step, epoch_idx):
            if shuffle:
                key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                         epoch_idx)
                perm = jax.random.permutation(key, num_examples)
            else:
                perm = None

            def one_step(s):
                pos = (s.step - base_step) % steps_per_epoch
                start = pos * batch_size
                if perm is not None:
                    idx = jax.lax.dynamic_slice_in_dim(perm, start,
                                                       batch_size)
                    batch = jax.tree_util.tree_map(
                        lambda a: jnp.take(a, idx, axis=0), data)
                else:
                    batch = jax.tree_util.tree_map(
                        lambda a: jax.lax.dynamic_slice_in_dim(
                            a, start, batch_size), data)
                return inner(s, batch)

            if num_steps == 1:
                return one_step(state)
            state, logs_seq = jax.lax.scan(
                lambda s, _: one_step(s), state, None,
                length=num_steps)
            return state, self._reduce_scan_logs(logs_seq)

        if self._mesh is None:
            jitted = runtime.instrumented_jit(run, donate_argnums=0)
        else:
            jitted = runtime.instrumented_jit(
                run,
                in_shardings=(self._state_sharding, resident.sharding,
                              sharding_lib.replicated(self._mesh),
                              sharding_lib.replicated(self._mesh)),
                out_shardings=(self._state_sharding, None),
                donate_argnums=0)
        cache[key] = (jitted, self._train_scalar_unmasked)
        return jitted

    @staticmethod
    def _metric_accepts_mask(fn):
        """Opt-in masked-metric signature: fn(outputs, y, mask=...).

        The opt-in must be the EXPLICIT named parameter — treating a
        bare ``**kwargs`` as mask-aware would silently hand scalar
        metrics that ignore it an unmasked mean on padded batches, the
        exact leak the mask contract exists to close.
        """
        try:
            params = inspect.signature(fn).parameters
        except (TypeError, ValueError):
            return False
        return "mask" in params

    def _make_eval_step(self):
        metric_fns = self.metric_fns
        loss_fn = self.loss_fn
        eval_kwargs = self.eval_kwargs
        mask_aware = {name: self._metric_accepts_mask(fn)
                      for name, fn in metric_fns.items()}
        # Names of metrics that return a scalar AND can't take the
        # valid-mask: populated at trace time (shape info is static),
        # read by evaluate() to fail loudly on padded tail batches
        # instead of silently averaging padded duplicates in.
        scalar_unmasked = self._scalar_unmasked_metrics = set()

        def eval_step(state, batch):
            # mask flags real examples (times any sample weights);
            # padded tail duplicates (wrapped by ArrayDataset for
            # static shapes) carry zero weight, so metrics are exact
            # example-weighted means.
            x, y, mask = batch
            outputs = self._apply(state.params, x,
                                  extra_vars=state.extra_vars,
                                  **eval_kwargs)
            per_ex = _per_example_view(loss_fn(outputs, y), mask.shape[0])
            logs = {"loss": _weighted_mean(per_ex, mask)}
            for name, fn in metric_fns.items():
                if mask_aware[name]:
                    v = jnp.asarray(fn(outputs, y, mask=mask))
                    if v.ndim >= 1:
                        logs[name] = _weighted_mean(
                            _per_example_view(v, mask.shape[0]), mask)
                    else:
                        # Scalar from a mask-aware fn: it already
                        # weighted out the padded rows.
                        logs[name] = v
                    continue
                v = jnp.asarray(fn(outputs, y))
                if v.ndim >= 1:
                    logs[name] = _weighted_mean(
                        _per_example_view(v, mask.shape[0]), mask)
                else:
                    # Scalar custom metric with no way to apply the
                    # valid-mask: correct on full unweighted batches
                    # only. evaluate() raises otherwise.
                    scalar_unmasked.add(name)
                    logs[name] = v
            # The batch's TOTAL aggregation weight (valid rows x any
            # sample weights), summed over the GLOBAL mask: on pods the
            # host only holds a local shard, so this in-graph sum is
            # the one place the global batch weight exists. evaluate()
            # pops it before reporting.
            logs["_batch_weight"] = jnp.sum(mask)
            return logs

        if self._mesh is None:
            return runtime.instrumented_jit(eval_step)
        batch_sharding = sharding_lib.batch_sharding(self._mesh)
        return runtime.instrumented_jit(
            eval_step,
            in_shardings=(self._state_sharding,
                          (batch_sharding, batch_sharding,
                           batch_sharding)))

    # -- feeding --------------------------------------------------------

    def _feed(self, batch):
        """Host batch -> device batch (global array on multi-host).

        On multi-host pods `batch` must be this process's local shard
        (`_epoch_batches` handles that for ArrayDataset; custom iterables
        must yield process-local batches).
        """
        if self._mesh is None:
            # Commit to device explicitly: jit would transfer uncommitted
            # host arrays itself, but an explicit put (a) is a no-op for
            # already-device-resident arrays, so callers that reuse a
            # batch don't pay the host->device copy per step (a
            # 256x224x224x3 fp32 batch is 154 MB on the wire), and (b)
            # keeps feeding semantics
            # uniform with the mesh path below.
            runtime.record_h2d(batch)
            return jax.device_put(batch)
        if jax.process_count() > 1:
            return sharding_lib.make_global_batch(batch, self._mesh)
        return sharding_lib.shard_batch(batch, self._mesh)

    def _epoch_batches(self, dataset, start_step=0):
        """One epoch of host batches, process-local on multi-host pods.

        Dispatch on the protocol, not the class: ArrayDataset provides
        `process_local_view`, and wrappers (ThreadedDataset) forward it,
        so pod sharding survives wrapping. `start_step` re-bases the
        epoch mid-stream for graftguard resume: datasets exposing
        `iter_from` skip WITHOUT materializing the prefix (the
        permutation is just sliced further along); anything else pays
        an islice drop of the first `start_step` batches.
        """
        if (jax.process_count() > 1
                and hasattr(dataset, "process_local_view")):
            if start_step:
                return dataset.process_local_view(start_step=start_step)
            return dataset.process_local_view()
        if start_step and hasattr(dataset, "iter_from"):
            return dataset.iter_from(start_step)
        if start_step:
            return itertools.islice(iter(dataset), int(start_step), None)
        return iter(dataset)

    def _host_batches(self, dataset, cast, start_step=0):
        """One epoch of host batches with the `input_cast` narrowing
        applied to the features slot — bytes on the wire drop 2x
        (bfloat16) or 4x (uint8); the jitted step's widener restores
        float32 in-graph."""
        batches = self._epoch_batches(dataset, start_step)
        if cast is None:
            return batches

        def narrowed():
            for batch in batches:
                if isinstance(batch, tuple) and len(batch) == 3:
                    x, y, w = batch
                    yield (cast.host_cast(x), y, w)
                elif isinstance(batch, tuple) and len(batch) == 2:
                    x, y = batch
                    yield (cast.host_cast(x), y)
                else:
                    yield cast.host_cast(batch)
        return narrowed()

    def _pad_tail(self, batch, steady, weighted):
        """Host-side ragged-tail padding: reshapes an n-row tail batch
        to the steady B-row geometry so it dispatches through the
        ALREADY-COMPILED full-shape weighted executable instead of
        minting a one-off ragged variant (a fresh trace + XLA compile
        per distinct tail size — the cost `runtime.compile_stats()`
        exists to pin at zero in steady state).

        Rows wrap (real data, NaN-safe) and the weight vector makes the
        math exact: real rows carry weight * (B/n), wrapped pads carry
        0, so the weighted loss mean(per_ex * w) over B rows equals the
        ragged mean over n rows EXACTLY — gradients included — and
        weighted-mean metrics reduce to means over the real rows (the
        B/n scale cancels).

        Returns ((x, y, w'), real_weight_sum), or None when the
        contract can't hold and the caller must fall back to ragged
        dispatch: multi-process feeding (the scale needs the global
        real count), models that sow losses (the aux-loss mean has no
        weight slot, so wrapped rows would shift gradients), models
        with extra_vars (BatchNorm-style batch statistics would fold
        the wrapped rows in), and unlabeled batches (no (x, y) slots
        to carry a weight alongside).
        """
        if jax.process_count() > 1:
            return None
        if getattr(self, "_sows_losses", False):
            return None
        if (self.state is not None
                and jax.tree_util.tree_leaves(self.state.extra_vars)):
            return None
        if weighted:
            if not (isinstance(batch, tuple) and len(batch) == 3):
                return None
            x, y, w = batch
        elif isinstance(batch, tuple) and len(batch) == 2:
            x, y = batch
            w = None
        else:
            return None
        n = _lead_count(batch)
        if n <= 0 or n >= steady:
            return None
        idx = np.arange(steady) % n
        real = (np.arange(steady) < n).astype(np.float32)
        scale = steady / float(n)
        take = lambda a: np.asarray(a)[idx]
        x_p = jax.tree_util.tree_map(take, x)
        y_p = jax.tree_util.tree_map(take, y)
        if w is None:
            w_p = real * scale
            real_w_sum = float(n)
        else:
            w_np = np.asarray(w, np.float32)
            w_p = w_np[idx] * real * scale
            real_w_sum = float(w_np.sum())
        return (x_p, y_p, w_p), real_w_sum

    def _tail_step_fn(self, weighted, cast):
        """The executable a padded tail dispatches through.

        Weighted fits reuse the fit's own step (the padded triple has
        the steady aval signature — no new trace at all). Unweighted
        fits need the WEIGHTED variant (the pad mask rides in the
        weight slot); it is built once, cached in the ordinary step
        cache (so alternating fits reuse it), and compiles only on the
        first tail of the run — warm for every later epoch.
        """
        if weighted:
            return self._jit_train_step
        key = (True if cast is None else (True, cast.cache_key))
        step_cache = getattr(self, "_train_step_cache", None)
        if step_cache is None:
            step_cache = self._train_step_cache = {}
        if key not in step_cache:
            # _make_train_step_body re-points _train_scalar_unmasked at
            # the new variant's set; restore the fit's own pointer so
            # the first-step guard keeps reading the right slot.
            prev = getattr(self, "_train_scalar_unmasked", set())
            step = self._make_train_step(
                weighted=True, widen=self._batch_widener(cast, True))
            step_cache[key] = (step, self._train_scalar_unmasked)
            self._train_scalar_unmasked = prev
        step, scalar_set = step_cache[key]
        if scalar_set and not getattr(self, "_warned_tail_scalar", False):
            self._warned_tail_scalar = True
            warnings.warn(
                "Custom metrics {} return scalars that cannot be "
                "masked; their logged values for padded tail batches "
                "include the wrapped pad rows (loss, gradients and "
                "per-example metrics stay exact).".format(
                    sorted(scalar_set)))
        return step

    def _fix_tail_logs(self, logs, weighted, real_w_sum):
        """Host-side epoch-aggregation fixup for a padded tail's logs.

        The executable's in-graph `_batch_weight` is sum(w') =
        scale * sum(w) — right for the in-step math, wrong for epoch
        re-weighting, so weighted fits restore the REAL weight sum.
        Unweighted fits strip the key entirely: their epoch aggregation
        is a plain per-step mean and a lone `_batch_weight` entry would
        flip it into the weighted branch.
        """
        logs = dict(logs)
        if weighted:
            logs["_batch_weight"] = jnp.asarray(real_w_sum, jnp.float32)
        else:
            logs.pop("_batch_weight", None)
        return logs

    def _grouped_host_batches(self, batches, limit, spe, pad_tail=None):
        """Yields ("multi", n, stacked_group) for each full group of
        `spe` host batches and ("single", n, batch) for the leftovers —
        the steps_per_execution input shape. With `pad_tail` (a
        callable (batch, steady) -> ((x, y, w'), w_sum) or None),
        ragged leftovers smaller than the steady batch yield
        ("padded", n, padded) so they reuse the full-shape executable
        instead of tracing a one-off ragged variant."""
        steady = None
        group = []

        def emit_single(b):
            n = _lead_count(b)
            if pad_tail is not None and steady is not None and n < steady:
                padded = pad_tail(b, steady)
                if padded is not None:
                    return "padded", n, padded
            return "single", n, b

        for i, batch in enumerate(batches):
            if limit is not None and i >= limit:
                break
            if steady is None:
                steady = _lead_count(batch)
            if group and _lead_count(batch) != _lead_count(group[0]):
                # Ragged batch (e.g. drop_remainder=False tails):
                # np.stack can't group it — flush what we have as
                # singles and keep going.
                for b in group:
                    yield emit_single(b)
                group = []
            group.append(batch)
            if len(group) == spe:
                stacked = jax.tree_util.tree_map(
                    lambda *xs: np.stack(xs), *group)
                yield ("multi", sum(_lead_count(b) for b in group),
                       stacked)
                group = []
        for batch in group:
            yield emit_single(batch)

    def _feed_grouped(self, item):
        """Feed for the steps_per_execution path: stacked groups get
        the [None, dp, ...] layout the multi-step jit expects; leftover
        singles use the ordinary feed. On multi-host pods the stacked
        group holds this process's LOCAL batches; the global array is
        assembled across processes like make_global_batch, one stacking
        level up."""
        kind, _, batch = item
        if kind == "padded":
            # (padded_triple, real_weight_sum): the triple feeds like
            # any single batch; the weight sum stays host-side.
            return self._feed(batch[0])
        if kind == "single":
            return self._feed(batch)
        if self._mesh is None:
            runtime.record_h2d(batch)
            return jax.device_put(batch)
        bs = sharding_lib.batch_sharding(self._mesh)
        stacked = NamedSharding(self._mesh, P(None, *bs.spec))
        if jax.process_count() > 1:
            return sharding_lib.make_global_batch(batch,
                                                  sharding=stacked)
        runtime.record_h2d(batch)
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, stacked), batch)

    def _prefetch_batches(self, batches, limit=None, size=2):
        """Yields (local_example_count, device_batch) with `size` batches
        of read-ahead (see data.prefetch_to_device; this just adds the
        mesh-aware feed and the host-side example count)."""

        def feed(batch):
            lead = next((l for l in jax.tree_util.tree_leaves(batch)
                         if getattr(l, "shape", ())), None)
            n = int(lead.shape[0]) if lead is not None else 0
            return (n, self._feed(batch))

        return data_lib.prefetch_to_device(batches, size=size, feed=feed,
                                           limit=limit)

    # -- AOT warm start -------------------------------------------------

    def _ensure_host_steps(self, weighted, policy):
        """Installs the host-path step executables for this fit's
        variant, through the step cache: alternating
        weighted/unweighted fits reuse each compiled variant instead of
        re-tracing on every flip (bare bool keys; input_cast fits get
        (weighted, policy) tuple keys because the widener is baked into
        the compiled step). Each slot carries its scalar-unmasked set
        (written by that variant's trace), so switching variants
        re-points the guard _fit_epochs reads rather than leaking the
        other slot's names."""
        key = (weighted if policy is None
               else (weighted, policy.cache_key))
        widen = self._batch_widener(policy, weighted)
        step_cache = getattr(self, "_train_step_cache", None)
        if step_cache is None:
            step_cache = self._train_step_cache = {}
        if key not in step_cache:
            step = self._make_train_step(weighted=weighted,
                                         widen=widen)
            step_cache[key] = (step, self._train_scalar_unmasked)
        self._jit_train_step, scalar_set = step_cache[key]
        self._train_scalar_unmasked = (scalar_set if weighted
                                       else set())

        spe = self.steps_per_execution
        self._jit_multi_step = None
        if spe > 1:
            mcache = getattr(self, "_multi_step_cache", None)
            if mcache is None:
                mcache = self._multi_step_cache = {}
            if key not in mcache:
                mcache[key] = self._make_multi_train_step(
                    spe, weighted=weighted, widen=widen)
            self._jit_multi_step = mcache[key]

    def _state_struct(self):
        """ShapeDtypeStructs mirroring the live train state (the AOT
        lowering input; jit's explicit in_shardings supply layouts)."""
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
            self.state)

    @staticmethod
    def _batch_struct(batch):
        """ShapeDtypeStructs for a HOST batch, with dtypes
        canonicalized exactly as jit dispatch would (float64 ->
        float32 under the default x64-off), so the AOT executable's
        aval signature matches the real calls."""
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(
                np.shape(l),
                jax.dtypes.canonicalize_dtype(np.asarray(l).dtype)),
            batch)

    @staticmethod
    def _cast_sample(sample, policy):
        """Applies the input_cast host narrowing to a peeked sample so
        warm-start structs see the on-the-wire dtypes."""
        if policy is None:
            return sample
        if isinstance(sample, tuple) and len(sample) == 3:
            x, y, w = sample
            return (policy.host_cast(x), y, w)
        if isinstance(sample, tuple) and len(sample) == 2:
            x, y = sample
            return (policy.host_cast(x), y)
        return policy.host_cast(sample)

    def _warm_fit_steps(self, sample, weighted, policy):
        """AOT-compiles (`lower().compile()`) the installed fit
        executables for this fit's data geometry. The compiled
        executables land in each wrapper's warm table, so the epoch
        loop's first dispatch runs them directly — no trace, no
        compile, `runtime.compile_stats()` unmoved by step 1."""
        del weighted  # geometry comes from the sample itself
        state_struct = self._state_struct()
        batch_struct = self._batch_struct(
            self._cast_sample(sample, policy))
        self._jit_train_step.warm(state_struct, batch_struct)
        if getattr(self, "_jit_multi_step", None) is not None:
            spe = self.steps_per_execution
            stacked = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(
                    (spe,) + tuple(s.shape), s.dtype), batch_struct)
            self._jit_multi_step.warm(state_struct, stacked)

    def warmup(self, x, y=None, batch_size=32, sample_weight=None,
               input_cast=None, include_eval=False,
               include_predict=False):
        """AOT-compiles the step executables for a data geometry,
        ahead of (and without) running any training.

        The standalone form of `fit(warm_start=True)`: builds the model
        from a sample batch, installs the train-step executables for
        the (batch_size, weighted, input_cast) variant, and
        `lower().compile()`s them from ShapeDtypeStructs. A subsequent
        `fit()` over the same geometry starts trace-free, and with the
        persistent compile cache enabled
        (`parallel.compile_cache.enable`) a restarted process pays
        deserialization, not XLA, here.

        include_eval / include_predict additionally warm the
        evaluate() / predict() executables for the same batch geometry
        (include_eval needs labels `y`).

        Returns `runtime.compile_stats()` after warming (the warm-up's
        own compiles are visible there; steady-state assertions should
        snapshot AFTER warmup returns).
        """
        ds_kwargs = {}
        if sample_weight is not None:
            ds_kwargs["sample_weight"] = np.asarray(sample_weight,
                                                    np.float32)
        dataset = data_lib.as_dataset(x, y, batch_size=batch_size,
                                      shuffle=False, **ds_kwargs)
        weighted = (isinstance(dataset, data_lib.ArrayDataset)
                    and dataset.sample_weight is not None)
        sample = next(iter(dataset))
        sample_x = sample[0] if isinstance(sample, tuple) else sample
        self.build(sample_x)
        policy = None
        if input_cast not in (None, "none"):
            if isinstance(dataset, data_lib.ArrayDataset):
                policy = data_lib.make_input_cast(input_cast, dataset.x)
            else:
                policy = data_lib.make_input_cast(input_cast, sample_x)
        self._ensure_host_steps(weighted, policy)
        self._warm_fit_steps(sample, weighted, policy)
        state_struct = self._state_struct()
        if include_eval:
            if not (isinstance(sample, tuple) and len(sample) >= 2):
                raise ValueError(
                    "warmup(include_eval=True) needs labels y.")
            if self._jit_eval_step is None:
                self._jit_eval_step = self._make_eval_step()
            xb, yb = sample[0], sample[1]
            mask = jax.ShapeDtypeStruct((_lead_count(sample),),
                                        jnp.float32)
            self._jit_eval_step.warm(
                state_struct, (self._batch_struct(xb),
                               self._batch_struct(yb), mask))
        if include_predict:
            if self._jit_predict_step is None:
                self._jit_predict_step = self._make_predict_step()
            self._jit_predict_step.warm(
                state_struct, self._batch_struct(sample_x))
        return runtime.compile_stats()

    def _maybe_capture_step_flops(self, fn, n_steps, *args):
        """Captures model flops per TRAIN STEP for the graftscope MFU
        gauge, once per enabled telemetry session.

        Uses jit cost analysis on a lowering of the step executable
        (`fn.lower(*args).cost_analysis()['flops']` — no XLA compile),
        divided by `n_steps` for grouped/resident executables that run
        several steps per dispatch. Called at the FIRST dispatch of a
        fit, before the call consumes its donated buffers; the extra
        trace lands in epoch 0, ahead of the retrace-sentinel baseline.
        No-ops (one dict lookup) when telemetry is off.
        """
        telemetry = sys.modules.get("cloud_tpu.monitoring.telemetry")
        if telemetry is None:
            return
        tele = telemetry.get()
        if tele is None or not tele.active or tele.step_flops:
            return
        try:
            analysis = fn.lower(*args).cost_analysis()
            flops = float(analysis.get("flops", 0.0) or 0.0)
            if flops > 0:
                tele.set_step_flops(flops / max(int(n_steps), 1))
        except Exception:  # telemetry must never break training
            logger.debug("step-flops capture failed", exc_info=True)

    # -- public API -----------------------------------------------------

    @_env_watched
    @_env_telemetry
    @_env_sanitized
    def fit(self,
            x=None,
            y=None,
            epochs=1,
            batch_size=32,
            shuffle=True,
            validation_data=None,
            validation_split=0.0,
            initial_epoch=0,
            callbacks=(),
            steps_per_epoch=None,
            verbose=True,
            resume_from=None,
            prefetch=2,
            sample_weight=None,
            class_weight=None,
            cache=None,
            input_cast=None,
            async_logging=True,
            warm_start=False,
            on_retrace=None,
            resume=None,
            retries=None):
        """Trains the model; returns a history dict of per-epoch logs.

        resume: "auto" runs the fit under graftguard
        (`resilience.resilient_fit`): typed faults — the watchdog's
        `BackendUnavailable`, `Preemption`, `CheckpointCorrupt`,
        `DataStall`, `TerminateOnNaN(rollback=True)`'s `NaNLoss` — are
        caught, answered with a rescue/rollback checkpoint, and
        retried with capped exponential backoff; re-entry restores the
        latest checkpoint, re-bases the shuffle stream to the saved
        mid-epoch position (bit-identical continuation), and reuses
        the warm executables (zero new compiles). The checkpoint
        directory is `resume_from` (else `CLOUD_TPU_RESUME_DIR`, else
        `./graftguard_ckpt`), auto-checkpointed every epoch.

        retries: graftguard's retry budget (with resume="auto" only);
        default `CLOUD_TPU_RETRIES` (3).

        warm_start: AOT-compile the fit executables (train step, and
        the steps_per_execution / device-resident variants) from
        `ShapeDtypeStruct`s BEFORE the epoch loop — step 1 dispatches a
        finished executable without tracing anything
        (`runtime.compile_stats()` does not move on the first step).
        The same executables are also eligible for the persistent
        compile cache (`parallel.compile_cache.enable`), making the
        warm-up near-free on a restart.

        on_retrace: The retrace sentinel's policy — "warn" (default;
        also via the CLOUD_TPU_ON_RETRACE env var), "raise", or
        "ignore". After the first completed epoch (whose compiles are
        legitimate: the step executables, validation, callbacks), a
        steady-state epoch that traces or compiles ANYTHING raises/
        warns `runtime.RetraceWarning` — the counted invariant is zero
        new compiles after epoch 1, and the usual culprits (ragged
        tails, input dtype drift) are bugs worth hearing about.

        async_logging: The async host loop (default on). Epoch metrics
        stay device scalars, coalesce into ONE pytree, and are fetched
        by a background reader thread — the train loop never blocks on
        a device->host round trip unless a callback actually reads a
        metric value (callbacks receive a lazily-resolving logs dict).
        False fetches synchronously at each epoch boundary — still one
        coalesced fetch per epoch, and bit-identical values (the
        device-side aggregation is shared). Either way
        `runtime.transfer_stats()["d2h_fetches"]` counts at most one
        fetch per logging interval. Fetch errors from the background
        thread re-raise on the training thread at the next epoch
        boundary (or at fit exit for the last epoch).

        cache: "device" uploads the whole dataset to device HBM ONCE
        and draws every batch in-graph (device-side per-epoch
        permutation + dynamic_slice/take): steady-state training does
        zero host->device data transfers while keeping `shuffle=True`
        semantics (same threefry permutation as the host path) and
        composing with steps_per_execution and gradient accumulation.
        Array inputs that fit the HBM budget only — anything else
        falls back to host streaming with one warning line (see
        data.DeviceResidentDataset.build).

        input_cast: Transfer policy narrowing features on the wire —
        "bfloat16" (2x fewer bytes, works on any input) or "uint8"
        (4x fewer bytes, affine-quantized; array inputs only, since
        lo/scale calibrate on the full arrays). The jitted step widens
        back to float32 in-graph, so the model's compute dtype is
        unchanged. Composes with cache="device" (the resident copy
        stays narrow in HBM).

        prefetch: Device read-ahead depth — `prefetch` batches are kept
        in flight ahead of the one being consumed (up to prefetch+1
        resident). 0 feeds synchronously, the minimal-HBM mode for
        workloads already near capacity.

        resume_from: Optional checkpoint directory (a ModelCheckpoint
        filepath from an earlier run). When it holds a checkpoint, the
        full train state (params, optimizer state, step, rng) is
        restored before training — the failure-recovery path the
        reference leaves to manual SavedModel reloads (and explicitly
        does not support for remote tuner trials, reference
        tuner/tuner.py:562-567). Missing/empty directories are ignored,
        so a preemption-restart loop can always pass it.

        sample_weight: Optional [num_examples] per-example weights
        (Keras `fit(sample_weight=)`): the loss becomes
        mean(per_example * w) and per-example metrics weighted means.
        Array inputs only; `validation_data` may be (x, y, w) too.

        validation_split: Keras parity — float in (0, 1): hold out the
        LAST fraction of the (un-shuffled) input arrays as validation
        data, weights included; mutually exclusive with
        validation_data, array inputs only. Training shuffle (if on)
        applies only to the retained training fraction, like Keras.

        initial_epoch: Keras parity — epoch index to start from
        (epochs still names the FINAL epoch bound, so `epochs=10,
        initial_epoch=4` runs 6 epochs numbered 4..9); pairs with
        `resume_from=` so callback epoch numbering and schedules
        driven by epoch continue where the interrupted run stopped.

        class_weight: Optional {label: weight} dict (Keras
        `fit(class_weight=)`) for imbalanced classification — sugar
        for a per-example sample_weight derived from integer labels
        `y` (multiplies into any explicit sample_weight). Labels
        absent from the dict weigh 1.0.
        """
        kwargs = dict(
            x=x, y=y, epochs=epochs, batch_size=batch_size,
            shuffle=shuffle, validation_data=validation_data,
            validation_split=validation_split,
            initial_epoch=initial_epoch, callbacks=callbacks,
            steps_per_epoch=steps_per_epoch, verbose=verbose,
            resume_from=resume_from, prefetch=prefetch,
            sample_weight=sample_weight, class_weight=class_weight,
            cache=cache, input_cast=input_cast,
            async_logging=async_logging, warm_start=warm_start,
            on_retrace=on_retrace)
        if resume in (None, False, "none"):
            if retries is not None:
                raise ValueError(
                    "retries= only applies with resume='auto'.")
            return self._fit_impl(**kwargs)
        if resume != "auto":
            raise ValueError(
                "resume must be 'auto' or None; got {!r}.".format(resume))
        from cloud_tpu.training import resilience

        return resilience.resilient_fit(self, retries=retries, **kwargs)

    def _fit_impl(self,
                  x=None,
                  y=None,
                  epochs=1,
                  batch_size=32,
                  shuffle=True,
                  validation_data=None,
                  validation_split=0.0,
                  initial_epoch=0,
                  callbacks=(),
                  steps_per_epoch=None,
                  verbose=True,
                  resume_from=None,
                  prefetch=2,
                  sample_weight=None,
                  class_weight=None,
                  cache=None,
                  input_cast=None,
                  async_logging=True,
                  warm_start=False,
                  on_retrace=None,
                  data_seed=None,
                  history=None):
        """One fit attempt — `fit`'s whole body, minus the graftguard
        dispatch. The retry loop calls this directly (inside fit's
        env scopes, so the watchdog/telemetry/sanitizer persist across
        attempts) with two extras: `data_seed` overrides the dataset
        shuffle seed (NaN rollback resumes with a fresh data order)
        and `history` accumulates one dict ACROSS attempts.
        """
        if validation_split:
            if not 0.0 < validation_split < 1.0:
                raise ValueError(
                    "validation_split must be in (0, 1); got {}."
                    .format(validation_split))
            if validation_data is not None:
                raise ValueError(
                    "Pass validation_split OR validation_data, not "
                    "both.")
            if y is None or not (
                    hasattr(x, "shape") or isinstance(x, (dict, list,
                                                          tuple))):
                raise ValueError(
                    "validation_split needs raw array inputs (x, y); "
                    "datasets should pre-split and pass "
                    "validation_data.")
            n = jax.tree_util.tree_leaves(x)[0].shape[0]
            split = int(n * (1.0 - validation_split))
            if split == 0 or split == n:
                raise ValueError(
                    "validation_split={} leaves an empty {} set for {}"
                    " examples.".format(
                        validation_split,
                        "training" if split == 0 else "validation", n))
            # Keras semantics: the LAST fraction, taken before any
            # shuffling, is validation.
            take = lambda lo, hi: jax.tree_util.tree_map(
                lambda a: a[lo:hi], x)
            y_arr = np.asarray(y)
            if sample_weight is not None:
                sw = np.asarray(sample_weight, np.float32)
                validation_data = (take(split, n), y_arr[split:],
                                   sw[split:])
                sample_weight = sw[:split]
            else:
                validation_data = (take(split, n), y_arr[split:])
            x, y = take(0, split), y_arr[:split]
        if class_weight is not None:
            labels = None if y is None else np.asarray(y)
            if labels is None or labels.ndim != 1:
                raise ValueError(
                    "class_weight= needs 1-D integer labels `y`.")
            cw = np.ones(labels.shape[0], np.float32)
            for label, weight in class_weight.items():
                cw[labels == label] = float(weight)
            sample_weight = (cw if sample_weight is None
                             else np.asarray(sample_weight,
                                             np.float32) * cw)
        if sample_weight is not None and not (
                hasattr(x, "shape") or isinstance(x, (dict, list, tuple))):
            # Pre-built datasets ignore as_dataset kwargs — silently
            # dropping the weights would train unweighted.
            raise ValueError(
                "sample_weight= needs raw array inputs; pre-built "
                "datasets carry their own weights via "
                "ArrayDataset(sample_weight=...).")
        ds_kwargs = {}
        if sample_weight is not None:
            ds_kwargs["sample_weight"] = sample_weight
        dataset = data_lib.as_dataset(
            x, y, batch_size=batch_size, shuffle=shuffle,
            seed=(self.seed if data_seed is None else data_seed),
            **ds_kwargs)
        if (sample_weight is not None
                and not isinstance(dataset, data_lib.ArrayDataset)):
            raise ValueError(
                "sample_weight= needs array inputs (datasets carry "
                "their own weights by yielding (x, y, w) via "
                "ArrayDataset(sample_weight=...)).")
        weighted = (isinstance(dataset, data_lib.ArrayDataset)
                    and dataset.sample_weight is not None)
        if steps_per_epoch is None:
            # Dataset-level cap (e.g. GeneratorDataset over an unbounded
            # stream) applies when the caller sets none.
            steps_per_epoch = getattr(dataset, "steps_per_epoch", None)
        # Safe to peek: as_dataset returns re-iterables only (one-shot
        # iterators were materialized into a list).
        sample = next(iter(dataset))
        sample_x = sample[0] if isinstance(sample, tuple) else sample
        self.build(sample_x)
        start_step = 0
        if resume_from is not None:
            from cloud_tpu.training import checkpoint as checkpoint_lib
            ckpt_step = checkpoint_lib.latest_step(resume_from)
            if ckpt_step is not None:
                # CheckpointCorrupt propagates from here to graftguard,
                # which quarantines the step and re-enters on the
                # previous one.
                self.state = checkpoint_lib.restore(resume_from,
                                                    self.state,
                                                    step=ckpt_step)
                logger.info("Resumed training from %s at step %d.",
                            resume_from, int(self.state.step))
                meta = checkpoint_lib.load_metadata(resume_from,
                                                    ckpt_step) or {}
                initial_epoch, start_step = self._apply_data_state(
                    dataset, meta.get("data_state"), initial_epoch,
                    data_seed)

        policy = None
        if input_cast not in (None, "none"):
            if isinstance(dataset, data_lib.ArrayDataset):
                policy = data_lib.make_input_cast(input_cast, dataset.x)
            elif (input_cast in ("bfloat16", "bf16")
                  or isinstance(input_cast, data_lib.InputCast)):
                # Parameterless policies calibrate from the sample.
                policy = data_lib.make_input_cast(input_cast, sample_x)
            else:
                raise ValueError(
                    "input_cast='uint8' calibrates lo/scale from the "
                    "full arrays and needs array inputs; streaming "
                    "datasets support input_cast='bfloat16'.")

        resident = None
        if cache not in (None, "none", False):
            if cache != "device":
                raise ValueError(
                    "Unknown cache={!r}; expected 'device'.".format(
                        cache))
            resident = data_lib.DeviceResidentDataset.build(
                dataset, input_cast=policy, mesh=self._mesh)

        # Resident fits build their executables through the
        # per-geometry _resident_run_cache (the permutation geometry is
        # baked into the key) and skip the host step caches.
        if resident is None:
            self._ensure_host_steps(weighted, policy)
            if warm_start:
                self._warm_fit_steps(sample, weighted, policy)

        history = {} if history is None else history
        self.stop_training = False
        self._abort_epoch = False
        # graftchaos arm: only when the chaos module is loaded (a test
        # installed a plan) or CLOUD_TPU_CHAOS asks for it — the normal
        # fit path stays import- and branch-free in the hot loop.
        chaos_mod = sys.modules.get("cloud_tpu.analysis.chaos")
        if chaos_mod is None and os.environ.get("CLOUD_TPU_CHAOS"):
            from cloud_tpu.analysis import chaos as chaos_mod
        self._chaos = None if chaos_mod is None else chaos_mod.active_plan()
        # Retrace sentinel state (see on_retrace above): the baseline
        # is snapshotted at the end of the first COMPLETED epoch; the
        # counters are process-wide, so a second Trainer compiling
        # mid-fit also trips it (that, too, is compile traffic the
        # steady state shouldn't have).
        self._retrace_baseline = None
        self._warned_tail_scalar = False
        on_retrace = (on_retrace
                      or os.environ.get("CLOUD_TPU_ON_RETRACE")
                      or "warn")
        if on_retrace not in ("warn", "raise", "ignore"):
            raise ValueError(
                "on_retrace must be 'warn', 'raise' or 'ignore'; got "
                "{!r}.".format(on_retrace))
        self._on_retrace = on_retrace
        # Async host loop state: one reader thread per Trainer (reused
        # across fits — the thread is lazy and survives idle), one
        # pending-history list per fit (drained at the exit barrier).
        self._async_logging = bool(async_logging)
        if getattr(self, "_metric_reader", None) is None:
            self._metric_reader = async_logs_lib.AsyncMetricReader()
        self._pending_history = []
        # Visible to callbacks at on_train_begin (e.g. ProfilerCallback
        # checks its target epochs will actually run). The epoch range
        # of THIS fit is [initial_epoch, planned_epochs).
        self.planned_epochs = epochs
        self.initial_epoch = initial_epoch
        for cb in callbacks:
            cb.set_trainer(self)
            cb.on_train_begin()

        try:
            if resident is not None:
                self._fit_epochs_resident(
                    resident, epochs, steps_per_epoch, validation_data,
                    batch_size, callbacks, history, verbose, prefetch,
                    initial_epoch=initial_epoch, warm_start=warm_start,
                    start_step=start_step)
            else:
                self._fit_epochs(dataset, epochs, steps_per_epoch,
                                 validation_data, batch_size, callbacks,
                                 history, verbose, prefetch,
                                 initial_epoch=initial_epoch,
                                 cast=policy, weighted=weighted,
                                 start_step=start_step)
        finally:
            # The epoch loops label this thread "step"/"boundary" for
            # graftsan; an abort can exit mid-"step". Clear the label so
            # post-fit host code is never counted against the step loop.
            runtime.set_phase(None)
            # Guaranteed even when a train step raises (OOM, interrupt):
            # callbacks holding external resources (profiler traces,
            # open files) rely on on_train_end for cleanup. Isolated per
            # callback so one failing teardown (e.g. an async checkpoint
            # commit error) cannot skip the others; the first error
            # still surfaces after all have run.
            teardown_error = None
            # The async host loop's exit barrier, BEFORE on_train_end:
            # materialize the deferred per-epoch history appends so
            # callbacks reading `history` at teardown (and the caller)
            # see every epoch. A failed background fetch surfaces here
            # like a teardown error — after the remaining epochs
            # drained, without masking a propagating train exception.
            try:
                self._materialize_history(history)
            except Exception as e:  # noqa: BLE001 - must not mask
                logger.exception("deferred metric fetch failed")
                teardown_error = e
            for cb in callbacks:
                try:
                    cb.on_train_end(history)
                except Exception as e:  # noqa: BLE001 - must not mask
                    logger.exception("on_train_end failed for %r", cb)
                    if teardown_error is None:
                        teardown_error = e
            # Async checkpoint drain on EVERY fit exit path (normal,
            # EarlyStopping/request_stop, raising train step): without
            # this, fit could return — or the process exit — with a
            # background Orbax write still in flight, and the caller's
            # "training finished" would race a torn checkpoint.
            # sys.modules.get: if nothing ever imported checkpoint
            # (and so no async save can be pending), don't pull in
            # orbax just to ask.
            ckpt_lib = sys.modules.get("cloud_tpu.training.checkpoint")
            if ckpt_lib is not None:
                try:
                    ckpt_lib.wait_until_finished()
                except Exception as e:  # noqa: BLE001 - must not mask
                    logger.exception("async checkpoint drain failed")
                    if teardown_error is None:
                        teardown_error = e
            # Surface a teardown failure only when no training exception
            # is already propagating — raising inside `finally` would
            # replace it, hiding the error that actually killed the run.
            if teardown_error is not None and sys.exc_info()[1] is None:
                raise teardown_error
        return history

    def _materialize_history(self, history):
        """Drains `_pending_history` into `history` (the exit barrier).

        Each record is (future, device_key_order, host_items): device
        metrics first, then host-side entries (steps_per_sec, val_*) —
        the same key order the eager path always produced. The first
        future whose fetch failed re-raises AFTER the loop so every
        healthy epoch still lands in history.
        """
        pending, self._pending_history = self._pending_history, []
        fetch_error = None
        for future, dev_keys, host_items in pending:
            resolved = {}
            if future is not None:
                try:
                    resolved = future.result()
                except Exception as e:  # noqa: BLE001 - raised below
                    if fetch_error is None:
                        fetch_error = e
                    continue
            for k in dev_keys:
                history.setdefault(k, []).append(resolved[k])
            for k, v in host_items.items():
                history.setdefault(k, []).append(v)
        if fetch_error is not None:
            raise fetch_error

    def request_stop(self):
        """Stops training at the next step boundary (signal-safe).

        The preemption hook: sets two plain host flags — the step loop
        breaks out of the current epoch at its next iteration (no
        device sync, no interrupted collective), the partial epoch
        still runs its epoch-end callbacks (so ModelCheckpoint /
        PreemptionCheckpoint save a resumable state), and fit()
        returns. Safe to call from a signal handler or another thread.
        """
        self._abort_epoch = True
        self.stop_training = True

    # -- graftguard: the resumable data-stream position ----------------

    def current_data_state(self):
        """The resumable data-stream position, for checkpoint metadata.

        Returns `{"epoch", "step_in_epoch", "dataset_epoch",
        "data_seed"}` describing where the shuffle stream stands as of
        the CURRENT train state, or None outside a fit. `step_in_epoch`
        derives from the step counter itself (`state.step` minus the
        epoch's base step, one device read at save time) rather than
        host-side bookkeeping, so a watchdog fault async-raised between
        a dispatch and its bookkeeping still checkpoints a position
        consistent with the params — resume never double-applies a
        step. Positions at the epoch boundary normalize to
        `(epoch + 1, 0)`.
        """
        progress = self._data_progress
        if progress is None or self.state is None:
            return None
        try:
            step_in_epoch = max(
                int(self.state.step) - progress["epoch_base"], 0)
        except Exception:
            # Donated/invalidated buffers (a fault landed mid-dispatch):
            # no trustworthy position — and no trustworthy state to
            # save it with either.
            return None
        epoch = int(progress["epoch"])
        dataset_epoch = int(progress["dataset_epoch"])
        spe = progress.get("steps_per_epoch")
        if spe and step_in_epoch >= spe:
            rolls = step_in_epoch // spe
            epoch += rolls
            dataset_epoch += rolls
            step_in_epoch -= rolls * spe
        return {"epoch": epoch, "step_in_epoch": step_in_epoch,
                "dataset_epoch": dataset_epoch,
                "data_seed": progress.get("data_seed")}

    def _apply_data_state(self, dataset, data_state, initial_epoch,
                          data_seed):
        """Re-bases the shuffle stream to a checkpoint's mid-epoch
        position (graftguard exact resume); returns the effective
        `(initial_epoch, start_step)`.

        The metadata carries `(epoch, step_in_epoch, dataset_epoch,
        data_seed)` as of the save. When the live dataset draws from
        the same seed, its epoch counter is rewound to the in-progress
        epoch's value (overwriting the tick this fit's shape-inference
        peek consumed) and the fit loop skips the epoch's first
        `step_in_epoch` batches — the resumed run continues the
        interrupted threefry permutation exactly, and with the per-step
        train rng keyed off the restored global step, the loss
        trajectory is bit-identical to an uninterrupted run. A
        DIFFERENT seed (NaN rollback resumes with a fresh data-order
        rng) instead restarts the interrupted epoch from batch 0 under
        the new permutation.
        """
        if not data_state:
            return initial_epoch, 0
        epoch = int(data_state.get("epoch", initial_epoch))
        step_in_epoch = int(data_state.get("step_in_epoch", 0))
        dataset_epoch = data_state.get("dataset_epoch")
        seed_then = data_state.get("data_seed")
        seed_now = getattr(
            dataset, "seed", self.seed if data_seed is None else data_seed)
        initial_epoch = max(initial_epoch, epoch)
        if dataset_epoch is not None and hasattr(dataset, "_epoch"):
            dataset._epoch = int(dataset_epoch)
        if seed_then is not None and seed_then != seed_now:
            logger.info(
                "Resuming epoch %d from its start with a fresh data "
                "order (seed %s -> %s).", epoch, seed_then, seed_now)
            return initial_epoch, 0
        if step_in_epoch:
            logger.info("Resuming mid-epoch: epoch %d, batch %d.",
                        epoch, step_in_epoch)
        return initial_epoch, step_in_epoch

    def _note_dispatch_done(self):
        """Per-dispatch epilogue shared by the fit loops: the watchdog
        step beat, then the one-shot graftguard resume probe (latency +
        compile delta after the first completed dispatch of a resumed
        attempt)."""
        watch_lib.notify_step()
        probe = self._resume_probe
        if probe is not None:
            self._resume_probe = None
            probe.first_step()

    def _fit_epochs(self, dataset, epochs, steps_per_epoch,
                    validation_data, batch_size, callbacks, history,
                    verbose, prefetch=2, initial_epoch=0, cast=None,
                    weighted=False, start_step=0):
        pad_tail = lambda b, steady: self._pad_tail(b, steady, weighted)
        # Feeder items are (kind, examples, tail_weight_sum, batch):
        # the weight sum is only meaningful for "padded" tails (the
        # host-side value _fix_tail_logs restores into the epoch
        # aggregation); everything else carries None.
        unpack = lambda item: (
            item[0], item[1],
            item[2][1] if item[0] == "padded" else None)
        # Host mirror of the global step at epoch entry: ONE boundary
        # sync per fit (the scalar is quiescent here), advanced by the
        # host step count at each epoch end. current_data_state
        # subtracts it from the live step counter to get the mid-epoch
        # position without trusting hot-loop bookkeeping.
        host_base = int(self.state.step)
        for epoch in range(initial_epoch, epochs):
            epoch_start = int(start_step) if epoch == initial_epoch else 0
            if steps_per_epoch is not None:
                epoch_start = min(epoch_start, steps_per_epoch)
            self._data_progress = {
                "epoch": epoch,
                "epoch_base": host_base - epoch_start,
                # Recorded BEFORE iteration advances it: the value this
                # epoch's permutation will draw from.
                "dataset_epoch": int(getattr(dataset, "_epoch", 0)),
                "steps_per_epoch": steps_per_epoch,
                "data_seed": getattr(dataset, "seed", None),
            }
            for cb in callbacks:
                cb.on_epoch_begin(epoch)
            step_logs = []
            count = 0
            examples = 0
            t0 = time.time()
            # Thread label for graftsan: a device fetch from inside the
            # step loop is the violation the sanitizer exists to catch;
            # _post_epoch_logs flips the label back to "boundary" where
            # the per-epoch coalesced fetch is sanctioned.
            runtime.set_phase("step")
            # graftscope: the whole step-loop section is one "step"
            # span; each feeder iteration becomes a "train_step" span
            # containing "data_wait" + "dispatch". Always on: each is a
            # profiler annotation (a flag test while no profile is
            # captured) and a SpanTracer record when telemetry is on.
            step_section = spans_lib.begin("step")
            spe = self.steps_per_execution
            multi_step = getattr(self, "_jit_multi_step", None)
            if spe > 1 and multi_step is not None:
                epoch_limit = (None if steps_per_epoch is None
                               else steps_per_epoch - epoch_start)
                feeder = data_lib.prefetch_to_device(
                    self._grouped_host_batches(
                        self._host_batches(dataset, cast,
                                           start_step=epoch_start),
                        epoch_limit, spe, pad_tail=pad_tail),
                    size=prefetch,
                    feed=lambda item: unpack(item) + (
                        self._feed_grouped(item),))
                feeder = spans_lib.trace_steps(feeder)
                first = True
                for kind, batch_examples, w_sum, fed in feeder:
                    if self._abort_epoch:
                        break
                    if self._chaos is not None:
                        self._chaos.pre_dispatch(
                            host_base + count,
                            spe if kind == "multi" else 1)
                    examples += batch_examples
                    if kind == "multi":
                        if first and epoch == initial_epoch:
                            self._maybe_capture_step_flops(
                                multi_step, spe, self.state, fed)
                        with spans_lib.span("dispatch"):
                            self.state, logs = multi_step(self.state,
                                                          fed)
                        if "_batch_weight" in logs:
                            # The group log already carries the GROUP
                            # weight sum: append once (duplicating
                            # would double-weight groups vs leftover
                            # singles in the epoch re-weighting). The
                            # loss, however, is a plain per-step mean
                            # (Keras sum-over-batch-size semantics), so
                            # the entry must record how many steps it
                            # stands for or a group would count equal
                            # to one leftover single batch.
                            logs = dict(logs)
                            logs["_steps"] = spe
                            step_logs.append(logs)
                        else:
                            # Unweighted epoch mean is a per-step mean:
                            # the group mean stands for `spe` steps.
                            step_logs.extend([logs] * spe)
                        count += spe
                    elif kind == "padded":
                        tail_step = self._tail_step_fn(weighted, cast)
                        with spans_lib.span("dispatch"):
                            self.state, logs = tail_step(self.state,
                                                         fed)
                        step_logs.append(self._fix_tail_logs(
                            logs, weighted, w_sum))
                        count += 1
                    else:
                        with spans_lib.span("dispatch"):
                            self.state, logs = self._jit_train_step(
                                self.state, fed)
                        step_logs.append(logs)
                        count += 1
                    if (first and epoch == initial_epoch
                            and getattr(self, "_train_scalar_unmasked",
                                        None)):
                        # Same loud failure as the single-step path: a
                        # scalar metric can't be sample-weighted.
                        raise ValueError(
                            "Custom metrics {} return a scalar and "
                            "cannot apply sample_weight. Give them a "
                            "mask-aware signature "
                            "fn(outputs, y, mask=...) or return "
                            "per-example values.".format(
                                sorted(self._train_scalar_unmasked)))
                    # graftwatch: one completed dispatch = one beat
                    # (one global load + None check when unwatched),
                    # plus the one-shot graftguard resume probe.
                    self._note_dispatch_done()
                    first = False
                spans_lib.end(step_section)
                host_base += count
                if not (self._abort_epoch and count == 0):
                    # A zero-step aborted epoch has no metrics; an
                    # epoch-end with only steps_per_sec would desync
                    # history keys and hand callbacks a loss-less epoch.
                    self._post_epoch_logs(step_logs, count, examples,
                                          t0, epoch, validation_data,
                                          batch_size, callbacks,
                                          history, verbose, prefetch)
                if self.stop_training:
                    break
                continue
            epoch_bound = (None if steps_per_epoch is None
                           else steps_per_epoch - epoch_start)

            def singles():
                # The limit check precedes the pull: a bounded stream
                # (steps_per_epoch over an expensive generator) must
                # never be drawn past the bound.
                steady = None
                it = iter(self._host_batches(dataset, cast,
                                             start_step=epoch_start))
                i = 0
                while epoch_bound is None or i < epoch_bound:
                    try:
                        b = next(it)
                    except StopIteration:
                        break
                    i += 1
                    n = _lead_count(b)
                    if steady is None:
                        steady = n
                    if n < steady:
                        padded = pad_tail(b, steady)
                        if padded is not None:
                            yield "padded", n, padded
                            continue
                    yield "single", n, b

            feeder = data_lib.prefetch_to_device(
                singles(), size=prefetch,
                feed=lambda item: unpack(item) + (
                    self._feed(item[2][0] if item[0] == "padded"
                               else item[2]),))
            feeder = spans_lib.trace_steps(feeder)
            for kind, batch_examples, w_sum, batch in feeder:
                if self._abort_epoch:
                    break
                if self._chaos is not None:
                    self._chaos.pre_dispatch(host_base + count, 1)
                examples += batch_examples
                if kind == "padded":
                    tail_step = self._tail_step_fn(weighted, cast)
                    with spans_lib.span("dispatch"):
                        self.state, logs = tail_step(self.state, batch)
                    logs = self._fix_tail_logs(logs, weighted, w_sum)
                else:
                    if count == 0 and epoch == initial_epoch:
                        self._maybe_capture_step_flops(
                            self._jit_train_step, 1, self.state, batch)
                    with spans_lib.span("dispatch"):
                        self.state, logs = self._jit_train_step(
                            self.state, batch)
                if (count == 0 and epoch == initial_epoch
                        and getattr(self, "_train_scalar_unmasked", None)):
                    # Populated during the trace that just ran: a
                    # scalar metric can't be sample-weighted — fail
                    # loudly like evaluate() does, instead of logging
                    # unweighted numbers for the whole run.
                    raise ValueError(
                        "Custom metrics {} return a scalar and cannot "
                        "apply sample_weight. Give them a mask-aware "
                        "signature fn(outputs, y, mask=...) or return "
                        "per-example values.".format(
                            sorted(self._train_scalar_unmasked)))
                # Keep logs as device arrays: no host sync inside the hot
                # loop (async dispatch overlaps host batching with the
                # device step); convert once per epoch below.
                step_logs.append(logs)
                count += 1
                # graftwatch beat + graftguard resume probe.
                self._note_dispatch_done()
            spans_lib.end(step_section)
            host_base += count
            if not (self._abort_epoch and count == 0):
                # Same zero-step-abort guard as the multi-step path.
                self._post_epoch_logs(step_logs, count, examples, t0,
                                      epoch, validation_data,
                                      batch_size, callbacks, history,
                                      verbose, prefetch)
            if self.stop_training:
                break

    def _fit_epochs_resident(self, resident, epochs, steps_per_epoch,
                             validation_data, batch_size, callbacks,
                             history, verbose, prefetch=2,
                             initial_epoch=0, warm_start=False,
                             start_step=0):
        """The device-resident fit loop: every batch is drawn in-graph
        from `resident.data`, so the epoch loop issues executable calls
        only — ZERO per-step host->device data transfers (pinned by
        tests/unit/test_resident_data.py via runtime.transfer_stats).

        steps_per_execution composes: full groups of `spe` steps run in
        one dispatch; a ragged tail (steps_per_epoch % spe) runs
        through a second executable with its own baked scan length, so
        a call never straddles an epoch boundary (the in-graph
        permutation is derived once per call).

        start_step (graftguard resume): skip the first `start_step`
        steps of the FIRST epoch by dropping whole executable calls and
        re-basing the position arithmetic — in-graph batch indices
        continue the interrupted epoch's permutation exactly. Dispatch
        is the abort granularity, so checkpointed positions are always
        call-aligned; a foreign (unaligned) position falls back to
        replaying the epoch from 0 with a warning.
        """
        weighted = resident.kind == "xyw"
        steps = resident.steps_per_epoch
        if steps_per_epoch is not None:
            steps = min(steps, int(steps_per_epoch))
        spe = min(self.steps_per_execution, steps)
        n_groups, leftover = divmod(steps, spe)
        start = int(start_step)
        if start and (start % spe or start >= steps):
            logger.warning(
                "Resident resume position step_in_epoch=%d does not "
                "sit on a dispatch boundary (steps_per_execution=%d, "
                "steps_per_epoch=%d); replaying the epoch from its "
                "start instead.", start, spe, steps)
            start = 0
        # Each executable build re-points self._train_scalar_unmasked
        # at a fresh set (populated at trace time); keep a reference to
        # every build's set so the first-step guard below sees whichever
        # executable traced first.
        scalar_sets = []
        run_group = run_tail = None
        if n_groups:
            run_group = self._make_resident_run(spe, steps, resident,
                                                weighted)
            scalar_sets.append(self._train_scalar_unmasked)
        if leftover:
            run_tail = self._make_resident_run(leftover, steps,
                                               resident, weighted)
            scalar_sets.append(self._train_scalar_unmasked)
        if warm_start:
            # AOT-compile both executables before the loop: structs
            # mirror (state, data, base_step, epoch_idx), so the first
            # epoch's first dispatch is the finished executable.
            struct = lambda tree: jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)
            scalar_i32 = jax.ShapeDtypeStruct((), jnp.int32)
            for run in (run_group, run_tail):
                if run is not None:
                    run.warm(struct(self.state), struct(resident.data),
                             scalar_i32, scalar_i32)
        # The epoch index lives on device and is advanced there (one
        # tiny add per epoch, no transfer); it starts from the source
        # dataset's `_epoch` counter so shuffled order matches the
        # host path exactly (fit's shape-inference peek has already
        # consumed one epoch of that counter) and keeps advancing it,
        # so a later host-path fit on the same dataset resumes the
        # shuffle stream where this one left off.
        src = resident.source
        ep_idx = jnp.asarray(getattr(src, "_epoch", 0), dtype=jnp.int32)
        if self._mesh is not None:
            ep_idx = jax.device_put(ep_idx,
                                    sharding_lib.replicated(self._mesh))
        data = resident.data
        first_epoch = True
        # Host step mirror for current_data_state / graftchaos: one
        # boundary sync here, advanced by the host count per epoch.
        host_base = int(self.state.step)

        for epoch in range(initial_epoch, epochs):
            epoch_start = start if epoch == initial_epoch else 0
            self._data_progress = {
                "epoch": epoch,
                "epoch_base": host_base - epoch_start,
                # The counter value this epoch's permutation draws
                # from — read BEFORE the += 1 below.
                "dataset_epoch": int(getattr(src, "_epoch", 0)),
                "steps_per_epoch": steps,
                "data_seed": getattr(src, "seed", None),
            }
            for cb in callbacks:
                cb.on_epoch_begin(epoch)
            if not first_epoch:
                ep_idx = ep_idx + 1
            first_epoch = False
            if hasattr(src, "_epoch"):
                src._epoch += 1
            # Position arithmetic is relative to the step counter at
            # EPOCH entry (a mid-epoch abort leaves step partially
            # advanced; re-basing keeps the next epoch's positions at
            # 0..steps-1). On a mid-epoch resume the restored counter
            # is `epoch_start` PAST the epoch's base, so subtract it —
            # the in-graph `(step - base) % steps` then lands on the
            # interrupted permutation position. A REAL copy: each call
            # donates the state (and with it the live step buffer).
            base = jnp.array(self.state.step, copy=True)
            if epoch_start:
                base = base - epoch_start
            if self._mesh is not None:
                base = jax.device_put(
                    base, sharding_lib.replicated(self._mesh))
            step_logs = []
            count = 0
            t0 = time.time()
            # Same graftsan step label as _fit_epochs: executable calls
            # only between here and _post_epoch_logs' "boundary".
            runtime.set_phase("step")
            # graftscope: same span contract as _fit_epochs — the
            # resident loop has no data wait (batches are drawn
            # in-graph), so each call is one train_step span whose
            # body is all dispatch.
            step_section = spans_lib.begin("step")
            calls = [(run_group, spe)] * n_groups
            if leftover:
                calls.append((run_tail, leftover))
            if epoch_start:
                # Aligned by the guard above: drop the already-run
                # whole calls; the base re-basing keeps the remaining
                # calls' in-graph positions continuous.
                calls = calls[epoch_start // spe:]
            for run, n_steps in calls:
                if self._abort_epoch:
                    break
                if self._chaos is not None:
                    self._chaos.pre_dispatch(host_base + count, n_steps)
                if count == 0 and epoch == initial_epoch:
                    self._maybe_capture_step_flops(
                        run, n_steps, self.state, data, base, ep_idx)
                train_handle = spans_lib.begin("train_step")
                with spans_lib.span("dispatch"):
                    self.state, logs = run(self.state, data, base,
                                           ep_idx)
                spans_lib.end(train_handle)
                if "_batch_weight" in logs:
                    if n_steps > 1:
                        # Same group-entry semantics as the
                        # steps_per_execution path (_fit_epochs).
                        logs = dict(logs)
                        logs["_steps"] = n_steps
                    step_logs.append(logs)
                else:
                    step_logs.extend([logs] * n_steps)
                if (count == 0 and epoch == initial_epoch
                        and any(scalar_sets)):
                    raise ValueError(
                        "Custom metrics {} return a scalar and cannot "
                        "apply sample_weight. Give them a mask-aware "
                        "signature fn(outputs, y, mask=...) or return "
                        "per-example values.".format(
                            sorted(set().union(*scalar_sets))))
                count += n_steps
                # graftwatch beat + graftguard resume probe.
                self._note_dispatch_done()
            spans_lib.end(step_section)
            host_base += count
            if not (self._abort_epoch and count == 0):
                self._post_epoch_logs(step_logs, count,
                                      count * resident.batch_size, t0,
                                      epoch, validation_data,
                                      batch_size, callbacks, history,
                                      verbose, prefetch)
            if self.stop_training:
                break

    def _post_epoch_logs(self, step_logs, count, examples, t0, epoch,
                         validation_data, batch_size, callbacks, history,
                         verbose, prefetch):
        """Epoch-end: aggregate step logs, validate, notify callbacks.

        The aggregation math runs ON DEVICE and the result is ONE
        pytree of scalars, fetched with a single coalesced
        `runtime.device_fetch` — one device→host round trip per epoch
        instead of one per metric (N x float(), each a blocking
        fetch). With
        `async_logging` (fit's default) even that one fetch moves to
        the background reader thread; callbacks get a `LazyLogs` that
        resolves only when something actually reads a metric value,
        and the history append is deferred to fit's exit barrier.
        """
        # Epoch boundary: host syncs (the coalesced fetch, validation,
        # verbose printing) are sanctioned here — relabel the thread so
        # graftsan doesn't count them against the step loop.
        runtime.set_phase("boundary")
        # graftwatch: boundary host work (validation, checkpoint, the
        # coalesced fetch) is progress too — beat so a long validation
        # pass isn't mistaken for a stalled step loop.
        watch_lib.heartbeat()
        # graftscope: the boundary host work (aggregation, validation,
        # callbacks, sentinel) is one "boundary" span, ended right
        # before the method returns.
        boundary_handle = spans_lib.begin("boundary")
        if step_logs and "_batch_weight" in step_logs[0]:
            # Weighted fit: epoch metrics re-weight each batch's
            # weighted mean by that batch's weight sum (exact over
            # the epoch); the loss keeps Keras sum-over-batch-size
            # semantics (plain mean over equal-size batches).
            ws = jnp.stack([l["_batch_weight"] for l in step_logs])
            total_w = jnp.maximum(jnp.sum(ws), 1e-9)
            # Per-entry step counts: a steps_per_execution group entry
            # carries the mean over `spe` steps and must weigh `spe`
            # times a leftover single batch in the per-step loss mean
            # (mirrors the extend([logs]*spe) semantics of the
            # unweighted path).
            ns = jnp.asarray([float(l.get("_steps", 1))
                              for l in step_logs])
            dev_logs = {}
            for k in step_logs[0]:
                if k in ("_batch_weight", "_steps"):
                    continue
                vals = jnp.stack([l[k] for l in step_logs])
                if k == "loss":
                    dev_logs[k] = jnp.sum(vals * ns) / jnp.sum(ns)
                else:
                    dev_logs[k] = jnp.sum(vals * ws) / total_w
        elif step_logs:
            dev_logs = dict(jax.tree_util.tree_map(
                lambda *xs: jnp.mean(jnp.stack(xs)), *step_logs))
        else:
            dev_logs = {}
        elapsed = max(time.time() - t0, 1e-9)
        host_logs = {"steps_per_sec": count / elapsed}
        _emit_runtime_metrics(count, examples, elapsed)
        _emit_telemetry_epoch(count, examples, elapsed)

        if validation_data is not None and self._abort_epoch:
            # Preemption abort: the eviction grace window is for the
            # checkpoint (PreemptionCheckpoint saves in on_epoch_end,
            # below) — a full validation pass here could eat it.
            validation_data = None
        if validation_data is not None:
            # Keras-style (x, y) or (x, y, sample_weight).
            if len(validation_data) == 3:
                val_x, val_y, val_sw = validation_data
            else:
                val_x, val_y = validation_data
                val_sw = None
            val_logs = self.evaluate(val_x, val_y,
                                     batch_size=batch_size,
                                     verbose=False,
                                     prefetch=prefetch,
                                     sample_weight=val_sw)
            host_logs.update(
                {"val_" + k: v for k, v in val_logs.items()})

        # The SAME device computation feeds both paths — sync vs async
        # differ only in who calls device_fetch and when, so the values
        # are bit-identical (pinned by test_async_host_loop). History
        # append is DEFERRED to fit's exit barrier either way:
        # appending here on the async path would force the fetch and
        # stall the loop, and the deferred snapshot (taken BEFORE the
        # callbacks run) preserves the existing contract that callback
        # mutations to `logs` are not recorded in history.
        if dev_logs and self._async_logging:
            future = self._metric_reader.submit(dev_logs)
            logs = async_logs_lib.LazyLogs(
                future, device_keys=tuple(dev_logs), host_items=host_logs)
            self._pending_history.append(
                (future, tuple(dev_logs), dict(host_logs)))
        else:
            if dev_logs:
                fetched = runtime.device_fetch(dev_logs)
                logs = {k: float(v) for k, v in fetched.items()}
                logs.update(host_logs)
            else:
                logs = dict(host_logs)
            self._pending_history.append((None, (), dict(logs)))
        if verbose and jax.process_index() == 0:
            # Progress output needs the values; this resolves the
            # future — still ONE coalesced fetch for the interval, just
            # no longer an off-thread one.
            logger.info("epoch %d: %s", epoch, {
                k: round(v, 4) for k, v in logs.items()})
        for cb in callbacks:
            cb.on_epoch_end(epoch, logs)

        # Retrace sentinel: the baseline snapshots at the end of the
        # FIRST completed epoch (its compiles are legitimate — step
        # executables, validation's eval step, callback one-offs);
        # any later epoch that moved the trace/compile counters is the
        # regression the counted invariant exists to catch (ragged
        # tails, input dtype drift, a new decode shape). Checked after
        # the callbacks so epoch-scoped callback compiles are counted
        # against the epoch that ran them.
        stats = runtime.compile_stats()
        snapshot = (stats["n_traces"], stats["n_compiles"])
        baseline = getattr(self, "_retrace_baseline", None)
        if baseline is None:
            self._retrace_baseline = snapshot
        elif snapshot != baseline:
            # Re-base first: one event, one report (and a "raise" that
            # gets caught shouldn't re-raise every later epoch).
            self._retrace_baseline = snapshot
            msg = ("Steady-state retrace: epoch {} performed {} new "
                   "trace(s) / {} new compile(s) after the first "
                   "epoch's warm-up. Ragged tail batches, input dtype "
                   "drift and per-epoch callback compiles are the "
                   "usual causes; runtime.compile_stats() has the "
                   "running census.".format(
                       epoch, snapshot[0] - baseline[0],
                       snapshot[1] - baseline[1]))
            policy = getattr(self, "_on_retrace", "warn")
            if policy == "raise":
                raise runtime.RetraceWarning(msg)
            if policy == "warn":
                warnings.warn(runtime.RetraceWarning(msg))
        # One completed epoch: graftsan's retrace check (GS002) arms
        # only after the warm-up epoch has finished, mirroring the
        # sentinel's own baseline timing above.
        runtime.notify_epoch(epoch)
        spans_lib.end(boundary_handle)

    def summary(self, print_fn=None):
        """Keras `model.summary()` parity: per-top-level-module
        parameter counts plus totals (params and, when present, extra
        variable collections like BatchNorm stats). Returns the text.
        Requires a built model (fit() or build())."""
        if self.state is None:
            raise RuntimeError("Model is not built; call fit() first or "
                               "build() with a sample batch.")

        def count(tree):
            return sum(int(np.prod(l.shape))
                       for l in jax.tree_util.tree_leaves(tree))

        def nbytes(tree):
            return sum(int(np.prod(l.shape)) * l.dtype.itemsize
                       for l in jax.tree_util.tree_leaves(tree))

        params = self.state.params
        rows = []
        if isinstance(params, dict):
            for name in sorted(params):
                rows.append((name, count(params[name])))
        total = count(params)
        width = max([len(n) for n, _ in rows]
                    + [len("Extra vars (e.g. BN stats)")])
        lines = ["{:<{w}}  {:>14}".format("Module", "Params", w=width),
                 "-" * (width + 16)]
        for name, n in rows:
            lines.append("{:<{w}}  {:>14,}".format(name, n, w=width))
        lines.append("-" * (width + 16))
        lines.append("{:<{w}}  {:>14,}".format("Total params", total,
                                               w=width))
        lines.append("{:<{w}}  {:>14}".format(
            "Param bytes", "{:,}".format(nbytes(params)), w=width))
        extra = count(self.state.extra_vars)
        if extra:
            lines.append("{:<{w}}  {:>14,}".format(
                "Extra vars (e.g. BN stats)", extra, w=width))
        text = "\n".join(lines)
        (print_fn or (lambda t: logger.info("%s", t)))(text)
        return text

    @property
    def ema_params(self):
        """The EMA shadow parameters (requires `ema_decay=`)."""
        if self.ema_decay is None:
            raise RuntimeError(
                "No EMA is tracked; construct Trainer(ema_decay=...).")
        if self.state is None:
            raise RuntimeError("Model is not built; call fit() first.")
        opt_state = self.state.opt_state
        if self.gradient_accumulation_steps > 1:
            opt_state = opt_state.inner_opt_state
        return opt_state[-1].ema

    def _eval_state(self, use_ema):
        if not use_ema:
            return self.state
        s = self.state
        return TrainState(s.step, self.ema_params, s.opt_state, s.rng,
                          s.extra_vars)

    def save_checkpoint(self, directory, use_async=False):
        """Saves the full train state under `<directory>/<step>` (local
        or gs://). Keras `model.save` parity at the state level; pair
        with `restore_checkpoint` or `fit(resume_from=...)`. With
        use_async=True the write happens on a background thread
        (checkpoint.wait_until_finished() blocks on it)."""
        from cloud_tpu.training import checkpoint as checkpoint_lib

        if self.state is None:
            raise RuntimeError("Model is not built; nothing to save.")
        return checkpoint_lib.save(directory, self.state,
                                   step=int(self.state.step),
                                   use_async=use_async)

    def restore_checkpoint(self, directory, sample_x, step=None):
        """Builds congruent state from `sample_x`, then restores the
        checkpoint into it (shardings respected)."""
        from cloud_tpu.training import checkpoint as checkpoint_lib

        self.build(sample_x)
        self.state = checkpoint_lib.restore(directory, self.state,
                                            step=step)
        return self.state

    @_env_watched
    @_env_telemetry
    @_env_sanitized
    def evaluate(self, x, y=None, batch_size=32, verbose=True,
                 steps=None, prefetch=2, use_ema=False,
                 sample_weight=None):
        """Returns exact example-weighted mean loss/metrics.

        Tail batches are padded by wrapping (never dropped) so shapes
        stay static for XLA, but padded duplicates are masked out inside
        the eval step and each batch is weighted by its real example
        count — metrics match a hand-computed mean over the dataset
        (Keras-exact), regardless of tail padding. Custom metrics may
        opt into the valid-mask via a `fn(outputs, y, mask=...)`
        signature; a custom metric that returns a scalar WITHOUT taking
        the mask raises on padded batches rather than silently folding
        duplicated rows into its mean.

        `steps` caps the batch loop; when unset, a dataset-level
        `steps_per_epoch` (e.g. GeneratorDataset over an unbounded
        stream) applies, mirroring fit(). `prefetch` is the device
        read-ahead depth (0 = synchronous), mirroring fit(); fit()
        forwards its own value to the per-epoch validation pass.

        `sample_weight`: optional [num_examples] per-example weights;
        every reported value becomes the weighted mean
        sum(v_i * w_i) / sum(w_i) over the dataset (weights compose
        with the tail-padding mask). Array inputs; works multi-process
        (the per-batch weight is summed in-graph over the global mask).
        """
        if self.state is None:
            raise RuntimeError("Model is not built; call fit() first or "
                               "build() with a sample batch.")
        if self._jit_eval_step is None:
            self._jit_eval_step = self._make_eval_step()
        if sample_weight is not None and not (
                hasattr(x, "shape") or isinstance(x, (dict, list, tuple))):
            raise ValueError(
                "sample_weight= needs raw array inputs; pre-built "
                "datasets carry their own weights via "
                "ArrayDataset(sample_weight=...).")
        ds_kwargs = {}
        if sample_weight is not None:
            ds_kwargs["sample_weight"] = sample_weight
        dataset = data_lib.as_dataset(x, y, batch_size=batch_size,
                                      drop_remainder=False, **ds_kwargs)
        if (sample_weight is not None
                and not isinstance(dataset, data_lib.ArrayDataset)):
            raise ValueError(
                "sample_weight= needs array inputs (wrap the dataset "
                "in ArrayDataset(sample_weight=...) instead).")
        weighted_eval = (isinstance(dataset, data_lib.ArrayDataset)
                         and dataset.sample_weight is not None)
        if steps is None:
            steps = getattr(dataset, "steps_per_epoch", None)
        num_examples = getattr(dataset, "num_examples", None)
        global_bs = getattr(dataset, "batch_size", None)
        process_count = jax.process_count()
        process_index = jax.process_index()
        def masked_batches():
            """(aggregation_weight, padded, (x, y, mask)) per batch —
            `mask` is the valid-row mask times any per-example weights
            (the eval step's masked means are then weighted means),
            and `aggregation_weight` is the batch's share of the final
            example-weighted (or sample-weighted) average."""
            for i, batch in enumerate(self._epoch_batches(dataset)):
                if steps is not None and i >= steps:
                    break
                # Same unpacking the train step applies: a 3-sequence
                # is (x, y, sample_weight), a 2-sequence is (x, y);
                # anything else is unlabeled input.
                wb = None
                if isinstance(batch, (tuple, list)) and len(batch) == 3:
                    xb, yb, wb = batch
                elif isinstance(batch, (tuple, list)) and len(batch) == 2:
                    xb, yb = batch
                else:
                    xb, yb = batch, None
                local_b = jax.tree_util.tree_leaves(xb)[0].shape[0]
                if num_examples is not None and global_bs is not None:
                    # ArrayDataset pads the tail by wrapping: only the
                    # first `real` rows of the global batch are fresh.
                    real = min(global_bs, num_examples - i * global_bs)
                else:
                    # Arbitrary iterables yield their own (unpadded)
                    # batches.
                    real = local_b * process_count
                # This process holds global rows
                # [offset, offset + local_b).
                offset = (process_index * local_b
                          if process_count > 1 else 0)
                mask = ((np.arange(local_b) + offset) < real).astype(
                    np.float32)
                padded = real < local_b * process_count
                if wb is not None:
                    mask = mask * np.asarray(wb, np.float32)
                    agg = float(mask.sum())
                else:
                    agg = float(real)
                yield agg, padded, (xb, yb, mask)

        feeder = data_lib.prefetch_to_device(
            masked_batches(), size=prefetch,
            feed=lambda item: (item[0], item[1], self._feed(item[2])))
        eval_state = self._eval_state(use_ema)
        totals, weight = {}, 0.0
        for agg, padded, fed in feeder:
            logs = dict(self._jit_eval_step(eval_state, fed))
            # graftwatch: an eval batch is liveness (but not a train
            # step — it beats without advancing the step census).
            watch_lib.heartbeat()
            batch_w = logs.pop("_batch_weight")
            if weighted_eval:
                # The host-side `agg` summed only this process's local
                # mask shard; the in-graph sum covers the GLOBAL mask,
                # making weighted evaluate exact on pods (round-3 gap:
                # this path used to raise NotImplementedError under
                # process_count > 1). Stays a device scalar — no sync.
                agg = batch_w
            # Padding only ever happens on the ArrayDataset path
            # (num_examples known, tail wrapped); datasets that just
            # yield a short final batch (e.g. shard tails) are short,
            # not padded — their mask is all-ones and every metric is
            # exact. A scalar metric that can't take the mask is also
            # wrong under sample weights, padded or not.
            if ((padded or weighted_eval)
                    and self._scalar_unmasked_metrics):
                raise ValueError(
                    "Custom metrics {} return a scalar and cannot be "
                    "masked, but this evaluation needs per-row "
                    "weighting ({}). Give the metric a mask-aware "
                    "signature fn(outputs, y, mask=...) (weight rows "
                    "by mask), or return per-example values "
                    "instead.".format(
                        sorted(self._scalar_unmasked_metrics),
                        "sample_weight" if weighted_eval
                        else "padded tail batch"))
            weight += agg
            for k, v in logs.items():
                # Device-side accumulation: no host sync per batch (one
                # blocking fetch per eval batch otherwise); the
                # coalesced fetch below is the only barrier.
                totals[k] = totals.get(k, 0.0) + v * agg
        # ONE coalesced fetch for the whole evaluation: the weight and
        # every metric total come back in a single device_get (counted
        # once in transfer_stats()["d2h_fetches"]) — this used to be
        # N+1 float() round trips, each blocking.
        weight, totals = runtime.device_fetch((weight, totals))
        weight = float(weight)
        if weight == 0.0:
            if weighted_eval:
                raise ValueError(
                    "evaluate(): total sample_weight is zero — no "
                    "example carries weight, so no mean exists.")
            raise ValueError("evaluate() received an empty dataset.")
        logs = {k: float(v) / weight for k, v in totals.items()}
        if verbose and jax.process_index() == 0:
            logger.info("evaluate: %s", {
                k: round(v, 4) for k, v in logs.items()})
        return logs

    def _make_predict_step(self):
        eval_kwargs = self.eval_kwargs

        def predict_step(state, xb):
            return self._apply(state.params, xb,
                               extra_vars=state.extra_vars, **eval_kwargs)

        if self._mesh is None:
            return runtime.instrumented_jit(predict_step)
        return runtime.instrumented_jit(
            predict_step,
            in_shardings=(self._state_sharding,
                          sharding_lib.batch_sharding(self._mesh)))

    def predict(self, x, batch_size=32, prefetch=2, use_ema=False):
        """Returns stacked model outputs for `x`.

        Jitted and prefetched like fit/evaluate: batches stream to
        device `prefetch` ahead, outputs stay on device until one
        gather at the end.
        """
        if self.state is None:
            raise RuntimeError("Model is not built; call fit() first.")
        if self._jit_predict_step is None:
            self._jit_predict_step = self._make_predict_step()
        dataset = data_lib.as_dataset(x, None, batch_size=batch_size,
                                      drop_remainder=False)
        feeder = data_lib.prefetch_to_device(
            iter(dataset), size=prefetch, feed=self._feed)
        # One-behind gather: batch i's output is pulled to host while
        # batch i+1 computes — transfer overlaps compute without ever
        # holding more than two batches of outputs in HBM. Outputs are
        # arbitrary pytrees (a tuple/dict-returning model, e.g. MoEMLP's
        # (out, aux)): transfer and concatenation are per leaf, and the
        # result keeps the model's output structure.
        outs = []
        pending = None
        predict_state = self._eval_state(use_ema)
        for xb in feeder:
            out = self._jit_predict_step(predict_state, xb)
            if pending is not None:
                outs.append(runtime.device_fetch(pending))
            pending = out
        if pending is not None:
            outs.append(runtime.device_fetch(pending))
        n = jax.tree_util.tree_leaves(x)[0].shape[0]

        def join(*leaves):
            # A 0-d leaf (e.g. MoEMLP's scalar aux loss) is per-BATCH,
            # not per-example: stack into [num_batches] instead of
            # concatenating along a batch axis it doesn't have.
            if np.ndim(leaves[0]) == 0:
                return np.stack(leaves)
            return np.concatenate(leaves, axis=0)[:n]

        return jax.tree_util.tree_map(join, *outs)
