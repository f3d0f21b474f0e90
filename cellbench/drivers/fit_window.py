"""Training cells: `Trainer.fit` under the ambient `tpu_slice` mesh over every
local chip, timed for a window, and its first steps held against the plain
reference.

Set-up builds one Trainer, gives it the benchmark's seeded weights through
`build(variables=)`, and drives it through its first `check_steps` steps by
the same `fit` call and feed as the window (one step a call, rows all
different), reading each step's loss, Adam's first moment after step 1 (the
gradient as the optimizer got it) and the parameters' change after the last.
The same object then runs the window. The reference follows the same rows
after the window has closed and the program's state is freed.
"""

import gc
import threading

import numpy as np

from cellbench import harness, tracing, weights
from cellbench.reference import common as ref


def _adam_state(opt_state):
    import jax

    found = [n for n in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda n: hasattr(n, "mu")) if hasattr(n, "mu")]
    if len(found) != 1:
        raise RuntimeError("expected one Adam state, found {}".format(len(found)))
    return found[0]


class Built:
    """Set-up's one object: the Trainer with its state and compiled step, and
    the rows it is fed. The first steps and the window both drive it."""

    def __init__(self, run):
        import optax

        from cloud_tpu.parallel import runtime
        from cloud_tpu.training import Trainer

        cfg, mix = run.cell.config, run.cell.traffic
        assumed = cfg["assumed"]
        self.cfg, self.mix, self.seed = cfg, mix, run.seed
        self.opt = dict(assumed["optimizer"])
        if self.opt.pop("name") != "adamw":
            raise ValueError("fit_window follows AdamW only")
        runtime.reset()
        self.chips = runtime.initialize(strategy="tpu_slice").mesh.size
        model = weights.build_model(cfg)
        self.seq = int(assumed["seq_len"])
        self.batch = int(mix["batch_per_chip"]) * self.chips
        self.k = int(mix["check_steps"])
        self.rows = (self.k + int(mix["steps_per_epoch"])) * self.batch
        data = harness.rng(run.seed, 1).integers(
            0, cfg["vocab_size"], (self.rows, self.seq + 1)).astype(np.int32)
        self.x, self.y = data[:, :-1], data[:, 1:]
        self.shapes = weights.param_shapes(model)
        params0 = weights.make_params(self.shapes, run.seed)
        self.trainer = Trainer(model, optimizer=optax.adamw(**self.opt), metrics=())
        self.trainer.build(self.x[:self.batch], variables={"params": params0})
        del params0
        if run.plant is not None:
            run.plant(self)

    def fit(self, lo, hi, epochs):
        return self.trainer.fit(
            self.x[lo:hi], self.y[lo:hi], epochs=epochs, batch_size=self.batch,
            shuffle=False, verbose=False, on_retrace="raise")

    def check_batches(self):
        b = self.batch
        return [(self.x[i * b:(i + 1) * b], self.y[i * b:(i + 1) * b])
                for i in range(self.k)]

    def first_steps(self):
        """The first steps through the window's own call and feed: each
        step's loss, the first gradient's norm per leaf as Adam got it, the
        parameters' change per leaf after the last."""
        import jax
        import jax.numpy as jnp

        shapes = self.shapes
        norms = jax.jit(lambda tree: {
            ref.path_name(p): jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]})
        moved = jax.jit(lambda params, key: jax.tree_util.tree_map(
            lambda a, b: a - b, params, weights.fill(shapes, key)))
        losses, grad_norms = [], None
        for i in range(self.k):
            history = self.fit(i * self.batch, (i + 1) * self.batch, 1)
            losses.append(float(history["loss"][0]))
            if i == 0:
                mu = norms(_adam_state(self.trainer.state.opt_state).mu)
                grad_norms = {n: float(v) / (1.0 - self.opt["b1"])
                              for n, v in mu.items()}
        delta = norms(moved(self.trainer.state.params, weights.seed_key(self.seed)))
        return {"losses": losses, "grad_norms": grad_norms,
                "delta_norms": {n: float(v) for n, v in delta.items()}}

    def free(self):
        from cloud_tpu.parallel import runtime

        self.trainer.state = None
        self.trainer = None
        runtime.reset()
        gc.collect()


def run(run):
    import jax

    watch = harness.CompileWatch()
    built = Built(run)
    trainer, mix, chips = built.trainer, built.mix, built.chips
    got = built.first_steps()
    # One whole epoch of the window's own length: the epoch's end reduces as
    # many step logs as the window's epochs will, so nothing is left to
    # compile there.
    built.fit(built.k * built.batch, built.rows, 1)
    step0 = int(trainer.state.step)

    # -- the window.
    tracer = tracing.Slice(run, mix)
    # Stops at the first epoch boundary past `--seconds`: every epoch of the
    # window is whole, so each ends in programs that set-up has run.
    stopper = threading.Timer(
        run.seconds, lambda: setattr(trainer, "stop_training", True))
    stopper.daemon = True
    t0 = harness.now()
    setup_s = run.setup_s(t0)
    compiles = watch.mark()
    tracer.arm(t0)
    stopper.start()
    try:
        built.fit(built.k * built.batch, built.rows, 10 ** 9)
        t_returned = harness.now()
        jax.block_until_ready(trainer.state.params)
        t1 = harness.now()
    finally:
        stopper.cancel()
        tracer.close()
        compiled, compile_s = watch.since(compiles)
        watch.close()
    steps = int(trainer.state.step) - step0
    window_s = t1 - t0
    tokens = steps * built.batch * built.seq
    peak = harness.memory_peak_bytes()
    traces = getattr(trainer._jit_train_step, "n_traces", None)

    # -- free the program's state, then the reference on the same rows.
    del trainer
    built.free()
    t_ref = harness.now()
    compared = harness.Compared()
    want = reference_readings(built.cfg, built.check_batches(), built.shapes,
                              run.seed, built.opt)
    leaves = compare_readings(compared, got, want, run.cell.limits)
    compared.require("window_steps_ran", steps >= 1)
    compared.require("no_compile_in_window", compiled == 0 and traces in (None, 1))
    return {
        "attempted": steps, "failed": 0, "compared": compared,
        "end_to_end": {"train_tokens_per_s": tokens / window_s / chips,
                       "setup_s": setup_s},
        "memory_peak_bytes": peak, "window_s": window_s,
        "reference_s": harness.now() - t_ref,
        "trace": tracer.reduced(chips),
        "config": built.cfg, "traffic": mix, "peaks": run.peaks, "chips": chips,
        "counters": {"steps": steps, "tokens": tokens, "batch": built.batch,
                     "seq": built.seq, "fit_returned_s": t_returned - t0,
                     "train_step_traces": traces,
                     "compiles_in_window": compiled,
                     "compile_s_in_window": compile_s, **leaves},
    }


def reference_readings(cfg, batches, shapes, seed, opt, precision="float32"):
    """What `first_steps` reads, from the plain reference over the same rows
    (or, at a lower `precision`, from the control put in the program's place)."""
    import jax

    fam = ref.family(cfg["family"])
    losses, grad, params = ref.train_steps(
        fam, weights.make_params(shapes, seed), batches, cfg,
        (opt["learning_rate"], opt["b1"], opt["b2"], opt["eps"],
         opt["weight_decay"]), precision=precision)
    diff = jax.jit(lambda a, b: jax.tree_util.tree_map(lambda u, v: u - v, a, b))
    delta = {n: float(v) for n, v in ref.leaf_norms(
        diff(params, weights.make_params(shapes, seed))).items()}
    return {"losses": losses, "grad_norms": grad, "delta_norms": delta}


def compare_readings(compared, got, want, limits):
    """The program's readings against the reference's, leaf by leaf: the gap
    between the two norms over the reference's norm of that leaf or of the
    median leaf, whichever is larger.

    The first gradient is held by the median leaf's gap: the worst leaf's is
    the rounding of one 128-wide bias and swings fourfold from seed to seed
    (PERF.md section 2 gives both readings). The parameters' change is held by
    the worst leaf; leaves whose reference gradient is under a thousandth of
    the median leaf's move by round-off alone under Adam and are left out.
    Each step's loss is read and printed but not held to a limit: no control
    and no fault reads three times what sound runs do. Returns what a reader
    of a failed run wants beside the numbers."""
    grad = want["grad_norms"]
    grad_gaps = leaf_gaps(got["grad_norms"], grad)
    compared.add("grad_norm_gap_median_leaf",
                 float(np.median(list(grad_gaps.values()))),
                 limits["grad_norm_gap_median_leaf"])
    floor = 1e-3 * float(np.median(list(grad.values())))
    moving = [n for n, g in grad.items() if g >= floor]
    update_gaps = leaf_gaps({n: got["delta_norms"][n] for n in moving},
                            {n: want["delta_norms"][n] for n in moving})
    compared.add("update_norm_gap", max(update_gaps.values()),
                 limits["update_norm_gap"])
    worst = lambda gaps: [[n, gaps[n]] for n in
                          sorted(gaps, key=gaps.get, reverse=True)[:3]]
    return {"loss_gaps": [abs(a - b) / abs(b)
                          for a, b in zip(got["losses"], want["losses"])],
            "grad_worst_leaves": worst(grad_gaps),
            "update_worst_leaves": worst(update_gaps),
            "leaves_left_out_of_update": len(grad) - len(moving)}


def leaf_gaps(got, want):
    if set(got) != set(want):
        raise RuntimeError("leaves differ: {}".format(sorted(set(got) ^ set(want))[:4]))
    median = float(np.median(list(want.values())))
    return {n: abs(got[n] - want[n]) / max(want[n], median, 1e-30) for n in want}
