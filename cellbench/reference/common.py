"""The plain reference's machinery, shared by the families: float32 arrays,
matrix products at HIGHEST precision, one layer at a time so that a model of
any depth compiles one layer program and fits beside nothing else.

A family module (`llama.py`, `gpt2.py`) gives three plain functions over the
benchmark's own weights (cellbench/weights.py), addressed by the parameter
names the program uses for them:

    embed(params, tokens, cfg)      -> x [B, S, d]
    layer(x, layer_params, cfg, mm) -> x
    head(x, params, cfg, mm)        -> logits [B, S, V]
    layer_names(params)             -> the layer keys, in order

`mm(a, b)` is the matrix product of the stated precision: "float32" as the
reference, or a lower one ("bfloat16", "fp8", "int8") for the control that
`correct` has to fail. Nothing here imports the program.
"""

import functools
import importlib
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("float32", "bfloat16", "fp8", "int8")


def family(name):
    return importlib.import_module("cellbench.reference." + name)


def _fake_quant(a, precision):
    if precision == "float32":
        return a
    if precision == "bfloat16":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    peak = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    if precision == "fp8":      # e4m3 with a per-tensor scale, as fp8 paths do
        scale = peak / 448.0
        return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    if precision == "int8":     # symmetric per-tensor int8
        scale = peak / 127.0
        return jnp.clip(jnp.round(a / scale), -127, 127) * scale
    raise ValueError("precision must be one of {}".format(PRECISIONS))


def make_mm(precision):
    """einsum at HIGHEST whose operands are first rounded to `precision`
    (straight-through for gradients)."""
    def q(a):
        a = a.astype(jnp.float32)
        return a + jax.lax.stop_gradient(_fake_quant(a, precision) - a)

    def mm(spec, a, b):
        return jnp.einsum(spec, q(a), q(b), precision=HIGHEST)
    return mm


def causal_attention(q, k, v, mm):
    """q [B,S,H,D], k and v [B,S,H,D] (already repeated for GQA)."""
    depth = q.shape[-1]
    scores = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(depth)
    seq = q.shape[1]
    mask = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return mm("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------- serving

def logits_rows(fam, params, cfg, tokens, rows, precision="float32"):
    """Logits [len(rows), V] of one sequence `tokens` [T] at positions
    `rows`, computed layer by layer."""
    layer = _jit_layer(fam, _freeze(cfg), precision)
    x = fam.embed(params, jnp.asarray(tokens, jnp.int32)[None], cfg)
    for name in fam.layer_names(params):
        x = layer(x, params[name])
    x = x[:, jnp.asarray(rows, jnp.int32)]
    return _jit_head(fam, _freeze(cfg), precision)(x, fam.head_params(params))[0]


def _freeze(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _jit_layer(fam, cfg_items, precision):
    cfg, mm = dict(cfg_items), make_mm(precision)
    return jax.jit(lambda x, lp: fam.layer(x, lp, cfg, mm))


@functools.lru_cache(maxsize=None)
def _jit_head(fam, cfg_items, precision):
    cfg, mm = dict(cfg_items), make_mm(precision)
    return jax.jit(lambda x, hp: fam.head(x, hp, cfg, mm))


@functools.lru_cache(maxsize=None)
def _jit_layer_bwd(fam, cfg_items, precision):
    cfg, mm = dict(cfg_items), make_mm(precision)

    def bwd(x, lp, dy):
        _, vjp = jax.vjp(lambda x, lp: fam.layer(x, lp, cfg, mm), x, lp)
        return vjp(dy)
    return jax.jit(bwd)


# --------------------------------------------------------------- training

def _leaf_norms(tree):
    return {path_name(p): jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def path_name(path):
    return "/".join(str(getattr(k, "key", k)) for k in path)


leaf_norms = jax.jit(_leaf_norms)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adamw(p, m, v, g, t, opt):
    lr, b1, b2, eps, wd = opt

    def one(p, m, v, g):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p), m, v
    out = jax.tree_util.tree_map(one, p, m, v, g)
    pick = lambda i: jax.tree_util.tree_map(
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def _head_chunk_fn(fam, cfg, mm):
    def chunk_loss(x, hp, labels):
        logits = fam.head(x[None], hp, cfg, mm)[0]
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(logz - picked)
    return jax.jit(jax.value_and_grad(chunk_loss, argnums=(0, 1)))


def train_steps(fam, params, batches, cfg, opt, precision="float32",
                head_rows=1024):
    """Follows the program's first steps. `params` is consumed. `batches` is a
    list of (tokens [B,S], labels [B,S]); `opt` = (lr, b1, b2, eps, wd) of
    AdamW as optax applies it. Returns (losses, first-step gradient norm per
    leaf, final params)."""
    mm = make_mm(precision)
    frozen = _freeze(cfg)
    layer, layer_bwd = (_jit_layer(fam, frozen, precision),
                        _jit_layer_bwd(fam, frozen, precision))
    head_chunk = _head_chunk_fn(fam, cfg, mm)
    names = list(fam.layer_names(params))
    params = dict(params)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    m = {k: zeros(v) for k, v in params.items()}
    v = {k: zeros(p) for k, p in params.items()}
    opt = tuple(float(o) for o in opt)
    losses, grad_norms = [], {}

    def apply(keys, grads, t):
        # AdamW has no cross-leaf term, so a group is updated as soon as its
        # gradient exists and the gradient is dropped.
        if t == 1:
            for key in keys:
                for leaf, norm in leaf_norms(grads[key]).items():
                    grad_norms[key + "/" + leaf] = norm
        for key in keys:
            params[key], m[key], v[key] = _adamw(
                params[key], m[key], v[key], grads[key], float(t), opt)

    for t, (tokens, labels) in enumerate(batches, start=1):
        tokens = jnp.asarray(tokens, jnp.int32)
        labels = jnp.asarray(labels, jnp.int32)
        x = fam.embed(params, tokens, cfg)
        inputs = []
        for name in names:
            inputs.append(x)
            x = layer(x, params[name])
        rows = x.reshape(-1, x.shape[-1])
        flat_labels = labels.reshape(-1)
        count = rows.shape[0]
        head_p = fam.head_params(params)
        total, d_rows, d_head = 0.0, [], None
        for lo in range(0, count, head_rows):
            loss, (dx, dhp) = head_chunk(rows[lo:lo + head_rows], head_p,
                                         flat_labels[lo:lo + head_rows])
            total = total + loss
            d_rows.append(dx)
            d_head = dhp if d_head is None else jax.tree_util.tree_map(
                jnp.add, d_head, dhp)
        losses.append(total / count)
        dx = (jnp.concatenate(d_rows) / count).reshape(x.shape)
        d_head = jax.tree_util.tree_map(lambda g: g / count, d_head)
        del rows, d_rows
        apply(list(d_head), d_head, t)
        for name, x_in in zip(reversed(names), reversed(inputs)):
            dx, dlp = layer_bwd(x_in, params[name], dx)
            apply([name], {name: dlp}, t)
        d_embed = fam.embed_grad(params, tokens, dx, cfg)
        apply(list(d_embed), d_embed, t)
    return ([float(l) for l in losses],
            {k: float(n) for k, n in grad_norms.items()}, params)
