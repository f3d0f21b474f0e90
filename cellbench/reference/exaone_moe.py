"""Plain reference of the EXAONE-MoE decoder (`model_type: exaone_moe`,
K-EXAONE-236B-A23B) as its config.json and the family's published convention
describe it, for one chip's share of an expert-parallel deployment.

A layer, token i: q = W_q h (H x D), k = W_k h, v = W_v h (H_kv x D); q and k
each through a per-head RMSNorm over the D features; on a window layer
(`layer_types[l] == "sliding_attention"`) q and k are rotated by rotate-half
RoPE at the token's position, on a full layer they are not; scores q.k/sqrt(D)
over keys j <= i, on a window layer also i - j < sliding_window; softmax; each
key/value head serves H/H_kv query heads; o = W_o concat(heads). The norm sits
on each sub-layer's OUTPUT: h <- h + RMSNorm(Attn(h)), h <- h + RMSNorm(FFN(h)).
FFN is a dense SwiGLU on the first `first_k_dense_replace` layers and the
expert layer after: s = sigmoid(W_r h) over ALL experts the router has, the
`num_experts_per_tok` chosen are the top of s + b, w_e = routed_scaling_factor
x s_e / sum over the chosen of s, y = sum over the chosen of w_e E_e(h) +
E_shared(h). This chip holds `experts_held` only: it adds the chosen experts
it holds and the shared expert, and that partial sum goes on to the next
layer (cellbench/configs/k-exaone-236b-a23b-ep8.json, `deployment`). A final
RMSNorm, an untied head over the vocabulary slice. No multi-token-prediction
module (the file's `departures`).

Float32, every matrix product at HIGHEST through `common.make_mm` (or, for the
control, with operands rounded to a lower precision), one layer at a time, and
attention in blocks of query rows, so that 64 x 4096 x 4096 scores are never
held. Parameter names are the program's (`block_3/moe/expert_gate`). Nothing
here imports the program.

The family protocol of `common.logits_rows` passes a layer no index, and this
model's layers differ by index (window or full, dense or sparse), so the
module brings its own `logits_rows`, which also returns how near each row's
routing is to a tie, in units of the router's logit (`closed_loop_routed`
reads it).
"""

import functools
import math

import jax
import jax.numpy as jnp

from cellbench.reference import common

QUERY_BLOCK = 256


def layer_names(params):
    return sorted((k for k in params if k.startswith("block_")),
                  key=lambda k: int(k.split("_")[1]))


def head_params(params):
    return {"norm_final": params["norm_final"], "lm_head": params["lm_head"]}


def embed(params, tokens, cfg):
    return params["embed"]["embedding"][tokens].astype(jnp.float32)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [T, H, D], rotate-half pairing, position = row."""
    seq, depth = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, depth, 2, dtype=jnp.float32) / depth)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :depth // 2], x[..., depth // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window, mm, block=QUERY_BLOCK):
    """q [T, H, D], k and v [T, H_kv, D] -> [T, H, D]: causal, banded where
    `window`, a block of query rows at a time."""
    seq, heads, depth = q.shape
    kv_heads = k.shape[1]
    block = min(block, seq)
    if seq % block:
        raise ValueError("sequence {} is no multiple of {}".format(seq, block))
    qg = q.reshape(seq // block, block, kv_heads, heads // kv_heads, depth)
    keys = jnp.arange(seq)

    def rows(args):
        start, q_rows = args
        scores = mm("qhgd,khd->hgqk", q_rows, k) / math.sqrt(depth)
        at = start + jnp.arange(block)
        seen = keys[None, :] <= at[:, None]
        if window:
            seen = seen & (at[:, None] - keys[None, :] < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return mm("hgqk,khd->qhgd", probs, v)

    out = jax.lax.map(rows, (jnp.arange(0, seq, block), qg))
    return out.reshape(seq, heads, depth)


def swiglu(h, p, mm):
    gate = jax.nn.silu(mm("td,df->tf", h, p["gate"]["kernel"]))
    up = mm("td,df->tf", h, p["up"]["kernel"])
    return mm("tf,fd->td", gate * up, p["down"]["kernel"])


def route(h, p, top_k, scale, normalise, mm):
    """(chosen ids [T, k], weights [T, k], margin [T], edge ids [T, 2]): the
    edge is the last chosen expert and the first one not chosen, the margin
    how far the router's logit of one of the two would have to move for them
    to change places (their distance in s + b over the steeper of the two
    sigmoids' slopes: the top scores lie where the sigmoid is flat, so a
    distance in s + b says little)."""
    scores = jax.nn.sigmoid(mm("td,de->te", h, p["router"]))
    choice = scores + p["router_bias"].astype(jnp.float32)[None, :]
    top, ids = jax.lax.top_k(choice, top_k + 1)
    edge = ids[:, top_k - 1:]
    at_edge = jnp.take_along_axis(scores, edge, axis=-1)
    slope = jnp.max(at_edge * (1.0 - at_edge), axis=-1)
    margin = (top[:, top_k - 1] - top[:, top_k]) / jnp.maximum(slope, 1e-30)
    ids = ids[:, :top_k]
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    if normalise:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return ids, picked * scale, margin, edge


def expert_layer(h, p, held, top_k, scale, normalise, mm):
    """This chip's part of the expert layer: the chosen experts among `held`
    (row e of the stacked weights is expert held[e]) and the shared expert."""
    ids, weights, margin, edge = route(h, p, top_k, scale, normalise, mm)
    held_ids = jnp.asarray(held, jnp.int32)

    def one(total, expert):
        gate, up, down, ident = expert
        w = jnp.sum(jnp.where(ids == ident, weights, 0.0), axis=-1)
        y = mm("tf,fd->td",
               jax.nn.silu(mm("td,df->tf", h, gate)) * mm("td,df->tf", h, up),
               down)
        return total + w[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (p["expert_gate"], p["expert_up"], p["expert_down"], held_ids))
    edge_held = jnp.any(edge[:, :, None] == held_ids[None, None, :], axis=(1, 2))
    return routed + swiglu(h, p["shared"], mm), margin, edge_held


def layer(h, p, spec, mm):
    """One block on h [T, d]. `spec`: (window or 0, eps, theta, held, top_k,
    scale, normalise). Returns (h, margin [T], edge_held [T]): the routing's
    margin (inf on a dense layer) and whether an expert at the edge of the
    choice is held here."""
    window, eps, theta, held, top_k, scale, normalise = spec
    att = p["attention"]
    proj = lambda name: mm("td,dhk->thk", h, att[name]["kernel"])
    q = _rms(proj("query"), att["q_norm"]["scale"], eps)
    k = _rms(proj("key"), att["k_norm"]["scale"], eps)
    if window:
        q, k = _rope(q, theta), _rope(k, theta)
    out = mm("thk,hkd->td", attention(q, k, proj("value"), window, mm),
             att["out"]["kernel"])
    h = h + _rms(out, p["norm_attn_post"]["scale"], eps)
    if "moe" in p:
        y, margin, edge_held = expert_layer(h, p["moe"], held, top_k, scale,
                                            normalise, mm)
    else:
        y = swiglu(h, p["mlp"], mm)
        margin = jnp.full(h.shape[:1], jnp.inf, jnp.float32)
        edge_held = jnp.zeros(h.shape[:1], bool)
    return h + _rms(y, p["norm_mlp_post"]["scale"], eps), margin, edge_held


def head(x, hp, cfg, mm):
    x = _rms(x, hp["norm_final"]["scale"], cfg["rms_norm_eps"])
    return mm("td,dv->tv", x, hp["lm_head"]["kernel"])


def layer_spec(cfg, index):
    window = (int(cfg["sliding_window"])
              if cfg["layer_types"][index] == "sliding_attention" else 0)
    return (window, float(cfg["rms_norm_eps"]),
            float(cfg["rope_parameters"]["rope_theta"]),
            tuple(int(e) for e in cfg["experts_held"]),
            int(cfg["num_experts_per_tok"]), float(cfg["routed_scaling_factor"]),
            bool(cfg["norm_topk_prob"]))


@functools.lru_cache(maxsize=None)
def _jit_layer(spec, precision):
    mm = common.make_mm(precision)
    return jax.jit(lambda h, p: layer(h, p, spec, mm))


@functools.lru_cache(maxsize=None)
def _jit_head(eps, precision):
    mm = common.make_mm(precision)
    return jax.jit(lambda x, hp: head(x, hp, {"rms_norm_eps": eps}, mm))


def logits_rows(params, cfg, tokens, rows, precision="float32"):
    """Of one sequence `tokens` [T] at positions `rows`: logits [len(rows), V],
    and per expert layer the routing's margin and whether the choice's edge
    touches a held expert, each [layers, len(rows)]."""
    rows = jnp.asarray(rows, jnp.int32)
    h = embed(params, jnp.asarray(tokens, jnp.int32), cfg)
    margins, edges = [], []
    for index, name in enumerate(layer_names(params)):
        h, margin, edge_held = _jit_layer(layer_spec(cfg, index), precision)(
            h, params[name])
        if "moe" in params[name]:
            margins.append(margin[rows])
            edges.append(edge_held[rows])
    logits = _jit_head(float(cfg["rms_norm_eps"]), precision)(
        h[rows], head_params(params))
    return logits, jnp.stack(margins), jnp.stack(edges)
