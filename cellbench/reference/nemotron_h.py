"""Plain reference of the Nemotron-H hybrid decoder (`model_type: nemotron_h`,
NVIDIA-Nemotron-3-Super-120B-A12B) as its config.json, the family's paper
(Nemotron-H, arXiv:2504.03624) and the Mamba-2 paper (arXiv:2405.21060)
describe it, for one chip's share of an expert-parallel deployment.

Every block is h <- h + Mixer(RMSNorm(h)) with ONE mixer, chosen by the
pattern string (`pattern_kept` in the configuration's file): `M` Mamba-2, `*`
attention, `E` experts. A final RMSNorm, an untied head over the vocabulary
slice. With u = RMSNorm(h), token t:

M. [z | xBC | dt] = W_in u (inner | inner + 2 G N | heads);
   xBC_t = silu(b_c + sum_{k<K} w_c[k] xBC_{t-K+1+k}) (depthwise, causal, zeros
   before the sequence); x, B, C = split(xBC) (inner | G N | G N);
   dt_t = softplus(dt_t + dt_bias), A = -exp(A_log) a head;
   S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, S a head: head_dim x N, head h
   reads group h // (heads / G); y_t = S_t C_t + D x_t;
   out = W_out (GroupRMSNorm_G(y_t silu(z_t)) scale). The recurrence is a
   sequential scan over the tokens: no chunks.
*. q = W_q u (H x D), k = W_k u, v = W_v u (H_kv x D), NO rotation (the
   family's attention carries no position embedding); scores q.k / sqrt(D) over
   keys j <= t; softmax; each key/value head serves H / H_kv query heads;
   out = W_o concat(heads).
E. s = sigmoid(W_r u) in float32 over ALL experts the router has; the
   `num_experts_per_tok` chosen are the top of s + b; g_e = routed_scaling_factor
   x s_e / sum over the chosen of s; l = W_dn u (the latent); r = sum over the
   chosen of g_e W2_e relu(W1_e l)^2; out = W_up r + V2 relu(V1 u)^2 (the shared
   expert, at full width). This chip holds `experts_held` only: r sums the
   chosen experts it holds, and `W_up r + shared` goes on to the next layer
   (cellbench/configs/nemotron-3-super-120b-a12b-ep4.json, `deployment`).

No multi-token-prediction module (the file's `departures`).

Float32, every matrix product at HIGHEST through `common.make_mm` (or, for the
control, with operands rounded to a lower precision), one layer at a time, the
experts one at a time and attention in blocks of query rows, so that it fits
beside nothing else on the chip. Parameter names are the program's
(`block_1/moe/expert_up`). Nothing here imports the program.

The contract is `reference/exaone_moe.py`'s: `logits_rows` also returns how
near each row's routing is to a tie, in units of the router's logit, and
whether an expert at the choice's edge is held here. `states_after` gives the
state-space layers' states once a count of tokens has passed, for the
comparison of the served state itself (`drivers/closed_loop_hybrid.py`, (c)).
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


def round_to(x, dtype):
    """Float32 `x` rounded to the values `dtype` holds. Not `astype` there and
    back: XLA takes such a pair out (it allows itself excess precision)."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


# ------------------------------------------------------------------ Mamba-2

def mamba(u, p, shape, eps, mm, count, state_dtype):
    """u [T, d] -> ([T, d], the state after token `count` - 1 as [heads,
    head_dim, state]). `shape`: (heads, head_dim, groups, state, taps). The
    state stops at `count` (dt is 0 from there on: exp(0) S + 0), so rows from
    `count` on are not the layer's output; a caller that wants every row gives
    the sequence's length. `state_dtype`, where it is not float32, is what the
    state is rounded to after every token: the control of the precision the
    configuration states for it (`assumed.ssm_state_dtype`)."""
    heads, head_dim, groups, n, taps = shape
    inner = heads * head_dim
    seq = u.shape[0]
    zxbcdt = mm("td,df->tf", u, p["in_proj"]["kernel"])
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:2 * inner + 2 * groups * n]
    dt = zxbcdt[:, 2 * inner + 2 * groups * n:]
    before = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32), xbc])
    w = p["conv_kernel"].astype(jnp.float32)
    xbc = jax.nn.silu(p["conv_bias"].astype(jnp.float32) + sum(
        w[k] * before[k:k + seq] for k in range(taps)))
    x = xbc[:, :inner].reshape(seq, heads, head_dim)
    b = jnp.repeat(xbc[:, inner:inner + groups * n].reshape(seq, groups, n),
                   heads // groups, axis=1)                  # [T, heads, N]
    c = jnp.repeat(xbc[:, inner + groups * n:].reshape(seq, groups, n),
                   heads // groups, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))   # [T, heads]
    dt = jnp.where(jnp.arange(seq)[:, None] < count, dt, 0.0)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))

    def step(state, token):
        x_t, b_t, c_t, dt_t = token
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        state = round_to(state, state_dtype)
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    state, y = jax.lax.scan(step, jnp.zeros((heads, head_dim, n), jnp.float32),
                            (x, b, c, dt))
    y = y + p["D"].astype(jnp.float32)[:, None] * x
    gated = (y.reshape(seq, inner) * jax.nn.silu(z)).reshape(
        seq, groups, inner // groups)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + eps)
    return mm("tf,fd->td", normed.reshape(seq, inner)
              * p["scale"].astype(jnp.float32), p["out_proj"]["kernel"]), state


# ---------------------------------------------------------------- attention

def attention(u, p, mm, block=QUERY_BLOCK):
    """u [T, d] -> [T, d]: causal, no rotation, a block of query rows at a
    time."""
    proj = lambda name: mm("td,dhk->thk", u, p[name]["kernel"])
    q, k, v = proj("query"), proj("key"), proj("value")
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
        seen = keys[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return mm("hgqk,khd->qhgd", probs, v)

    out = jax.lax.map(rows, (jnp.arange(0, seq, block), qg))
    return mm("thk,hkd->td", out.reshape(seq, heads, depth), p["out"]["kernel"])


# ------------------------------------------------------------------ experts

def route(u, p, top_k, scale, normalise, mm):
    """(chosen ids [T, k], weights [T, k], margin [T], edge ids [T, 2]): the
    edge is the last chosen expert and the first one not chosen, the margin
    how far the router's logit of one of the two would have to move for them
    to change places (their distance in s + b over the steeper of the two
    sigmoids' slopes)."""
    scores = jax.nn.sigmoid(mm("td,de->te", u, p["router"]))
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


def routed_part(u, p, held, top_k, scale, normalise, mm):
    """What the experts in `held` add, in the latent space [T, latent] (row e
    of the stacked weights is expert held[e]), with the routing's margin and
    whether the choice's edge touches a held expert."""
    ids, weights, margin, edge = route(u, p, top_k, scale, normalise, mm)
    latent = mm("td,dl->tl", u, p["latent_down"]["kernel"])
    held_ids = jnp.asarray(held, jnp.int32)

    def one(total, expert):
        up, down, ident = expert
        w = jnp.sum(jnp.where(ids == ident, weights, 0.0), axis=-1)
        y = mm("tf,fl->tl", relu2(mm("tl,lf->tf", latent, up)), down)
        return total + w[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(latent),
                             (p["expert_up"], p["expert_down"], held_ids))
    edge_held = jnp.any(edge[:, :, None] == held_ids[None, None, :], axis=(1, 2))
    return routed, margin, edge_held


def shared_expert(u, p, mm):
    return mm("tf,fd->td", relu2(mm("td,df->tf", u, p["up"]["kernel"])),
              p["down"]["kernel"])


def expert_layer(u, p, held, top_k, scale, normalise, mm):
    """This chip's part of the expert layer: the latent sum of the chosen
    experts among `held` projected up, and the shared expert."""
    routed, margin, edge_held = routed_part(u, p, held, top_k, scale,
                                            normalise, mm)
    out = mm("tl,ld->td", routed, p["latent_up"]["kernel"])
    return out + shared_expert(u, p["shared"], mm), margin, edge_held


# -------------------------------------------------------------------- model

def layer(h, p, spec, mm, count):
    """One block on h [T, d]. `spec`: (kind, eps, mamba shape, held, top_k,
    scale, normalise, state dtype). Returns (h, margin [T], edge_held [T],
    state): the routing's margin (inf on a layer without experts), whether an
    expert at the edge of the choice is held here, and a state-space layer's
    state after token `count` - 1 (None on the other layers)."""
    kind, eps, shape, held, top_k, scale, normalise, state_dtype = spec
    u = _rms(h, p["norm"]["scale"], eps)
    margin = jnp.full(h.shape[:1], jnp.inf, jnp.float32)
    edge_held = jnp.zeros(h.shape[:1], bool)
    state = None
    if kind == "M":
        y, state = mamba(u, p["mamba"], shape, eps, mm, count, state_dtype)
    elif kind == "*":
        y = attention(u, p["attention"], mm)
    elif kind == "E":
        y, margin, edge_held = expert_layer(u, p["moe"], held, top_k, scale,
                                            normalise, mm)
    else:
        raise ValueError("unknown layer kind {!r}".format(kind))
    return h + y, margin, edge_held, state


def head(x, hp, cfg, mm):
    x = _rms(x, hp["norm_final"]["scale"], cfg["norm_eps"])
    return mm("td,dv->tv", x, hp["lm_head"]["kernel"])


def layer_spec(cfg, index, state_dtype="float32"):
    return (cfg["pattern_kept"][index], float(cfg["norm_eps"]),
            (int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"]),
             int(cfg["n_groups"]), int(cfg["ssm_state_size"]),
             int(cfg["conv_kernel"])),
            tuple(int(e) for e in cfg["experts_held"]),
            int(cfg["num_experts_per_tok"]), float(cfg["routed_scaling_factor"]),
            bool(cfg["norm_topk_prob"]), state_dtype)


@functools.lru_cache(maxsize=None)
def _jit_layer(spec, precision):
    mm = common.make_mm(precision)
    return jax.jit(lambda h, p, count: layer(h, p, spec, mm, count))


@functools.lru_cache(maxsize=None)
def _jit_head(eps, precision):
    mm = common.make_mm(precision)
    return jax.jit(lambda x, hp: head(x, hp, {"norm_eps": eps}, mm))


def logits_rows(params, cfg, tokens, rows, precision="float32",
                state_dtype="float32"):
    """Of one sequence `tokens` [T] at positions `rows`: logits [len(rows), V],
    and per expert layer the routing's margin and whether the choice's edge
    touches a held expert, each [layers, len(rows)]."""
    rows = jnp.asarray(rows, jnp.int32)
    h = embed(params, jnp.asarray(tokens, jnp.int32), cfg)
    margins, edges = [], []
    for index, name in enumerate(layer_names(params)):
        h, margin, edge_held, _ = _jit_layer(
            layer_spec(cfg, index, state_dtype), precision)(
                h, params[name], jnp.int32(len(tokens)))
        if "moe" in params[name]:
            margins.append(margin[rows])
            edges.append(edge_held[rows])
    logits = _jit_head(float(cfg["norm_eps"]), precision)(
        h[rows], head_params(params))
    return logits, jnp.stack(margins), jnp.stack(edges)


def states_after(params, cfg, tokens, count, precision="float32",
                 state_dtype="float32"):
    """Each state-space layer's state once the first `count` tokens of the
    sequence `tokens` [T] have passed, in the order of the layers, each
    [heads, head_dim, state] float32."""
    h = embed(params, jnp.asarray(tokens, jnp.int32), cfg)
    names = layer_names(params)
    last = max(i for i, name in enumerate(names) if "mamba" in params[name])
    states = []
    for index, name in enumerate(names[:last + 1]):
        h, _, _, state = _jit_layer(
            layer_spec(cfg, index, state_dtype), precision)(
                h, params[name], jnp.int32(count))
        if state is not None:
            states.append(state)
    return states
