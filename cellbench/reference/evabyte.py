"""Plain reference of the EvaByte decoder: a byte embedding, pre-RMSNorm
blocks of EVA attention (Zheng, Wang, Kong: "Efficient Attention via Control
Variates", arXiv:2302.04542, in the form the family's public modelling code
gives it) and SwiGLU with the residual sum in float32, a final RMSNorm, and one
untied matrix for the `num_pred_heads` prediction heads, head 0 (the next
byte) first. Parameter names are the program's
(`block_3/attention/query/kernel`, `block_3/attention/phi`).

EVA, for a head of size D with its two learned vectors `phi` and `mu`, window
W = `window_size` and chunk C = `chunk_size`, after rotate-half RoPE of q and k
at absolute positions:

    a_m  = softmax over the C positions m of chunk c of (k_m . phi)
    k~_c = sum_m a_m k_m + mu,   v~_c = sum_m a_m v_m
    o_t  = one softmax over the exact keys of t's own ALIGNED window up to t
           and the summaries (k~, v~) of every chunk of the windows before it

Written from those equations: every chunk's summary first, then a `lax.map`
over the windows, each one softmax over `[summaries | window, causal]`, so that
no score over the whole sequence exists at 32768 tokens. No cache, no pages, no
kernel, nothing imported from the program.
"""

import math

import jax
import jax.numpy as jnp


def layer_names(params):
    return sorted((k for k in params if k.startswith("block_")),
                  key=lambda k: int(k.split("_")[1]))


def head_params(params):
    return {"norm_final": params["norm_final"], "lm_head": params["lm_head"]}


def embed(params, tokens, cfg):
    return params["embed"]["embedding"][tokens].astype(jnp.float32)


def _rms(x, scale, eps):
    # `scale` holds the multiplier 1 + g of the family's unit-offset norm.
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    seq, depth = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, depth, 2, dtype=jnp.float32) / depth)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    x1, x2 = x[..., :depth // 2], x[..., depth // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def chunk_weights(k, phi, chunk):
    """`a`: [B, chunks, C, H], a softmax over the C positions of each chunk of
    k [B, T, H, D] (T a multiple of C)."""
    batch, seq, heads, depth = k.shape
    kc = k.reshape(batch, seq // chunk, chunk, heads, depth)
    return jax.nn.softmax(jnp.sum(kc * phi, axis=-1), axis=2)


def summaries(k, v, phi, mu, chunk):
    """(k~, v~), each [B, chunks, H, D]."""
    batch, seq, heads, depth = k.shape
    a = chunk_weights(k, phi, chunk)[..., None]
    by_chunk = lambda x: x.reshape(batch, seq // chunk, chunk, heads, depth)
    return jnp.sum(a * by_chunk(k), axis=2) + mu, jnp.sum(a * by_chunk(v), axis=2)


def attention(q, k, v, k_sum, v_sum, cfg, mm):
    """EVA over q, k, v [B, T, H, D] (T a multiple of the window) with every
    chunk's summary [B, T / C, H, D]: window by window, one softmax over the
    summaries of the chunks before the window and the window's own keys up to
    the query."""
    window, chunk = cfg["window_size"], cfg["chunk_size"]
    batch, seq, heads, depth = q.shape
    windows = seq // window
    by_window = lambda x: jnp.moveaxis(
        x.reshape(batch, windows, window, heads, depth), 1, 0)
    causal = jnp.tril(jnp.ones((window, window), bool))
    chunk_index = jnp.arange(seq // chunk)

    def one(args):
        n, qn, kn, vn = args
        seen = jnp.broadcast_to(chunk_index < n * (window // chunk),
                                (window, seq // chunk))
        mask = jnp.concatenate([seen, causal], axis=1)
        keys = jnp.concatenate([k_sum, kn], axis=1)
        values = jnp.concatenate([v_sum, vn], axis=1)
        scores = mm("bqhd,bkhd->bhqk", qn, keys) / math.sqrt(depth)
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return mm("bhqk,bkhd->bqhd", probs, values)

    out = jax.lax.map(one, (jnp.arange(windows), by_window(q), by_window(k),
                            by_window(v)))
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads, depth)


def layer(x, p, cfg, mm, attend=attention):
    """`attend`: the attention over `[summaries | window]`; a readings tool
    puts a faulty one in its place (cellbench/tools/readings_eva.py)."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    window, chunk = cfg["window_size"], cfg["chunk_size"]
    att = p["attention"]
    h = _rms(x, p["norm_attn"]["scale"], eps)
    proj = lambda name: mm("bsd,dhk->bshk", h, att[name]["kernel"])
    q, k, v = _rope(proj("query"), theta), _rope(proj("key"), theta), proj("value")
    seq = x.shape[1]
    pad = -seq % window
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
    k_sum, v_sum = summaries(k, v, att["phi"].astype(jnp.float32),
                             att["mu"].astype(jnp.float32), chunk)
    o = attend(q, k, v, k_sum, v_sum, cfg, mm)[:, :seq]
    x = x + mm("bshk,hkd->bsd", o, att["out"]["kernel"])
    h = _rms(x, p["norm_mlp"]["scale"], eps)
    mlp = p["mlp"]
    gate = jax.nn.silu(mm("bsd,df->bsf", h, mlp["gate"]["kernel"]))
    up = mm("bsd,df->bsf", h, mlp["up"]["kernel"])
    return x + mm("bsf,fd->bsd", gate * up, mlp["down"]["kernel"])


def all_heads(x, hp, cfg, mm):
    """Logits of every prediction head: [B, S, num_pred_heads, V]; head j
    predicts byte t + 1 + j."""
    x = _rms(x, hp["norm_final"]["scale"], cfg["rms_norm_eps"])
    logits = mm("bsd,dv->bsv", x, hp["lm_head"]["kernel"])
    return logits.reshape(*logits.shape[:-1], cfg["num_pred_heads"],
                          cfg["vocab_size"])


def head(x, hp, cfg, mm):
    """Head 0's logits, the next byte's: what the served token is held to."""
    return all_heads(x, hp, cfg, mm)[:, :, 0]


def first_chunk_weights(params, cfg, tokens):
    """The in-chunk softmax weights `a` [chunks, C, H] of the first layer
    over `tokens` [T] (T a multiple of the chunk): how far from uniform the
    seeded `phi` makes them, which the cell's driver prints."""
    from cellbench.reference.common import make_mm

    p = params[layer_names(params)[0]]
    x = embed(params, jnp.asarray(tokens, jnp.int32)[None], cfg)
    h = _rms(x, p["norm_attn"]["scale"], cfg["rms_norm_eps"])
    k = _rope(make_mm("float32")("bsd,dhk->bshk", h,
                                 p["attention"]["key"]["kernel"]),
              cfg["rope_theta"])
    return chunk_weights(k, p["attention"]["phi"].astype(jnp.float32),
                         cfg["chunk_size"])[0]
