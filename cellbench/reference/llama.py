"""Plain reference of the Llama/Qwen2 decoder as its papers and config.json
describe it: token embedding, pre-RMSNorm blocks of grouped-query attention
with rotate-half RoPE and optionally biased q/k/v, SwiGLU, final RMSNorm, an
untied head (the program has no tied one; see the configuration's file).
Parameter names are the program's (`block_3/attention/query/kernel`)."""

import jax
import jax.numpy as jnp


def layer_names(params):
    return sorted((k for k in params if k.startswith("block_")),
                  key=lambda k: int(k.split("_")[1]))


def head_params(params):
    return {"norm_final": params["norm_final"], "lm_head": params["lm_head"]}


def embed(params, tokens, cfg):
    return params["embed"]["embedding"][tokens].astype(jnp.float32)


def embed_grad(params, tokens, dx, cfg):
    table = params["embed"]["embedding"]
    return {"embed": {"embedding": jnp.zeros_like(table).at[tokens].add(dx)}}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    seq, depth = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, depth, 2, dtype=jnp.float32) / depth)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    x1, x2 = x[..., :depth // 2], x[..., depth // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, p, cfg, mm):
    from cellbench.reference.common import causal_attention

    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    att = p["attention"]
    h = _rms(x, p["norm_attn"]["scale"], eps)

    def proj(name):
        out = mm("bsd,dhk->bshk", h, att[name]["kernel"])
        return out + att[name]["bias"] if "bias" in att[name] else out
    q, k, v = _rope(proj("query"), theta), _rope(proj("key"), theta), proj("value")
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    x = x + mm("bshk,hkd->bsd", causal_attention(q, k, v, mm),
               att["out"]["kernel"])
    h = _rms(x, p["norm_mlp"]["scale"], eps)
    mlp = p["mlp"]
    gate = jax.nn.silu(mm("bsd,df->bsf", h, mlp["gate"]["kernel"]))
    up = mm("bsd,df->bsf", h, mlp["up"]["kernel"])
    return x + mm("bsf,fd->bsd", gate * up, mlp["down"]["kernel"])


def head(x, hp, cfg, mm):
    x = _rms(x, hp["norm_final"]["scale"], cfg["rms_norm_eps"])
    return mm("bsd,dv->bsv", x, hp["lm_head"]["kernel"])
