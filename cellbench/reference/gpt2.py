"""Plain reference of the GPT-2 decoder (Radford et al. 2019; config.json of
openai-community/gpt2-xl): token plus learned position embeddings,
pre-LayerNorm blocks of multi-head attention and a tanh-GELU MLP, all with
biases, final LayerNorm, an untied head (the program has no tied one).
Parameter names are the program's (`block_3/mlp_in/kernel`)."""

import jax
import jax.numpy as jnp


def layer_names(params):
    return sorted((k for k in params if k.startswith("block_")),
                  key=lambda k: int(k.split("_")[1]))


def head_params(params):
    return {"ln_final": params["ln_final"], "lm_head": params["lm_head"]}


def embed(params, tokens, cfg):
    pos = params["pos_embed"]["embedding"][:tokens.shape[1]]
    return (params["embed"]["embedding"][tokens] + pos[None]).astype(jnp.float32)


def embed_grad(params, tokens, dx, cfg):
    table, pos = params["embed"]["embedding"], params["pos_embed"]["embedding"]
    d_pos = jnp.zeros_like(pos).at[:tokens.shape[1]].add(dx.sum(0))
    return {"embed": {"embedding": jnp.zeros_like(table).at[tokens].add(dx)},
            "pos_embed": {"embedding": d_pos}}


def _ln(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def layer(x, p, cfg, mm):
    from cellbench.reference.common import causal_attention

    eps = cfg["layer_norm_epsilon"]
    att = p["attention"]
    h = _ln(x, p["ln_attn"], eps)
    proj = lambda name: (mm("bsd,dhk->bshk", h, att[name]["kernel"])
                         + att[name]["bias"])
    out = causal_attention(proj("query"), proj("key"), proj("value"), mm)
    x = x + mm("bshk,hkd->bsd", out, att["out"]["kernel"]) + att["out"]["bias"]
    h = _ln(x, p["ln_mlp"], eps)
    h = jax.nn.gelu(mm("bsd,df->bsf", h, p["mlp_in"]["kernel"])
                    + p["mlp_in"]["bias"], approximate=True)
    return x + mm("bsf,fd->bsd", h, p["mlp_out"]["kernel"]) + p["mlp_out"]["bias"]


def head(x, hp, cfg, mm):
    x = _ln(x, hp["ln_final"], cfg["layer_norm_epsilon"])
    return mm("bsd,dv->bsv", x, hp["lm_head"]["kernel"])
