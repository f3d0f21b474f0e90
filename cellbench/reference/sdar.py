"""Plain reference of the SDAR MoE decoder (`model_type: sdar_moe`,
SDAR-30B-A3B-Chat) and of its block-diffusion steps, as its config.json
and the family's published convention describe them
(cellbench/configs/sdar-30b-a3b-chat-pp8.json, `assumed`).

A layer, token at position t, blocks of B: a = RMSNorm(x); q_h = RMSNorm_q
(W_q^h a), k_g = RMSNorm_k(W_k^g a), v_g = W_v^g a (32 query heads, 4 key/
value heads of 128); q and k rotated by rotate-half RoPE at t, theta 1e6;
o_h = softmax_j(q_h . k_{h//8, j} / sqrt(128)) v_{h//8, j} over the keys j
with j // B <= t // B (block-causal: bidirectional inside a block);
x' = x + W_o [o_1 .. o_32]; b = RMSNorm(x'); p = softmax(W_r b) over the 128
experts, S = the top 8, w_e = p_e / sum_S p; x'' = x' + sum_{e in S} w_e
W_down^e(silu(W_gate^e b) * W_up^e b). A final RMSNorm and an untied head;
the output at position i is read as the prediction of the token at i.

Block diffusion, replayed from what the program did: `replay` takes a served
sequence (prompt + new tokens) and, for each new token, the step of its block
at which the program committed it. (1) One block-causal forward over the
sequence gives every layer's keys and values, which are what a closed block
leaves in the cache (its clean pass). (2) Every step of every block the
sequence generated is run again: the block's B rows at the mask state that
step saw (the prompt's rows and the rows committed at earlier steps clean,
the others the mask token; rows past the new tokens' end stay masked: the
configuration's `departures`), over the keys and values of the earlier
blocks from (1). The reference follows the program's choice of positions;
the comparison is of the logits at the rows it committed, and of the
confidences of the rows a step could choose from.

Float32, every matrix product at HIGHEST through `common.make_mm` (or, for
the control, with operands rounded to a lower precision), one layer's
weights at a time, attention in blocks of query rows and the replay in
chunks of steps, the head in chunks of rows. Parameter names are the
program's (`block_3/moe/expert_gate`). Nothing here imports the program.

`fault` replays a fault the comparison has to see (tools/readings_sdar.py,
tests/cellbench/test_sdar_cell.py): "causal_block", attention inside a block
made causal, in the forward and in the steps; "no_clean_pass", a block closed
without its clean pass, so the cache keeps the rows of its last step (rows
committed at that step still mask tokens).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from cellbench.reference import common

QUERY_BLOCK = 256
STEP_CHUNK = 64
HEAD_ROWS = 512
FAULTS = ("causal_block", "no_clean_pass")


def layer_names(params):
    return sorted((k for k in params if k.startswith("block_")),
                  key=lambda k: int(k.split("_")[1]))


def head_params(params):
    return {"norm_final": params["norm_final"], "lm_head": params["lm_head"]}


def embed(params, tokens, cfg):
    return params["embed"]["embedding"][tokens].astype(jnp.float32)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _rope(x, positions, theta):
    """x [..., T, H, D] rotate-half at `positions` [..., T]."""
    depth = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, depth, 2, dtype=jnp.float32) / depth)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :depth // 2], x[..., depth // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _qkv(h, p, positions, spec, mm):
    eps, theta = spec[0], spec[1]
    att = p["attention"]
    a = _rms(h, p["norm_attn"]["scale"], eps)
    proj = lambda name: mm("...d,dhk->...hk", a, att[name]["kernel"])
    q = _rope(_rms(proj("query"), att["q_norm"]["scale"], eps), positions,
              theta)
    k = _rope(_rms(proj("key"), att["k_norm"]["scale"], eps), positions,
              theta)
    return q, k, proj("value")


def _experts(b, p, top_k, normalise, mm):
    """The expert layer on rows b [N, d]: (y [N, d], margin [N]), the
    margin being how far apart the router's 8th and 9th logits lie (where
    a rounding may swap them)."""
    logits = mm("nd,de->ne", b, p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top, ids = jax.lax.top_k(logits, top_k + 1)
    margin = top[:, top_k - 1] - top[:, top_k]
    ids = ids[:, :top_k]
    weights = jnp.take_along_axis(probs, ids, axis=-1)
    if normalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    def one(total, expert):
        gate, up, down, ident = expert
        w = jnp.sum(jnp.where(ids == ident, weights, 0.0), axis=-1)
        y = mm("nf,fd->nd",
               jax.nn.silu(mm("nd,df->nf", b, gate)) * mm("nd,df->nf", b, up),
               down)
        return total + w[:, None] * y, None

    experts = p["expert_gate"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(b), (
        p["expert_gate"], p["expert_up"], p["expert_down"],
        jnp.arange(experts, dtype=jnp.int32)))
    return y, margin


def _softmax_out(scores, v, mm):
    probs = jax.nn.softmax(scores, axis=-1)
    return mm("hgqk,khd->qhgd", probs, v)


def _tail(h, out, p, spec, mm):
    """The residual, the expert layer and its residual; out [N, H, D]."""
    eps, _, top_k, normalise, _ = spec
    h = h + mm("nhk,hkd->nd", out, p["attention"]["out"]["kernel"])
    y, margin = _experts(_rms(h, p["norm_mlp"]["scale"], eps), p["moe"],
                         top_k, normalise, mm)
    return h + y, margin


def layer_forward(h, p, spec, mm, block=QUERY_BLOCK):
    """One layer over the whole sequence h [T, d] (T a multiple of the query
    block), block-causal (causal under the "causal_block" fault). Returns
    (h, k, v)."""
    blk, causal = spec[4]
    seq = h.shape[0]
    q, k, v = _qkv(h, p, jnp.arange(seq), spec, mm)
    heads, depth = q.shape[1:]
    kv_heads = k.shape[1]
    qg = q.reshape(seq // block, block, kv_heads, heads // kv_heads, depth)
    keys = jnp.arange(seq)

    def rows(args):
        start, q_rows = args
        scores = mm("qhgd,khd->hgqk", q_rows, k) / math.sqrt(depth)
        at = start + jnp.arange(block)
        if causal:
            seen = keys[None, :] <= at[:, None]
        else:
            seen = keys[None, :] // blk <= at[:, None] // blk
        return _softmax_out(jnp.where(seen, scores, -1e30), v, mm)

    out = jax.lax.map(rows, (jnp.arange(0, seq, block), qg))
    h, _ = _tail(h, out.reshape(seq, heads, depth), p, spec, mm)
    return h, k, v


def layer_steps(h, p, spec, mm, k_ctx, v_ctx, starts):
    """One layer over the steps' rows h [G, B, d], step g's block at
    `starts[g]`: each row attends the context's keys before its block and
    its own block's B rows (all of them; only those up to itself under the
    "causal_block" fault). Returns (h, margin [G, B])."""
    blk, causal = spec[4]
    steps = h.shape[0]
    positions = starts[:, None] + jnp.arange(blk)
    q, k, v = _qkv(h, p, positions, spec, mm)
    heads, depth = q.shape[2:]
    kv_heads = k.shape[2]
    group = heads // kv_heads
    keys = jnp.arange(k_ctx.shape[0])
    own = jnp.arange(blk)
    own_seen = (own[None, :] <= own[:, None]) if causal else jnp.ones(
        (blk, blk), bool)

    def chunk(args):
        start, qc, kc, vc = args          # [C], [C, B, H, D], [C, B, Hkv, D]
        qg = qc.reshape(qc.shape[:2] + (kv_heads, group, depth))
        before = mm("cqhgd,khd->chgqk", qg, k_ctx) / math.sqrt(depth)
        before = jnp.where(keys[None, None, None, None, :]
                           < start[:, None, None, None, None], before, -1e30)
        mine = mm("cqhgd,ckhd->chgqk", qg, kc) / math.sqrt(depth)
        mine = jnp.where(own_seen, mine, -1e30)
        probs = jax.nn.softmax(jnp.concatenate([before, mine], -1), -1)
        n = k_ctx.shape[0]
        return (mm("chgqk,khd->cqhgd", probs[..., :n], v_ctx)
                + mm("chgqk,ckhd->cqhgd", probs[..., n:], vc))

    pad = -steps % STEP_CHUNK
    padded = lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    shaped = lambda x: padded(x).reshape((-1, STEP_CHUNK) + x.shape[1:])
    out = jax.lax.map(chunk, (shaped(starts), shaped(q), shaped(k),
                              shaped(v)))
    out = out.reshape((-1,) + out.shape[2:])[:steps]
    h, margin = _tail(h.reshape(steps * blk, -1),
                      out.reshape(steps * blk, heads, depth), p, spec, mm)
    return h.reshape(steps, blk, -1), margin.reshape(steps, blk)


def layer_spec(cfg, fault=None):
    return (float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
            int(cfg["num_experts_per_tok"]), bool(cfg["norm_topk_prob"]),
            (int(cfg["assumed"]["block_length"]), fault == "causal_block"))


@functools.lru_cache(maxsize=None)
def _jit(kind, spec, precision):
    mm = common.make_mm(precision)
    if kind == "forward":
        return jax.jit(lambda h, p: layer_forward(h, p, spec, mm))
    if kind == "steps":
        return jax.jit(lambda h, p, k, v, s: layer_steps(h, p, spec, mm, k, v,
                                                         s))
    eps = spec[0]

    def head(x, hp, judged):
        """Of rows x [N, d]: (logits of `judged` [N], best logit [N],
        argmax [N], log of the argmax's confidence [N])."""
        x = _rms(x, hp["norm_final"]["scale"], eps)
        logits = mm("nd,dv->nv", x, hp["lm_head"]["kernel"])
        best = jnp.max(logits, axis=-1)
        return (jnp.take_along_axis(logits, judged[:, None], -1)[:, 0], best,
                jnp.argmax(logits, axis=-1).astype(jnp.int32),
                best - jax.nn.logsumexp(logits, axis=-1))
    return jax.jit(head)


def plan(tokens, prompt_len, commit_steps, cfg):
    """The steps a served sequence's blocks took, from the program's
    commit steps: per step its block's first position, the B rows' input
    tokens, and per row whether it is masked at that step and whether the
    program committed it there."""
    blk = int(cfg["assumed"]["block_length"])
    mask = int(cfg["assumed"]["mask_token"])
    tokens = np.asarray(tokens, np.int64)
    end = len(tokens)
    step_of = np.full(-(-end // blk) * blk, -1, np.int64)
    step_of[prompt_len:end] = np.asarray(commit_steps, np.int64)
    starts, rows, masked, committed = [], [], [], []
    for start in range(prompt_len // blk * blk, end, blk):
        at = step_of[start:start + blk]
        padded = np.full(blk, mask, np.int64)
        have = tokens[start:start + blk]
        padded[:len(have)] = have
        for step in range(int(at.max()) + 1):
            clean = (np.arange(start, start + blk) < prompt_len) | (
                (at >= 0) & (at < step))
            starts.append(start)
            rows.append(np.where(clean, padded, mask))
            masked.append((at >= step))
            committed.append(at == step)
    return (np.asarray(starts, np.int32), np.asarray(rows, np.int32),
            np.asarray(masked), np.asarray(committed))


def context_tokens(tokens, prompt_len, commit_steps, cfg, fault=None):
    """What the cache's closed blocks were computed from: the sequence
    itself, or under "no_clean_pass" each generated block as its last step
    saw it."""
    tokens = np.asarray(tokens, np.int32).copy()
    if fault != "no_clean_pass":
        return tokens
    starts, rows, _, _ = plan(tokens, prompt_len, commit_steps, cfg)
    blk = int(cfg["assumed"]["block_length"])
    for start, row in zip(starts, rows):   # the last step of a block wins
        n = min(blk, len(tokens) - start)
        tokens[start:start + n] = row[:n]
    return tokens


def replay(params, cfg, tokens, prompt_len, commit_steps, precision="float32",
           fault=None, judge=None):
    """For the new tokens of one served sequence, in order: (the logit of
    the served token — of `judge`'s, where given — at its commit step, the
    best logit there, the argmax there, the routing's least margin over the
    layers there); and for every step the program took, in `plan`'s order,
    (the log of each row's confidence [G, B], the rows masked [G, B], the
    rows the program committed [G, B]), for the comparison of the program's
    choice with the reference's ranking."""
    spec = layer_spec(cfg, fault)
    blk = spec[4][0]
    starts, rows, masked, committed = plan(tokens, prompt_len, commit_steps,
                                           cfg)
    ctx = context_tokens(tokens, prompt_len, commit_steps, cfg, fault)
    length = int(starts.max())
    seq = max(-(-length // QUERY_BLOCK) * QUERY_BLOCK, QUERY_BLOCK)
    padded = np.zeros(seq, np.int32)
    padded[:length] = ctx[:length]
    h = embed(params, jnp.asarray(padded), cfg)
    x = embed(params, jnp.asarray(rows), cfg)
    margins = []
    for name in layer_names(params):
        h_next, k, v = _jit("forward", spec, precision)(h, params[name])
        x, margin = _jit("steps", spec, precision)(
            x, params[name], k, v, jnp.asarray(starts))
        margins.append(margin)
        h = h_next
    margin = jnp.min(jnp.stack(margins), axis=0)
    # The served token judged at every row (the rows the program did not
    # commit at this step are read only for their confidence).
    served = np.asarray(tokens, np.int32).copy()
    if judge is not None:
        served[prompt_len:] = np.asarray(judge, np.int32)
    positions = starts[:, None] + np.arange(blk)
    judged = np.where(positions < len(served),
                      served[np.minimum(positions, len(served) - 1)], 0)
    flat = x.reshape(-1, x.shape[-1])
    head = _jit("head", spec, precision)
    parts = [head(flat[i:i + HEAD_ROWS], head_params(params),
                  jnp.asarray(judged.reshape(-1)[i:i + HEAD_ROWS]))
             for i in range(0, flat.shape[0], HEAD_ROWS)]
    picked, best, argmax, conf = (np.concatenate([np.asarray(p[i])
                                                  for p in parts])
                                  .reshape(rows.shape) for i in range(4))
    margin = np.asarray(margin)
    # Back to the order of the new tokens.
    at = positions[committed]
    order = np.argsort(at, kind="stable")
    pick = lambda a: a[committed][order]
    return (pick(picked), pick(best), pick(argmax), pick(margin),
            (conf, masked, committed))
