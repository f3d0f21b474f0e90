"""Speculative decoding: draft proposes, target verifies.

The latency optimization for single-stream decoding: a small DRAFT
model proposes `num_draft` tokens one at a time (cheap steps), and the
large TARGET model scores all of them in ONE forward pass (a single
large, MXU-friendly dispatch instead of `num_draft` small ones).

Two verification modes, selected by `temperature`:

- Greedy (temperature=0, the default): every proposal matching the
  target's own greedy choice is accepted; the first mismatch is
  replaced by the target's token — so the output is TOKEN-IDENTICAL
  to plain greedy decoding with the target model whenever the two
  paths' logits agree on every argmax, only faster wall-clock when
  the draft's acceptance rate is decent. The parity tests pin exact
  equality in f32; in bf16 on TPU, XLA may tile the (k+1)-token
  verification forward differently from generate()'s single-token
  steps, and a near-exact argmax tie could flip — rare in practice;
  the match fraction there is not measured.

- Stochastic (temperature>0): the Leviathan et al. accept/reject
  scheme (arXiv 2211.17192). The draft SAMPLES each proposal from its
  warped distribution q; the target computes its warped distribution
  p at every position in the one verification forward; proposal i is
  accepted with probability min(1, p(x_i)/q(x_i)), and the first
  rejection is replaced by a sample from norm(max(p - q, 0)) — after
  full acceptance a bonus token is sampled from p. The committed
  stream is distributed EXACTLY as target-only sampling (the paper's
  Theorem 3.5), and because both sides share `generate()`'s warper
  (models/decoding.py warp_logits: top-k → temperature → top-p), the
  scheme composes with the whole sampling surface. The accept/reject
  math itself lives in `_accept_and_residual` (pure, unit-tested
  against a numpy oracle; the distribution-parity statistical test
  drives the same function through vmap).

Works with any pair of decode-capable models sharing a vocabulary
(`TransformerLM`, `LlamaLM`, `DeepseekLM` — e.g. a 2-layer draft for
a 16-layer target, or an imported small checkpoint drafting for a
large one). Batch size 1: acceptance counts differ per example, which
would force per-row cache rewinds; speculative decoding is a
latency (not throughput) tool, so the single-stream restriction is
the standard one.

Cache bookkeeping rides the slot-addressed decode caches
(models/decoding.py): rejected draft entries are rolled back by
rewinding the write pointer, slot validity, and token counts — the
stale k/v values beyond the pointer are overwritten by the next
write and never attended in between.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from cloud_tpu.models.decoding import (best_effort_donation,
                                       empty_cache, warp_logits)
from cloud_tpu.parallel import SEQUENCE_PARALLEL_IMPLS
from cloud_tpu.parallel import runtime

_BOOKKEEPING = ("cache_index", "token_count", "pos_count")


class SpeculativeBatchError(ValueError):
    """`generate_speculative` is single-stream: acceptance counts
    differ per example, which would force per-row cache rewinds the
    batch-synchronous fused round cannot express. (The serving tick's
    per-SLOT speculation is the batched form — serving/engine.py.)
    Subclasses ValueError for callers that caught the untyped error."""


class SpeculativeShardingError(NotImplementedError):
    """`generate_speculative` decodes on a single mesh shard; a
    sequence-parallel attention_impl on either model cannot run the
    fused round. Subclasses NotImplementedError for callers that
    caught the untyped error."""


def greedy_accept(drafts, greedy):
    """Leading-match acceptance count for greedy verification: the
    number of proposals matching the target's own greedy choices
    before the first mismatch, `sum(cumprod(drafts == greedy[:k]))`.

    Pure and shape-generic over leading batch dims (`drafts` [..., k],
    `greedy` [..., >=k]) — the single-stream fused round uses it at
    [k] and the serving tick's per-slot speculation at [S, k], so the
    two paths cannot drift (per-slot bit-identity rides on this being
    the same math).
    """
    k = drafts.shape[-1]
    accept = (drafts == greedy[..., :k]).astype(jnp.int32)
    return jnp.sum(jnp.cumprod(accept, axis=-1), axis=-1)


def observe_accept_rate(accepted, proposed):
    """Feeds the shared accepted-token-rate histogram (telemetry name
    SERVE_SPEC_ACCEPT_HISTOGRAM) — one observation per verification
    round, value accepted/proposed in [0, 1]. Zero-cost when telemetry
    is off: a sys.modules dict lookup, no import."""
    import sys

    telemetry = sys.modules.get("cloud_tpu.monitoring.telemetry")
    if telemetry is None or not telemetry.enabled():
        return
    tele = telemetry.get()
    if tele is None or not tele.active:
        return
    tele.registry.histogram(
        telemetry.SERVE_SPEC_ACCEPT_HISTOGRAM,
        start=1.0 / 64.0, factor=2.0 ** 0.5, buckets=16).observe(
            accepted / proposed if proposed else 0.0)


def _rewind_cache(cache, n, new_idx):
    """Roll back the last n cache slots (bookkeeping only).

    Runs INSIDE the fused round executable with a traced n (n == 0 is
    a no-op by construction: pointer -= 0, and the slot mask keeps
    exactly the already-valid entries when new_idx equals the current
    count). new_idx: the write pointer AFTER the rewind — the number
    of committed cache entries.
    """
    def fix(path, leaf):
        key = getattr(path[-1], "key", None)
        if key in _BOOKKEEPING:
            return leaf - n
        if key == "slot_valid":
            length = leaf.shape[-1]
            return leaf & (jnp.arange(length)[None, :] < new_idx)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


@functools.lru_cache(maxsize=128)
def _chunk_fn(decoder):
    """Jitted chunk feed: returns (new_cache, greedy tokens [B, S])."""

    # donate_argnums=1: callers always rebind the cache they pass in,
    # so the KV buffers update in place.
    @functools.partial(runtime.instrumented_jit, donate_argnums=1)
    def chunk(params, cache, tokens):
        logits, vars_ = decoder.apply(
            {"params": params, "cache": cache}, tokens,
            mutable=["cache"])
        return vars_["cache"], jnp.argmax(
            logits.astype(jnp.float32), axis=-1).astype(jnp.int32)

    return best_effort_donation(chunk)


def _fixup_caches(target_cache, draft, draft_params, d_cache, drafts,
                  n_acc, k, base_len):
    """Post-verification cache bookkeeping, on device (traced n_acc).

    Both caches must end holding entries for the new seq[:-1], i.e.
    base_len + n_acc committed entries. The target wrote k+1 entries
    (last_tok, d1..dk): keep n_acc+1. The draft wrote k entries
    (last_tok, d1..d_{k-1}): rejections rewind for free; only full
    acceptance needs the one missing d_k entry, written under the
    lax.cond so it costs a draft forward only when taken.
    """
    kept = base_len + n_acc
    target_cache = _rewind_cache(target_cache, k - n_acc, kept)

    def rewound(dc):
        return _rewind_cache(dc, k - n_acc - 1, kept)

    def caught_up(dc):
        _, vars_ = draft.apply(
            {"params": draft_params, "cache": dc},
            drafts[-1][None, None], mutable=["cache"])
        return vars_["cache"]

    d_cache = jax.lax.cond(n_acc < k, rewound, caught_up, d_cache)
    return target_cache, d_cache


@functools.lru_cache(maxsize=128)
def _greedy_round_fn(target, draft, k):
    """One FUSED greedy speculative round: the k-step draft scan, the
    target verification forward, argmax acceptance, and both cache
    fix-ups — a single dispatch, with one [k+1]-token fetch per round
    (the old loop paid k draft dispatches, each with a host sync for
    the argmax token, plus the verify — a blocking host round trip per
    dispatch)."""

    # Donate both caches: the round loop rebinds them every iteration.
    @functools.partial(runtime.instrumented_jit, donate_argnums=(2, 3))
    def round_step(params, draft_params, t_cache, d_cache, last_tok,
                   base_len):
        def draft_body(carry, _):
            d_cache, tok = carry
            logits, vars_ = draft.apply(
                {"params": draft_params, "cache": d_cache}, tok,
                mutable=["cache"])
            nxt = jnp.argmax(logits[:, -1].astype(jnp.float32),
                             axis=-1).astype(jnp.int32)[:, None]
            return (vars_["cache"], nxt), nxt[0, 0]

        (d_cache, _), drafts = jax.lax.scan(
            draft_body, (d_cache, last_tok), None, length=k)

        verify_in = jnp.concatenate([last_tok[0], drafts])[None, :]
        logits, vars_ = target.apply(
            {"params": params, "cache": t_cache}, verify_in,
            mutable=["cache"])
        greedy = jnp.argmax(logits[0].astype(jnp.float32),
                            axis=-1).astype(jnp.int32)  # [k+1]
        n_acc = greedy_accept(drafts, greedy)
        committed = jnp.concatenate(
            [drafts, jnp.zeros((1,), jnp.int32)])
        committed = committed.at[n_acc].set(greedy[n_acc])
        t_cache, d_cache = _fixup_caches(
            vars_["cache"], draft, draft_params, d_cache, drafts,
            n_acc, k, base_len)
        return t_cache, d_cache, committed, n_acc

    return best_effort_donation(round_step)


def _accept_and_residual(p, q, d_tokens, uniforms):
    """Leviathan et al. accept/reject math (pure; oracle-tested).

    Args:
        p: [k+1, V] target probabilities (post-warp softmax) at the
            k+1 verification positions.
        q: [k, V] draft probabilities the k proposals were drawn from.
        d_tokens: [k] int32 proposals.
        uniforms: [k] U[0,1) draws, one per proposal.

    Returns (n_acc, resid):
        n_acc: number of LEADING proposals accepted — proposal i is
            accepted iff uniforms[i] < min(1, p_i(x_i)/q_i(x_i)), and
            acceptance stops at the first failure.
        resid: [V] the distribution for the extra committed token —
            norm(max(p - q, 0)) at the first rejected position, or
            p[k] (the bonus position) when all k were accepted. The
            committed stream (accepted proposals + this sample) is
            then distributed exactly as target-only sampling.
    """
    k = q.shape[0]
    idx = jnp.arange(k)
    p_tok = p[idx, d_tokens]
    q_tok = q[idx, d_tokens]
    # q(x_i) > 0 by construction (x_i was sampled from q); the
    # denominator guard is numerical only.
    accept = uniforms < jnp.minimum(
        1.0, p_tok / jnp.maximum(q_tok, 1e-38))
    n_acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32)))
    p_row = p[n_acc]
    q_row = jnp.where(n_acc < k, q[jnp.minimum(n_acc, k - 1)],
                      jnp.zeros_like(p_row))
    resid = jnp.maximum(p_row - q_row, 0.0)
    total = jnp.sum(resid)
    # total == 0 would need a rejection at a position where p == q,
    # which has probability 0 in exact arithmetic; the fallback to
    # p_row guards float underflow only.
    resid = jnp.where(total > 0.0, resid / total, p_row)
    return n_acc, resid


@functools.lru_cache(maxsize=128)
def _stochastic_round_fn(target, draft, k, temperature, top_k, top_p):
    """One FUSED stochastic speculative round: the k-step sampling
    draft scan (each step's warped logits captured as the
    q-distribution its token was drawn from), the target verification
    forward, the Leviathan accept/reject + replacement/bonus sample,
    and both cache fix-ups — a single dispatch, one [k+1]-token fetch
    per round."""

    # Donate both caches: the round loop rebinds them every iteration.
    @functools.partial(runtime.instrumented_jit, donate_argnums=(2, 3))
    def round_step(params, draft_params, t_cache, d_cache, last_tok,
                   base_len, rng):
        rngs = jax.random.split(rng, k + 2)
        step_rngs, uni_rng, extra_rng = rngs[:k], rngs[k], rngs[k + 1]

        def draft_body(carry, step_rng):
            d_cache, tok = carry
            logits, vars_ = draft.apply(
                {"params": draft_params, "cache": d_cache}, tok,
                mutable=["cache"])
            warped = warp_logits(logits[:, -1], temperature, top_k,
                                 top_p)
            nxt = jax.random.categorical(
                step_rng, warped, axis=-1).astype(jnp.int32)[:, None]
            return (vars_["cache"], nxt), (nxt[0, 0], warped[0])

        (d_cache, _), (drafts, q_warped) = jax.lax.scan(
            draft_body, (d_cache, last_tok), step_rngs)

        verify_in = jnp.concatenate([last_tok[0], drafts])[None, :]
        logits, vars_ = target.apply(
            {"params": params, "cache": t_cache}, verify_in,
            mutable=["cache"])
        p_warped = warp_logits(logits[0], temperature, top_k, top_p)
        n_acc, resid = _accept_and_residual(
            jax.nn.softmax(p_warped, axis=-1),
            jax.nn.softmax(q_warped, axis=-1), drafts,
            jax.random.uniform(uni_rng, (k,)))
        extra = jax.random.categorical(
            extra_rng, jnp.log(resid)).astype(jnp.int32)
        committed = jnp.concatenate(
            [drafts, jnp.zeros((1,), jnp.int32)])
        committed = committed.at[n_acc].set(extra)
        t_cache, d_cache = _fixup_caches(
            vars_["cache"], draft, draft_params, d_cache, drafts,
            n_acc, k, base_len)
        return t_cache, d_cache, committed, n_acc

    return best_effort_donation(round_step)


def generate_speculative(model, params, draft_model, draft_params,
                         prompt, max_new_tokens, num_draft=4,
                         eos_token=None, rng=None, temperature=0.0,
                         top_k=None, top_p=None, return_stats=False):
    """Decode with draft-model speculation (greedy or stochastic).

    Args:
        model / params: the TARGET model. With temperature=0 its
            greedy output is what this function reproduces, token for
            token; with temperature>0 the committed stream is
            distributed exactly as sampling from the target alone.
        draft_model / draft_params: the cheap proposal model (same
            vocabulary; any decode-capable family).
        prompt: [1, S] int32 (batch 1 — see module docstring).
        max_new_tokens: tokens to generate beyond the prompt.
        num_draft: proposals per verification round. Each round is ONE
            fused dispatch (a num_draft-step draft scan + one target
            forward over num_draft+1 tokens + accept math + cache
            fix-ups) and commits between 1 and num_draft+1 tokens.
        eos_token: optional stop token; the tail is filled with it.
        rng: PRNGKey; required when temperature > 0.
        temperature: 0 = greedy verification (the default, original
            behavior); > 0 = stochastic accept/reject targeting the
            temperature-scaled distribution.
        top_k / top_p: sampling warpers, exactly `generate()`'s
            semantics; applied to BOTH the draft's proposal
            distribution and the target's verification distribution
            (temperature > 0 only — greedy ignores them, as argmax is
            warp-invariant).
        return_stats: when True, returns (tokens, stats) where stats
            has `rounds`, `proposed`, `accepted_drafts`, and
            `acceptance_rate` (accepted_drafts / proposed).

    Returns:
        [1, S + max_new_tokens] int32 — with temperature=0, identical
        to `generate(model, params, prompt, max_new_tokens,
        temperature=0.0)`. With return_stats, a (tokens, dict) pair.
    """
    batch, prompt_len = prompt.shape
    if batch != 1:
        raise SpeculativeBatchError(
            "generate_speculative is single-stream (batch 1); got "
            "batch={}. Use generate() for batched decoding, or the "
            "serving engine's per-slot speculation for concurrent "
            "streams.".format(batch))
    if num_draft < 1:
        raise ValueError("num_draft must be >= 1; got {}.".format(
            num_draft))
    if max_new_tokens < 0:
        raise ValueError("max_new_tokens must be >= 0; got {}.".format(
            max_new_tokens))
    stochastic = bool(temperature)
    if stochastic and rng is None:
        raise ValueError("Sampling (temperature > 0) needs `rng`.")
    if top_k is not None and not 1 <= top_k <= model.vocab_size:
        raise ValueError(
            "top_k must be in [1, vocab_size={}]; got {}.".format(
                model.vocab_size, top_k))
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(
            "top_p must be in (0, 1]; got {}.".format(top_p))
    stats = {"rounds": 0, "proposed": 0, "accepted_drafts": 0,
             "acceptance_rate": 0.0}

    def finish(tokens):
        if stats["proposed"]:
            stats["acceptance_rate"] = (
                stats["accepted_drafts"] / stats["proposed"])
        return (tokens, stats) if return_stats else tokens

    if max_new_tokens == 0:
        return finish(prompt)
    for m, name in ((model, "model"), (draft_model, "draft_model")):
        if m.attention_impl in SEQUENCE_PARALLEL_IMPLS:
            raise SpeculativeShardingError(
                "generate_speculative decodes on a single mesh shard; "
                "{} uses a sequence-parallel attention_impl.".format(
                    name))
    total = prompt_len + max_new_tokens
    for m, name in ((model, "model"), (draft_model, "draft_model")):
        # Final rounds clamp their draft count to the remaining token
        # budget, so the caches never need slack past `total` — the
        # same bound generate() has.
        if total > m.max_seq_len:
            raise ValueError(
                "prompt ({}) + max_new_tokens ({}) exceeds {}'s "
                "max_seq_len {}.".format(prompt_len, max_new_tokens,
                                         name, m.max_seq_len))

    target = model.clone(decode=True, dropout_rate=0.0)
    draft = draft_model.clone(decode=True, dropout_rate=0.0)
    target_chunk = _chunk_fn(target)
    draft_chunk = _chunk_fn(draft)
    if stochastic:
        warp_key = (float(temperature),
                    None if top_k is None else int(top_k),
                    None if top_p is None else float(top_p))
    t_cache = empty_cache(target, 1)
    d_cache = empty_cache(draft, 1)

    from cloud_tpu.models.decoding import (decode_latency_finish,
                                           decode_latency_start)

    latency = decode_latency_start()
    seq = [int(t) for t in np.asarray(prompt)[0]]
    # Invariant between rounds: both caches hold entries for seq[:-1].
    if prompt_len > 1:
        prefix = jnp.asarray([seq[:-1]], jnp.int32)
        t_cache, _ = target_chunk(params, t_cache, prefix)
        d_cache, _ = draft_chunk(draft_params, d_cache, prefix)

    while len(seq) < total:
        # Clamp the final rounds to the remaining budget: with
        # k = remaining, the verification writes len(seq)-1 + (k+1) =
        # `total` cache entries at peak — the same bound generate()
        # has — and a full-acceptance round overshoots the budget by
        # at most one committed token, trimmed by seq[:total] below.
        # At most num_draft distinct k values, so compilations stay
        # bounded (each k compiles its own fused round executable).
        k = min(num_draft, total - len(seq))

        # One FUSED dispatch per round (draft scan + verify + accept
        # + cache fix-ups), one [k+1]-token fetch. base_len rides as a
        # device scalar so round executables are shared across rounds.
        last = jnp.asarray([[seq[-1]]], jnp.int32)
        base = jnp.asarray(len(seq), jnp.int32)
        if stochastic:
            rng, round_rng = jax.random.split(rng)
            round_step = _stochastic_round_fn(target, draft, k,
                                              *warp_key)
            t_cache, d_cache, committed_dev, n_acc = round_step(
                params, draft_params, t_cache, d_cache, last, base,
                round_rng)
        else:
            round_step = _greedy_round_fn(target, draft, k)
            t_cache, d_cache, committed_dev, n_acc = round_step(
                params, draft_params, t_cache, d_cache, last, base)
        committed_h, accepted = jax.device_get((committed_dev, n_acc))
        accepted = int(accepted)
        committed = [int(t) for t in committed_h[:accepted + 1]]

        stats["rounds"] += 1
        stats["proposed"] += k
        stats["accepted_drafts"] += accepted
        observe_accept_rate(accepted, k)

        seq.extend(committed)
        if eos_token is not None and eos_token in committed:
            seq = seq[:len(seq) - len(committed)
                      + committed.index(eos_token) + 1]
            break

    seq = seq[:total]
    # The per-round device_get above already retired every dispatch;
    # n_tokens is what was actually generated (EOS may cut the budget).
    decode_latency_finish(latency, len(seq) - prompt_len)
    if eos_token is not None and len(seq) < total:
        seq = seq + [eos_token] * (total - len(seq))
    return finish(jnp.asarray([seq], jnp.int32))


__all__ = ["SpeculativeBatchError", "SpeculativeShardingError",
           "generate_speculative", "greedy_accept",
           "observe_accept_rate"]
