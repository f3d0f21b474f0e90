"""Beam search decoding over the slot-addressed KV caches.

Batched beam search (`B` prompts × `beam_width` hypotheses) for any
decode-capable model (`TransformerLM`, `LlamaLM`, `DeepseekLM`): the
B×W hypothesis grid rides the BATCH dimension of one decode cache
(row-major: prompt b, beam w → row b*W + w), so each step is a single
[B*W, 1] forward, and beam reordering is a gather on the leading axis
of every cache leaf (the caches are batch-first throughout —
models/decoding.py). Variable-length prompt batches use the same
left-padded `prompt_mask` contract as `generate()`: each row's beams
expand exactly as that prompt's solo beam search would.

The WHOLE generation loop is device-resident: one `lax.scan` carries
(cache, scores, finished, token buffer) through forward → per-prompt
`jax.lax.top_k` ranking → cache reorder → token bookkeeping, so
decoding costs one dispatch and ONE device→host fetch total — no
per-token host sync (each a blocking device→host round trip) and no [B*W, V] log-prob transfer (a 128k-vocab imported
checkpoint would otherwise pay an O(W·V log W·V) host sort every
token).

Scoring is accumulated log-probability with optional length
normalization (score / length**length_penalty, the standard GNMT-style
alpha). Scores accumulate in float32 ON DEVICE (TPUs have no f64;
keeping the ranking on device is the point) — two hypotheses whose
true summed log-probs differ by less than f32 resolution at the
accumulated magnitude can rank either way, the same tolerance every
TPU decode stack accepts. Finished hypotheses (eos) are frozen: their row keeps
re-feeding eos with score held fixed, so shapes never change.

`beam_width=1` reduces exactly to greedy decoding (tested), a padded
batch row matches its solo beam search (tested), and with a beam wide
enough to cover every alive prefix the search is exhaustive (tested
against brute force on a tiny vocabulary).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from cloud_tpu.models.decoding import (best_effort_donation,
                                       empty_cache,
                                       validate_prompt_mask)
from cloud_tpu.parallel import SEQUENCE_PARALLEL_IMPLS
from cloud_tpu.parallel import runtime


def _step_logp(decoder, params, cache, tokens, mask=None):
    """One decode forward → (new_cache, last-position log-probs
    [rows, V]) — the single recipe shared by the prefill executable
    and the scan body, so the two cannot drift."""
    logits, vars_ = decoder.apply(
        {"params": params, "cache": cache}, tokens, mask,
        mutable=["cache"])
    logp = jax.nn.log_softmax(
        logits[:, -1].astype(jnp.float32), axis=-1)
    return vars_["cache"], logp


@functools.lru_cache(maxsize=64)
def _logprob_fn(decoder):
    """Jitted chunk feed returning (new_cache, log-probs [rows, V])."""

    # donate_argnums=1: prefill consumes the fresh empty cache; no
    # caller reuses it, so the KV buffers update in place.
    @functools.partial(runtime.instrumented_jit, donate_argnums=1)
    def step(params, cache, tokens, mask=None):
        return _step_logp(decoder, params, cache, tokens, mask)

    return best_effort_donation(step)


@functools.lru_cache(maxsize=64)
def _beam_scan_fn(decoder, width, eos_token):
    """Jitted device-resident beam loop: one `lax.scan` carrying
    (cache, scores, finished, token buffer, feed) — forward, ranking
    (`lax.top_k`), cache reorder, and token bookkeeping all stay on
    device, so the whole generation costs ONE dispatch and ONE
    device→host fetch regardless of length (a per-token host sync
    is a blocking round trip each). With eos set, an
    all-frozen step short-circuits through `lax.cond` (the
    device-resident analogue of a host-loop early exit). Like
    generate()'s decode_steps, the scan length is baked into the
    executable: distinct max_new_tokens values compile their own
    specializations, as they must under static shapes."""

    # Donate the cache and token buffer: generate_beam passes both in
    # exactly once, so the scan's carries reuse their storage.
    @functools.partial(runtime.instrumented_jit, donate_argnums=(1, 4))
    def run(params, cache, scores, finished, buf, feed, step_ids):
        batch = scores.shape[0]

        def expand(carry, t):
            cache, scores, finished, buf, feed = carry
            cache, logp = _step_logp(decoder, params, cache, feed)
            vocab = logp.shape[-1]
            cand = scores[:, :, None] + logp.reshape(batch, width,
                                                     vocab)
            if eos_token is not None:
                # A frozen row contributes exactly one continuation
                # (eos, score unchanged) so it survives ranking
                # without forking. Invariant exception: with
                # width > vocab the pool of finite candidates
                # (≤ width·vocab minus the frozen rows' -inf entries)
                # can run short of width, so top_k backfills with -inf
                # candidates and a frozen row may re-enter the beam as
                # -inf duplicates — degenerate hypotheses a caller
                # ranking by score discards anyway, so no behavioral
                # guard; beams wider than the vocabulary are already
                # meaningless.
                frozen = jnp.full((vocab,), -jnp.inf,
                                  jnp.float32).at[eos_token].set(0.0)
                cand = jnp.where(
                    finished[:, :, None],
                    scores[:, :, None] + frozen[None, None, :], cand)
            scores, flat = jax.lax.top_k(
                cand.reshape(batch, width * vocab), width)
            rows = flat // vocab
            toks = (flat % vocab).astype(jnp.int32)
            finished = jnp.take_along_axis(finished, rows, axis=1)
            if eos_token is not None:
                finished = finished | (toks == eos_token)
            order = (jnp.arange(batch)[:, None] * width
                     + rows).reshape(-1)
            cache = _reorder(cache, order)
            buf = jnp.take_along_axis(buf, rows[:, :, None], axis=1)
            buf = buf.at[:, :, t].set(toks)
            return (cache, scores, finished, buf,
                    toks.reshape(-1, 1))

        def body(carry, t):
            if eos_token is None:
                return expand(carry, t), None
            # Every hypothesis of every prompt frozen: keep the frozen
            # state (buf column t must still be eos for the tail fill)
            # instead of running the forward — the device-resident
            # analogue of the old host loop's early exit.
            def frozen_step(carry, t=t):
                cache, scores, finished, buf, feed = carry
                buf = buf.at[:, :, t].set(eos_token)
                return (cache, scores, finished, buf, feed)

            carry = jax.lax.cond(
                jnp.all(carry[2]),
                frozen_step,
                lambda c, t=t: expand(c, t),
                carry)
            return carry, None

        (cache, scores, finished, buf, feed), _ = jax.lax.scan(
            body, (cache, scores, finished, buf, feed), step_ids)
        return scores, finished, buf

    return best_effort_donation(run)


def _reorder(cache, order):
    """Gather hypothesis rows: every batch-first cache leaf follows the
    surviving hypotheses; scalars (the shared write pointer) pass
    through."""
    rows = order.shape[0]

    def pick(leaf):
        if leaf.ndim and leaf.shape[0] == rows:
            return leaf[order]
        return leaf

    return jax.tree_util.tree_map(pick, cache)


def generate_beam(model, params, prompt, max_new_tokens, beam_width=4,
                  length_penalty=0.0, eos_token=None, prompt_mask=None):
    """Beam-search decode; returns the best hypothesis per prompt.

    Args:
        model / params: a decode-capable model (same contract as
            `generate`).
        prompt: [B, S] int32 — every row runs its own `beam_width`-wide
            search in one shared forward/ranking pipeline.
        max_new_tokens: tokens to generate beyond the prompt.
        beam_width: hypotheses kept per prompt per step.
        length_penalty: 0.0 = raw summed log-prob; alpha > 0 divides
            each hypothesis' score by (generated_length ** alpha) when
            ranking FINAL hypotheses. In-loop pruning compares RAW
            scores, so a frozen (shorter) eos hypothesis competes at
            its raw score against longer alive ones — the standard
            beam bias: a hypothesis that would win only after length
            normalization can be pruned mid-loop.
        eos_token: optional stop token; a hypothesis sampling it is
            frozen and its tail is filled with eos_token.
        prompt_mask: optional [B, S] bool marking REAL prompt tokens,
            LEFT-padded (`generate()`'s variable-length contract):
            each row's search behaves exactly as its unpadded solo
            search would.

    Returns:
        ([B, S + max_new_tokens] int32 best sequences,
         score) — `score` is a float for B == 1 (back-compat) and a
         [B] float numpy array otherwise.
    """
    batch, prompt_len = prompt.shape
    if beam_width < 1:
        raise ValueError("beam_width must be >= 1; got {}.".format(
            beam_width))
    if max_new_tokens < 0:
        raise ValueError("max_new_tokens must be >= 0; got {}.".format(
            max_new_tokens))
    if max_new_tokens == 0:
        return prompt, (0.0 if batch == 1 else np.zeros(batch))
    if model.attention_impl in SEQUENCE_PARALLEL_IMPLS:
        raise NotImplementedError(
            "generate_beam decodes on a single mesh shard; use a "
            "non-sequence-parallel attention_impl for inference.")
    total = prompt_len + max_new_tokens
    if total > model.max_seq_len:
        raise ValueError(
            "prompt ({}) + max_new_tokens ({}) exceeds max_seq_len {}."
            .format(prompt_len, max_new_tokens, model.max_seq_len))
    if prompt_mask is not None:
        validate_prompt_mask(prompt_mask, batch, prompt_len,
                             "beam ranking")

    width = int(beam_width)
    decoder = model.clone(decode=True, dropout_rate=0.0)
    step = _logprob_fn(decoder)

    # Prefill ONCE at batch B, then tile each prompt's cache rows to
    # the beam width (jnp.repeat keeps the b*W + w row-major layout):
    # the W copies would be byte-identical, so B*W prompt forwards
    # would buy nothing. Per-example bookkeeping (slot_valid,
    # token_count) repeats with its prompt; the scalar write pointer
    # passes through exactly as it passes through _reorder's gather.
    from cloud_tpu.models.decoding import (decode_latency_finish,
                                           decode_latency_start)

    latency = decode_latency_start()
    mask_arg = (None if prompt_mask is None
                else jnp.asarray(prompt_mask, bool))
    cache_b, logp = step(params, empty_cache(decoder, batch), prompt,
                         mask_arg)
    cache = jax.tree_util.tree_map(
        lambda leaf: (jnp.repeat(leaf, width, axis=0)
                      if leaf.ndim and leaf.shape[0] == batch else leaf),
        cache_b)

    vocab = logp.shape[-1]
    # First expansion: top width tokens per prompt, all in eager
    # device ops (no host fetch — the shapes are static, so the
    # width > vocab branch is plain Python). width > vocab (the
    # exhaustive-search configuration): only vocab distinct first
    # expansions exist; surplus rows duplicate the best one at -inf so
    # they can never win a ranking.
    s0, t0 = jax.lax.top_k(logp, min(width, vocab))
    if width > vocab:
        pad = width - vocab
        t0 = jnp.concatenate(
            [t0, jnp.repeat(t0[:, :1], pad, axis=1)], axis=1)
        s0 = jnp.concatenate(
            [s0, jnp.full((batch, pad), -jnp.inf, s0.dtype)], axis=1)
    t0 = t0.astype(jnp.int32)
    scores = s0.astype(jnp.float32)                          # [B, W]
    finished = (jnp.zeros(t0.shape, bool) if eos_token is None
                else t0 == eos_token)
    feed = t0.reshape(-1, 1)                                 # [B*W, 1]
    buf = jnp.zeros((batch, width, max_new_tokens), jnp.int32)
    buf = buf.at[:, :, 0].set(t0)

    if max_new_tokens > 1:
        run = _beam_scan_fn(decoder, width, None if eos_token is None
                            else int(eos_token))
        scores, finished, buf = run(params, cache, scores, finished,
                                    buf, feed,
                                    jnp.arange(1, max_new_tokens))
    # The ONLY device→host fetch of the whole generation. The fetch
    # retires every decode dispatch, so the latency handle closes here
    # (result=None: this device_get IS the block).
    scores_h, buf_h = jax.device_get((scores, buf))
    decode_latency_finish(latency, max_new_tokens)
    scores_h = np.asarray(scores_h, np.float64)                # [B, W]
    seqs = [[buf_h[b, w].tolist() for w in range(width)]
            for b in range(batch)]

    def final_score(b, w):
        if length_penalty:
            n = len(seqs[b][w])
            if eos_token is not None and eos_token in seqs[b][w]:
                n = seqs[b][w].index(eos_token) + 1
            return scores_h[b, w] / (n ** length_penalty)
        return scores_h[b, w]

    prompt_h = np.asarray(prompt)
    full_rows, best_scores = [], []
    for b in range(batch):
        best = max(range(width), key=lambda w: final_score(b, w))
        out = seqs[b][best]
        if eos_token is not None and eos_token in out:
            cut = out.index(eos_token) + 1
            out = out[:cut] + [eos_token] * (len(out) - cut)
        # buf always holds max_new_tokens entries (a frozen hypothesis
        # keeps re-feeding eos), so rows are full-length by
        # construction.
        row = [int(t) for t in prompt_h[b]] + out
        full_rows.append(row)
        best_scores.append(float(final_score(b, best)))
    tokens = jnp.asarray(full_rows, jnp.int32)
    if batch == 1:
        return tokens, best_scores[0]
    return tokens, np.asarray(best_scores)


__all__ = ["generate_beam"]
