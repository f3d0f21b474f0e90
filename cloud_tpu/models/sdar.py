"""SDAR-family LM: a Qwen3-MoE decoder that generates by diffusion over
blocks (JetLM SDAR, `model_type: sdar_moe`).

The block is `LlamaLM`'s (RMSNorm, rotate-half RoPE, grouped-query
attention with a per-head RMSNorm on q and k, softmax-routed top-k
SwiGLU experts in every layer, no shared expert, an untied head). What
differs is the mask and the generation:

- **Block-causal attention.** The sequence is cut into blocks of
  `block_length` B: the token at i attends keys j with
  j // B <= i // B, causal between blocks and bidirectional inside
  one (`attn_block`; `ops.attention`'s `block=` on the flash path).
- **Generation by denoising a block at a time.** A block opens as B
  rows, clean tokens where it holds some of the prompt and
  `mask_token` elsewhere. Each denoising step runs the block's B rows
  over the cache of the earlier blocks; at every masked row the
  candidate is the argmax of that row's own logits and its confidence
  the softmax probability of it; the step commits the `transfer_count`
  most confident masked rows (SDAR's static low-confidence remasking,
  `get_num_transfer_tokens`). A block with no mask left is run once
  more, clean: its keys and values become the block's final rows in
  the cache, and the next block opens.

So a step yields 1 to B tokens a sequence, out of order inside the
block, and the cache rows of the open block are rewritten at every
step. `serving/engine.py` serves the class through a block tick
(`DecodeEngine.block`); `generate` below is the same arithmetic for one
sequence, the oracle the engine is held to bit for bit.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax.core import unfreeze

from cloud_tpu.models.decoding import paged_slot_advance
from cloud_tpu.models.llama import LlamaLM


class SdarLM(LlamaLM):
    """`LlamaLM` with SDAR's block-causal mask, its mask token and the
    family's defaults (q/k norm, rotate-half RoPE, softmax routing over
    every layer, drop-free). `block_length` is B; `mask_token` the id a
    masked row carries."""

    block_length: int = 4
    mask_token: int = 151669
    qk_norm: bool = True
    rope_style: str = "rotate_half"
    moe_router: str = "softmax"
    moe_capacity_factor: Optional[float] = None

    def __post_init__(self):
        if self.block_length < 1:
            raise ValueError("block_length must be >= 1; got {}.".format(
                self.block_length))
        if not 0 <= self.mask_token < self.vocab_size:
            raise ValueError(
                "mask_token {} is outside the vocabulary of {}.".format(
                    self.mask_token, self.vocab_size))
        super().__post_init__()

    def attn_block(self):
        return self.block_length


def transfer_count(masks0, steps, step):
    """SDAR's `get_num_transfer_tokens`: of `masks0` masked rows a block
    opened with, denoised in `steps` steps, how many step `step` (from
    0) commits — masks0 // steps, plus one in the first masks0 % steps
    steps. Arrays or ints."""
    return masks0 // steps + (step < masks0 % steps)


def confidence(logits):
    """(candidate [..., B], its confidence [..., B]) of logits
    [..., B, V]: each row's argmax and the softmax probability of it,
    in float32."""
    lf = logits.astype(jnp.float32)
    best = jnp.max(lf, axis=-1)
    cand = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    conf = jnp.exp(best - jax.nn.logsumexp(lf, axis=-1))
    return cand, conf


def select(conf, masked, count):
    """Rows [..., B] a step commits: the `count` [...] most confident
    masked rows (a tie goes to the lower row)."""
    b = conf.shape[-1]
    score = jnp.where(masked, conf, -jnp.inf)
    rows = jnp.arange(b)
    ahead = ((score[..., None, :] > score[..., :, None])
             | ((score[..., None, :] == score[..., :, None])
                & (rows[None, :] < rows[:, None])))
    rank = jnp.sum(ahead, axis=-1)
    return masked & (rank < count[..., None])


def denoise_step(logits, tokens, masked, count):
    """One denoising step of open blocks: logits [S, B, V] of the
    blocks' rows, their tokens and masked rows [S, B], rows to commit
    [S]. Returns (tokens, masked, committed rows)."""
    cand, conf = confidence(logits)
    commit = select(conf, masked, count)
    return (jnp.where(commit, cand, tokens), masked & ~commit, commit)


def open_block(prompt, block_length, mask_token, end=None):
    """The first block a request opens: the prompt's last partial block
    (clean) with mask rows after it, and the position of its first row
    (the prompt's whole blocks, which the prefill writes). Rows at or
    past `end` (where the request's new tokens end) carry the mask
    token but are not masked rows: nothing commits them (`masked_rows`)."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    start = len(prompt) // block_length * block_length
    tokens = np.full((block_length,), mask_token, np.int32)
    tail = prompt[start:]
    tokens[:len(tail)] = tail
    masked = np.arange(block_length) >= len(tail)
    if end is not None:
        masked &= start + np.arange(block_length) < end
    return tokens, masked, start


def masked_rows(start, end, block_length):
    """The masked rows of a block opened at `start` [...]: those before
    `end` [...], where the new tokens end. The last block's rows past
    it stay mask tokens and are never committed, so the block closes
    when the rows asked for are (SDAR denoises them too, and cuts them
    away)."""
    return start[..., None] + jnp.arange(block_length) < end[..., None]


def generate(model, params, prompt, max_new_tokens, denoising_steps=None,
             page_size=None):
    """Greedy block-diffusion generation of one sequence (SDAR's
    `block_diffusion_generate`, static low-confidence remasking): the
    prompt's whole blocks prefilled block-causally, then each block
    from the one that holds the prompt's end denoised in
    `denoising_steps` steps (default B) and closed with a clean pass,
    until position `len(prompt) + max_new_tokens - 1` is in a closed
    block (the last block's rows past it stay masked: `masked_rows`).
    The cache is a one-slot paged pool, the form the serving tick
    reads.

    Returns (tokens [len(prompt) + max_new_tokens], commit steps
    [max_new_tokens]: the step of its block at which each new token
    was committed)."""
    from cloud_tpu.models.decoding import empty_cache

    blk = model.block_length
    steps = int(denoising_steps or blk)
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    end = len(prompt) + int(max_new_tokens)
    if len(prompt) < blk or not 1 <= steps <= blk:
        raise ValueError(
            "a prompt of at least block_length ({}) tokens and 1 <= "
            "denoising_steps <= block_length; got {} and {}.".format(
                blk, len(prompt), steps))
    page = int(page_size or blk)
    if model.max_seq_len % page or -(-end // blk) * blk > model.max_seq_len:
        raise ValueError(
            "max_seq_len {} must be a multiple of the page ({}) and hold "
            "the last block.".format(model.max_seq_len, page))
    ppn = model.max_seq_len // page
    paged = model.clone(decode=True, dropout_rate=0.0, kv_page_size=page,
                        kv_num_pages=ppn + 1)
    cache = _set_pages(empty_cache(paged, 1),
                       jnp.arange(1, ppn + 1, dtype=jnp.int32)[None])
    apply = _apply_fn(paged)
    tokens, masked, start = open_block(prompt, blk, model.mask_token, end)
    if start:
        _, cache = apply(params, cache, jnp.asarray(prompt[None, :start]))
        cache = paged_slot_advance(cache, jnp.asarray([start], jnp.int32))
    out = {}
    step_of = {}
    step = 0
    masks0 = int(masked.sum())
    x, m = jnp.asarray(tokens[None]), jnp.asarray(masked[None])
    while start < end:
        logits, cache = apply(params, cache, x)
        if not bool(m.any()):
            # Closed: the clean pass wrote the block's final rows.
            cache = paged_slot_advance(cache,
                                       jnp.asarray([blk], jnp.int32))
            start += blk
            tokens = np.full((blk,), model.mask_token, np.int32)
            x = jnp.asarray(tokens[None])
            m = masked_rows(jnp.asarray([start]), jnp.asarray([end]), blk)
            step, masks0 = 0, int(m.sum())
            continue
        count = transfer_count(masks0, steps, step)
        x, m, commit = denoise_step(logits, x, m,
                                    jnp.asarray([count], jnp.int32))
        for j in np.flatnonzero(np.asarray(commit[0])):
            out[start + int(j)] = int(x[0, j])
            step_of[start + int(j)] = step
        step += 1
    new = range(len(prompt), end)
    return (np.concatenate([prompt, np.asarray([out[p] for p in new],
                                               np.int32)]),
            np.asarray([step_of[p] for p in new], np.int32))


@functools.lru_cache(maxsize=16)
def _apply_fn(paged):
    """One jitted forward of the one-slot paged clone: logits and the
    cache it leaves."""
    @jax.jit
    def apply(params, cache, tokens):
        logits, vars_ = paged.apply({"params": params, "cache": cache},
                                    tokens, mutable=["cache"])
        return logits, unfreeze(vars_["cache"])
    return apply


def _set_pages(cache, table):
    """`cache` with every layer's page table `table`."""
    def walk(tree):
        if "key_pages" in tree:
            return dict(tree, page_table=table)
        return {k: walk(v) for k, v in tree.items()}
    return walk(unfreeze(cache))


__all__ = ["SdarLM", "confidence", "denoise_step", "generate",
           "masked_rows", "open_block", "select", "transfer_count"]
