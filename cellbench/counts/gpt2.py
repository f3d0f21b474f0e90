"""Operations and bytes of the GPT-2 decoder, from the configuration's numbers.

Model FLOPs as the algorithm needs them (2 per multiply-add, nothing
recomputed; norms, GELU and softmax left out). Bytes are the least a decode
tick must move: every weight matrix once at its stored type, and the keys and
values of the live tokens at the page type.
"""

from cellbench.counts import least_seconds

BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def matmul_params(cfg):
    d = cfg["n_embd"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * cfg["n_inner"]) + d * cfg["vocab_size"]


def kv_bytes_per_token(cfg):
    return 2 * cfg["n_embd"] * BYTES[cfg["assumed"]["kv_page_dtype"]] * cfg["n_layer"]


def tick_flops(cfg, active, live_tokens):
    """One decode tick: `active` sequences each emit a token over
    `live_tokens` cached tokens in all."""
    attention = 4 * live_tokens * cfg["n_embd"] * cfg["n_layer"]
    return 2 * matmul_params(cfg) * active + attention


def tick_bytes(cfg, live_tokens):
    weights = matmul_params(cfg) * BYTES[cfg["assumed"]["param_dtype"]]
    return weights + kv_bytes_per_token(cfg) * live_tokens


def tick_least_seconds(cfg, active, live_tokens, peaks):
    return least_seconds(tick_flops(cfg, active, live_tokens),
                         tick_bytes(cfg, live_tokens), peaks)


def attention_shape(cfg):
    """(query heads, key/value heads, head size)."""
    return cfg["n_head"], cfg["n_head"], cfg["n_embd"] // cfg["n_head"]


def layers(cfg):
    return cfg["n_layer"]
