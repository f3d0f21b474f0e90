"""Operations and bytes of one chip's share of the EXAONE-MoE decoder, from
the configuration's numbers (cellbench/configs/k-exaone-236b-a23b-ep8.json).

Model FLOPs as the algorithm needs them (2 per multiply-add, nothing
recomputed; norms, RoPE, the activation, softmax and the router's top-k left
out). Bytes are the least a decode tick must move: every matrix it uses once
at its stored type, and the keys and values it attends to at the page type.
Two things make a tick here unlike a dense decoder's. A routed expert's three
matrices are read only if a token of the tick chose it, so the experts count
by the program's counter of experts touched, never as all that are held, and
their FLOPs by the (token, expert) pairs computed. A window layer attends to
at most `sliding_window` tokens a slot, a full layer to the whole context.
"""

from cellbench.counts import least_seconds

BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def attention_shape(cfg):
    """(query heads, key/value heads, head size)."""
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def layers(cfg):
    return cfg["num_hidden_layers"]


def layer_kinds(cfg):
    """(window layers, full layers, dense-MLP layers, expert layers) of the
    layers kept."""
    n = layers(cfg)
    window = sum(t == "sliding_attention" for t in cfg["layer_types"][:n])
    sparse = sum(t == "sparse" for t in cfg["mlp_layer_types"][:n])
    return window, n - window, n - sparse, sparse


def attention_params(cfg):
    heads, kv_heads, depth = attention_shape(cfg)
    return cfg["hidden_size"] * depth * (2 * heads + 2 * kv_heads)


def expert_params(cfg):
    """One routed expert (the shared expert is `num_shared_experts` as wide)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def always_params(cfg):
    """Matrix parameters every token of a tick passes through: attention of
    every layer, the dense layers' MLP, the shared experts, the routers, the
    head over the vocabulary slice."""
    _, _, dense, sparse = layer_kinds(cfg)
    d = cfg["hidden_size"]
    return (layers(cfg) * attention_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + sparse * (cfg["num_shared_experts"] * expert_params(cfg)
                        + d * cfg["experts_routed"])
            + d * cfg["vocab_size"])


def kv_row_bytes(cfg):
    """Bytes of one token's keys and values in one layer."""
    _, kv_heads, depth = attention_shape(cfg)
    return 2 * kv_heads * depth * BYTES[cfg["assumed"]["kv_page_dtype"]]


def attended_tokens(cfg, full_tokens, window_tokens):
    """Token rows a tick's attention reads, over the layers: `full_tokens`
    (the slots' live tokens) a full layer, `window_tokens` (each slot's
    min(live, window)) a window layer."""
    window, full, _, _ = layer_kinds(cfg)
    return full * full_tokens + window * window_tokens


def tick_flops(cfg, active, full_tokens, window_tokens, pairs_held):
    heads, _, depth = attention_shape(cfg)
    attention = 4 * heads * depth * attended_tokens(cfg, full_tokens,
                                                    window_tokens)
    return (2 * always_params(cfg) * active
            + 2 * expert_params(cfg) * pairs_held + attention)


def tick_bytes(cfg, active, full_tokens, window_tokens, experts_touched):
    item = BYTES[cfg["assumed"]["param_dtype"]]
    router_extra = (4 - item) * layer_kinds(cfg)[3] * cfg["hidden_size"] * cfg[
        "experts_routed"]                       # the router is float32
    weights = (always_params(cfg) + expert_params(cfg) * experts_touched) * item
    embedding = active * cfg["hidden_size"] * item
    return (weights + router_extra + embedding
            + kv_row_bytes(cfg) * attended_tokens(cfg, full_tokens, window_tokens))


def tick_least_seconds(cfg, active, full_tokens, window_tokens, pairs_held,
                       experts_touched, peaks):
    """One decode tick: `active` slots each emit a token; `pairs_held` (token,
    expert) pairs are computed in `experts_touched` routed experts, both
    summed over the expert layers."""
    return least_seconds(
        tick_flops(cfg, active, full_tokens, window_tokens, pairs_held),
        tick_bytes(cfg, active, full_tokens, window_tokens, experts_touched),
        peaks)


def experts_least_seconds(cfg, pairs_held, experts_touched, peaks):
    """The routed experts of a tick alone: each touched expert's matrices
    once, each pair's three products, the pairs' rows in and out."""
    item = BYTES[cfg["assumed"]["param_dtype"]]
    rows = 2 * pairs_held * cfg["hidden_size"] * item
    return least_seconds(2 * expert_params(cfg) * pairs_held,
                         expert_params(cfg) * experts_touched * item + rows,
                         peaks)


def paged_least_seconds(cfg, full_tokens, window_tokens, peaks):
    """The paged reads of a tick: key and value rows H_kv x D wide, each
    feeding every query head of its group (H x D multiply-adds a row for the
    scores and as many for the output)."""
    heads, _, depth = attention_shape(cfg)
    rows = attended_tokens(cfg, full_tokens, window_tokens)
    return least_seconds(4 * heads * depth * rows, kv_row_bytes(cfg) * rows,
                         peaks)
