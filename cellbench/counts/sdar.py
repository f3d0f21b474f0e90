"""Operations and bytes of one chip's stage of the SDAR MoE decoder, from the
configuration's numbers (cellbench/configs/sdar-30b-a3b-chat-pp8.json).

Model FLOPs as the algorithm needs them (2 per multiply-add, nothing
recomputed; norms, RoPE, the activation, softmax, the router's top-k and the
denoising step's choice left out). Bytes are the least a block tick must
move: every matrix it uses once at its stored type, and the keys and values
it attends to at the page type. A block tick runs B query rows a live slot
(`diffusion_rows_computed`), each of which attends to every key of its
slot's blocks up to its own (the slot's live keys, `kv_live_tokens`, which
the scheduler counts as the open block's end); a routed expert's three
matrices are read only if some row of the tick chose it (the program's
counter of experts touched), and its FLOPs are the (row, expert) pairs
computed.
"""

from cellbench.counts import least_seconds

BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def attention_shape(cfg):
    """(query heads, key/value heads, head size)."""
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def layers(cfg):
    return cfg["num_hidden_layers"]


def block_length(cfg):
    return int(cfg["assumed"]["block_length"])


def attention_params(cfg):
    heads, kv_heads, depth = attention_shape(cfg)
    return cfg["hidden_size"] * depth * (2 * heads + 2 * kv_heads)


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def always_params(cfg):
    """Matrix parameters every row of a tick passes through: attention and
    the router of every layer, and the head over the vocabulary."""
    d = cfg["hidden_size"]
    return (layers(cfg) * (attention_params(cfg) + d * cfg["num_experts"])
            + d * cfg["vocab_size"])


def layer_params(cfg):
    """Parameters of one layer, norms included."""
    heads, kv_heads, depth = attention_shape(cfg)
    d = cfg["hidden_size"]
    return (attention_params(cfg) + d * cfg["num_experts"]
            + cfg["num_experts"] * expert_params(cfg) + 2 * d + 2 * depth)


def model_params(cfg, num_layers=None):
    """Parameters of `num_layers` layers (the configuration's by default),
    the embedding, the final norm and the untied head."""
    n = layers(cfg) if num_layers is None else num_layers
    d = cfg["hidden_size"]
    return n * layer_params(cfg) + 2 * d * cfg["vocab_size"] + d


def kv_row_bytes(cfg):
    """Bytes of one token's keys and values in one layer."""
    _, kv_heads, depth = attention_shape(cfg)
    return 2 * kv_heads * depth * BYTES[cfg["assumed"]["kv_page_dtype"]]


def attention_flops(cfg, live_tokens):
    """One layer: B rows a slot, each against the slot's live keys
    (`live_tokens`, summed over the slots), for the scores and the
    weighted sum."""
    heads, _, depth = attention_shape(cfg)
    return 4 * heads * depth * block_length(cfg) * live_tokens


def tick_flops(cfg, rows, live_tokens, pairs_held):
    return (2 * always_params(cfg) * rows
            + 2 * expert_params(cfg) * pairs_held
            + layers(cfg) * attention_flops(cfg, live_tokens))


def tick_bytes(cfg, rows, live_tokens, experts_touched):
    item = BYTES[cfg["assumed"]["param_dtype"]]
    router_extra = (4 - item) * layers(cfg) * cfg["hidden_size"] * cfg[
        "num_experts"]                          # the router is float32
    weights = (always_params(cfg) + expert_params(cfg) * experts_touched) * item
    embedding = rows * cfg["hidden_size"] * item
    return (weights + router_extra + embedding
            + kv_row_bytes(cfg) * layers(cfg) * live_tokens)


def tick_least_seconds(cfg, rows, live_tokens, pairs_held, experts_touched,
                       peaks):
    """One block tick: `rows` query rows (B a live slot), `live_tokens` keys
    a layer over the slots, `pairs_held` (row, expert) pairs computed in
    `experts_touched` experts, both summed over the layers."""
    return least_seconds(tick_flops(cfg, rows, live_tokens, pairs_held),
                         tick_bytes(cfg, rows, live_tokens, experts_touched),
                         peaks)


def experts_least_seconds(cfg, pairs_held, experts_touched, peaks):
    """The routed experts of a tick alone: each touched expert's three
    matrices once, each (row, expert) pair's three products, the pairs'
    rows in and out; both counts summed over the layers."""
    item = BYTES[cfg["assumed"]["param_dtype"]]
    rows = 2 * pairs_held * cfg["hidden_size"] * item
    return least_seconds(2 * expert_params(cfg) * pairs_held,
                         expert_params(cfg) * experts_touched * item + rows,
                         peaks)


def block_decode_least_seconds(cfg, live_tokens, peaks):
    """One layer's paged block read: every live key and value row once,
    each against the B rows of every query head of its group."""
    return least_seconds(attention_flops(cfg, live_tokens),
                         kv_row_bytes(cfg) * live_tokens, peaks)
