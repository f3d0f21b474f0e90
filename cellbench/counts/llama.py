"""Operations of the Llama/Qwen2 decoder, from the configuration's numbers.

Model FLOPs as the algorithm needs them: 2 per multiply-add, backward twice
the forward, causal attention counted as the half it is, nothing recomputed,
norms, RoPE, softmax and the optimizer left out (under 1 % at these widths).
"""


def matmul_params(cfg):
    d, heads, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg[
        "num_key_value_heads"]
    depth = cfg.get("head_dim") or d // heads
    layer = (d * heads * depth + 2 * d * kv * depth + heads * depth * d
             + 3 * d * cfg["intermediate_size"])
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def train_flops_per_token(cfg, seq):
    heads = cfg["num_attention_heads"]
    depth = cfg.get("head_dim") or cfg["hidden_size"] // heads
    # QK^T and PV, causal: 2 * (seq / 2) * 2 multiply-adds' FLOPs a token.
    attention_fwd = 2 * seq * heads * depth * cfg["num_hidden_layers"]
    return 3 * (2 * matmul_params(cfg) + attention_fwd)


def attention_shape(cfg):
    """(query heads, key/value heads, head size)."""
    heads = cfg["num_attention_heads"]
    return (heads, cfg["num_key_value_heads"],
            cfg.get("head_dim") or cfg["hidden_size"] // heads)


def layers(cfg):
    return cfg["num_hidden_layers"]
