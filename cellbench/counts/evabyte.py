"""Operations and bytes of the EvaByte decoder, from the configuration's
numbers.

Model FLOPs as the algorithm needs them (2 per multiply-add, nothing
recomputed; norms, SiLU, the softmaxes and the chunk summaries left out). Bytes
are the least a decode tick must move: every weight matrix once at its stored
type (the head at all `num_pred_heads x vocab_size` columns, as the tick
computes it), and one key row and one value row, `hidden_size` wide at the page
type, for every row a query attends: the exact rows of its own window and the
summary rows behind it (`rows_read`: the program's counter `eva_rows_read`,
summed over the slots, layers counted once).
"""

from cellbench.counts import least_seconds

BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def matmul_params(cfg):
    d = cfg["hidden_size"]
    per_layer = 4 * d * d + 3 * d * cfg["intermediate_size"]
    return (cfg["num_hidden_layers"] * per_layer
            + d * cfg["num_pred_heads"] * cfg["vocab_size"])


def row_bytes(cfg):
    """A key row and a value row of one layer."""
    return 2 * cfg["hidden_size"] * BYTES[cfg["assumed"]["kv_page_dtype"]]


def tick_flops(cfg, active, rows_read):
    """One decode tick: `active` sequences each emit a byte, attending
    `rows_read` rows in all (a layer)."""
    attention = 4 * rows_read * cfg["hidden_size"] * cfg["num_hidden_layers"]
    return 2 * matmul_params(cfg) * active + attention


def tick_bytes(cfg, rows_read):
    weights = matmul_params(cfg) * BYTES[cfg["assumed"]["param_dtype"]]
    return weights + rows_read * row_bytes(cfg) * cfg["num_hidden_layers"]


def tick_least_seconds(cfg, active, rows_read, peaks):
    return least_seconds(tick_flops(cfg, active, rows_read),
                         tick_bytes(cfg, rows_read), peaks)


def rows_least_seconds(cfg, rows_read, peaks):
    """The read alone, one layer of one tick."""
    return least_seconds(4 * rows_read * cfg["hidden_size"],
                         rows_read * row_bytes(cfg), peaks)


def attention_shape(cfg):
    """(query heads, key/value heads, head size)."""
    heads = cfg["num_attention_heads"]
    return heads, cfg["num_key_value_heads"], cfg["hidden_size"] // heads


def layers(cfg):
    return cfg["num_hidden_layers"]
