"""Operations and bytes of one chip's share of the Nemotron-H hybrid decoder,
from the configuration's numbers
(cellbench/configs/nemotron-3-super-120b-a12b-ep4.json).

Model FLOPs as the algorithm needs them (2 per multiply-add, nothing
recomputed; norms, the convolution, activations, softmax and the router's
top-k left out). Bytes are the least a decode tick must move: every matrix it
uses once at its stored type, the keys and values it attends to at the page
type, and the recurrent state. Three things make a tick here unlike a dense
decoder's. A state-space (Mamba-2) layer reads and writes a slot's whole state
every token, whatever the slot's depth: heads x head_dim x state float32
values, beside the convolution's last inputs. A routed expert's two matrices
(up and down, in the latent space: the experts are not gated) are read only if
a token of the tick chose it, so the experts count by the program's counter of
experts touched, never as all that are held, and their FLOPs by the (token,
expert) pairs computed. Only the `*` layers of the pattern hold keys and
values, and every one of them is a full layer: the signatures keep the
`window_tokens` of `counts/exaone_moe.py`, which the accepted readers pass,
and it counts for nothing here.
"""

from cellbench.counts import least_seconds

BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}
# S <- a S + (dt x) (x) B is two multiplies and an add an element of the
# state, y = S C a multiply and an add.
STATE_FLOPS_PER_ELEMENT = 5


def attention_shape(cfg):
    """(query heads, key/value heads, head size)."""
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def layer_kinds(cfg):
    """(state-space layers, attention layers, expert layers) of the layers
    kept."""
    pattern = cfg["pattern_kept"]
    return pattern.count("M"), pattern.count("*"), pattern.count("E")


def mamba_shape(cfg):
    """(heads, head size, groups, state size, convolution taps)."""
    return (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"], cfg["conv_kernel"])


def mamba_params(cfg):
    """The two projections of a state-space layer."""
    heads, head_dim, groups, state, _ = mamba_shape(cfg)
    inner = heads * head_dim
    return cfg["hidden_size"] * (2 * inner + 2 * groups * state + heads
                                 + inner)


def attention_params(cfg):
    heads, kv_heads, depth = attention_shape(cfg)
    return cfg["hidden_size"] * depth * (2 * heads + 2 * kv_heads)


def expert_params(cfg):
    """One routed expert: up and down, in the latent space."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def expert_layer_params(cfg):
    """What every token passes through in an expert layer, the router aside:
    the two latent projections and the shared expert."""
    d = cfg["hidden_size"]
    return 2 * d * (cfg["moe_latent_size"]
                    + cfg["moe_shared_expert_intermediate_size"])


def always_params(cfg):
    """Matrix parameters every token of a tick passes through: the state-space
    layers' projections, attention, the routers, the latent projections, the
    shared experts, the head over the vocabulary slice."""
    mamba, attention, experts = layer_kinds(cfg)
    d = cfg["hidden_size"]
    return (mamba * mamba_params(cfg) + attention * attention_params(cfg)
            + experts * (expert_layer_params(cfg) + d * cfg["experts_routed"])
            + d * cfg["vocab_size"])


def state_elements(cfg):
    """Values of one slot's recurrent state in one state-space layer."""
    heads, head_dim, _, state, _ = mamba_shape(cfg)
    return heads * head_dim * state


def state_step_bytes(cfg):
    """Bytes one slot's step moves in one state-space layer: the state read
    and written, and the convolution's last inputs read and written."""
    heads, head_dim, groups, state, taps = mamba_shape(cfg)
    assumed = cfg["assumed"]
    conv = (taps - 1) * (heads * head_dim + 2 * groups * state)
    return 2 * (state_elements(cfg) * BYTES[assumed["ssm_state_dtype"]]
                + conv * BYTES[assumed["conv_state_dtype"]])


def kv_row_bytes(cfg):
    """Bytes of one token's keys and values in one layer."""
    _, kv_heads, depth = attention_shape(cfg)
    return 2 * kv_heads * depth * BYTES[cfg["assumed"]["kv_page_dtype"]]


def attended_tokens(cfg, full_tokens, window_tokens=0):
    """Token rows a tick's attention reads, over the layers: `full_tokens`
    (the slots' live tokens) an attention layer; there is no window layer."""
    del window_tokens
    return layer_kinds(cfg)[1] * full_tokens


def tick_flops(cfg, active, full_tokens, window_tokens, pairs_held):
    heads, _, depth = attention_shape(cfg)
    attention = 4 * heads * depth * attended_tokens(cfg, full_tokens)
    state = (STATE_FLOPS_PER_ELEMENT * state_elements(cfg) * active
             * layer_kinds(cfg)[0])
    return (2 * always_params(cfg) * active
            + 2 * expert_params(cfg) * pairs_held + attention + state)


def tick_bytes(cfg, active, full_tokens, window_tokens, experts_touched):
    item = BYTES[cfg["assumed"]["param_dtype"]]
    mamba, _, experts = layer_kinds(cfg)
    router_extra = (4 - item) * experts * cfg["hidden_size"] * cfg[
        "experts_routed"]                       # the router is float32
    weights = (always_params(cfg) + expert_params(cfg) * experts_touched) * item
    embedding = active * cfg["hidden_size"] * item
    return (weights + router_extra + embedding
            + state_step_bytes(cfg) * active * mamba
            + kv_row_bytes(cfg) * attended_tokens(cfg, full_tokens))


def tick_least_seconds(cfg, active, full_tokens, window_tokens, pairs_held,
                       experts_touched, peaks):
    """One decode tick: `active` slots each emit a token and advance their
    state in every state-space layer; `pairs_held` (token, expert) pairs are
    computed in `experts_touched` routed experts, both summed over the expert
    layers."""
    return least_seconds(
        tick_flops(cfg, active, full_tokens, window_tokens, pairs_held),
        tick_bytes(cfg, active, full_tokens, window_tokens, experts_touched),
        peaks)


def experts_least_seconds(cfg, pairs_held, experts_touched, peaks):
    """The routed experts of a tick alone: each touched expert's two matrices
    once, each pair's two products, the pairs' latent rows in and out."""
    item = BYTES[cfg["assumed"]["param_dtype"]]
    rows = 2 * pairs_held * cfg["moe_latent_size"] * item
    return least_seconds(2 * expert_params(cfg) * pairs_held,
                         expert_params(cfg) * experts_touched * item + rows,
                         peaks)


def paged_least_seconds(cfg, full_tokens, window_tokens, peaks):
    """The paged reads of a tick: key and value rows H_kv x D wide, each
    feeding every query head of its group."""
    heads, _, depth = attention_shape(cfg)
    rows = attended_tokens(cfg, full_tokens)
    return least_seconds(4 * heads * depth * rows, kv_row_bytes(cfg) * rows,
                         peaks)


def ssm_update_least_seconds(cfg, active, peaks):
    """The state updates of a tick alone (the kernel `ssm_decode_update`):
    `active` slots in every state-space layer, the state read once and written
    once, and the step's rows (x, dt, B, C in, y out) beside it. The
    convolution's window is not this kernel's."""
    heads, head_dim, groups, state, _ = mamba_shape(cfg)
    steps = active * layer_kinds(cfg)[0]
    item = BYTES[cfg["assumed"]["compute_dtype"]]
    rows = (heads * head_dim + 2 * groups * state) * item + (
        heads * head_dim + heads) * 4
    elements = state_elements(cfg)
    nbytes = 2 * elements * BYTES[cfg["assumed"]["ssm_state_dtype"]]
    return least_seconds(STATE_FLOPS_PER_ELEMENT * elements * steps,
                         (nbytes + rows) * steps, peaks)
