"""What the readers of a routed (expert) model's per-layer metrics share: the
window's means a tick from the driver's counters, the number of ticks the
traced slice holds, and the device time of an expert layer's parts in the
tick program.

XLA:TPU runs `jax.lax.ragged_dot` as a kernel of its own and names the
instruction `ragged-dot*` (its `op_name` is rewritten to that, so the scope
it sat in is lost); the trace holds the prefill's grouped products under the
same names, and the two are told apart by the rows of the product: a tick
sorts `slots x experts per token` pairs, a prefill many more. The shared
expert is the declared kernel `fused_swiglu_fwd` at its own width. Every other
op (the router, the sort, the gathers: some 50 us of a 12 ms tick on the v5e)
keeps the `jax.named_scope` it was lowered under in its `op_name`
(`jit(serve_tick)/.../moe_router/...`), but the v5e's trace gives an op's
instruction without its metadata (PR 28), so those are read only where a
trace's text does carry it.
"""

import re

from cellbench import kernel_events
from cellbench.layer_metrics import slot_occupancy_pct_serve

TICK = "serve_tick"
SCOPES = ("moe_router", "moe_routed_experts", "moe_shared_expert")


def tick_means(observed):
    """Means over the window's ticks, from the counters: active slots, tokens
    a full layer attends to, tokens a window layer attends to, (token, expert)
    pairs computed and routed experts touched (both summed over the expert
    layers). None where the program kept no such counters."""
    c = observed.get("counters", {})
    ticks = c.get("ticks")
    if not ticks or not c.get("moe_pairs_routed"):
        return None
    active = slot_occupancy_pct_serve.mean_active(c)
    if not active:
        return None
    return {"active": active,
            "full_tokens": c["live_token_ticks"] / ticks,
            "window_tokens": c["window_token_ticks"] / ticks,
            "pairs_held": c["moe_pairs_held"] / ticks,
            "experts_touched": c["moe_experts_touched"] / ticks}


def ticks_traced(trace):
    wanted = "jit_" + TICK
    return sum(n == wanted or n.startswith(wanted + "(")
               for n in trace.modules.names)


def tick_rows(cfg):
    """Rows of a tick's grouped products: a pair a slot and choice."""
    return int(cfg["assumed"]["slots"]) * int(cfg["num_experts_per_tok"])


def grouped_product_seconds(trace, rows):
    """Device seconds of the grouped-product kernels whose product has `rows`
    rows, or None where the trace has none."""
    shaped = re.compile(r"\[{},\d+\]".format(int(rows)))
    names = [n for n, text in trace.op_text.items()
             if n.startswith("ragged-dot") and " custom-call(" in text
             and shaped.search(text.partition(" custom-call(")[0])]
    seconds = sum(trace.op_seconds[n] for n in names)
    return seconds if names and seconds > 0 else None


def shared_expert_seconds(trace, cfg):
    """Device seconds of the tick's shared-expert calls: the kernel the
    program declares as `fused_swiglu_fwd`, told from the dense layers' by the
    width of its weight operand (`[hidden, shared experts x expert width]`)
    and from any other program's by the tick's rows (`[slots, hidden]`). None
    where the trace has none."""
    hidden = int(cfg["hidden_size"])
    weight = "[{},{}]".format(hidden, int(cfg["num_shared_experts"])
                              * int(cfg["moe_intermediate_size"]))
    rows = "[{},{}]".format(int(cfg["assumed"]["slots"]), hidden)
    names = [n for n, text in trace.op_text.items()
             if kernel_events.is_kernel(text, "fused_swiglu_fwd")
             and weight in text and rows in text]
    seconds = sum(trace.op_seconds[n] for n in names)
    return seconds if names and seconds > 0 else None


def scoped_seconds(trace, scopes, program=TICK):
    """Device seconds of the ops lowered under one of `scopes` inside
    `jit_<program>`, by the `op_name` in the trace's text; None where no op
    of the program carries an `op_name` (the trace does not give it)."""
    inside = 'op_name="jit({})/'.format(program)
    mine = [n for n, text in trace.op_text.items() if inside in text]
    if not mine:
        return None
    under = re.compile(r'op_name="[^"]*/(?:{})/'.format("|".join(scopes)))
    return sum(trace.op_seconds[n] for n in mine
               if under.search(trace.op_text[n]))


def program_seconds(trace, program=TICK):
    ms = kernel_events.program_ms(trace, program)
    return None if ms is None else ms / 1e3 * ticks_traced(trace)
