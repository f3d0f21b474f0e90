"""The routed experts' share of their roofline in a block tick: the least time
for the (row, expert) pairs computed and the experts touched in a tick
(cellbench/counts `experts_least_seconds`, from the program's counters
between the traced slice's start and end: `moe_pairs_held_traced`,
`moe_experts_touched_traced` over `ticks_traced`) over the device time a tick
of the grouped-product kernels whose product has the block tick's rows
(`routed_events.grouped_product_seconds` at slots x B x experts a row:
XLA:TPU's `ragged-dot*` kernels, the three projections of every layer).

A prefill of 256 tokens makes products of as many rows, so this reads only a
slice in which no prefill program ran. The cell's slice (8 to 10 s into the
window) lies after the first wave's last first token (about 2 s) and before
the first request ends (about 13 s). A program without the block tick's
counters, or a slice that holds a prefill: nothing to read."""

from cellbench import harness, routed_events

PREFILL = "jit_serve_prefill"


def read(observed):
    trace, peaks = observed.get("trace"), observed.get("peaks")
    c, cfg = observed.get("counters", {}), observed.get("config", {})
    ticks = c.get("ticks_traced")
    if (trace is None or not peaks or not ticks
            or not c.get("moe_pairs_held_traced")
            or not c.get("diffusion_rows_computed")):
        return None
    if any(n.startswith(PREFILL) for n in trace.modules.names):
        return None
    rows = (int(cfg["assumed"]["slots"]) * int(cfg["assumed"]["block_length"])
            * int(cfg["num_experts_per_tok"]))
    seconds = routed_events.grouped_product_seconds(trace, rows)
    traced = routed_events.ticks_traced(trace)
    counts = harness.find("counts", cfg["family"])
    if (seconds is None or not traced
            or not hasattr(counts, "experts_least_seconds")):
        return None
    least, _ = counts.experts_least_seconds(
        cfg, c["moe_pairs_held_traced"] / ticks,
        c["moe_experts_touched_traced"] / ticks, peaks)
    return 100.0 * least * traced / seconds
