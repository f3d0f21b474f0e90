"""Whole block tick of a block-diffusion model against the chip's peak: the
least time the tick needs (cellbench/counts/sdar.py: every matrix once at its
stored type with the routed experts counted by the program's counter of
experts touched, the FLOPs of the query rows the ticks ran
(`diffusion_rows_computed`) and of the (row, expert) pairs computed, a key
and a value row for every live key a layer, `kv_live_tokens`), each over the
window's ticks, over the time a tick took (`tick_ms.serve`). A program
without the block tick's counters: nothing to read."""

from cellbench import harness
from cellbench.layer_metrics import tick_ms_serve


def read(observed):
    peaks, c = observed.get("peaks"), observed.get("counters", {})
    tick_ms = tick_ms_serve.read(observed)
    ticks, rows = c.get("ticks"), c.get("diffusion_rows_computed")
    if not peaks or not tick_ms or not ticks or not rows:
        return None
    counts = harness.find("counts", observed["config"]["family"])
    if not hasattr(counts, "block_decode_least_seconds"):
        return None
    least, _ = counts.tick_least_seconds(
        observed["config"], rows / ticks, c["kv_live_tokens"] / ticks,
        c["moe_pairs_held"] / ticks, c["moe_experts_touched"] / ticks, peaks)
    return 100.0 * least / (tick_ms / 1e3)
