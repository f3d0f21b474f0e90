"""Whole decode tick against the chip's peak: the least time a tick needs (the
larger of model FLOPs over peak FLOP/s and weights-once plus live K/V bytes
over peak bytes/s, from cellbench/counts) over the time a tick took."""

from cellbench import harness
from cellbench.layer_metrics import slot_occupancy_pct_serve, tick_ms_serve


def read(observed):
    peaks, c = observed.get("peaks"), observed["counters"]
    tick_ms = tick_ms_serve.read(observed)
    active = slot_occupancy_pct_serve.mean_active(c)
    if not peaks or not tick_ms or not active:
        return None
    counts = harness.find("counts", observed["config"]["family"])
    least, _ = counts.tick_least_seconds(
        observed["config"], active, c["live_token_ticks"] / c["ticks"], peaks)
    return 100.0 * least / (tick_ms / 1e3)
