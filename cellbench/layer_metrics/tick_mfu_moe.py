"""Whole decode tick of a routed model against the chip's peak: the least time
the tick needs (cellbench/counts/exaone_moe.py: FLOPs of the pairs computed,
every matrix read once with the routed experts counted by the program's
counter of experts touched and never as all that are held, a window layer's
keys and values as min(len, window) a slot) over the time a tick took."""

from cellbench import harness, routed_events
from cellbench.layer_metrics import tick_ms_serve


def read(observed):
    peaks = observed.get("peaks")
    tick_ms = tick_ms_serve.read(observed)
    means = routed_events.tick_means(observed)
    if not peaks or not tick_ms or means is None:
        return None
    counts = harness.find("counts", observed["config"]["family"])
    least, _ = counts.tick_least_seconds(
        observed["config"], means["active"], means["full_tokens"],
        means["window_tokens"], means["pairs_held"], means["experts_touched"],
        peaks)
    return 100.0 * least / (tick_ms / 1e3)
