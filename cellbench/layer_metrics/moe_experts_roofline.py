"""The routed experts' share of their roofline in a decode tick: the least
time for the pairs computed and the experts touched (cellbench/counts, from
the program's counters) over the device time of the tick's grouped-product
kernels (`routed_events.grouped_product_seconds`: XLA:TPU's `ragged-dot*`
kernels at the tick's row count, the three projections of every expert
layer), a tick traced."""

from cellbench import harness, routed_events


def read(observed):
    trace, peaks = observed.get("trace"), observed.get("peaks")
    means = routed_events.tick_means(observed)
    if trace is None or not peaks or means is None:
        return None
    cfg = observed["config"]
    seconds = routed_events.grouped_product_seconds(
        trace, routed_events.tick_rows(cfg))
    ticks = routed_events.ticks_traced(trace)
    if seconds is None or not ticks:
        return None
    least, _ = harness.find("counts", cfg["family"]).experts_least_seconds(
        cfg, means["pairs_held"], means["experts_touched"], peaks)
    return 100.0 * least * ticks / seconds
