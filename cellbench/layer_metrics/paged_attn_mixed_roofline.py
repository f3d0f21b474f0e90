"""The paged decode kernels' share of their roofline where full and window
layers stand side by side under grouped queries: the least time to read key
and value rows `H_kv x D` wide (cellbench/counts), a full layer by the slots'
live tokens and a window layer by min(live, window) a slot, over the device
time of the events of the kernels the program declares as `paged_decode` and
`paged_decode_window`. Each distinct name is one layer of the tick program,
so a kind's events over its names is the number of ticks traced."""

from cellbench import harness, kernel_events, routed_events


def read(observed):
    trace, peaks = observed.get("trace"), observed.get("peaks")
    means = routed_events.tick_means(observed)
    if trace is None or not peaks or means is None:
        return None
    found = [kernel_events.find(trace, name)
             for name in ("paged_decode", "paged_decode_window")]
    found = [f for f in found if f is not None]
    if not found:
        return None
    cfg = observed["config"]
    sites, seconds, events = (sum(f[i] for f in found) for i in range(3))
    least, _ = harness.find("counts", cfg["family"]).paged_least_seconds(
        cfg, means["full_tokens"], means["window_tokens"], peaks)
    return 100.0 * least * (events / sites) / seconds
