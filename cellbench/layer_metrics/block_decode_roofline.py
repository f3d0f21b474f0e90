"""The block tick's paged read against its roofline: the least time to read
the live keys and values a tick attends in one layer and to compute the B
rows' scores and sums over them (cellbench/counts `block_decode_least_seconds`)
over the device time of the events of the kernel the program declares for
the read, `paged_block_decode`. Each distinct name is one layer of the tick
program, so the events are layers x ticks traced. The live keys a tick are
those of the traced slice itself: the program's counter `kv_live_tokens`
over its ticks, both between the slice's start and its end
(`kv_live_tokens_traced`, `ticks_traced`, which the driver reads on the
slice's own timers). A program without the kernel or the counters: nothing
to read."""

from cellbench import harness, kernel_events

KERNEL = "paged_block_decode"


def read(observed):
    trace, peaks = observed.get("trace"), observed.get("peaks")
    c, cfg = observed.get("counters", {}), observed.get("config", {})
    ticks, live = c.get("ticks_traced"), c.get("kv_live_tokens_traced")
    if trace is None or not peaks or not ticks or not live:
        return None
    found = kernel_events.find(trace, KERNEL)
    counts = harness.find("counts", cfg["family"])
    if found is None or not hasattr(counts, "block_decode_least_seconds"):
        return None
    _, seconds, events = found
    least, _ = counts.block_decode_least_seconds(cfg, live / ticks, peaks)
    return 100.0 * least * events / seconds
