"""The EVA cache read's share of its roofline: the least time to read the rows
a tick attends in one layer (ring rows of the queries' own windows and the
summary rows behind them; cellbench/counts `rows_least_seconds`) over the
device time of the events of the kernel the program declares for the read,
`paged_decode_window` (the paged walk from a slot's first live row to its
last). Each distinct name is one layer of the tick program, so the events are
layers x ticks traced. The rows a tick are those of the traced slice itself:
the program's counter `eva_rows_read` over its ticks, both between the slice's
start and its end (`eva_rows_read_traced`, `ticks_traced`, which the driver
reads on the slice's own timers; the window's mean would be a third of a
decode-only slice's rows among the prefills and a tenth above them at the
end). A program without the kernel or the counters: nothing to read."""

from cellbench import harness, kernel_events

KERNEL = "paged_decode_window"


def read(observed):
    trace, peaks = observed.get("trace"), observed.get("peaks")
    c, cfg = observed.get("counters", {}), observed.get("config", {})
    ticks, rows = c.get("ticks_traced"), c.get("eva_rows_read_traced")
    if trace is None or not peaks or not ticks or not rows:
        return None
    found = kernel_events.find(trace, KERNEL)
    counts = harness.find("counts", cfg["family"])
    if found is None or not hasattr(counts, "rows_least_seconds"):
        return None
    _, seconds, events = found
    least, _ = counts.rows_least_seconds(cfg, rows / ticks, peaks)
    return 100.0 * least * events / seconds
