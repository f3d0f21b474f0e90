"""The paged decode kernel's share of its roofline: the least time the chip
needs to read the live tokens' keys and values for a tick (cellbench/counts)
over the device time of the kernel's events in the trace.

The trace names the kernel by the scope it sits in: a custom call whose name
holds `_paged_decode_attention`. Each distinct name is one layer of the
tick program, so events / distinct names is the number of ticks traced. Live
tokens a tick are the window's mean (from the requests served)."""

from cellbench import harness
from cellbench.counts import gpt2, paged_attention


def is_paged(text):
    name, _, rest = text.partition(" = ")
    return "_paged_decode_attention" in name and " custom-call(" in rest


def read(observed):
    trace, peaks = observed.get("trace"), observed.get("peaks")
    c = observed["counters"]
    if trace is None or not peaks or not c.get("ticks"):
        return None
    names = [n for n, text in trace.op_text.items() if is_paged(text)]
    seconds = sum(trace.op_seconds[n] for n in names)
    events = sum(trace.op_counts[n] for n in names)
    if not names or seconds <= 0:
        return None
    cfg = observed["config"]
    counts = harness.find("counts", cfg["family"])
    heads, _, depth = counts.attention_shape(cfg)
    itemsize = gpt2.BYTES[cfg["assumed"]["kv_page_dtype"]]
    least, _ = paged_attention.tick_least_seconds(
        c["live_token_ticks"] / c["ticks"], heads * depth, itemsize, peaks)
    return 100.0 * least * events / seconds
