"""The flash-attention kernels' share of their roofline in a training step:
the least time the chip needs for a step's attention (cellbench/counts) over
the device time of the kernels' events in the trace.

The Pallas calls pass no `name=`, so the trace names each by the flax scope
it sits in: a custom call whose name starts `attention.` (forward, dq
and dk/dv alike). Each distinct name is one call site of the step program, so
events / distinct names is the number of steps traced."""

from cellbench import harness
from cellbench.counts import flash_attention


def is_flash(text):
    name, _, rest = text.partition(" = ")
    return (name.lstrip("%").startswith("attention.")
            and "_paged_decode_attention" not in name and " custom-call(" in rest)


def read(observed):
    trace, peaks = observed.get("trace"), observed.get("peaks")
    if trace is None or not peaks:
        return None
    names = [n for n, text in trace.op_text.items() if is_flash(text)]
    seconds = sum(trace.op_seconds[n] for n in names)
    events = sum(trace.op_counts[n] for n in names)
    if not names or seconds <= 0:
        return None
    cfg, c = observed["config"], observed["counters"]
    counts = harness.find("counts", cfg["family"])
    heads, kv_heads, depth = counts.attention_shape(cfg)
    least, _ = flash_attention.train_least_seconds(
        c["batch"] // observed["chips"], heads, kv_heads, c["seq"], depth, peaks)
    steps = events / len(names)
    return 100.0 * least * counts.layers(cfg) * steps / seconds
