"""The flash-attention forward kernel's share of its roofline in a training
step: the least time the chip needs for the forward's two products
(cellbench/counts/flash_attention_passes.py) over the device time of the
events of the kernel the program declares as `flash_fwd`. Each call site is
one layer of the step program, so events / call sites is the steps traced."""

from cellbench import harness, kernel_events
from cellbench.counts import flash_attention_passes as passes


def read(observed, kernels=("flash_fwd",),
         least_seconds=passes.forward_least_seconds):
    trace, peaks = observed.get("trace"), observed.get("peaks")
    if trace is None or not peaks:
        return None
    found = [kernel_events.find(trace, k) for k in kernels]
    if None in found:
        return None
    sites, _, events = found[0]
    cfg, c = observed["config"], observed["counters"]
    counts = harness.find("counts", cfg["family"])
    heads, kv_heads, depth = counts.attention_shape(cfg)
    least, _ = least_seconds(c["batch"] // observed["chips"], heads, kv_heads,
                             c["seq"], depth, peaks)
    steps = events / sites
    return 100.0 * least * counts.layers(cfg) * steps / sum(f[1] for f in found)
