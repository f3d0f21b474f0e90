"""The fused SwiGLU forward kernel's share of its roofline in a training step:
the least time for its three products (cellbench/counts/fused_mlp.py) over the
device time of the events of the kernel the program declares as
`fused_swiglu_fwd`. Forward only: the backward is plain lax. Where the
backward recomputes the forward, the kernel's events hold the recomputation
and the count does not, so the share reads lower."""

from cellbench import harness, kernel_events
from cellbench.counts import fused_mlp


def read(observed):
    trace, peaks = observed.get("trace"), observed.get("peaks")
    if trace is None or not peaks:
        return None
    found = kernel_events.find(trace, "fused_swiglu_fwd")
    cfg = observed["config"]
    if found is None or "intermediate_size" not in cfg:
        return None
    sites, seconds, events = found
    c = observed["counters"]
    tokens = c["batch"] // observed["chips"] * c["seq"]
    least, _ = fused_mlp.forward_least_seconds(
        tokens, cfg["hidden_size"], cfg["intermediate_size"], peaks)
    layers = harness.find("counts", cfg["family"]).layers(cfg)
    return 100.0 * least * layers * (events / sites) / seconds
