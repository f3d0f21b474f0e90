"""The state-space decode update's share of its roofline: the least time to
read and write the state of the slots a tick advanced, in every state-space
layer (cellbench/counts: `ssm_update_least_seconds`, from the program's
counter `ssm_slot_steps`), over the device time of the events of the kernel
the program declares as `ssm_decode_update`. Each distinct name is one layer
of the tick program, so the events over the names is the number of ticks
traced. A program without the kernel or the counter: nothing to read."""

from cellbench import harness, kernel_events


def read(observed):
    trace, peaks = observed.get("trace"), observed.get("peaks")
    counters, cfg = observed.get("counters", {}), observed.get("config", {})
    ticks, steps = counters.get("ticks"), counters.get("ssm_slot_steps")
    if trace is None or not peaks or not ticks or not steps:
        return None
    found = kernel_events.find(trace, "ssm_decode_update")
    counts = harness.find("counts", cfg["family"])
    if found is None or not hasattr(counts, "ssm_update_least_seconds"):
        return None
    sites, seconds, events = found
    active = steps / ticks / counts.layer_kinds(cfg)[0]
    least, _ = counts.ssm_update_least_seconds(cfg, active, peaks)
    return 100.0 * least * (events / sites) / seconds
