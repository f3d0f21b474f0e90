"""Share of a decode tick's device time spent reading the EVA cache: the device
time of the kernel the program declares for the read (`paged_decode_window`)
over the device time of the runs of `jit_serve_tick`. It says whether the
mechanism is the tick. Read only where the program counted EVA rows (another
model's window layers run the same kernel); a program without the kernel or
the counter: nothing to read."""

from cellbench import kernel_events, routed_events
from cellbench.layer_metrics.eva_read_roofline import KERNEL


def read(observed):
    trace = observed.get("trace")
    if trace is None or not observed.get("counters", {}).get("eva_rows_read"):
        return None
    found = kernel_events.find(trace, KERNEL)
    whole = routed_events.program_seconds(trace)
    if found is None or not whole:
        return None
    return 100.0 * found[1] / whole
