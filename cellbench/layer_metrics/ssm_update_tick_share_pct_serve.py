"""Share of a decode tick's device time spent in the state-space layers' state
update: the device time of the kernel the program declares as
`ssm_decode_update` over the device time of the runs of `jit_serve_tick`. A
program without the kernel: nothing to read."""

from cellbench import kernel_events, routed_events


def read(observed):
    trace = observed.get("trace")
    if trace is None:
        return None
    found = kernel_events.find(trace, "ssm_decode_update")
    whole = routed_events.program_seconds(trace)
    if found is None or not whole:
        return None
    return 100.0 * found[1] / whole
