"""Share of a decode tick's device time spent in the expert layers: the tick's
grouped-product kernels (the routed experts) and its shared-expert kernel
calls (`routed_events`), with, where the trace's text carries `op_name`, the
remaining ops lowered under the declared scopes `moe_router`,
`moe_routed_experts` and `moe_shared_expert` inside `jit_serve_tick` (the
router, the sort and the gathers; the v5e's trace does not name them, and
they are under half a percent of the tick), over the device time of the tick
program's runs."""

from cellbench import routed_events


def read(observed):
    trace = observed.get("trace")
    if trace is None:
        return None
    cfg = observed["config"]
    grouped = routed_events.grouped_product_seconds(
        trace, routed_events.tick_rows(cfg))
    shared = routed_events.shared_expert_seconds(trace, cfg)
    whole = routed_events.program_seconds(trace)
    if grouped is None or shared is None or not whole:
        return None
    scoped = routed_events.scoped_seconds(trace, routed_events.SCOPES) or 0.0
    return 100.0 * (grouped + shared + scoped) / whole
