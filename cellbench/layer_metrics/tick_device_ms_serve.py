"""Mean device time of one decode tick: the runs of the program the server
declares as `serve_tick` on the trace's program line. Beside `tick_ms.serve`
(the wall time a tick) it gives the host's share of a tick."""

from cellbench import kernel_events


def read(observed):
    return kernel_events.program_ms(observed.get("trace"), "serve_tick")
