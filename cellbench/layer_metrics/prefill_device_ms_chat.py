"""Mean device time of one prefill: the runs of the program the server
declares as `serve_prefill` on the trace's program line. Beside
`prefill_mean_ms.chat` (the wall time a prefill, beside a running tick) it
says how much of a prefill is waiting."""

from cellbench import kernel_events


def read(observed):
    return kernel_events.program_ms(observed.get("trace"), "serve_prefill")
