"""Over the window's requests whose time to the first token is at or above its
90th percentile: the mean time spent waiting in its admission window for the
prefills ahead of it (window popped -> its own turn; no section of code, the
thread is busy with another request), from the program's per-request record.
The four `ttft_slow_*` add up to the slow decile's mean time to the first
token."""

from cellbench import request_records


def read(observed):
    return request_records.slow_phase_ms(observed, "window")
