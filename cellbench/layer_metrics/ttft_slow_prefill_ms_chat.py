"""Over the window's requests whose time to the first token is at or above its
90th percentile: the mean time spent in its prefill (pages reserved -> first
token on the host), from the program's per-request record. The four
`ttft_slow_*` add up to the slow decile's mean time to the first token."""

from cellbench import request_records


def read(observed):
    return request_records.slow_phase_ms(observed, "prefill")
