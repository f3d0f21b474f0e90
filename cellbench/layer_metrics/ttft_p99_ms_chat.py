"""99th percentile over the window's answered requests of (first token - due
time), by the harness's clock. Beside `ttft_p50_ms`: at this load about one
request in twenty meets a stall of some 300 ms, so this percentile reads the stalled mode (PERF.md section 2)."""


def read(observed):
    ladder = observed["counters"].get("ttft_ms")
    return ladder["p99"] if ladder else None
