"""95th percentile over the window's answered requests of (first token - due
time), by the harness's clock. Beside `ttft_p50_ms`: at this load about one
request in twenty meets a stall of some 300 ms, so this percentile sits on the edge between the two modes and swings (PERF.md section 2)."""


def read(observed):
    ladder = observed["counters"].get("ttft_ms")
    return ladder["p95"] if ladder else None
