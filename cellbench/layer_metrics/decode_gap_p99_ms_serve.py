"""99th percentile over the window's served TOKENS of the gap to the same
request's previous token, from the program's per-request record (each later
token's commit time; the first gap from the first token, so it holds the wait
for a slot). `tpot_p95_ms` is a mean gap a request and averages a stall
away; this does not."""

from cellbench import harness, request_records


def read(observed):
    records = request_records.finished(observed)
    if records is None:
        return None
    gaps = [g for r in records for g in r.token_gaps()]
    return 1e3 * harness.percentile(gaps, 99) if gaps else None
