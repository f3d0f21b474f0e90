"""Whole decode tick of an EVA model against the chip's peak: the least time
the tick needs (cellbench/counts/evabyte.py: every matrix once at its stored
type, a key and a value row for every row attended, from the program's counter
`eva_rows_read` over its ticks) over the time a tick took. A program without
the counter: nothing to read."""

from cellbench import harness
from cellbench.layer_metrics import slot_occupancy_pct_serve, tick_ms_serve


def read(observed):
    peaks, c = observed.get("peaks"), observed.get("counters", {})
    tick_ms = tick_ms_serve.read(observed)
    active = slot_occupancy_pct_serve.mean_active(c)
    rows = c.get("eva_rows_read")
    if not peaks or not tick_ms or not active or not rows:
        return None
    counts = harness.find("counts", observed["config"]["family"])
    least, _ = counts.tick_least_seconds(
        observed["config"], active, rows / c["ticks"], peaks)
    return 100.0 * least / (tick_ms / 1e3)
