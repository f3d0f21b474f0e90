"""The program's own record of each request served in the window
(`cloud_tpu.serving.reqtrace.recent()`: kept in memory, bounded, outliving
the Scheduler), for the readers of request-side per-layer metrics.

Nothing is read unless the finished records number the requests the driver
counted as completed, so a mismatch (another server's records, a ring that
overflowed, a program without the record) shows as a missing metric and never
as a wrong one.
"""

from cellbench import harness

PHASES = ("queue", "window", "reserve", "prefill")


def finished(observed):
    try:
        from cloud_tpu.serving import reqtrace
    except ImportError:
        return None
    recent = getattr(reqtrace, "recent", None)
    if recent is None:
        return None
    records = recent()
    completed = observed.get("counters", {}).get("completed")
    if not records or len(records) != completed:
        return None
    return records


def slow_decile(records):
    """The requests whose time to the first token is at or above the 90th
    percentile of the window's."""
    cut = harness.percentile([r.ttft_s for r in records], 90)
    return [r for r in records if r.ttft_s >= cut]


def slow_phase_ms(observed, phase):
    """Mean milliseconds the slow decile spent in `phase`; the four phases add
    up to its mean time to the first token."""
    records = finished(observed)
    if records is None:
        return None
    slow = slow_decile(records)
    return 1e3 * sum(r.phases()[phase] for r in slow) / len(slow)
