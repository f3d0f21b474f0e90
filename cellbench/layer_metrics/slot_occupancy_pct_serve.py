"""Mean active slots a tick, over the slots, from the scheduler's per-geometry
counts (exact sums, no histogram bucket is read)."""


def mean_active(counters):
    per = counters.get("occupancy") or {}
    ticks = sum(t for t, _ in per.values())
    return sum(t * mean for t, mean in per.values()) / ticks if ticks else None


def read(observed):
    active = mean_active(observed["counters"])
    return 100.0 * active / observed["counters"]["slots"] if active else None
