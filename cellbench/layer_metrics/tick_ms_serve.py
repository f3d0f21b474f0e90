"""Window seconds over the scheduler's count of decode ticks in the window."""


def read(observed):
    ticks = observed["counters"].get("ticks")
    return 1e3 * observed["window_s"] / ticks if ticks else None
