"""Mean wait in the admission queue: the window's delta of the histogram's
exact sum over its count (its power-of-two buckets are not read)."""


def read(observed):
    c = observed["counters"]
    if not c.get("queue_wait_count"):
        return None
    return 1e3 * c["queue_wait_sum"] / c["queue_wait_count"]
