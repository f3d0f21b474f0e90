"""Mean time of a prefill as the scheduler timed it (gather, dense prefill and
the blocking fetch of the first token): the window's delta of the histogram's
exact sum over its count."""


def read(observed):
    c = observed["counters"]
    if not c.get("prefill_count"):
        return None
    return 1e3 * c["prefill_sum"] / c["prefill_count"]
