"""Operations and bytes of paged decode attention for one layer and one tick:
every live token's key and value row is read once (at the page type), each
feeding one multiply-add a feature for the scores and one for the output."""

from cellbench.counts import least_seconds


def tick_bytes(live_tokens, width, itemsize=2):
    return 2 * live_tokens * width * itemsize


def tick_flops(live_tokens, width):
    return 4 * live_tokens * width


def tick_least_seconds(live_tokens, width, itemsize, peaks):
    return least_seconds(tick_flops(live_tokens, width),
                         tick_bytes(live_tokens, width, itemsize), peaks)
