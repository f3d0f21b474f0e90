"""Share of the rows the paged kernel's walk fetches that a query attends:
the sum over the window's ticks of the keys their slots attended over the sum
of the keys the walk fetched for those depths (whole groups of pages), from
the program's per-tick record (`kv_live` / `kv_walked`, the tick's terms of
`stats()["kv_live_tokens"]` and `["kv_walked_tokens"]`). The rest of the walk
is dead rows."""

from cellbench import tick_records


def read(observed):
    win = tick_records.window(observed)
    if win is None:
        return None
    walked = sum(t.kv_walked for t in win.ticks)
    if not walked:
        return None
    return tick_records.positive(
        100.0 * sum(t.kv_live for t in win.ticks) / walked)
