"""Share of the window's decode ticks that were dispatched while the tick
before them was still unfetched (the device did not wait for the host), from
the program's per-tick record: what `stats()["ticks_overlapped"]` over `ticks`
gives inside the program."""

from cellbench import tick_records


def read(observed):
    win = tick_records.window(observed)
    if win is None:
        return None
    return tick_records.positive(
        100.0 * sum(t.overlapped for t in win.ticks) / len(win.ticks))
