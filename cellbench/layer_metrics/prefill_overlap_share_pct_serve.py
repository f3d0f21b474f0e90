"""Share of the window's whole-prompt prefills that were dispatched while the
prefill before them was still unfetched, from the dispatch notes in the
program's per-tick record (`stats()["prefills_overlapped"]` over the misses,
inside the program). None where the window noted no whole prefill (a cell
whose prefills are all chunks) or none overlapped."""

from cellbench import tick_records


def read(observed):
    win = tick_records.window(observed)
    if win is None:
        return None
    whole = win.notes((tick_records.WHOLE_PREFILL,))
    if not whole:
        return None
    return tick_records.positive(
        100.0 * sum(n.overlapped for n in whole) / len(whole))
