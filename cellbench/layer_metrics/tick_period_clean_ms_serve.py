"""The device's tick period over the whole window, from the program's per-tick
record: the median time from one tick's tokens to the next's over the ticks
that overlapped the tick before, with nothing but a decode tick dispatched
round them (no prefill, insert, eviction, nap or idle wait in them or in
either neighbour). Beside `tick_device_ms.serve` (the program's device time in
the 2 s traced slice) it covers every second of the window, and beside
`tick_ms.serve` (window over ticks) it leaves out what shares the device with
the ticks."""

from cellbench import tick_records


def read(observed):
    account = tick_records.account(observed)
    return None if account is None else 1e3 * account.clean_s
