"""Share of the window that prefills took from the decode ticks, from the
program's per-tick record: over the tick-to-tick intervals that hold a
prefill's dispatch note (a whole prompt or a chunk), the time from the note
(or the interval's start) to the interval's end, less one clean tick period;
summed, over the window (`cellbench/tick_records.py` has the rules). With
`window_tick_share_pct.serve` and `window_wait_share_pct.serve` it accounts
for the window; what the three leave of 100 is inserts, evictions, slow
commits and whatever has no name."""

from cellbench import tick_records


def read(observed):
    account = tick_records.account(observed)
    if account is None:
        return None
    return tick_records.positive(account.share_pct(account.prefill_s))
