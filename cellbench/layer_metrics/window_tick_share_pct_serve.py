"""Share of the window the device spent in decode ticks, by the program's own
account: the window's ticks x the clean tick period
(`tick_period_clean_ms.serve`), over the window. 100 less this,
`window_prefill_share_pct.serve` and `window_wait_share_pct.serve` is what no
record names: it should lie between -1 and 5."""

from cellbench import tick_records


def read(observed):
    account = tick_records.account(observed)
    if account is None:
        return None
    return tick_records.positive(account.share_pct(account.tick_s))
