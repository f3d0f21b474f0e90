"""Share of the window the tick thread spent napping (`tick_pace`) or waiting
with no slot occupied (`tick_idle`) while the device had nothing of a prefill
to do: over the intervals whose tick's record carries a nap or an idle wait,
the time beyond one clean tick period that `window_prefill_share_pct.serve`
did not take. None in a window without a nap or an idle wait."""

from cellbench import tick_records


def read(observed):
    account = tick_records.account(observed)
    if account is None:
        return None
    return tick_records.positive(account.share_pct(account.wait_s))
