"""What one prefill costs the decode side: the window's prefill time (as
`window_prefill_share_pct.serve` counts it: its cache's zero program, the
insert behind it and whatever else sat in the same interval included) over the
prefill programs the engine noted in the window, a chunk of a chunked prefill
counting as one."""

from cellbench import tick_records


def read(observed):
    account = tick_records.account(observed)
    if account is None or not account.prefills:
        return None
    return tick_records.positive(1e3 * account.prefill_s / account.prefills)
