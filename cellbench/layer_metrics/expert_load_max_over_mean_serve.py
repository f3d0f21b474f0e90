"""Imbalance of the routed load over the experts held here: the most pairs an
expert got in the window over the mean an expert, from the program's per-expert
counter (1 = even; the tick waits on the fullest expert's rows)."""


def read(observed):
    load = observed.get("counters", {}).get("moe_expert_load") or []
    total = sum(load)
    return max(load) * len(load) / total if total > 0 else None
