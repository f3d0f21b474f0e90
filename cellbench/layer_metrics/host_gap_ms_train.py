"""Mean device-idle gap between consecutive train-step programs, from the
trace's program line. The step program is the one that ran most often."""

import collections


def read(observed):
    trace = observed.get("trace")
    if trace is None or not trace.modules.names:
        return None
    step = collections.Counter(trace.modules.names).most_common(1)[0][0]
    gaps = trace.module_gaps_s(lambda name: name == step)
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
