"""Finding a kernel's events in the reduced trace by the name the program
declares for it (`pl.pallas_call(name=...)`; the table in
`cloud_tpu/monitoring/spans.py`), not by the scope it happens to sit in.

XLA:TPU names a Pallas custom call by its innermost scope, which is the
declared name: `%fused_swiglu_fwd.7`, `%attention.flash_fwd.143` (some calls
keep a prefix for the accepted readers' sake). A component of the dotted name
has to equal the declared name, so `fused_rmsnorm` does not find
`fused_rmsnorm_residual`, and a fusion that merely takes the kernel's result
as an operand is not found at all. Where a transform has mangled the
instruction's name, the `op_name` metadata decides, if the text carries it.
"""

import re


def is_kernel(text, declared):
    name, _, rest = text.partition(" = ")
    if " custom-call(" not in rest:
        return False
    if declared in name.lstrip("%").split("."):
        return True
    return re.search(r'op_name="[^"]*(?<![A-Za-z0-9_])' + re.escape(declared)
                     + r'\)*/pallas_call', rest) is not None


def find(trace, declared):
    """(call sites, device seconds, events) of the kernel in `trace`, or None
    where it has no event."""
    names = [n for n, text in trace.op_text.items() if is_kernel(text, declared)]
    seconds = sum(trace.op_seconds[n] for n in names)
    events = sum(trace.op_counts[n] for n in names)
    if not names or seconds <= 0:
        return None
    return len(names), seconds, events


def program_ms(trace, program):
    """Mean device milliseconds of the runs of `jit_<program>` on the trace's
    program line, or None where it did not run."""
    if trace is None:
        return None
    wanted = "jit_" + program
    durs = [d for n, d in zip(trace.modules.names, trace.modules.dur)
            if n == wanted or n.startswith(wanted + "(")]
    return sum(durs) / len(durs) / 1e6 if durs else None
