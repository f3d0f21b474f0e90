"""The program's spans in a profile, beside the device's idle gaps. Never run
by the benchmark; by hand, on the chip.

    python3 cellbench/tools/spans.py --xplane <file.xplane.pb>
    python3 cellbench/tools/spans.py --workload <cell> --seed <n> --seconds <s>

The second form runs the cell once with `--trace 1` (the result line is
printed as always) and reads the slice's `.xplane.pb` before the harness
deletes it. Printed, and written to `chiprun_out/spans_<cell>.txt`:

- for each host thread that holds spans of the program
  (`cloud_tpu/monitoring/spans.py`'s table): each span's count, total and self
  time (its time less the spans nested in it on the same thread). The
  profiler names every Python thread `python`, so a thread is called by what
  it runs: `tick` holds `serve_tick`, `admission` holds `admit` without it,
  `train` holds `train_step`;
- for every idle gap of the first chip longer than 1 ms: the innermost span of
  the dispatching thread (`tick` or `train`) that covers the gap's start, by
  name with the count, total and longest gap. This is the by-hand form of what
  a later `benchmark` PR moves into `tracing.reduce_events`.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from cellbench import harness, tracing  # noqa: E402

MIN_GAP_NS = 1_000_000
ROLES = (("tick", "serve_tick"), ("train", "train_step"), ("admission", "admit"))


def span_names():
    from cloud_tpu.monitoring import spans

    return set(spans.names("Spans"))


def load(path):
    """(threads, first chip's ops): `threads` is a list of sorted
    (start_ns, end_ns, name) lists, one a host thread that holds a span."""
    import jax

    wanted = span_names()
    threads, ops = [], None
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        device = plane.name.startswith(tracing.DEVICE_PLANE)
        for line in plane.lines:
            if device and line.name == tracing.OPS_LINE and ops is None:
                ops = tracing.Events.of([(e.name, e.start_ns, e.duration_ns)
                                         for e in line.events])
            elif plane.name.startswith("/host:"):
                found = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                               for e in line.events if e.name in wanted)
                if found:
                    threads.append(found)
    return threads, ops


def role(events, index):
    held = {name for _, _, name in events}
    for label, marker in ROLES:
        if marker in held:
            return label
    return "thread{}".format(index)


def self_times(events):
    """name -> [count, total_ns, self_ns] over one thread's spans, which nest
    by containment."""
    out, stack = {}, []

    def close(span):
        start, end, name, nested = span
        row = out.setdefault(name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += end - start
        row[2] += end - start - nested

    for start, end, name in events:
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(end, stack[-1][1]) - start
        stack.append([start, end, name, 0.0])
    while stack:
        close(stack.pop())
    return out


def innermost_at(events, t):
    """The innermost span of one thread that covers time `t`, or None."""
    best = None
    for start, end, name in events:
        if start > t:
            break
        if end > t and (best is None or start >= best[0]):
            best = (start, end, name)
    return best[2] if best else None


def gaps_by_span(ops, dispatcher):
    """name -> [count, total_ns, longest_ns] of the first chip's idle gaps over
    MIN_GAP_NS, by the dispatching thread's innermost span at the gap's start."""
    out = {}
    if ops is None or len(ops.start) < 2:
        return out
    s, e = tracing.merged(ops.start, ops.dur)
    for lo, hi in zip(e[:-1], s[1:]):
        if hi - lo < MIN_GAP_NS:
            continue
        name = (innermost_at(dispatcher, lo) if dispatcher else None) or "(no span)"
        row = out.setdefault(name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += hi - lo
        row[2] = max(row[2], hi - lo)
    return out


def report(path):
    threads, ops = load(path)
    lines = []
    labelled = [(role(events, i), events) for i, events in enumerate(threads)]
    for label, events in sorted(labelled, key=lambda t: t[0]):
        lines.append("thread `{}`".format(label))
        lines.append("| span | count | total ms | self ms |")
        lines.append("| --- | --- | --- | --- |")
        rows = self_times(events)
        for name, (count, total, own) in sorted(rows.items(),
                                                key=lambda kv: -kv[1][2]):
            lines.append("| `{}` | {} | {:.2f} | {:.2f} |".format(
                name, count, total / 1e6, own / 1e6))
        lines.append("")
    dispatcher = next((events for label, events in labelled
                       if label in ("tick", "train")), None)
    if ops is not None and len(ops.start):
        s, e = tracing.merged(ops.start, ops.dur)
        window = float(e[-1] - s[0])
        idle = window - float(np.sum(e - s))
        lines.append("first chip: window {:.1f} ms, idle {:.2f} ms ({:.2f} %), "
                     "gaps over 1 ms by the dispatching thread's innermost span "
                     "at the gap's start".format(window / 1e6, idle / 1e6,
                                                 100 * idle / max(window, 1.0)))
        lines.append("| span | gaps | total ms | longest ms |")
        lines.append("| --- | --- | --- | --- |")
        for name, (count, total, longest) in sorted(
                gaps_by_span(ops, dispatcher).items(), key=lambda kv: -kv[1][1]):
            lines.append("| `{}` | {} | {:.2f} | {:.2f} |".format(
                name, count, total / 1e6, longest / 1e6))
    else:
        lines.append("no device plane in this profile")
    return "\n".join(lines)


def run_cell(args):
    """Runs the cell traced, and reads the slice before the harness deletes it."""
    from cellbench import run

    reports = []
    load_xplane = tracing.load_xplane

    def keep(path):
        reports.append(report(path))
        return load_xplane(path)

    tracing.load_xplane = keep
    try:
        run.main(["--workload", args.workload, "--seed", str(args.seed),
                  "--seconds", str(args.seconds), "--trace", "1"])
    finally:
        tracing.load_xplane = load_xplane
    return reports[0] if reports else "no trace was taken"


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--xplane")
    parser.add_argument("--workload")
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=45.0)
    args = parser.parse_args(argv)
    if not args.xplane and not args.workload:
        parser.error("give --xplane or --workload")
    text = report(args.xplane) if args.xplane else run_cell(args)
    print(text, file=sys.stderr, flush=True)
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    name = args.workload or os.path.basename(args.xplane).split(".")[0]
    with open(os.path.join(out, "spans_{}_{}.txt".format(name, args.seed)), "w",
              encoding="utf-8") as f:
        f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
