"""What every cell shares: finding a cell's files by the names in
BENCHMARK.json, the device stamp, the table of peaks, percentiles, the list of
numbers compared for `correct`, and the result line.

Nothing here knows a workload, a configuration or a metric by name.
"""

import dataclasses
import importlib
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))
# Traces are written here (git-ignored), read back and deleted.
OUT_DIR = os.path.join(ROOT, ".cellbench_out")


def load_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_benchmark(root=ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find(kind, name):
    """The module `cellbench/<kind>/<name>.py`, named by a data file."""
    if not name.replace("_", "").replace(".", "").replace("-", "").isalnum():
        raise ValueError("bad {} name {!r}".format(kind, name))
    return importlib.import_module("cellbench.{}.{}".format(
        kind, name.replace(".", "_").replace("-", "_")))


@dataclasses.dataclass
class Cell:
    """One entry of `workloads`, with its files loaded."""
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list      # metric entries of BENCHMARK.json for this cell
    per_layer: list
    limits: dict = None   # of the numbers compared for `correct`


def _applies(metric, workload, reported_e2e=None):
    if "workloads" in metric:
        return workload in metric["workloads"]
    if reported_e2e is None:
        return True
    return metric["moves"] in reported_e2e


def load_cell(workload, bench=None, root=ROOT):
    bench = bench or load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit("unknown workload {!r}; BENCHMARK.json has {}".format(
            workload, sorted(cells)))
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[entry["config"]]["file"]))
    traffic = load_traffic(entry["traffic"], root)
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, workload, names)]
    return Cell(workload, int(entry["chips"]), entry["config"], config,
                entry["traffic"], traffic, e2e, layer,
                load_limits(workload, root))


def load_traffic(name, root=ROOT):
    return load_json(os.path.join(root, "cellbench", "traffic", name + ".json"))


def load_limits(workload, root=ROOT):
    """The limits of the numbers compared for `correct` in this cell."""
    return load_json(os.path.join(root, "cellbench", "limits", workload + ".json"))


def device_stamp(chips):
    """The device as JAX reports it. Exits non-zero off-TPU or short of chips:
    no fallback to the CPU."""
    import jax

    devices = jax.devices()
    stamp = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    if stamp["platform"] != "tpu" or stamp["count"] < chips:
        raise SystemExit(
            "cellbench needs {} TPU chip(s); JAX reports platform={platform!r} "
            "kind={kind!r} count={count}".format(chips, **stamp))
    return stamp


def peaks_for(device_kind):
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table or device_kind == "source":
        raise SystemExit("no peaks for device_kind {!r} in cellbench/peaks.json"
                         .format(device_kind))
    return table[device_kind]


def memory_peak_bytes(devices=None):
    """Peak bytes in use on the fullest local chip, or None off-device."""
    import jax

    peaks = []
    for d in devices or jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def percentile(values, q):
    """q-th percentile (0..100) by linear interpolation, as numpy's default."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def rng(seed, stream):
    """A numpy generator for one use (`stream`) of a run's seed."""
    import numpy as np

    return np.random.default_rng(np.random.SeedSequence([int(seed), int(stream)]))


def seed_words(seed, n=2):
    """`n` uint32 words from a seed of any size (the driver's pass 2**31)."""
    import numpy as np

    return np.random.SeedSequence(int(seed)).generate_state(n)


class Compared:
    """The numbers `correct` rests on, each beside its limit."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit):
        value = float(value)
        ok = math.isfinite(value) and value <= limit
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": bool(ok)})

    def require(self, name, ok):
        """An exact comparison: 0 mismatches allowed."""
        self.add(name, 0.0 if ok else 1.0, 0.0)

    @property
    def ok(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def as_dict(self):
        return {r["name"]: {"value": r["value"], "limit": r["limit"]}
                for r in self.rows}

    def print_stderr(self):
        for r in self.rows:
            print("compared {name}: value={value:.6g} limit={limit:g} {0}".format(
                "ok" if r["ok"] else "BEYOND", **r), file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What a driver is given."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float                 # perf_counter at process start
    peaks: dict = None               # None off-device (tests)
    device: dict = None
    # Test hook: a callable applied to the system under test after it is
    # built, to plant a fault.
    plant: object = None

    def setup_s(self, t_window_start):
        return t_window_start - self.t_process


def metric_values(cell, run, observed):
    """The cell's metrics for this kind of run: end-to-end ones from what the
    driver timed (`observed['end_to_end']`), per-layer ones from their
    readers. A reader that finds nothing returns None and is left out."""
    out = {}
    if not run.trace:
        for m in cell.end_to_end:
            value = observed["end_to_end"].get(m["name"])
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out
    for m in cell.per_layer:
        reader = find("layer_metrics", m["name"])
        value = reader.read(observed)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell, run, observed):
    """The one JSON object a run prints last."""
    device = dict(run.device or {})
    device["memory_peak_bytes"] = observed.get("memory_peak_bytes")
    line = {"correct": bool(observed["compared"].ok),
            "attempted": int(observed["attempted"]),
            "failed": int(observed["failed"]),
            "metrics": metric_values(cell, run, observed),
            "device": device}
    reduced = observed.get("trace")
    if run.trace and reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        line["breakdown"] = reduced.breakdown()
    line["workload"] = cell.name
    line["seed"] = run.seed
    line["window_s"] = observed.get("window_s")
    line["reference_s"] = observed.get("reference_s")
    line["counters"] = observed.get("counters", {})
    line["compared"] = observed["compared"].as_dict()
    return line


def now():
    return time.perf_counter()


class CompileWatch:
    """Counts what JAX hands to the backend compiler, persistent-cache hits
    included: the harness's own count, beside the program's retrace sentinel.
    A window may add nothing to it."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.count, self.seconds = 0, 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += float(duration)

    def since(self, mark):
        return self.count - mark[0], self.seconds - mark[1]

    def close(self):
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on)

    def mark(self):
        return self.count, self.seconds
