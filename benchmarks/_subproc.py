"""Shared bounded-subprocess point runner for the benchmark sweeps.

One implementation of the isolation pattern the sweeps need (sweep.py
grid points, flash_autotune.py tile points): run a command in its own
process with a hard timeout — an infeasible kernel config can abort in
the Mosaic compiler — then salvage the last intact JSON line from
stdout, or return a diagnosed error record instead of taking the sweep
down.

A chip belongs to one process at a time. A parent that calls this must
not have imported JAX: if it holds the chip, the child cannot open it.
Keep parents to argument parsing and bookkeeping, and run the children
one after another.
"""

import json
import subprocess


def run_json_point(cmd, timeout, cwd, env=None, error_extra=None):
    """Runs `cmd`; returns (record, None) or (None, error_record).

    The error record carries `error` plus `error_extra` so sweep output
    stays one-JSON-line-per-point even for failed points.
    """
    base = dict(error_extra or {})

    def err(msg):
        rec = dict(base)
        rec["error"] = msg
        return None, rec

    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout, cwd=cwd, env=env)
    except subprocess.TimeoutExpired:
        return err("hung past {:.0f}s".format(timeout))
    except OSError as e:
        return err("failed to launch: {}".format(e))
    for line in reversed((proc.stdout or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line), None
            except ValueError:
                continue  # cut mid-write; keep scanning
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()
    return err(tail[-1][:160] if tail else "rc={}".format(proc.returncode))
