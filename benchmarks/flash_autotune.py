"""Flash-attention block-size autotune: block_q x block_k on real TPU.

Tunes the Pallas kernel's tile sizes from measurements, not defaults.
Sweeps (block_q, block_k) for forward and forward+grad at
representative shapes, timing around `block_until_ready`, and prints
one JSON line per point plus a final best-config line with the
flash-vs-reference speedup table.

Each point runs in its own bounded subprocess: an infeasible tile
config fails in the Mosaic compiler and must not take the sweep down
with it. One process for each chip: the PARENT here never imports JAX
(only `_point_worker`, which runs in the child, does), so it never
holds the chip its children need.

Usage:
    python benchmarks/flash_autotune.py                  # real TPU
    JAX_PLATFORMS=cpu python benchmarks/flash_autotune.py --tiny
                                    # plumbing test, interpreted kernel
    python benchmarks/flash_autotune.py --blocks 128,256,512
"""

import argparse
import itertools
import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _subproc import run_json_point


def _point_worker(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cloud_tpu.ops import flash_attention, mha_reference

    b, s, h, d = args.batch, args.seq, args.heads, args.head_dim
    h_kv = h // args.gqa_group
    rng = np.random.default_rng(0)
    on_tpu = jax.default_backend() == "tpu"
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), dt)
    k = jnp.asarray(rng.normal(size=(b, s, h_kv, d)), dt)
    v = jnp.asarray(rng.normal(size=(b, s, h_kv, d)), dt)
    interpret = None  # compiled on a TPU, interpreted elsewhere

    def run(block_q, block_k, use_ref=False):
        if use_ref:
            fwd = jax.jit(lambda q, k, v: mha_reference(
                q, k, v, causal=True))
            loss = lambda q, k, v: mha_reference(
                q, k, v, causal=True).astype(jnp.float32).sum()
        else:
            fwd = jax.jit(lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k,
                interpret=interpret))
            loss = lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k,
                interpret=interpret).astype(jnp.float32).sum()
        bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        sync = jax.block_until_ready

        out = fwd(q, k, v); sync(out)           # compile + warm
        g = bwd(q, k, v); sync(g)
        reps = args.reps
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fwd(q, k, v)
        sync(out)
        fwd_ms = 1e3 * (time.perf_counter() - t0) / reps
        t0 = time.perf_counter()
        for _ in range(reps):
            g = bwd(q, k, v)
        sync(g)
        bwd_ms = 1e3 * (time.perf_counter() - t0) / reps
        return fwd_ms, bwd_ms

    bq, bk = args.point
    if bq == 0:  # reference oracle point
        fwd_ms, bwd_ms = run(0, 0, use_ref=True)
        record = {"kernel": "mha_reference"}
    else:
        fwd_ms, bwd_ms = run(bq, bk)
        record = {"kernel": "flash", "block_q": bq, "block_k": bk}
    record.update({
        "fwd_ms": round(fwd_ms, 3), "fwd_grad_ms": round(bwd_ms, 3),
        "batch": b, "seq": s, "heads": h, "kv_heads": h_kv,
        "head_dim": d, "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
    })
    print(json.dumps(record), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="128,256,512")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--gqa-group", type=int, default=1,
                    help="q heads per kv head (1 = MHA)")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--timeout", type=float, default=300.0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--point", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.tiny:
        args.batch, args.seq, args.heads, args.reps = 1, 256, 2, 2

    if args.point is not None:
        args.point = tuple(int(v) for v in args.point.split(","))
        return _point_worker(args)


    blocks = [int(v) for v in args.blocks.split(",")]
    grid = [(0, 0)] + [  # (0,0) = the jnp reference oracle point
        (bq, bk) for bq, bk in itertools.product(blocks, blocks)
        if bq <= args.seq and bk <= args.seq]
    results = []
    for bq, bk in grid:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--point", "{},{}".format(bq, bk),
               "--blocks", args.blocks, "--batch", str(args.batch),
               "--seq", str(args.seq), "--heads", str(args.heads),
               "--head-dim", str(args.head_dim),
               "--gqa-group", str(args.gqa_group),
               "--reps", str(args.reps)]
        if args.tiny:
            cmd.append("--tiny")
        record, err = run_json_point(
            cmd, args.timeout, _REPO_ROOT,
            error_extra={"block_q": bq, "block_k": bk})
        if record is None:
            print(json.dumps(err), flush=True)
            continue
        print(json.dumps(record), flush=True)
        results.append(record)

    flash = [r for r in results if r.get("kernel") == "flash"]
    ref = next((r for r in results if r.get("kernel") == "mha_reference"),
               None)
    if not flash:
        print(json.dumps({"autotune": "failed",
                          "hint": "no flash point completed"}))
        return 1
    best = min(flash, key=lambda r: r["fwd_grad_ms"])
    summary = {
        "autotune": "best",
        "block_q": best["block_q"], "block_k": best["block_k"],
        "fwd_ms": best["fwd_ms"], "fwd_grad_ms": best["fwd_grad_ms"],
    }
    if ref is not None:
        summary["speedup_vs_reference_fwd"] = round(
            ref["fwd_ms"] / best["fwd_ms"], 2)
        summary["speedup_vs_reference_fwd_grad"] = round(
            ref["fwd_grad_ms"] / best["fwd_grad_ms"], 2)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
