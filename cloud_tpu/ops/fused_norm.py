"""Fused RMSNorm + residual-add as a Pallas TPU kernel.

The pre-norm transformer tail `h = x + residual; y = rmsnorm(h) * scale`
is two HBM round trips when written as separate ops (the residual add
materializes h, the norm re-reads it). This kernel does both in ONE HBM
pass: each grid step streams a row block through VMEM, adds the
residual, computes the f32 row statistics, and writes BOTH the normed
rows and the updated residual stream h.

Numerics mirror `flax.linen.RMSNorm` exactly: statistics are computed
in f32 on the promoted input (`var = mean(h_f32^2)`), the scale param is
f32 `[features]`, and the output is `h * (rsqrt(var + eps) * scale)`
cast to the requested dtype — so swapping a flax norm for this op is a
bitwise no-op in f32 and tolerance-level in bf16 (same single rounding
point).

Backward is `jax.custom_vjp` with the standard RMSNorm gradient
recomputed from the saved h (one residual tensor, no (x, residual)
pair): dh folds the normed-output cotangent AND the residual-stream
cotangent, and both inputs of the fused add receive it. The backward
runs as plain lax — decode never differentiates, and training backward
is dominated by the matmuls either way; the single-pass claim is for
the forward serving/training hot path.

On non-TPU backends a forced kernel runs in Pallas interpret mode, so
parity tests exercise the same code path CPU-side. Under a device mesh
the kernel runs per shard (ops/partition.py). Which path a call takes
is `fused_rmsnorm`'s rule over `impl` and the platform, nothing else.
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.sharding import PartitionSpec as P

from cloud_tpu.ops import partition

#: The kernels' declared names (`pl.pallas_call(name=)`; table in
#: monitoring/spans.py).
FUSED_RMSNORM = "fused_rmsnorm"
FUSED_RMSNORM_RESIDUAL = "fused_rmsnorm_residual"

_BLOCK_ROWS = 128


class _NormConfig(NamedTuple):
    eps: float
    block_rows: int
    out_dtype: str   # dtype name (hashable for the custom_vjp config)
    interpret: bool


def rmsnorm_residual_reference(x, scale, residual=None, eps=1e-6,
                               out_dtype=None):
    """Pure-lax fused norm tail: returns (normed, h).

    h = x + residual (or x when residual is None); normed is flax
    `RMSNorm(epsilon=eps, dtype=out_dtype)` applied to h, math-for-math
    (f32 statistics on the promoted input, `h * (rsqrt(var+eps)*scale)`,
    one cast at the end).
    """
    h = x if residual is None else x + residual
    if out_dtype is None:
        out_dtype = h.dtype
    hf = h.astype(jnp.float32)
    var = jnp.mean(hf * hf, axis=-1, keepdims=True)
    mul = jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return (hf * mul).astype(out_dtype), h


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, r_ref, w_ref, o_ref, h_ref, *, config):
    """One row block: h = x (+ r), f32 stats, normed — one VMEM pass."""
    if r_ref is None:
        h = x_ref[...]
    else:
        h = x_ref[...] + r_ref[...]
        h_ref[...] = h
    hf = h.astype(jnp.float32)
    var = jnp.mean(hf * hf, axis=-1, keepdims=True)
    mul = jax.lax.rsqrt(var + config.eps) * w_ref[...]
    o_ref[...] = (hf * mul).astype(o_ref.dtype)


def _norm_forward(config, x, residual, scale):
    """x/residual: [rows, D] (row-padded); scale: [1, D] f32 ->
    (normed [rows, D] out_dtype, h [rows, D] x.dtype)."""
    vma = partition.vma_of(x, residual, scale)
    rows, features = x.shape
    block = config.block_rows
    grid = (rows // block,)
    out_dtype = jnp.dtype(config.out_dtype)
    row_spec = pl.BlockSpec((block, features), lambda i: (i, 0))
    w_spec = pl.BlockSpec((1, features), lambda i: (0, 0))
    if residual is None:
        kernel = functools.partial(
            lambda x_ref, w_ref, o_ref, **kw: _fwd_kernel(
                x_ref, None, w_ref, o_ref, None, **kw),
            config=config)
        normed = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[row_spec, w_spec],
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct((rows, features), out_dtype,
                                           vma=vma),
            interpret=config.interpret,
            name=FUSED_RMSNORM,
        )(x, scale)
        return normed, x
    kernel = functools.partial(_fwd_kernel, config=config)
    normed, h = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[row_spec, row_spec, w_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, features), out_dtype,
                                 vma=vma),
            jax.ShapeDtypeStruct((rows, features), x.dtype, vma=vma),
        ],
        interpret=config.interpret,
        name=FUSED_RMSNORM_RESIDUAL,
    )(x, residual, scale)
    return normed, h


def _norm_bwd_math(config, h, scale, g_normed, g_h):
    """Standard RMSNorm gradient in f32 from the saved residual stream:
    dh = g*w*r - h * r^3/D * sum(g*w*h) (+ the h cotangent), with both
    fused-add inputs receiving dh; dscale sums over rows."""
    features = h.shape[-1]
    hf = h.astype(jnp.float32)
    gf = g_normed.astype(jnp.float32)
    w = scale.astype(jnp.float32)
    var = jnp.mean(hf * hf, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + config.eps)
    gw = gf * w
    inner = jnp.sum(gw * hf, axis=-1, keepdims=True)
    dh = gw * r - hf * (r * r * r / features) * inner
    if g_h is not None:
        dh = dh + g_h.astype(jnp.float32)
    dscale = jnp.sum(gf * hf * r, axis=0,
                     keepdims=True).astype(scale.dtype)
    return dh, dscale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_rmsnorm(config, x, scale):
    return _norm_forward(config, x, None, scale)


def _fused_rmsnorm_fwd(config, x, scale):
    out = _norm_forward(config, x, None, scale)
    return out, (x, scale)


def _fused_rmsnorm_bwd(config, residuals, grads):
    x, scale = residuals
    g_normed, g_h = grads
    dh, dscale = _norm_bwd_math(config, x, scale, g_normed, g_h)
    return dh.astype(x.dtype), dscale


_fused_rmsnorm.defvjp(_fused_rmsnorm_fwd, _fused_rmsnorm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_rmsnorm_residual(config, x, residual, scale):
    return _norm_forward(config, x, residual, scale)


def _fused_rmsnorm_residual_fwd(config, x, residual, scale):
    normed, h = _norm_forward(config, x, residual, scale)
    return (normed, h), (h, scale)


def _fused_rmsnorm_residual_bwd(config, residuals, grads):
    h, scale = residuals
    g_normed, g_h = grads
    dh, dscale = _norm_bwd_math(config, h, scale, g_normed, g_h)
    return dh.astype(h.dtype), dh.astype(h.dtype), dscale


_fused_rmsnorm_residual.defvjp(_fused_rmsnorm_residual_fwd,
                               _fused_rmsnorm_residual_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def fused_rmsnorm(x, scale, residual=None, eps=1e-6, out_dtype=None,
                  impl="auto", interpret: Optional[bool] = None,
                  block_rows=None):
    """Dispatching fused RMSNorm(+residual) tail: returns (normed, h).

    x: [..., D]; residual: same shape or None; scale: [D] (the flax
    RMSNorm "scale" param, f32). h = x + residual (the continuing
    residual stream; x itself when residual is None); normed =
    RMSNorm(h) in `out_dtype` (default: h's dtype).

    impl selects the path, from the platform; no environment name does:
      "auto"       the Pallas kernel on a TPU, the lax reference
                   elsewhere; what every model passes;
      "fused"      the kernel wherever it runs: compiled on a TPU, in
                   Pallas interpret mode elsewhere (parity tests,
                   chip_smoke.py);
      "reference"  the lax path (what tests compare against).
    `interpret` overrides that choice of mode and `block_rows` the row
    block (`_BLOCK_ROWS` rows a grid step otherwise); both are for
    tests. Differentiable w.r.t. x, residual, and scale on either
    path. tests/unit/test_kernel_selection.py holds the table.
    """
    features = x.shape[-1]
    if scale.shape != (features,):
        raise ValueError(
            "scale must be [features] = ({},); got {}.".format(
                features, scale.shape))
    if residual is not None and residual.shape != x.shape:
        raise ValueError(
            "residual must match x's shape {}; got {}.".format(
                x.shape, residual.shape))
    if impl == "fused":
        use_kernel = True
    elif impl == "reference":
        use_kernel = False
    else:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return rmsnorm_residual_reference(x, scale, residual=residual,
                                          eps=eps, out_dtype=out_dtype)

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_rows is None:
        block_rows = _BLOCK_ROWS
    if out_dtype is None:
        out_dtype = x.dtype if residual is None else jnp.promote_types(
            x.dtype, residual.dtype)

    def kernel(x, scale, *residual):
        """One device's rows."""
        lead = x.shape[:-1]
        rows = 1
        for dim in lead:
            rows *= dim
        block = min(block_rows, max(rows, 1))
        rows_pad = -(-rows // block) * block
        # eps stays as passed (a static Python scalar — the config is a
        # hashable static kernel arg); a float(...) cast here would
        # read as a host sync to graftlint's jit-chain analysis.
        config = _NormConfig(eps=eps, block_rows=int(block),
                             out_dtype=jnp.dtype(out_dtype).name,
                             interpret=bool(interpret))

        def fold(a):
            a = a.reshape(rows, features)
            if rows_pad != rows:
                # Zero rows: var = 0, rsqrt(eps) finite, output rows 0
                # — sliced away below; pad/slice autodiff owns the
                # edges.
                a = jnp.pad(a, ((0, rows_pad - rows), (0, 0)))
            return a

        # One varying-axes type, cast out here: the cast's transpose is
        # the psum that sums the replicated scale's gradient over the
        # axes the rows are split on.
        xf, w, *rf = partition.common_vma(
            fold(x), scale.astype(jnp.float32)[None, :],
            *(fold(r) for r in residual))
        if rf:
            normed, h = _fused_rmsnorm_residual(config, xf, rf[0], w)
        else:
            normed, h = _fused_rmsnorm(config, xf, w)
        return (normed[:rows].reshape(lead + (features,)),
                h[:rows].reshape(lead + (features,)))

    def plan(mesh):
        """Rows over the data axis, the scale everywhere."""
        rows = partition.rows_spec(mesh, x)
        in_specs = (rows, P()) + ((rows,) if residual is not None
                                  else ())
        return in_specs, (rows, rows), None

    args = (x, scale) + ((residual,) if residual is not None else ())
    return partition.per_shard(kernel, args, plan, interpret)

