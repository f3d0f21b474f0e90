"""Fused SwiGLU MLP tail as a Pallas TPU kernel.

The gated MLP `down(act(gate(x)) * up(x))` is the last unfused hot op
in the Llama block: written as three `nn.Dense` calls it materializes
the two `[rows, d_ff]` projections and the gated product in HBM between
matmuls. This kernel walks a (row block, d_ff tile) grid: per step it
projects the row block through one `[D, tile]` slice of gate and up,
applies the gate nonlinearity and the product, and adds that tile's
down projection into an f32 VMEM accumulator — so the `[rows, d_ff]`
intermediates never touch HBM and no weight has to fit VMEM whole
(three whole matrices are 26 MB at Qwen2.5-0.5B widths, 270 MB at 7B,
against 16 MiB of scoped VMEM).

Numerics mirror the flax module: inputs and kernels are cast to the
compute dtype (flax `promote_dtype` with `dtype=compute_dtype`), each
projection accumulates in f32 and rounds to the compute dtype (what
`lax.dot_general` does with default precision), and the activation
runs on the projected compute-dtype values. The down projection's sum
over d_ff is kept in f32 across tiles and rounded once, so swapping
the unfused SwiGLU for this op is tolerance-level: f32 differs by the
tile order of one sum, bf16 by where the VPU rounds.

Backward is `jax.custom_vjp` with the standard gated-MLP gradient in
f32 from the saved (x, weights): dh = dy@Wd^T, du = dh*act(g),
da = dh*u, dg via the activation's own vjp, dx = dg@Wg^T + du@Wu^T,
and the three kernel grads from the corresponding outer products. The
backward runs as plain lax — decode never differentiates, and the
single-pass claim is for the forward serving/training hot path.

On non-TPU backends a forced kernel runs in Pallas interpret mode, so
parity tests exercise the same code path CPU-side. Under a device mesh
the kernel runs per shard (ops/partition.py). Which path a call takes
is `fused_swiglu`'s rule over `impl`, the platform and the shapes.
"""

import functools
import math
import types
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from cloud_tpu.ops import partition

#: The kernel's declared name (`pl.pallas_call(name=)`; table in
#: monitoring/spans.py). The backward is plain lax.
FUSED_SWIGLU_FWD = "fused_swiglu_fwd"

_BLOCK_ROWS = 128
_LANES = 128
# Double-buffered gate/up/down tiles may take this much of the 16 MiB
# scoped VMEM; the row block, accumulator and projections need the rest.
_WEIGHT_TILE_BYTES = 6 * 1024 * 1024

# Mirrors llama._GATE_ACTIVATIONS (ops must not import models); flax
# nn.silu/nn.gelu ARE jax.nn.silu/jax.nn.gelu, so the reference stays
# math-for-math the module. Immutable: traced functions bake the
# lookup in at trace time, so the table must never change underneath
# a warm executable.
_ACTIVATIONS = types.MappingProxyType({
    "silu": jax.nn.silu,
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
})


#: Scoped VMEM a kernel's blocks may take on the TPU.
_SCOPED_VMEM_BYTES = 16 * 1024 * 1024


def kernel_fits(rows, features, d_out, itemsize, block_rows=_BLOCK_ROWS):
    """Whether one grid step's blocks fit scoped VMEM at the narrowest
    d_ff tile `_ff_tile` falls back to: the three double-buffered
    weight tiles, and a row the double-buffered input and output
    blocks and the float32 accumulator. They do not at 6144 features
    and 128 rows (18.9 MB; Mosaic refuses the call), while a decode
    tick's few rows do."""
    block = min(block_rows, max(rows, 1))
    weights = 2 * (2 * features + d_out) * _LANES * itemsize
    per_row = 2 * features * itemsize + 2 * d_out * itemsize + 4 * d_out
    return weights + block * per_row <= _SCOPED_VMEM_BYTES


class _MLPConfig(NamedTuple):
    activation: str
    block_rows: int
    block_ff: int
    out_dtype: str   # dtype name (hashable for the custom_vjp config)
    interpret: bool


def _ff_tile(features, d_ff, d_out, itemsize):
    """(tile, padded d_ff): the widest lane-multiple tile that divides
    d_ff and keeps the three double-buffered weight tiles inside
    `_WEIGHT_TILE_BYTES`. A d_ff of at most one tile is its own block;
    one that no lane multiple divides is zero-padded to the next."""
    if d_ff <= _LANES:
        return d_ff, d_ff
    padded = -(-d_ff // _LANES) * _LANES
    per_column = 2 * (2 * features + d_out) * itemsize
    for tile in (512, 256):
        if padded % tile == 0 and tile * per_column <= _WEIGHT_TILE_BYTES:
            return tile, padded
    return _LANES, padded


def _contract(x, w):
    """The exact `nn.Dense(use_bias=False)` contraction: last axis of x
    against axis 0 of w, default precision."""
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())))


def swiglu_reference(x, w_gate, w_up, w_down, activation="silu",
                     compute_dtype=None):
    """Pure-lax gated MLP: down(act(gate(x)) * up(x)).

    Math-for-math the flax SwiGLU module (three bias-free `nn.Dense`
    with `dtype=compute_dtype`): everything is cast to `compute_dtype`
    up front (flax `promote_dtype` semantics; the promoted type of
    x/w_gate when None), then three default-precision dot_generals with
    the activation on the projected values.
    """
    try:
        act = _ACTIVATIONS[activation]
    except KeyError:
        raise ValueError(
            "Unknown mlp activation {!r}; expected one of {}.".format(
                activation, sorted(_ACTIVATIONS)))
    if compute_dtype is None:
        compute_dtype = jnp.promote_types(x.dtype, w_gate.dtype)
    x = x.astype(compute_dtype)
    g = _contract(x, w_gate.astype(compute_dtype))
    u = _contract(x, w_up.astype(compute_dtype))
    return _contract(act(g) * u, w_down.astype(compute_dtype))


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *,
                config, num_ff):
    """One (row block, d_ff tile) step: both projections of the tile,
    the gated product, and the tile's share of the down projection
    added into the f32 accumulator."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    act = _ACTIVATIONS[config.activation]
    x = x_ref[...]
    # Round each projection to the compute dtype (the reference's
    # rounding point), then gate in f32: the VPU has no narrower math,
    # and Mosaic rejects the bf16 form of the activations' constants.
    project = lambda w_ref: jnp.dot(
        x, w_ref[...], preferred_element_type=jnp.float32).astype(
            x.dtype).astype(jnp.float32)
    h = (act(project(wg_ref)) * project(wu_ref)).astype(x.dtype)
    acc_ref[...] += jnp.dot(h, wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(j == num_ff - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _swiglu_forward(config, x, w_gate, w_up, w_down):
    """x: [rows, D] (row-padded, compute dtype); weights compute dtype,
    d_ff a multiple of the tile -> [rows, D_out] out_dtype."""
    vma = partition.vma_of(x, w_gate, w_up, w_down)
    rows, features = x.shape
    d_ff = w_gate.shape[1]
    d_out = w_down.shape[1]
    block = config.block_rows
    tile = config.block_ff
    num_ff = d_ff // tile
    kernel = functools.partial(_fwd_kernel, config=config,
                               num_ff=num_ff)
    return pl.pallas_call(
        kernel,
        grid=(rows // block, num_ff),
        in_specs=[
            pl.BlockSpec((block, features), lambda i, j: (i, 0)),
            pl.BlockSpec((features, tile), lambda i, j: (0, j)),
            pl.BlockSpec((features, tile), lambda i, j: (0, j)),
            pl.BlockSpec((tile, d_out), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block, d_out), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (rows, d_out), jnp.dtype(config.out_dtype), vma=vma),
        scratch_shapes=[pltpu.VMEM((block, d_out), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=config.interpret,
        name=FUSED_SWIGLU_FWD,
    )(x, w_gate, w_up, w_down)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_swiglu(config, x, w_gate, w_up, w_down):
    return _swiglu_forward(config, x, w_gate, w_up, w_down)


def _fused_swiglu_fwd(config, x, w_gate, w_up, w_down):
    out = _swiglu_forward(config, x, w_gate, w_up, w_down)
    return out, (x, w_gate, w_up, w_down)


def _fused_swiglu_bwd(config, residuals, dy):
    x, w_gate, w_up, w_down = residuals
    act = _ACTIVATIONS[config.activation]
    xf = x.astype(jnp.float32)
    wgf = w_gate.astype(jnp.float32)
    wuf = w_up.astype(jnp.float32)
    wdf = w_down.astype(jnp.float32)
    g = xf @ wgf
    u = xf @ wuf
    a, act_vjp = jax.vjp(act, g)
    dyf = dy.astype(jnp.float32)
    dh = dyf @ wdf.T
    dwd = (a * u).T @ dyf
    du = dh * a
    da = dh * u
    dg = act_vjp(da)[0]
    dx = dg @ wgf.T + du @ wuf.T
    dwg = xf.T @ dg
    dwu = xf.T @ du
    return (dx.astype(x.dtype), dwg.astype(w_gate.dtype),
            dwu.astype(w_up.dtype), dwd.astype(w_down.dtype))


_fused_swiglu.defvjp(_fused_swiglu_fwd, _fused_swiglu_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def fused_swiglu(x, w_gate, w_up, w_down, activation="silu",
                 compute_dtype=None, impl="auto",
                 interpret: Optional[bool] = None, block_rows=None):
    """Dispatching fused SwiGLU tail: down(act(gate(x)) * up(x)).

    x: [..., D]; w_gate/w_up: [D, F]; w_down: [F, D_out] (the bare
    `kernel` params of the three bias-free Dense projections, any
    param dtype — cast to `compute_dtype` here, flax-style).

    impl selects the path, from the platform and the shapes; no
    environment name does:
      "auto"       the Pallas kernel on a TPU where a step's blocks
                   fit VMEM (`kernel_fits`: not a many-row call at
                   6144 features, whose products XLA's own matmuls
                   run), else the lax reference; what every model passes;
      "fused"      the kernel wherever it runs: compiled on a TPU, in
                   Pallas interpret mode elsewhere (parity tests,
                   chip_smoke.py);
      "reference"  the lax path (what tests compare against).
    `interpret` overrides that choice of mode and `block_rows` the row
    block (`_BLOCK_ROWS` otherwise); both are for tests.
    Differentiable w.r.t. x and all three weights on either path.
    """
    features = x.shape[-1]
    if w_gate.ndim != 2 or w_gate.shape[0] != features:
        raise ValueError(
            "w_gate must be [features={}, d_ff]; got {}.".format(
                features, w_gate.shape))
    if w_up.shape != w_gate.shape:
        raise ValueError(
            "w_up must match w_gate's shape {}; got {}.".format(
                w_gate.shape, w_up.shape))
    if w_down.ndim != 2 or w_down.shape[0] != w_gate.shape[1]:
        raise ValueError(
            "w_down must be [d_ff={}, d_out]; got {}.".format(
                w_gate.shape[1], w_down.shape))
    if impl == "fused":
        use_kernel = True
    elif impl == "reference":
        use_kernel = False
    else:
        use_kernel = jax.default_backend() == "tpu" and kernel_fits(
            math.prod(x.shape[:-1]), features, w_down.shape[1],
            jnp.dtype(compute_dtype or jnp.promote_types(
                x.dtype, w_gate.dtype)).itemsize)
    if not use_kernel:
        return swiglu_reference(x, w_gate, w_up, w_down,
                                activation=activation,
                                compute_dtype=compute_dtype)

    if activation not in _ACTIVATIONS:
        raise ValueError(
            "Unknown mlp activation {!r}; expected one of {}.".format(
                activation, sorted(_ACTIVATIONS)))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_rows is None:
        block_rows = _BLOCK_ROWS
    if compute_dtype is None:
        compute_dtype = jnp.promote_types(x.dtype, w_gate.dtype)
    compute_dtype = jnp.dtype(compute_dtype)

    def kernel(x, w_gate, w_up, w_down):
        """One device's rows against its slice of d_ff."""
        lead = x.shape[:-1]
        rows = 1
        for dim in lead:
            rows *= dim
        block = min(block_rows, max(rows, 1))
        rows_pad = -(-rows // block) * block
        d_ff, d_out = w_down.shape
        tile, ff_pad = _ff_tile(features, d_ff, d_out,
                                compute_dtype.itemsize)
        config = _MLPConfig(activation=activation,
                            block_rows=int(block), block_ff=tile,
                            out_dtype=compute_dtype.name,
                            interpret=bool(interpret))
        folded = x.astype(compute_dtype).reshape(rows, features)
        # Zero rows project to zero, and zero d_ff columns gate to
        # act(0) * 0 = 0 against zero down rows — both sliced away or
        # summed as nothing; pad/slice autodiff owns the edges.
        folded = jnp.pad(folded, ((0, rows_pad - rows), (0, 0)))
        cols = ((0, 0), (0, ff_pad - d_ff))
        # Cast to one varying-axes type out here: the cast's transpose
        # is the psum that sums a replicated operand's gradient over
        # the axes the others are split on.
        operands = partition.common_vma(
            folded,
            jnp.pad(w_gate.astype(compute_dtype), cols),
            jnp.pad(w_up.astype(compute_dtype), cols),
            jnp.pad(w_down.astype(compute_dtype), cols[::-1]))
        out = _fused_swiglu(config, *operands)
        return out[:rows].reshape(lead + (d_out,))

    def plan(mesh):
        """Rows over the data axis; d_ff over the model axis (the
        Megatron layout of gate/up columns and down rows), which leaves
        each device a partial down projection to sum."""
        tp = partition.model_axis(mesh, w_gate.shape[1])
        rows = partition.rows_spec(mesh, x)
        return ((rows, P(None, tp), P(None, tp), P(tp, None)), rows,
                tp)

    return partition.per_shard(kernel, (x, w_gate, w_up, w_down), plan,
                               interpret)

