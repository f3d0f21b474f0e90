"""The Mamba-2 decode step's state update as one Pallas TPU kernel.

A decode tick advances every slot's recurrent state by one token:

    S <- exp(dt A) S + (dt x) (x) B        S: [heads, head_dim, state]
    y  = S C + D x

per head, with `A`, `D` a scalar a head, `dt` a scalar a head and slot,
and `B`, `C` shared by the heads of a group. The state is float32 and
large (128 x 64 x 128 x 4 B = 4.2 MB a slot and layer at the published
widths), everything else is small, so the step is a stream over the
state: the kernel reads it once and writes it once in place
(`input_output_aliases`), and computes `y` from the block while it is
in VMEM.

Layout. The state is kept PACKED: `[slots, heads / pack, state, pack x
head_dim]`, `pack` consecutive heads of one group side by side on the
lanes (2 heads of 64 at the published widths: 128 lanes). With the
state's `state` axis on the sublanes every operand of the update is a
lane-dense row (`dt x`, `exp(dt A)`, `D x`, and `y` itself are rows of
`pack x head_dim`) and the sum over the state axis is a sum of vector
registers; `B` and `C`, which vary along the sublanes, are turned from
rows into columns once a group and slot (a masked lane reduction). No
operand has a minor dimension under 128 at the published widths, so
XLA hands them over without a copy. `pack_state` / `unpack_state` go
between this layout and the natural `[.., heads, head_dim, state]` one
of the chunked scan (models/mamba2.py).

A slot whose `dt` is 0 (an inactive slot of the tick) keeps its state
bit for bit: `exp(0) S + B 0`.

Off the TPU `impl="auto"` takes the `jax.numpy` form below, which works
in the same packed layout; a forced kernel runs interpreted (tests).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: The kernel's declared name (`pl.pallas_call(name=)`; table in
#: monitoring/spans.py).
SSM_DECODE_UPDATE = "ssm_decode_update"

_LANES = 128
# A grid step holds one slot's groups up to this many bytes of state
# (read and written, each double-buffered: four such blocks in VMEM).
_BLOCK_BYTES = 4 << 20
_VMEM_LIMIT = 48 << 20


def pack_factor(heads, groups, head_dim):
    """Heads of one group laid side by side on the lanes: the most that
    fit 128 lanes and divide a group's heads."""
    per_group = heads // groups
    best = 1
    for pack in range(1, max(1, _LANES // head_dim) + 1):
        if per_group % pack == 0:
            best = pack
    return best


def packed_shape(heads, groups, head_dim, state):
    """Shape of one slot's packed state."""
    pack = pack_factor(heads, groups, head_dim)
    return (heads // pack, state, pack * head_dim)


def pack_state(state, groups):
    """`[.., heads, head_dim, state]` -> the packed layout."""
    *lead, heads, head_dim, n = state.shape
    pack = pack_factor(heads, groups, head_dim)
    x = state.reshape(*lead, heads // pack, pack, head_dim, n)
    x = jnp.moveaxis(x, -1, -3)           # [.., rows, n, pack, head_dim]
    return x.reshape(*lead, heads // pack, n, pack * head_dim)


def unpack_state(packed, heads, groups, head_dim):
    """The packed layout -> `[.., heads, head_dim, state]`."""
    *lead, rows, n, lanes = packed.shape
    pack = lanes // head_dim
    x = packed.reshape(*lead, rows, n, pack, head_dim)
    x = jnp.moveaxis(x, -3, -1)           # [.., rows, pack, head_dim, n]
    return x.reshape(*lead, heads, head_dim, n)


def _rows(x, dt, a, d, rows):
    """The update's row operands in the packed layout, each `[slots,
    rows, pack x head_dim]` float32: x, dt and A and D spread over a
    head's lanes."""
    slots, heads, head_dim = x.shape
    lanes = heads // rows * head_dim
    spread = lambda v: jnp.broadcast_to(
        v.astype(jnp.float32)[..., None],
        v.shape + (head_dim,)).reshape(*v.shape[:-1], rows, lanes)
    return (x.astype(jnp.float32).reshape(slots, rows, lanes),
            spread(dt), spread(a), spread(d))


def ssm_decode_update_reference(state, x, dt, a, d, b, c):
    """`jax.numpy` form of `ssm_decode_update`, in the packed layout."""
    slots, rows, n, lanes = state.shape
    groups = b.shape[1]
    xr, dtr, ar, dr = _rows(x, dt, a, d, rows)
    per_group = rows // groups
    column = lambda v: jnp.repeat(v.astype(jnp.float32), per_group,
                                  axis=1)[..., None]     # [S, rows, n, 1]
    new = (jnp.exp(dtr * ar[None])[:, :, None, :] * state
           + column(b) * (dtr * xr)[:, :, None, :])
    y = jnp.sum(new * column(c), axis=2) + dr[None] * xr
    return y.reshape(x.shape), new


def _kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, s_ref,
            y_ref, o_ref, *, groups_per_step, rows_per_group):
    n = s_ref.shape[2]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    first_group = pl.program_id(1) * groups_per_step

    def column(ref, group):
        # A group's row [1, n] as a column [n, 1]: what stands on the
        # diagonal of the row spread over the sublanes.
        row = ref[0, pl.ds(group, 1), :].astype(jnp.float32)
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    for g in range(groups_per_step):
        b_col = column(b_ref, first_group + g)
        c_col = column(c_ref, first_group + g)
        for r in range(g * rows_per_group, (g + 1) * rows_per_group):
            at = slice(r, r + 1)
            x = x_ref[0, at, :]
            dt = dt_ref[0, at, :]
            new = (jnp.exp(dt * a_ref[at, :]) * s_ref[0, r]
                   + b_col * (dt * x))
            o_ref[0, r] = new
            y_ref[0, at, :] = (jnp.sum(new * c_col, axis=0, keepdims=True)
                               + d_ref[at, :] * x)


def _groups_per_step(groups, rows_per_group, n, lanes):
    """Whole groups of one slot a grid step: as many as `_BLOCK_BYTES`
    of state hold, a divisor of `groups`; the row operands' block then
    has a multiple of 8 sublanes, or all of them."""
    group_bytes = rows_per_group * n * lanes * 4
    for k in range(groups, 0, -1):
        if groups % k or k * group_bytes > _BLOCK_BYTES:
            continue
        if k == groups or (k * rows_per_group) % 8 == 0:
            return k
    return None


def kernel_fits(state_shape, groups):
    """Whether the kernel takes this shape: lane-dense rows and a state
    axis that fills whole sublane tiles."""
    _, rows, n, lanes = state_shape
    return (lanes % _LANES == 0 and n % _LANES == 0 and rows % groups == 0
            and _groups_per_step(groups, rows // groups, n, lanes)
            is not None)


def _update_kernel(state, x, dt, a, d, b, c, interpret=False):
    slots, rows, n, lanes = state.shape
    groups = b.shape[1]
    per_group = rows // groups
    k = _groups_per_step(groups, per_group, n, lanes) or groups
    xr, dtr, ar, dr = _rows(x, dt, a, d, rows)
    step_rows = k * per_group
    row_spec = pl.BlockSpec((1, step_rows, lanes), lambda s, j: (s, j, 0))
    head_spec = pl.BlockSpec((step_rows, lanes), lambda s, j: (j, 0))
    group_spec = pl.BlockSpec((1, groups, n), lambda s, j: (s, 0, 0))
    state_spec = pl.BlockSpec((1, step_rows, n, lanes),
                              lambda s, j: (s, j, 0, 0))
    y, new = pl.pallas_call(
        functools.partial(_kernel, groups_per_step=k,
                          rows_per_group=per_group),
        grid=(slots, groups // k),
        in_specs=[row_spec, row_spec, head_spec, head_spec, group_spec,
                  group_spec, state_spec],
        out_specs=[row_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((slots, rows, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=SSM_DECODE_UPDATE,
    )(xr, dtr, ar, dr, b.astype(jnp.float32), c.astype(jnp.float32), state)
    return y.reshape(x.shape), new


def ssm_decode_update(state, x, dt, a, d, b, c, impl="auto",
                      interpret=None):
    """One token of every slot through the state-space recurrence.

    state: packed float32 `[slots, heads / pack, n, pack x head_dim]`
    (`pack_state`); x `[slots, heads, head_dim]`; dt `[slots, heads]`
    (after softplus; 0 keeps a slot's state); a, d `[heads]` (A is
    negative); b, c `[slots, groups, n]`. Returns (y `[slots, heads,
    head_dim]` float32, the new state).

    impl: "auto" (the kernel on a TPU where `kernel_fits`, the
    `jax.numpy` form elsewhere), "kernel" or "reference". A forced
    kernel runs interpreted off the TPU.
    """
    if impl not in ("auto", "kernel", "reference"):
        raise ValueError(
            "impl must be 'auto', 'kernel' or 'reference'; got "
            "{!r}.".format(impl))
    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        impl = ("kernel" if on_tpu and kernel_fits(state.shape, b.shape[1])
                else "reference")
    if impl == "reference":
        return ssm_decode_update_reference(state, x, dt, a, d, b, c)
    if interpret is None:
        interpret = not on_tpu
    return _update_kernel(state, x, dt, a, d, b, c,
                          interpret=bool(interpret))
