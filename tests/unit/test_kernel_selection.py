"""Which path each kernel dispatcher enters, row by row of its table.

`fused_rmsnorm`, `fused_swiglu` and `paged_attention` choose between a
Pallas kernel, a lax form and a reference from `impl`, the platform and
the shapes. Every cell of the benchmark runs `impl="auto"`, so a
dispatcher that fell through to the reference on a TPU would pass every
parity test and show only as a slower cell. Here the entry points are
recorders and `jax.default_backend` is patched: nothing is computed or
compiled, the operands are shapes (numpy arrays for the paged op, which
casts its table before it dispatches).
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.ops import fused_mlp
from cloud_tpu.ops import fused_norm
from cloud_tpu.ops import partition

# `cloud_tpu.ops.paged_attention` the attribute is the function.
pa = importlib.import_module("cloud_tpu.ops.paged_attention")

COMPILED = ("kernel", False)       # per_shard(..., interpret=False)
INTERPRETED = ("kernel", True)
PAGED_COMPILED = ("_paged_call", False)
REFERENCE = ("reference", None)
WALK = ("walk", None)


def _struct(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _rmsnorm(impl):
    x = _struct((8, 256))
    return fused_norm.fused_rmsnorm(x, _struct((256,), jnp.float32),
                                    residual=x, impl=impl)


def _swiglu(rows, features, d_ff, impl):
    return fused_mlp.fused_swiglu(
        _struct((rows, features)), _struct((features, d_ff)),
        _struct((features, d_ff)), _struct((d_ff, features)), impl=impl)


def _paged_operands():
    slots, heads, head_dim, page_size, pages = 2, 4, 8, 16, 3
    pool = np.zeros((slots * pages + 1, page_size, heads * head_dim),
                    np.float32)
    return (np.zeros((slots, 1, heads, head_dim), np.float32), pool,
            pool, np.zeros((slots, pages), np.int32),
            np.ones((slots, 1, pages * page_size), bool))


def _paged(impl):
    return pa.paged_attention(*_paged_operands(), impl=impl)


def _paged_decode():
    """The kernel path's own platform rule, under `interpret`."""
    return pa.paged_decode_attention(*_paged_operands(), interpret=None)


_BY_IMPL = [("auto", "cpu", REFERENCE), ("auto", "tpu", COMPILED),
            ("fused", "cpu", INTERPRETED), ("fused", "tpu", COMPILED),
            ("reference", "cpu", REFERENCE),
            ("reference", "tpu", REFERENCE)]

# The train cell's widths, where the kernel fits, and `kernel_fits`'s
# own case: 128 rows at 6144 features do not fit VMEM, and K-EXAONE's
# prefill leaves them to XLA's matmuls.
_TRAIN_MLP = functools.partial(_swiglu, 4096, 896, 4864)
_WIDE_MLP = functools.partial(_swiglu, 128, 6144, 18432)

# (id, call, backend, entered)
TABLE = (
    [("rmsnorm-%s-%s" % (impl, backend),
      functools.partial(_rmsnorm, impl), backend, want)
     for impl, backend, want in _BY_IMPL]
    + [("swiglu-%s-%s" % (impl, backend),
        functools.partial(_TRAIN_MLP, impl), backend, want)
       for impl, backend, want in _BY_IMPL]
    + [("swiglu-auto-tpu-does-not-fit",
        functools.partial(_WIDE_MLP, "auto"), "tpu", REFERENCE),
       ("paged-auto-cpu", functools.partial(_paged, "auto"), "cpu",
        REFERENCE),
       ("paged-auto-tpu", functools.partial(_paged, "auto"), "tpu",
        PAGED_COMPILED),
       ("paged-decode-cpu", _paged_decode, "cpu", WALK),
       ("paged-decode-tpu", _paged_decode, "tpu", PAGED_COMPILED)])


@pytest.mark.parametrize("call,backend,want",
                         [row[1:] for row in TABLE],
                         ids=[row[0] for row in TABLE])
def test_dispatcher_enters(monkeypatch, call, backend, want):
    entered = []

    def recorder(name):
        return lambda *args, **kwargs: entered.append((name, None))

    def per_shard(fn, args, plan, interpret=False):
        entered.append((getattr(fn, "func", fn).__name__, interpret))

    monkeypatch.delenv("CLOUD_TPU_PAGED_KERNEL", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(partition, "per_shard", per_shard)
    monkeypatch.setattr(fused_norm, "rmsnorm_residual_reference",
                        recorder("reference"))
    monkeypatch.setattr(fused_mlp, "swiglu_reference",
                        recorder("reference"))
    monkeypatch.setattr(pa, "paged_attention_reference",
                        recorder("reference"))
    monkeypatch.setattr(pa, "_paged_walk_lax", recorder("walk"))
    call()
    assert entered == [want]
