"""What `auto` selects on a TPU must lower (and, where libtpu is
installed, compile) for a TPU — checked here without a chip.

On the CPU `impl="auto"` picks the references and forced kernels run
interpreted, which checks neither Mosaic's block-shape rules nor VMEM
nor that a kernel inside a multi-device jit is partitioned by hand. So
this module patches `jax.default_backend` to "tpu", lowers with
`lowering_platforms=("tpu",)` at the widths chip_smoke.py runs, alone
and inside a 4-device mesh jit, and AOT-compiles against a deviceless
v5e topology when one can be described. It also runs the kernels
interpreted under a CPU mesh against the unsharded references, since
lowering says nothing about the values a per-shard call returns.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import chip_smoke
from cloud_tpu import ops
from cloud_tpu.models import LlamaLM, TransformerLM
from cloud_tpu.ops import fused_mlp, fused_norm

BF16 = jnp.bfloat16
F32 = jnp.float32
S = jax.ShapeDtypeStruct
TRAIN = chip_smoke.QWEN25_05B
SERVE = chip_smoke.GPT2_XL
SEQ = 1024


@pytest.fixture
def as_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@functools.lru_cache(maxsize=None)
def _tpu_topology():
    """Four deviceless v5e chips, or None where libtpu cannot describe
    them (then only lowering is checked)."""
    try:
        from jax.experimental import topologies
        return tuple(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices)
    except Exception:  # no libtpu in this installation
        return None


def _meshes(devices):
    """(label, mesh or None): one device, then dp x tp over four."""
    yield "single", None
    yield "dp2xtp2", Mesh(np.array(devices[:4]).reshape(2, 2),
                          ("dp", "tp"))


def _lower(fn, specs, mesh, shardings):
    """Lowers `fn` for the TPU platform: bare, or jitted over `mesh`
    with the given input shardings and `mesh` ambient."""
    if mesh is None:
        return jax.jit(fn).trace(*specs).lower(
            lowering_platforms=("tpu",))
    in_shardings = tuple(NamedSharding(mesh, spec) for spec in shardings)
    with mesh:
        return jax.jit(fn, in_shardings=in_shardings).trace(
            *specs).lower(lowering_platforms=("tpu",))


def _kernel_cases():
    """(name, fn, specs, shardings) for every kernel `auto` dispatches
    to, fwd+bwd where the op is differentiated in training."""
    heads, kv = TRAIN["num_heads"], TRAIN["num_kv_heads"]
    hd = TRAIN["d_model"] // heads
    d, ff = TRAIN["d_model"], TRAIN["d_ff"]
    total = lambda tree: sum(jnp.sum(leaf.astype(F32)) for leaf in
                             jax.tree_util.tree_leaves(tree))
    qkv = (S((4, SEQ, heads, hd), BF16), S((4, SEQ, kv, hd), BF16),
           S((4, SEQ, kv, hd), BF16))
    by_batch = P("dp", None, None, None)
    yield ("flash", jax.grad(
        lambda q, k, v: total(ops.attention(q, k, v)), (0, 1, 2)),
        qkv, (by_batch,) * 3)
    yield ("flash_masked", jax.grad(
        lambda q, k, v, m: total(ops.attention(q, k, v, mask=m)),
        (0, 1, 2)),
        qkv + (S((4, SEQ), jnp.bool_),), (by_batch,) * 3 + (P("dp"),))
    # The serving prefill's call (`GQAttention._decode_attention`) at
    # K-EXAONE's widths: forward only, a 4096-row frame, the cache's
    # key mask, a full layer and a window-128 layer.
    frame = (S((1, 4096, 64, 128), BF16), S((1, 4096, 8, 128), BF16),
             S((1, 4096, 8, 128), BF16), S((1, 4096), jnp.bool_))
    by_head = (P(None, None, "tp", None),) * 3 + (P(),)
    for name, window in (("flash_prefill_full", None),
                         ("flash_prefill_window", 128)):
        yield (name, lambda q, k, v, m, window=window: ops.attention(
            q, k, v, mask=m, window=window), frame, by_head)
    yield ("fused_rmsnorm", jax.grad(
        lambda x, r, s: total(ops.fused_rmsnorm(x, s, residual=r)),
        (0, 1, 2)),
        (S((4, SEQ, d), BF16), S((4, SEQ, d), BF16), S((d,), F32)),
        (P("dp"), P("dp"), P()))
    # value_and_grad: the backward is plain lax on the saved inputs,
    # so a bare grad would drop the forward kernel as dead code.
    yield ("fused_swiglu", jax.value_and_grad(
        lambda x, g, u, w: total(ops.fused_swiglu(
            x, g, u, w, compute_dtype=BF16)), (0, 1, 2, 3)),
        (S((4, SEQ, d), BF16), S((d, ff), F32), S((d, ff), F32),
         S((ff, d), F32)),
        (P("dp"), P(None, "tp"), P(None, "tp"), P("tp", None)))
    # GPT-2 XL has 25 heads; 24 here so they split over tp = 2.
    heads, hd, slots, page = 24, 64, 8, 16
    ppn = SERVE["max_seq_len"] // page
    pages = slots * ppn + 1
    call = (S((slots, 1, heads, hd), BF16),
            S((pages, page, heads * hd), BF16),
            S((pages, page, heads * hd), BF16),
            S((slots, ppn), jnp.int32),
            S((slots, 1, ppn * page), jnp.bool_))
    by_head = (P(None, None, "tp", None), P(None, None, "tp"),
               P(None, None, "tp"), P(), P())
    yield "paged_bf16", ops.paged_attention, call, by_head
    int8 = S((pages, page, heads * hd), jnp.int8)
    yield ("paged_int8",
           lambda q, kp, vp, pt, al, ks, vs: ops.paged_attention(
               q, kp, vp, pt, al, key_scales=ks, value_scales=vs),
           (call[0], int8, int8) + call[3:]
           + (S((pages, heads), F32),) * 2,
           by_head + (P(None, "tp"),) * 2)


@pytest.mark.parametrize("case", list(_kernel_cases()),
                         ids=lambda case: case[0])
def test_auto_kernels_lower_for_tpu(as_tpu, case):
    _, fn, specs, shardings = case
    for label, mesh in _meshes(jax.devices()):
        text = _lower(fn, specs, mesh, shardings).as_text()
        assert "tpu_custom_call" in text, (
            "{}: `auto` did not reach the Mosaic kernel".format(label))


@pytest.mark.parametrize("case", list(_kernel_cases()),
                         ids=lambda case: case[0])
def test_auto_kernels_compile_for_v5e(as_tpu, case):
    """Lowering accepts a kernel whose blocks overflow VMEM; only the
    compiler refuses it."""
    devices = _tpu_topology()
    if devices is None:
        pytest.skip("no libtpu: cannot describe a TPU topology")
    _, fn, specs, shardings = case
    for _, mesh in _meshes(devices):
        if mesh is None:  # compile needs TPU devices even for one
            mesh = Mesh(np.array(devices[:1]), ("one",))
            placed = (P(),) * len(specs)
        else:
            placed = shardings
        _lower(fn, specs, mesh, placed).compile()


def _train_step(model, mesh):
    """grad of the LM loss w.r.t. abstract params of `model`."""
    tokens = S((4, SEQ), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, SEQ), jnp.int32))["params"]

    def loss(params, tokens):
        logits = model.apply({"params": params}, tokens)
        return jnp.mean(logits[..., 0])

    replicated = jax.tree_util.tree_map(lambda _: P(), params)
    return (jax.grad(loss), (params, tokens),
            (replicated, P("dp")) if mesh is not None else None)


@pytest.mark.parametrize("family", ["llama", "transformer"])
def test_models_lower_for_tpu_with_auto(as_tpu, family):
    """One layer at the smoke's published widths, train step, alone and
    under the mesh: the kernels arrive through the model code."""
    if family == "llama":
        model = LlamaLM(compute_dtype=BF16,
                        **dict(TRAIN, num_layers=1))
        expected = 5  # flash fwd, dq, dkdv + norm x3 + swiglu >= 5
    else:
        model = TransformerLM(compute_dtype=BF16,
                              **dict(SERVE, num_layers=1, num_heads=20))
        expected = 3  # flash fwd, dq, dkdv
    for label, mesh in _meshes(jax.devices()):
        fn, specs, shardings = _train_step(model, mesh)
        if mesh is None:
            lowered = jax.jit(fn).trace(*specs).lower(
                lowering_platforms=("tpu",))
        else:
            to_sharding = lambda spec: NamedSharding(mesh, spec)
            with mesh:
                lowered = jax.jit(fn, in_shardings=jax.tree_util.tree_map(
                    to_sharding, shardings,
                    is_leaf=lambda x: isinstance(x, P))).trace(
                        *specs).lower(lowering_platforms=("tpu",))
        count = lowered.as_text().count("tpu_custom_call")
        assert count >= expected, (label, count)


@pytest.mark.parametrize("page_dtype", ["", "int8"])
def test_paged_decode_lowers_for_tpu_with_auto(as_tpu, page_dtype):
    """The serving tick's model call — `TransformerLM(decode=True,
    kv_page_size=16)` at GPT-2 XL widths — over bf16 and int8 pages."""
    slots, page = 8, 16
    model = TransformerLM(
        compute_dtype=BF16, decode=True, kv_page_size=page,
        kv_num_pages=slots * (SERVE["max_seq_len"] // page) + 1,
        kv_page_dtype=page_dtype, **dict(SERVE, num_layers=1))
    variables = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((slots, 1), jnp.int32))

    def tick(variables, tokens, active):
        return model.apply(variables, tokens, active, mutable=["cache"])

    lowered = jax.jit(tick).trace(
        variables, S((slots, 1), jnp.int32),
        S((slots, 1), jnp.bool_)).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
    devices = _tpu_topology()
    if devices is not None:
        one = NamedSharding(Mesh(np.array(devices[:1]), ("one",)), P())
        jax.jit(tick, in_shardings=one).trace(
            variables, S((slots, 1), jnp.int32),
            S((slots, 1), jnp.bool_)).lower(
                lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("seq", [1, 4])
@pytest.mark.parametrize("page_dtype", ["bf16", "int8"])
def test_paged_kernel_compiles_at_the_cells_geometry(as_tpu, page_dtype,
                                                     seq):
    """The serve cells' call — 16 slots, 64 pages of 16 tokens, 25
    heads x 64 — compiles for a v5e with no chip, as one Mosaic custom
    call under the name the benchmark's reader looks for; so does the
    verify window (`seq` 4) and the int8 pool."""
    devices = _tpu_topology()
    if devices is None:
        pytest.skip("no libtpu to describe a v5e")
    slots, ppn, page, heads, hd = 16, 64, 16, 25, 64
    pages = slots * ppn + 1
    pool = S((pages, page, heads * hd),
             BF16 if page_dtype == "bf16" else jnp.int8)
    specs = [S((slots, seq, heads, hd), BF16), pool, pool,
             S((slots, ppn), jnp.int32),
             S((slots, seq, ppn * page), jnp.bool_)]
    fn = ops.paged_attention
    if page_dtype == "int8":
        specs += [S((pages, heads), F32)] * 2
        fn = lambda q, kp, vp, pt, al, ks, vs: ops.paged_attention(
            q, kp, vp, pt, al, key_scales=ks, value_scales=vs)
    one = NamedSharding(Mesh(np.array(devices[:1]), ("one",)), P())
    text = jax.jit(fn, in_shardings=one).trace(*specs).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    calls = [line.split(" = ")[0].strip() for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 1, calls
    assert "_paged_decode_attention.paged_decode" in calls[0]


def test_eva_tick_compiles_at_the_cells_geometry(as_tpu):
    """The EVA cell's tick, one layer of it: 16 slots, a table of 256 pages
    of 16 rows a slot (2048 summary rows, then the 2048-row ring), rows of
    32 heads x 128 = 4096 lanes. It compiles for a v5e with no chip, the read
    one Mosaic custom call under `paged_decode_window`; the two 537 MB pools
    are aliased to the output and never copied, and no dense view of a
    slot's rows or of its positions (`[16, 4096, ...]`, `[16, 32768, ...]`)
    exists."""
    import re

    from cloud_tpu.models import EvaByteLM

    devices = _tpu_topology()
    if devices is None:
        pytest.skip("no libtpu to describe a v5e")
    slots, page = 16, 16
    model = EvaByteLM(
        vocab_size=320, num_layers=1, num_heads=32, d_model=4096, d_ff=11008,
        max_seq_len=32768, window_size=2048, chunk_size=16, num_pred_heads=8,
        compute_dtype=BF16, param_dtype=BF16, decode=True, kv_page_size=page,
        kv_num_pages=slots * 256 + 1)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                               jnp.zeros((slots, 1), jnp.int32))
    pool = variables["cache"]["block_0"]["attention"]["key_pages"]
    assert pool.shape == (4097, 16, 4096)

    def tick(params, cache, tokens, active):
        return model.apply({"params": params, "cache": cache}, tokens,
                           active, mutable=["cache"])

    one = NamedSharding(Mesh(np.array(devices[:1]), ("one",)), P())
    compiled = jax.jit(tick, in_shardings=one, donate_argnums=1).trace(
        variables["params"], variables["cache"], S((slots, 1), jnp.int32),
        S((slots, 1), jnp.bool_)).lower(
            lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    calls = [line.split(" = ")[0].strip() for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    reads = [c for c in calls if "paged_decode" in c]
    assert len(reads) == 1 and reads[0].split(".")[-2] == (
        "paged_decode_window"), calls
    assert not [line for line in text.splitlines()
                if " copy(" in line and "[4097,16,4096]" in line]
    assert not re.search(r"\[16,(4096|32768),(32,128|4096)\]", text)
    memory = compiled.memory_analysis()
    pool_bytes = 2 * pool.size * 2
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < 64 * 2 ** 20


def test_ssm_decode_update_compiles_at_the_cells_geometry(as_tpu):
    """The hybrid cell's call — 128 slots, 128 heads x 64 (two side by
    side on the lanes), 8 groups, a state of 128 — compiles for a v5e with
    no chip as one Mosaic custom call under its declared name, the 537 MB
    of state aliased to the output and never copied."""
    from cloud_tpu.ops import ssm

    devices = _tpu_topology()
    if devices is None:
        pytest.skip("no libtpu to describe a v5e")
    slots, heads, hd, n, groups = 128, 128, 64, 128, 8
    state = S((slots,) + ssm.packed_shape(heads, groups, hd, n), F32)
    assert state.shape == (128, 64, 128, 128)
    assert ssm.kernel_fits(state.shape, groups)
    specs = [state, S((slots, heads, hd), BF16), S((slots, heads), F32),
             S((heads,), F32), S((heads,), F32), S((slots, groups, n), BF16),
             S((slots, groups, n), BF16)]
    one = NamedSharding(Mesh(np.array(devices[:1]), ("one",)), P())
    compiled = jax.jit(ssm.ssm_decode_update, in_shardings=one,
                       donate_argnums=0).trace(*specs).lower(
                           lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    calls = [line.split(" = ")[0].strip() for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 1 and ssm.SSM_DECODE_UPDATE in calls[0], calls
    assert not [line for line in text.splitlines()
                if " copy(" in line and "[128,64,128,128]" in line]
    assert compiled.memory_analysis().alias_size_in_bytes >= 4 * state.size


def test_batched_experts_compile_copy_free_at_the_cells_geometry():
    """The hybrid cell's tick — 128 rows choosing 22 of 512 experts, 128
    held, relu^2 experts 1024 -> 2688 -> 1024 in bfloat16 — takes the form
    batched over the held experts, and XLA:TPU reads both weight stacks
    where and as they lie: no copy or transpose of a stack, and no
    temporary of the hidden rows' size in HBM (a copy of a stack would be
    1.4 GB a layer and tick, the layer's whole budget)."""
    import flax.linen as nn

    from cloud_tpu.models import moe

    devices = _tpu_topology()
    if devices is None:
        pytest.skip("no libtpu to describe a v5e")
    rows, top_k, experts, held, latent, d_ff = 128, 22, 512, 128, 1024, 2688
    assert moe.batched_over_held(rows, top_k, experts, None)

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x2d, top_idx, gates):
            return moe.routed_expert_ffn(
                self, x2d, top_idx, gates, experts, d_ff, None,
                moe.PLAIN_ACTIVATIONS["relu2"], BF16,
                held_experts=tuple(range(held)), param_dtype=BF16,
                gated=False)

    layer = Layer()
    specs = [S((rows, latent), BF16), S((rows, top_k), jnp.int32),
             S((rows, top_k), F32)]
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), *specs)
    one = NamedSharding(Mesh(np.array(devices[:1]), ("one",)), P())
    compiled = jax.jit(layer.apply, in_shardings=one).trace(
        params, *specs).lower(lowering_platforms=("tpu",)).compile()
    stacks = ("[{},{},{}]".format(held, latent, d_ff),
              "[{},{},{}]".format(held, d_ff, latent))
    moved = [line.strip()[:160] for line in compiled.as_text().splitlines()
             if (" copy(" in line or " transpose(" in line)
             and any(shape in line.split(" = ")[1].split("(")[0]
                     for shape in stacks)]
    assert not moved, moved
    assert "ragged" not in compiled.as_text()
    hidden_bytes = held * rows * d_ff * 2
    assert compiled.memory_analysis().temp_size_in_bytes < hidden_bytes


# -- values under a mesh (interpreted, CPU devices) ---------------------


def _rand(rng, *shape, dtype=F32, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


def _assert_close(got, want, tol=2e-5):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   atol=tol, rtol=tol)


@pytest.fixture
def cpu_mesh():
    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))


def _sharded(fn, mesh, shardings, args):
    with mesh:
        return jax.jit(fn, in_shardings=tuple(
            NamedSharding(mesh, spec) for spec in shardings))(*args)


def test_flash_under_a_mesh_matches_reference(cpu_mesh):
    rng = np.random.default_rng(0)
    q, k, v = (_rand(rng, 4, 32, heads, 16) for heads in (4, 2, 2))
    mask = jnp.asarray(np.arange(32)[None, :] < np.array(
        [[32], [20], [32], [7]]))
    total = lambda out: jnp.sum(out * jnp.cos(out))

    def run(attend):
        return jax.value_and_grad(
            lambda q, k, v: total(attend(q, k, v, mask=mask)),
            (0, 1, 2))

    by_batch_and_head = P("dp", None, "tp", None)
    got = _sharded(
        run(functools.partial(ops.flash_attention, interpret=True)),
        cpu_mesh, (by_batch_and_head,) * 3, (q, k, v))
    _assert_close(got, run(ops.mha_reference)(q, k, v))


def test_fused_rmsnorm_under_a_mesh_matches_reference(cpu_mesh):
    rng = np.random.default_rng(1)
    x, r = _rand(rng, 4, 8, 32), _rand(rng, 4, 8, 32)
    scale = 1.0 + _rand(rng, 32, scale=0.1)
    total = lambda outs: sum(jnp.sum(o * jnp.cos(o)) for o in outs)

    def run(impl):
        return jax.value_and_grad(
            lambda x, r, s: total(ops.fused_rmsnorm(
                x, s, residual=r, impl=impl)), (0, 1, 2))

    got = _sharded(run("fused"), cpu_mesh, (P("dp"), P("dp"), P()),
                   (x, r, scale))
    # The scale's gradient is summed over the dp shards by the psum
    # the varying-axes cast transposes to.
    _assert_close(got, run("reference")(x, r, scale))


def test_fused_swiglu_under_a_mesh_matches_reference(cpu_mesh):
    rng = np.random.default_rng(2)
    x = _rand(rng, 4, 8, 32)
    wg, wu = _rand(rng, 32, 256, scale=0.2), _rand(rng, 32, 256,
                                                  scale=0.2)
    wd = _rand(rng, 256, 32, scale=0.1)
    total = lambda out: jnp.sum(out * jnp.cos(out))

    def run(impl):
        return jax.value_and_grad(
            lambda *a: total(ops.fused_swiglu(*a, impl=impl)),
            (0, 1, 2, 3))

    # d_ff split over tp: each device holds half the hidden units and
    # a partial down projection; x's gradient sums over tp, the
    # weights' over dp.
    got = _sharded(run("fused"), cpu_mesh,
                   (P("dp"), P(None, "tp"), P(None, "tp"),
                    P("tp", None)), (x, wg, wu, wd))
    _assert_close(got, run("reference")(x, wg, wu, wd), tol=2e-4)


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_under_a_mesh_matches_reference(cpu_mesh, quantized):
    rng = np.random.default_rng(3)
    slots, heads, hd, page, ppn = 3, 4, 16, 8, 4
    pages = slots * ppn + 1
    q = _rand(rng, slots, 2, heads, hd)
    table = jnp.asarray(1 + rng.permutation(slots * ppn).reshape(
        slots, ppn), jnp.int32)
    depth = np.array([5, 30, 17])
    allowed = jnp.asarray(
        np.arange(page * ppn)[None, None, :]
        <= (depth[:, None] + np.arange(2))[:, :, None])
    shardings = [P(None, None, "tp", None), P(None, None, "tp"),
                 P(None, None, "tp"), P(), P()]
    if quantized:
        make = lambda: jnp.asarray(rng.integers(
            -127, 128, (pages, page, heads * hd)), jnp.int8)
        scales = {name: jnp.asarray(rng.uniform(
            0.5, 1.5, (pages, heads)) / 127.0, F32)
            for name in ("key_scales", "value_scales")}
        args = (q, make(), make(), table, allowed,
                scales["key_scales"], scales["value_scales"])
        shardings += [P(None, "tp")] * 2
        kernel = lambda q, kp, vp, pt, al, ks, vs: (
            ops.paged_decode_attention(
                q, kp, vp, pt, al, interpret=True, key_scales=ks,
                value_scales=vs))
        want = ops.paged_attention_reference(*args[:5], **scales)
    else:
        args = (q, _rand(rng, pages, page, heads * hd),
                _rand(rng, pages, page, heads * hd), table, allowed)
        kernel = functools.partial(ops.paged_decode_attention,
                                   interpret=True)
        want = ops.paged_attention_reference(*args)
    _assert_close(_sharded(kernel, cpu_mesh, shardings, args), want)


# -- the typed map (what runs on the chip), with lax stand-ins -----------
#
# Compiled kernels run under `check_vma=True`, where a replicated
# operand's gradient is summed by the transpose of `common_vma`'s cast.
# The interpreter cannot run there, so these swap the one function that
# calls Pallas for the same math in lax and keep everything around it:
# the custom_vjp, the casts, the specs, the psum.


def test_typed_map_sums_the_replicated_scale_gradient(cpu_mesh,
                                                      monkeypatch):
    def lax_forward(config, x, residual, scale):
        h = x if residual is None else x + residual
        hf = h.astype(F32)
        var = jnp.mean(hf * hf, axis=-1, keepdims=True)
        return ((hf * jax.lax.rsqrt(var + config.eps) * scale).astype(
            config.out_dtype), h)

    monkeypatch.setattr(fused_norm, "_norm_forward", lax_forward)
    rng = np.random.default_rng(4)
    x, r = _rand(rng, 4, 8, 32), _rand(rng, 4, 8, 32)
    scale = 1.0 + _rand(rng, 32, scale=0.1)
    total = lambda outs: sum(jnp.sum(o * jnp.cos(o)) for o in outs)

    def run(impl, **kw):
        return jax.value_and_grad(
            lambda x, r, s: total(ops.fused_rmsnorm(
                x, s, residual=r, impl=impl, **kw)), (0, 1, 2))

    got = _sharded(run("fused", interpret=False), cpu_mesh,
                   (P("dp"), P("dp"), P()), (x, r, scale))
    _assert_close(got, run("reference")(x, r, scale))


def test_typed_map_sums_partial_products_and_gradients(cpu_mesh,
                                                       monkeypatch):
    def lax_forward(config, x, w_gate, w_up, w_down):
        act = fused_mlp._ACTIVATIONS[config.activation]
        return ((act(x @ w_gate) * (x @ w_up)) @ w_down).astype(
            config.out_dtype)

    monkeypatch.setattr(fused_mlp, "_swiglu_forward", lax_forward)
    rng = np.random.default_rng(5)
    x = _rand(rng, 4, 8, 32)
    wg, wu = _rand(rng, 32, 256, scale=0.2), _rand(rng, 32, 256,
                                                  scale=0.2)
    wd = _rand(rng, 256, 32, scale=0.1)
    total = lambda out: jnp.sum(out * jnp.cos(out))

    def run(impl, **kw):
        return jax.value_and_grad(
            lambda *a: total(ops.fused_swiglu(*a, impl=impl, **kw)),
            (0, 1, 2, 3))

    got = _sharded(run("fused", interpret=False), cpu_mesh,
                   (P("dp"), P(None, "tp"), P(None, "tp"),
                    P("tp", None)), (x, wg, wu, wd))
    _assert_close(got, run("reference")(x, wg, wu, wd), tol=2e-4)
