"""The names the program declares for its spans, kernels and programs.

`monitoring/spans.py`'s docstring tables are the contract the benchmark's
readers, the docs and `cellbench/tools/spans.py` key on. Here the code is
held to them: every span the program opens stands in the table and the
other way round, each Pallas call carries its declared name in the jaxpr,
each program of the two hot loops lowers to a module named after it, and
under a short profile capture the spans land on the profiler's host plane
beside the ops, those of one request under its rid. All at toy widths on
the CPU.
"""

import glob
import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.models import decoding as decoding_lib
from cloud_tpu.models import mamba2 as mamba_lib
from cloud_tpu.models import moe as moe_lib
from cloud_tpu.monitoring import spans
from cloud_tpu.ops import fused_mlp, fused_norm
from cloud_tpu.ops import ssm as ssm_ops
from cloud_tpu.serving import engine as engine_lib
from cloud_tpu.training import trainer as trainer_lib

# `cloud_tpu.ops` exports functions under these two modules' names.
attention_ops = importlib.import_module("cloud_tpu.ops.attention")
paged_ops = importlib.import_module("cloud_tpu.ops.paged_attention")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
F32 = jnp.float32


EXPERT_SCOPES = (moe_lib.MOE_ROUTER, moe_lib.MOE_ROUTED_EXPERTS,
                 moe_lib.MOE_SHARED_EXPERT)
LATENT_SCOPES = (moe_lib.MOE_LATENT_DOWN, moe_lib.MOE_LATENT_UP)


@pytest.fixture(autouse=True)
def _no_tracer():
    spans.uninstall()
    yield
    spans.uninstall()


# ------------------------------------------------------------ the tables

def _span_literals():
    """Every name the program passes to span()/begin(), by reading its
    source."""
    call = re.compile(
        r'spans(?:_lib)?\.(?:span|begin)\(\s*(?:"([a-z_0-9]+)"|([A-Z_]+))')
    found = set()
    for path in glob.glob(os.path.join(ROOT, "cloud_tpu", "**", "*.py"),
                          recursive=True):
        with open(path, encoding="utf-8") as f:
            for literal, constant in call.findall(f.read()):
                found.add(literal or constant)
    return found


def test_span_table_and_code_name_the_same_spans():
    found = _span_literals()
    # engine.py opens its program's span under the program's constant.
    assert "SERVE_PREFILL" in found
    found = (found - {"SERVE_PREFILL"}) | {engine_lib.SERVE_PREFILL}
    # trace_steps() opens these two by its defaults.
    found |= {"train_step", "data_wait"}
    table = spans.names("Spans")
    assert len(table) == len(set(table))
    assert found == set(table)


def _span_ids_in_the_table():
    """span -> the id the "Spans" table gives it in brackets (`rid`,
    `tick`), for the spans it gives one."""
    ids, name, inside = {}, None, False
    for line in spans.__doc__.splitlines():
        if not line.startswith(" "):
            if line:
                inside = line == "Spans:"
            continue
        if not inside:
            continue
        if line[4] != " ":
            name = line.split()[0]
        for marked in re.findall(r"\((rid|tick)\b", line):
            ids[name] = marked
    return ids


def test_span_table_and_code_give_the_same_spans_an_id():
    """A span of one request carries `rid=`, a span of one tick
    `tick=`, and the table says so of exactly those."""
    call = re.compile(
        r'spans\.span\(\s*(?:"([a-z_0-9]+)"|([A-Z_]+)),\s*(rid|tick)=')
    found = {}
    for path in glob.glob(os.path.join(ROOT, "cloud_tpu", "serving", "*.py")):
        with open(path, encoding="utf-8") as f:
            for literal, constant, key in call.findall(f.read()):
                name = literal or getattr(engine_lib, constant)
                assert found.setdefault(name, key) == key, name
    assert found == _span_ids_in_the_table()
    assert {found[n] for n in ("serve_tick", "tick_dispatch", "tick_fetch",
                               "tick_commit")} == {"tick"}


def test_histogram_spans_stand_in_the_table():
    from cloud_tpu.monitoring import telemetry
    assert set(telemetry.SPAN_HISTOGRAMS) <= set(spans.names("Spans"))


def test_kernel_and_program_tables_equal_the_constants():
    assert spans.names("Kernels") == (
        attention_ops.FLASH_FWD, attention_ops.FLASH_BWD_DQ,
        attention_ops.FLASH_BWD_DKV, fused_mlp.FUSED_SWIGLU_FWD,
        fused_norm.FUSED_RMSNORM, fused_norm.FUSED_RMSNORM_RESIDUAL,
        paged_ops.PAGED_DECODE, paged_ops.PAGED_DECODE_WINDOW,
        paged_ops.PAGED_BLOCK_DECODE, paged_ops.PAGED_MLA_DECODE,
        attention_ops.MLA_PREFILL,
        ssm_ops.SSM_DECODE_UPDATE)
    assert spans.names("Scopes") == EXPERT_SCOPES + LATENT_SCOPES + (
        mamba_lib.SSM_IN_PROJ, mamba_lib.SSM_CONV, mamba_lib.SSM_SCAN,
        mamba_lib.SSM_GATE_NORM, mamba_lib.SSM_OUT_PROJ)
    assert spans.names("Programs") == (
        trainer_lib.TRAIN_STEP, engine_lib.SERVE_TICK,
        engine_lib.SERVE_PREFILL, engine_lib.SLOT_INSERT,
        engine_lib.SLOT_EVICT, engine_lib.SERVE_PREFILL_CHUNK,
        engine_lib.PREFIX_GATHER, engine_lib.SLOT_RESIZE,
        engine_lib.PAGE_SNAPSHOT, engine_lib.PAGE_PROMOTE,
        decoding_lib.CACHE_ZERO)


def test_dispatch_log_holds_no_name_the_program_table_lacks():
    """Every note the engine writes goes under a constant, and every
    serving program of the table has a note."""
    with open(engine_lib.__file__, encoding="utf-8") as f:
        noted = set(re.findall(r"\b_note\(\s*([A-Z_]+)", f.read()))
    values = {getattr(engine_lib, name, None)
              or getattr(decoding_lib, name) for name in noted}
    assert values == set(spans.names("Programs")) - {
        trainer_lib.TRAIN_STEP}


# --------------------------------------------------------------- kernels

def _pallas_names(fn, *args):
    """`name=` of every pallas_call equation in fn's jaxpr, nested
    jaxprs (custom_vjp, pjit) included."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
                continue
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else [value]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def _flash(q, k, v):
    return attention_ops.flash_attention(q, k, v, causal=True,
                                         interpret=True)


def _flash_loss(q, k, v):
    return jnp.sum(_flash(q, k, v))


def _kernel_cases():
    q = jnp.ones((1, 128, 2, 64), F32)
    x, w = jnp.ones((128, 128), F32), jnp.ones((128,), F32)
    wide = jnp.ones((128, 256), F32)
    pages = jnp.ones((5, 8, 128), F32)
    yield ("flash_fwd", _flash, (q, q, q), [attention_ops.FLASH_FWD])
    yield ("flash_bwd", jax.grad(_flash_loss, argnums=(0, 1, 2)),
           (q, q, q), [attention_ops.FLASH_FWD, attention_ops.FLASH_BWD_DQ,
                       attention_ops.FLASH_BWD_DKV])
    yield ("fused_swiglu_fwd",
           lambda x, g, u, d: fused_mlp.fused_swiglu(
               x, g, u, d, impl="fused", interpret=True),
           (x, wide, wide, wide.T), [fused_mlp.FUSED_SWIGLU_FWD])
    yield ("fused_rmsnorm",
           lambda x, w: fused_norm.fused_rmsnorm(
               x, w, impl="fused", interpret=True),
           (x, w), [fused_norm.FUSED_RMSNORM])
    yield ("fused_rmsnorm_residual",
           lambda x, w: fused_norm.fused_rmsnorm(
               x, w, residual=x, impl="fused", interpret=True),
           (x, w), [fused_norm.FUSED_RMSNORM_RESIDUAL])
    yield ("paged_decode",
           lambda q, kp, vp: paged_ops.paged_decode_attention(
               q, kp, vp, jnp.zeros((2, 4), jnp.int32),
               jnp.ones((2, 1, 32), bool), interpret=True),
           (jnp.ones((2, 1, 2, 64), F32), pages, pages),
           [paged_ops.PAGED_DECODE])
    # Grouped queries (4 heads on 1 key/value head) over a window.
    yield ("paged_decode_window",
           lambda q, kp, vp: paged_ops.paged_decode_attention(
               q, kp, vp, jnp.zeros((2, 4), jnp.int32),
               jnp.ones((2, 1, 32), bool), interpret=True, window=8),
           (jnp.ones((2, 1, 4, 128), F32), pages, pages),
           [paged_ops.PAGED_DECODE_WINDOW])
    # Eight heads on one latent row 256 wide, the value its first 128.
    yield ("paged_mla_decode",
           lambda q, rows: paged_ops.paged_mla_attention(
               q, rows, jnp.zeros((2, 4), jnp.int32),
               jnp.ones((2, 1, 32), bool), 0.1, 128, impl="paged",
               interpret=True),
           (jnp.ones((2, 1, 8, 256), F32), jnp.ones((5, 8, 256), F32)),
           [paged_ops.PAGED_MLA_DECODE])
    # Two heads' queries at cache row 4 over 32 cached rows.
    yield ("mla_prefill",
           lambda qn, qr, kn, kr: attention_ops.mla_prefill_attention(
               qn, qr, kn, kr, kn, jnp.int32(4), jnp.ones((1, 32), bool),
               0.1, interpret=True),
           (jnp.ones((1, 2, 8, 16), F32), jnp.ones((1, 2, 8, 8), F32),
            jnp.ones((1, 2, 32, 16), F32), jnp.ones((1, 32, 8), F32)),
           [attention_ops.MLA_PREFILL])
    # Two heads of 64 side by side: the lane-dense layout the kernel takes.
    yield ("ssm_decode_update",
           lambda s, x, dt, a, b: ssm_ops.ssm_decode_update(
               s, x, dt, a, a, b, b, impl="kernel", interpret=True),
           (jnp.ones((2, 2, 128, 128), F32), jnp.ones((2, 4, 64), F32),
            jnp.ones((2, 4), F32), -jnp.ones((4,), F32),
            jnp.ones((2, 2, 128), F32)),
           [ssm_ops.SSM_DECODE_UPDATE])


@pytest.mark.parametrize("case", list(_kernel_cases()),
                         ids=lambda case: case[0])
def test_pallas_call_carries_its_declared_name(case):
    _, fn, args, declared = case
    got = _pallas_names(fn, *args)
    assert len(got) == len(declared), got
    for name, want in zip(sorted(got), sorted(declared)):
        # The declared name is the call's last dotted component (the
        # attention kernels keep the accepted readers' prefix in front).
        assert name.split(".")[-1] == want, (name, want)


def test_attention_calls_keep_the_accepted_readers_prefix():
    """`flash_roofline` and `paged_attn_roofline` (benchmark files this
    repo may not edit) find the kernels by what stands before ` = ` in
    the trace, which XLA:TPU takes from the call's name."""
    from cellbench.layer_metrics import flash_roofline, paged_attn_roofline

    text = "%{}.7 = bf16[8] custom-call(bf16[8] %a)"
    for declared in (attention_ops.FLASH_FWD, attention_ops.FLASH_BWD_DQ,
                     attention_ops.FLASH_BWD_DKV):
        name = attention_ops._CALL_PREFIX + declared
        assert flash_roofline.is_flash(text.format(name))
        assert not paged_attn_roofline.is_paged(text.format(name))
    name = paged_ops._CALL_PREFIX + paged_ops.PAGED_DECODE
    assert paged_attn_roofline.is_paged(text.format(name))
    assert not flash_roofline.is_flash(text.format(name))


# -------------------------------------------------------------- programs

@pytest.fixture(scope="module")
def toy_engine():
    from cloud_tpu.models import TransformerLM
    model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                          d_model=32, d_ff=64, max_seq_len=32,
                          compute_dtype=F32)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return engine_lib.DecodeEngine(model, params, slots=2, page_size=8,
                                   num_pages=9), params


def _module_name(lowered):
    return re.search(r"module @(\w+)", lowered.as_text()).group(1)


def _serve_programs(toy):
    engine, params = toy
    from cloud_tpu.models.decoding import acquire_cache

    dense = engine_lib._plain(acquire_cache(engine._dense, 1))
    vec = jnp.zeros((engine.pages_per_slot,), jnp.int32)
    keys = jnp.zeros((engine.max_new_cap - 1, 2), jnp.uint32)
    scalars = (np.int32(0), vec, vec, keys, np.int32(2), np.int32(1),
               np.float32(0.0), np.int32(64), np.float32(1.0), np.int32(0),
               False)
    prefill = engine_lib._serve_prefill_fns(engine._dense, 0.0, None, None)
    tokens = jnp.zeros((1, 8), jnp.int32)
    host_pages = jax.eval_shape(engine._snapshot_impl, engine.cache, vec)
    return {
        engine_lib.SERVE_PREFILL_CHUNK: (
            engine_lib._cache_prefill_fn(engine._dense),
            (params, dense, tokens, jnp.ones((1, 8), bool))),
        engine_lib.PREFIX_GATHER: (
            engine._gather_pages,
            (dense, engine_lib._pool_pages_view(engine.cache), vec,
             np.int32(8))),
        engine_lib.SLOT_RESIZE: (engine._resize,
                                 (engine.cache, engine.ctl,
                                  jnp.zeros((2,), jnp.int32))),
        engine_lib.PAGE_SNAPSHOT: (engine._snapshot, (engine.cache, vec)),
        engine_lib.PAGE_PROMOTE: (engine._promote,
                                  (engine.cache, host_pages, vec)),
        decoding_lib.CACHE_ZERO: (decoding_lib._zero_in_place(), (dense,)),
        engine_lib.SERVE_TICK: (engine._tick,
                                (params, engine.cache, engine.ctl)),
        engine_lib.SLOT_INSERT: (engine._insert,
                                 (engine.cache, engine.ctl, dense) + scalars),
        engine_lib.SLOT_EVICT: (engine._evict,
                                (engine.cache, engine.ctl,
                                 jnp.zeros((2,), bool))),
        engine_lib.SERVE_PREFILL: (prefill,
                                   (params, dense, tokens,
                                    jax.random.PRNGKey(0),
                                    jnp.ones((1, 8), bool), np.int32(7))),
    }


@pytest.mark.parametrize("program", spans.names("Programs")[1:])
def test_serving_program_lowers_under_its_declared_name(toy_engine, program):
    fn, args = _serve_programs(toy_engine)[program]
    # best_effort_donation wraps the InstrumentedJit it was given.
    assert _module_name(fn.__wrapped__.lower(*args)) == "jit_" + program


def test_fresh_dense_cache_lowers_under_the_zero_programs_name(toy_engine):
    engine, _ = toy_engine
    fresh = decoding_lib._empty_cache_fn(engine._dense, 1)
    assert _module_name(fresh.lower()) == "jit_" + decoding_lib.CACHE_ZERO


def test_train_step_lowers_under_its_declared_name():
    import optax

    from cloud_tpu.models import MLP
    from cloud_tpu.parallel import runtime
    from cloud_tpu.training import Trainer

    runtime.reset()
    trainer = Trainer(MLP(hidden=8, num_classes=4),
                      optimizer=optax.sgd(0.1))
    x = np.zeros((8, 3), np.float32)
    y = np.zeros((8,), np.int32)
    trainer.fit(x, y, epochs=1, batch_size=8, verbose=False)
    lowered = trainer._jit_train_step.lower(
        trainer.state, (jnp.asarray(x), jnp.asarray(y)))
    assert _module_name(lowered) == "jit_" + trainer_lib.TRAIN_STEP


# ----------------------------------------------------- the seam's sinks

def test_span_without_tracer_or_profile_reaches_no_sink(tmp_path,
                                                        monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert spans.current_tracer() is None
    with spans.span("serve_tick"):
        with spans.span("serve_prefill", rid="r1"):
            pass
    spans.end(spans.begin("step"))
    assert list(spans.trace_steps([1, 2])) == [1, 2]
    assert spans.current_tracer() is None and os.listdir(tmp_path) == []


def test_span_with_tracer_records_as_before():
    tracer = spans.install()
    with spans.span("serve_prefill", rid="r7"):
        with spans.span("prefill_host", rid=None):
            pass
    handle = spans.begin("step")
    assert spans.end(handle) >= 0
    events = tracer.events()
    assert [e[0] for e in events] == ["prefill_host", "serve_prefill",
                                      "step"]
    inner, outer = events[0], events[1]
    assert outer[2] <= inner[2] and inner[2] + inner[3] <= outer[2] + outer[3]
    assert spans.names("Spans")[0] == "step"


def test_profile_capture_holds_the_spans_and_a_rid(tmp_path):
    """Under a capture the program's spans land in the `.xplane.pb`'s host
    plane, as the benchmark's reducer loads it, and carry the request."""
    from cellbench import tracing
    from cloud_tpu.models import TransformerLM
    from cloud_tpu.serving import Scheduler, ServeRequest

    model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                          d_model=32, d_ff=64, max_seq_len=32,
                          compute_dtype=F32)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    with Scheduler(model, params, slots=2, page_size=8) as sched:
        sched.warmup([8], sampling_configs=[(("temperature", 0.0),)])
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            results = [sched.submit(ServeRequest(
                prompt=[3, 5, 7, i], max_new_tokens=3,
                temperature=0.0)).result(timeout=300) for i in (1, 2)]
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    _, _, host, _ = tracing.load_xplane(path)
    assert {"serve_tick", "tick_dispatch", "tick_fetch", "serve_prefill",
            "tick_commit", "admit"} <= set(host.names)
    rids, ticks = {}, {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name in ("admit", "serve_prefill",
                                  "prefill_dispatch"):
                    rids.setdefault(event.name, set()).add(
                        dict(event.stats).get("rid"))
                if event.name in ("serve_tick", "tick_dispatch",
                                  "tick_fetch", "tick_commit"):
                    ticks.setdefault(event.name, set()).add(
                        int(dict(event.stats).get("tick")))
    # Spans of one tick share its `seq`, which is its record's.
    from cloud_tpu.serving import reqtrace
    recorded = {t.seq for t in reqtrace.recent_ticks()}
    # (The capture may begin or end between two spans of one tick.)
    assert all(seqs <= recorded for seqs in ticks.values())
    assert len(ticks) == 4 and set.intersection(*ticks.values())
    wanted = {r.trace.rid for r in results}
    assert None not in wanted
    # Spans of one request share its rid.
    assert rids["admit"] == rids["serve_prefill"] == wanted
    assert rids["prefill_dispatch"] == wanted


def test_tick_spans_nest_as_the_table_says():
    """The tick thread's spans under the one-deep pipeline: a
    `serve_tick` holds the dispatch of a tick and, where one was in
    flight, the fetch of the tick BEFORE it; a drained tick's fetch
    stands alone; `tick_commit` follows outside. Each tick is
    dispatched, fetched and committed once."""
    from cloud_tpu.models import TransformerLM
    from cloud_tpu.serving import Scheduler, ServeRequest

    model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                          d_model=32, d_ff=64, max_seq_len=32,
                          compute_dtype=F32)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    tracer = spans.install()
    with Scheduler(model, params, slots=2, page_size=8) as sched:
        for future in [sched.submit(ServeRequest(
                prompt=[3, 5, 7, i], max_new_tokens=8, temperature=0.0))
                for i in (1, 2)]:
            future.result(timeout=300)
        sched.assert_drained()
        stats = sched.stats()
    by_name = {}
    for name, _, start, dur in tracer.events():
        by_name.setdefault(name, []).append((start, start + dur))

    def inside(inner, outers):
        return any(lo <= inner[0] and inner[1] <= hi for lo, hi in outers)

    ticks = by_name["serve_tick"]
    assert len(ticks) == stats["ticks"] > 0
    for name in ("tick_dispatch", "tick_fetch", "tick_commit"):
        assert len(by_name[name]) == stats["ticks"], name
    assert all(inside(e, ticks) for e in by_name["tick_dispatch"])
    assert not any(inside(e, ticks) for e in by_name["tick_commit"])
    overlapped = sum(inside(e, ticks) for e in by_name["tick_fetch"])
    assert overlapped == stats["ticks_overlapped"] > 0
    # The d2h census stays exhaustive: every tick's fetch is a counted
    # read-back.
    for fetch in by_name["tick_fetch"]:
        assert any(inside(e, [fetch]) for e in by_name["d2h_fetch"])


def test_admission_spans_nest_as_the_table_says(monkeypatch):
    """The admission thread's spans under the one-deep prefill
    pipeline: a `serve_prefill` inside an `admit` goes as far as the
    dispatch (one `prefill_host`, one `prefill_dispatch`); a
    `prefill_fetch` inside an `admit` stands outside that `serve_prefill`
    and is the fetch of the prefill BEFORE; the last of a window is
    fetched outside every `admit`. `engine.prefill()`, the tick
    thread's form, holds all three."""
    from cloud_tpu.models import TransformerLM
    from cloud_tpu.serving import Scheduler, ServeRequest
    from tests.unit.tick_log import PrefillLog

    model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                          d_model=32, d_ff=64, max_seq_len=32,
                          compute_dtype=F32)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]

    def spans_of(tracer):
        by_name = {}
        for name, _, start, dur in tracer.events():
            by_name.setdefault(name, []).append((start, start + dur))
        return by_name

    def inside(inner, outers):
        return any(lo <= inner[0] and inner[1] <= hi for lo, hi in outers)

    sched = Scheduler(model, params, slots=4, page_size=8)
    tracer = spans.install()
    sampling = dict(temperature=0.0, top_k=None, top_p=None,
                    eos_token=None)
    sched.engine.release_prefill(sched.engine.prefill(
        np.asarray([3, 5, 7], np.int32), 4, None, sampling))
    whole = spans_of(tracer)
    (outer,) = whole["serve_prefill"]
    for name in ("prefill_host", "prefill_dispatch", "prefill_fetch"):
        (event,) = whole[name]
        assert inside(event, [outer]), name
    spans.uninstall()

    tracer = spans.install()
    log = PrefillLog(sched, monkeypatch)
    with sched:
        with log.hold():
            futures = [sched.submit(ServeRequest(
                prompt=[10 + i, 5, 7], max_new_tokens=4, temperature=0.0))
                for i in range(3)]
        for future in futures:
            future.result(timeout=300)
        sched.assert_drained()
        assert sched.stats()["prefills_overlapped"] == 2
    by_name = spans_of(tracer)
    admits, prefills = by_name["admit"], by_name["serve_prefill"]
    assert len(admits) == len(prefills) == 3
    assert all(inside(e, admits) for e in prefills)
    for name in ("prefill_host", "prefill_dispatch"):
        # Once a prefill: the second `prefill_host` (the eager key
        # split and its read-back) is gone.
        assert len(by_name[name]) == 3, name
        for outer in prefills:
            assert sum(inside(e, [outer]) for e in by_name[name]) == 1
    fetches = by_name["prefill_fetch"]
    assert len(fetches) == 3
    assert not any(inside(e, prefills) for e in fetches)
    assert sum(inside(e, admits) for e in fetches) == 2
    for fetch in fetches:
        assert any(inside(e, [fetch]) for e in by_name["d2h_fetch"])


# ------------------------------------------- an expert model's scopes

@pytest.fixture(scope="module")
def toy_expert_engine():
    from cloud_tpu.models import LlamaLM
    model = LlamaLM(vocab_size=64, num_layers=2, num_heads=2,
                    num_kv_heads=1, d_model=32, d_ff=64, max_seq_len=32,
                    compute_dtype=F32, attn_kinds="LG", sliding_window=8,
                    moe_experts=8, moe_top_k=2, moe_router="sigmoid",
                    moe_d_ff=16, moe_capacity_factor=None,
                    moe_held_experts=(0, 1), first_k_dense=1)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return engine_lib.DecodeEngine(model, params, slots=2, page_size=8,
                                   num_pages=9), params


@pytest.mark.parametrize("program", [engine_lib.SERVE_TICK,
                                     engine_lib.SERVE_PREFILL])
def test_expert_layer_lowers_under_its_declared_scopes(toy_expert_engine,
                                                       program):
    """Every part of the expert layer carries its scope in the op names
    of the tick and of the prefill, under the program's own name."""
    fn, args = _serve_programs(toy_expert_engine)[program]
    lowered = fn.__wrapped__.lower(*args)
    assert _module_name(lowered) == "jit_" + program
    text = lowered.as_text(debug_info=True)
    for scope in EXPERT_SCOPES:
        assert re.search(r"jit\({}\)/[^\"]*/moe/{}/".format(program, scope),
                         text), scope


def test_window_and_full_reads_are_told_apart_by_name(toy_expert_engine,
                                                      monkeypatch):
    """One window layer and one full layer: the tick's two paged reads
    go under the two declared kernel names."""
    # Traced only (nothing runs): what `impl="auto"` picks on the chip.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    engine, params = toy_expert_engine
    names = _pallas_names(engine._tick_impl, params, engine.cache,
                          engine.ctl)
    paged = sorted(n.split(".")[-1] for n in names
                   if n.startswith(paged_ops._CALL_PREFIX))
    assert paged == [paged_ops.PAGED_DECODE, paged_ops.PAGED_DECODE_WINDOW]


@pytest.fixture(scope="module")
def toy_hybrid_engine():
    from cloud_tpu.models import NemotronHLM
    model = NemotronHLM(vocab_size=64, d_model=32, pattern="ME*",
                        max_seq_len=32, num_heads=2, num_kv_heads=1,
                        mamba_heads=4, mamba_head_dim=8, ssm_groups=2,
                        ssm_state=16, chunk_size=8, moe_experts=8,
                        moe_top_k=2, moe_d_ff=16, moe_latent=12,
                        moe_shared_d_ff=24, moe_held_experts=(0, 1),
                        compute_dtype=F32)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return engine_lib.DecodeEngine(model, params, slots=2, page_size=8,
                                   num_pages=9), params


@pytest.mark.parametrize("program", [engine_lib.SERVE_TICK,
                                     engine_lib.SERVE_PREFILL])
def test_hybrid_layers_lower_under_their_declared_scopes(toy_hybrid_engine,
                                                         program):
    """A Mamba-2 layer's five parts and a latent expert layer's two
    projections (beside its other three parts) carry their scopes in
    the tick and in the prefill."""
    fn, args = _serve_programs(toy_hybrid_engine)[program]
    text = fn.__wrapped__.lower(*args).as_text(debug_info=True)
    under = {"mamba": spans.names("Scopes")[len(EXPERT_SCOPES
                                                + LATENT_SCOPES):],
             "moe": EXPERT_SCOPES + LATENT_SCOPES}
    for layer, scopes in under.items():
        for scope in scopes:
            assert re.search(r"jit\({}\)/[^\"]*/{}/{}/".format(
                program, layer, scope), text), scope


def test_tick_of_a_hybrid_model_holds_the_state_update_kernel(
        toy_hybrid_engine, monkeypatch):
    """Traced as on the chip at widths the kernel takes: one
    `ssm_decode_update` a Mamba-2 layer, its state aliased in place."""
    from cloud_tpu.models import NemotronHLM
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = NemotronHLM(vocab_size=64, d_model=32, pattern="MM*",
                        max_seq_len=32, num_heads=2, num_kv_heads=1,
                        head_dim=128, mamba_heads=4, mamba_head_dim=64,
                        ssm_groups=2, ssm_state=128, compute_dtype=F32)
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(1),
        jnp.zeros((1, 4), jnp.int32))["params"]
    engine = engine_lib.DecodeEngine(model, params, slots=2, page_size=8,
                                     num_pages=9)
    names = _pallas_names(engine._tick_impl, params, engine.cache,
                          engine.ctl)
    assert sorted(n for n in names if "paged" not in n
                  and "rmsnorm" not in n) == [ssm_ops.SSM_DECODE_UPDATE] * 2


def test_counter_table_names_the_stats_an_expert_model_adds():
    from cloud_tpu.models import TransformerLM
    from cloud_tpu.serving import Scheduler
    model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                          d_model=32, d_ff=64, max_seq_len=32,
                          compute_dtype=F32)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    with Scheduler(model, params, slots=2, page_size=8) as sched:
        stats = sched.stats()
    table = spans.names("Counters")
    assert set(table) == {k for k in stats
                          if k.startswith(("moe_", "ssm_", "eva_", "mla_",
                                           "diffusion_"))}
    assert [stats[k] for k in table] == [
        0, 0, 0, 0, [], 0, 0, 0, 0, {"ticks": 0, "prefills": 0}, 0, 0, 0,
        0, 0, 0, 0, 0]
