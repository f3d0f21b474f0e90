"""graftlint: per-rule fixtures, suppression, CLI contract, preflight.

Every rule gets at least one fixture that fires and one that stays
silent (the acceptance bar for heuristic rules: unambiguous pitfalls
flagged, idiomatic code untouched). The meta-test at the bottom pins
the self-run: this repository lints clean, and CI enforces that with
`--strict` from here on.
"""

import io
import json
import os
from unittest import mock

import pytest

import cloud_tpu
from cloud_tpu.analysis import engine
from cloud_tpu.analysis import lint
from cloud_tpu.analysis import preflight
from cloud_tpu.core import machine_config
from cloud_tpu.core import run as run_module
from cloud_tpu.utils import events

CONFIGS = machine_config.COMMON_MACHINE_CONFIGS


def rules_of(source):
    return [f.rule for f in engine.check_source(source)]


# A GL001 pitfall as a complete training script — used by the CLI and
# preflight tests below, and the shape of the "seeded pitfall" check
# from the acceptance criteria.
PITFALL_SCRIPT = """\
import jax
import jax.numpy as jnp

@jax.jit
def train_step(params, batch):
    loss = jnp.sum(batch)
    print("loss", float(loss))
    return params, loss
"""


class TestGL001HostSyncInJit:

    def test_float_print_item_asarray_fire(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    a = float(x)\n"
            "    print(x)\n"
            "    b = x.item()\n"
            "    c = np.asarray(x)\n"
            "    return a, b, c\n")
        assert rules_of(src) == ["GL001"] * 4

    def test_outside_jit_silent(self):
        src = (
            "import jax\n"
            "def f(x):\n"
            "    return float(x), x.item()\n"
            "loss = float(jax.numpy.ones(()))\n"
            "print(loss)\n")
        assert rules_of(src) == []

    def test_instrumented_jit_return_form_detected(self):
        # The trainer idiom: a nested def handed to instrumented_jit in
        # a return statement, no decorator, no assignment.
        src = (
            "from cloud_tpu.parallel import runtime\n"
            "def build():\n"
            "    def step(state, batch):\n"
            "        print(batch)\n"
            "        return state\n"
            "    return runtime.instrumented_jit(step, donate_argnums=0)\n")
        assert rules_of(src) == ["GL001"]

    def test_jax_debug_print_silent(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    jax.debug.print('x={x}', x=x)\n"
            "    return x\n")
        assert rules_of(src) == []


class TestGL002RetraceHazard:

    def test_loop_var_and_len_fire(self):
        src = (
            "import jax\n"
            "step = jax.jit(lambda x, i: x + i)\n"
            "def drive(x, xs):\n"
            "    for i in range(3):\n"
            "        x = step(x, i)\n"
            "    return step(x, len(xs))\n")
        assert rules_of(src) == ["GL002", "GL002"]

    def test_static_argnums_silences_call_site(self):
        src = (
            "import jax\n"
            "step = jax.jit(lambda x, i: x + i, static_argnums=1)\n"
            "def drive(x, xs):\n"
            "    for i in range(3):\n"
            "        x = step(x, i)\n"
            "    return step(x, len(xs))\n")
        assert rules_of(src) == []

    def test_dict_literal_arg_fires(self):
        src = (
            "import jax\n"
            "step = jax.jit(lambda x, cfg: x)\n"
            "out = step(1.0, {'lr': 0.1})\n")
        assert rules_of(src) == ["GL002"]

    def test_mutable_global_closure_fires(self):
        src = (
            "import jax\n"
            "SCALES = {'loss': 2.0}\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x * SCALES['loss']\n")
        assert rules_of(src) == ["GL002"]

    def test_shadowed_or_immutable_global_silent(self):
        src = (
            "import jax\n"
            "SCALES = {'loss': 2.0}\n"
            "SCALE = 2.0\n"
            "@jax.jit\n"
            "def f(x, SCALES=None):\n"
            "    return x * SCALE if SCALES is None else x\n")
        assert rules_of(src) == []


class TestGL003DonationAfterUse:

    def test_read_after_donation_fires(self):
        src = (
            "import jax\n"
            "step = jax.jit(lambda s, b: s, donate_argnums=0)\n"
            "def drive(state, batch):\n"
            "    new_state = step(state, batch)\n"
            "    return state\n")
        assert rules_of(src) == ["GL003"]

    def test_rebinding_silences(self):
        src = (
            "import jax\n"
            "step = jax.jit(lambda s, b: s, donate_argnums=0)\n"
            "def drive(state, batch):\n"
            "    state = step(state, batch)\n"
            "    return state\n")
        assert rules_of(src) == []

    def test_non_donated_position_silent(self):
        src = (
            "import jax\n"
            "step = jax.jit(lambda s, b: s, donate_argnums=0)\n"
            "def drive(state, batch):\n"
            "    state = step(state, batch)\n"
            "    return batch\n")
        assert rules_of(src) == []


class TestGL004RngKeyReuse:

    def test_reuse_fires(self):
        src = (
            "import jax\n"
            "def f(key, shape):\n"
            "    a = jax.random.normal(key, shape)\n"
            "    b = jax.random.bernoulli(key, 0.5, shape)\n"
            "    return a, b\n")
        assert rules_of(src) == ["GL004"]

    def test_split_and_rebind_silent(self):
        src = (
            "import jax\n"
            "def f(key, shape):\n"
            "    key, sub = jax.random.split(key)\n"
            "    a = jax.random.normal(sub, shape)\n"
            "    key, sub = jax.random.split(key)\n"
            "    b = jax.random.bernoulli(sub, 0.5, shape)\n"
            "    return a, b\n")
        assert rules_of(src) == []

    def test_from_jax_import_random_alias_tracked(self):
        src = (
            "from jax import random\n"
            "def f(key):\n"
            "    a = random.normal(key, (2,))\n"
            "    b = random.uniform(key, (2,))\n"
            "    return a, b\n")
        assert rules_of(src) == ["GL004"]

    def test_prngkey_creation_not_a_consumption(self):
        src = (
            "import jax\n"
            "def f(seed):\n"
            "    key = jax.random.PRNGKey(seed)\n"
            "    return jax.random.normal(key, (2,))\n")
        assert rules_of(src) == []


class TestGL005TracerControlFlow:

    def test_branch_on_traced_param_fires(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x, flag):\n"
            "    if flag:\n"
            "        x = x + 1\n"
            "    return x\n")
        assert rules_of(src) == ["GL005"]

    def test_while_on_traced_param_fires(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    while x > 0:\n"
            "        x = x - 1\n"
            "    return x\n")
        assert rules_of(src) == ["GL005"]

    def test_static_argnames_silences(self):
        src = (
            "import jax\n"
            "import functools\n"
            "@functools.partial(jax.jit, static_argnames=('flag',))\n"
            "def f(x, flag):\n"
            "    if flag:\n"
            "        x = x + 1\n"
            "    return x\n")
        assert rules_of(src) == []

    def test_static_facts_about_traced_args_silent(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x, mask=None):\n"
            "    if mask is None:\n"
            "        mask = x * 0\n"
            "    if x.ndim == 2:\n"
            "        x = x[None]\n"
            "    if len(x) > 1:\n"
            "        x = x + 1\n"
            "    if isinstance(mask, tuple):\n"
            "        mask = mask[0]\n"
            "    return x, mask\n")
        assert rules_of(src) == []


class TestGL006ShardingAxisMismatch:

    def test_undeclared_axis_fires(self):
        src = (
            "from jax.sharding import Mesh, PartitionSpec as P\n"
            "mesh = Mesh(devs, ('data', 'model'))\n"
            "spec = P('data', 'tensor')\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL006"]
        assert "'tensor'" in findings[0].message

    def test_declared_axes_and_none_silent(self):
        src = (
            "from jax.sharding import Mesh, PartitionSpec as P\n"
            "mesh = Mesh(devs, axis_names=('data', 'model'))\n"
            "spec = P('data', None)\n"
            "spec2 = P(('data', 'model'))\n")
        assert rules_of(src) == []

    def test_no_mesh_literal_no_opinion(self):
        # Axis names built dynamically: the rule cannot judge, so it
        # must not guess.
        src = (
            "from jax.sharding import PartitionSpec as P\n"
            "spec = P('anything')\n")
        assert rules_of(src) == []


class TestGL010DeadJitSignatureLeaf:

    def test_unused_traced_param_fires(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def gather(pages, page_table, pos_count):\n"
            "    return pages[page_table]\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL010"]
        assert "`pos_count`" in findings[0].message

    def test_all_params_read_silent(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x, y):\n"
            "    return x + y\n")
        assert rules_of(src) == []

    def test_underscore_rename_is_the_sanction(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x, _sig_pad):\n"
            "    return x * 2\n")
        assert rules_of(src) == []

    def test_static_param_not_a_leaf(self):
        # Static args are hashed, not traced: an unused static arg is
        # odd but does not widen the aval signature.
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnums=1)\n"
            "def f(x, mode):\n"
            "    return x * 2\n")
        assert rules_of(src) == []

    def test_forward_to_ignoring_helper_fires(self):
        # Interprocedural: the helper provably never reads its second
        # param, so forwarding is not a read.
        src = (
            "import jax\n"
            "def helper(x, unused):\n"
            "    return x * 2\n"
            "@jax.jit\n"
            "def f(x, extra):\n"
            "    return helper(x, extra)\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL010"]
        assert "`extra`" in findings[0].message
        assert "helper" in findings[0].message

    def test_forward_to_reading_helper_silent(self):
        src = (
            "import jax\n"
            "def helper(x, scale):\n"
            "    return x * scale\n"
            "@jax.jit\n"
            "def f(x, extra):\n"
            "    return helper(x, extra)\n")
        assert rules_of(src) == []

    def test_forward_to_method_is_conservative(self):
        # `self._scatter(x, extra)` is unresolvable — treated as a
        # read, the engine's own executables forward like this.
        src = (
            "import jax\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._f = jax.jit(self._impl)\n"
            "    def _impl(self, x, extra):\n"
            "        return self._mix(x, extra)\n")
        assert rules_of(src) == []

    def test_prefix_gather_dead_dict_leaves_fire(self):
        # Regression: the serving prefix-cache gather shipped per-slot
        # leaves (page_table/slot_steps/slot_valid/pos_count) the
        # traced gather never read, silently binding one executable
        # per slot count. GL010 must flag each dead leaf at the call.
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def gather(dense, pool, page_vec):\n"
            "    return dense + pool['key_pages'] + pool['value_pages']"
            " + page_vec\n"
            "def prefill_gather(dense, cache, page_vec):\n"
            "    return gather(dense, {\n"
            "        'key_pages': cache['key_pages'],\n"
            "        'value_pages': cache['value_pages'],\n"
            "        'page_table': cache['page_table'],\n"
            "        'slot_steps': cache['slot_steps'],\n"
            "        'slot_valid': cache['slot_valid'],\n"
            "        'pos_count': cache['pos_count'],\n"
            "    }, page_vec)\n")
        findings = engine.check_source(src)
        dead = [f for f in findings if f.rule == "GL010"]
        named = {leaf for f in dead
                 for leaf in ("page_table", "slot_steps", "slot_valid",
                              "pos_count") if repr(leaf) in f.message}
        assert len(dead) == 4
        assert named == {"page_table", "slot_steps", "slot_valid",
                         "pos_count"}

    def test_whole_dict_use_silences_leaves(self):
        # The dict escapes whole (tree_map): no leaf is provably dead.
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(tree):\n"
            "    return jax.tree_util.tree_map(lambda a: a + 1, tree)\n"
            "def call(x):\n"
            "    return f({'a': x, 'b': x})\n")
        assert [r for r in rules_of(src) if r == "GL010"] == []

    def test_bound_method_attribute_form_fires(self):
        # The serving engine's binding idiom:
        # `self._tick = partial(jit, ...)(self._tick_impl)`.
        src = (
            "import functools\n"
            "from cloud_tpu.parallel.runtime import instrumented_jit\n"
            "class Engine:\n"
            "    def __init__(self):\n"
            "        self._tick = functools.partial(\n"
            "            instrumented_jit, donate_argnums=(1,))("
            "self._tick_impl)\n"
            "    def _tick_impl(self, params, cache, slot_pad):\n"
            "        return params, cache + 1\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL010"]
        assert "`slot_pad`" in findings[0].message


class TestGL011UnhashableStaticArg:

    def test_list_literal_into_static_argnums_fires(self):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnums=1)\n"
            "def resize(x, widths):\n"
            "    return x\n"
            "def call(x):\n"
            "    return resize(x, [1, 2, 3])\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL011"]
        assert "list literal" in findings[0].message

    def test_dict_into_static_argname_fires(self):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnames=('cfg',))\n"
            "def step(x, cfg=None):\n"
            "    return x\n"
            "def call(x):\n"
            "    return step(x, cfg={'k': 1})\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL011"]
        assert "dict literal" in findings[0].message

    def test_ndarray_builder_fires(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnums=1)\n"
            "def step(x, table):\n"
            "    return x\n"
            "def call(x):\n"
            "    return step(x, np.zeros(4))\n")
        assert rules_of(src) == ["GL011"]

    def test_tuple_and_scalar_silent(self):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnums=(1, 2))\n"
            "def step(x, widths, mode):\n"
            "    return x\n"
            "def call(x):\n"
            "    return step(x, (1, 2, 3), 'greedy')\n")
        assert rules_of(src) == []


class TestGL012RetraceProneCacheKey:

    def test_shape_keyed_dict_lookup_fires(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def tick(x):\n"
            "    return x + 1\n"
            "_warm = {}\n"
            "def dispatch(batch):\n"
            "    fn = _warm[batch.shape[0]]\n"
            "    return tick(batch)\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL012"]
        assert "batch.shape" in findings[0].message

    def test_shape_branch_on_jit_path_fires(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def tick(x):\n"
            "    return x + 1\n"
            "def dispatch(batch):\n"
            "    if batch.shape[0] > 8:\n"
            "        return tick(batch)\n"
            "    return tick(batch[:8])\n")
        assert rules_of(src) == ["GL012"]

    def test_validation_guard_silent(self):
        # `if bad shape: raise` is the fix, not the hazard.
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def tick(x):\n"
            "    return x + 1\n"
            "def dispatch(batch, n):\n"
            "    if batch.shape[0] != n:\n"
            "        raise ValueError('bad batch')\n"
            "    return tick(batch)\n")
        assert rules_of(src) == []

    def test_no_jit_call_no_opinion(self):
        src = (
            "def pad(a, n):\n"
            "    if a.shape[0] == n:\n"
            "        return a\n"
            "    return a + n\n")
        assert rules_of(src) == []

    def test_indexing_the_param_itself_silent(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def tick(x):\n"
            "    return x + 1\n"
            "def dispatch(batch):\n"
            "    half = batch[batch.shape[0] // 2]\n"
            "    return tick(half)\n")
        assert rules_of(src) == []


class TestGL013LockDiscipline:
    """Fixture pair modeled on the Scheduler's `_ready_lock` fields:
    the prefill thread appends ready work under the lock, the tick
    thread consumes it."""

    _LOCKED = (
        "import threading\n"
        "class Sched:\n"
        "    def __init__(self):\n"
        "        self._ready_lock = threading.Lock()\n"
        "        self._ready = []\n"
        "        self._t1 = threading.Thread(target=self._prefill_loop)\n"
        "        self._t2 = threading.Thread(target=self._tick_loop)\n"
        "    def _prefill_loop(self):\n"
        "        with self._ready_lock:\n"
        "            self._ready.append(1)\n"
        "    def _tick_loop(self):\n"
        "        with self._ready_lock:\n"
        "            ready, self._ready = self._ready, []\n")

    def test_locked_pair_silent(self):
        assert rules_of(self._LOCKED) == []

    def test_unlocked_read_from_other_thread_fires(self):
        src = self._LOCKED.replace(
            "    def _tick_loop(self):\n"
            "        with self._ready_lock:\n"
            "            ready, self._ready = self._ready, []\n",
            "    def _tick_loop(self):\n"
            "        ready, self._ready = self._ready, []\n")
        findings = engine.check_source(src)
        assert {f.rule for f in findings} == {"GL013"}
        assert any("`self._ready`" in f.message
                   and "_ready_lock" in f.message for f in findings)

    def test_unlocked_public_reader_fires(self):
        src = self._LOCKED + (
            "    def stats(self):\n"
            "        return len(self._ready)\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL013"]
        assert "caller" in findings[0].message

    def test_sanction_comment_silences(self):
        src = self._LOCKED + (
            "    def stats(self):\n"
            "        return len(self._ready)"
            "  # graftlint: unlocked-ok\n")
        assert rules_of(src) == []

    def test_single_threaded_class_silent(self):
        # No Thread targets: nothing can interleave, lock or not.
        src = (
            "import threading\n"
            "class Pool:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._pages = []\n"
            "    def alloc(self):\n"
            "        with self._lock:\n"
            "            self._pages.append(1)\n"
            "    def stats(self):\n"
            "        return len(self._pages)\n")
        assert rules_of(src) == []

    def test_init_writes_exempt(self):
        # Construction precedes the threads; __init__ never flags.
        assert "__init__" not in "".join(
            f.message for f in engine.check_source(self._LOCKED))


# -- graftmesh rules (GL014-GL018): the axis-registry family ----------


class TestGL014UndeclaredCollectiveAxis:

    _MESH = ("import jax\n"
             "from jax import lax\n"
             "from jax.sharding import Mesh\n"
             "mesh = Mesh(devs, ('dp', 'tp'))\n")

    def test_psum_over_undeclared_axis_fires(self):
        src = self._MESH + (
            "def f(x):\n"
            "    return lax.psum(x, 'ep')\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL014"]
        assert "'ep'" in findings[0].message
        assert "dp" in findings[0].message  # names the declared axes

    def test_from_import_alias_fires(self):
        src = ("from jax.lax import all_gather as ag\n"
               "from jax.sharding import Mesh\n"
               "mesh = Mesh(devs, ('data',))\n"
               "def f(x):\n"
               "    return ag(x, axis_name='model')\n")
        assert rules_of(src) == ["GL014"]

    def test_axis_index_slot_zero_fires(self):
        # axis_index takes axis_name first, not second.
        src = self._MESH + (
            "def f():\n"
            "    return lax.axis_index('pp')\n")
        assert rules_of(src) == ["GL014"]

    def test_declared_axis_silent(self):
        src = self._MESH + (
            "def f(x):\n"
            "    return lax.psum(x, 'dp') + lax.pmean(x, ('dp', 'tp'))\n")
        assert rules_of(src) == []

    def test_no_mesh_literal_no_opinion(self):
        # The mesh may live in code we were not asked to lint — the
        # GL006 contract, inherited.
        src = ("from jax import lax\n"
               "def f(x):\n"
               "    return lax.psum(x, 'anything')\n")
        assert rules_of(src) == []

    def test_dynamic_axis_silent(self):
        # ring/ulysses/pipeline idiom: axis flows in as a parameter.
        src = self._MESH + (
            "def f(x, axis_name):\n"
            "    return lax.psum(x, axis_name)\n")
        assert rules_of(src) == []

    def test_axis_ok_sanction(self):
        src = self._MESH + (
            "def f(x):\n"
            "    return lax.psum(x, 'ep')  # graftlint: axis-ok\n")
        assert rules_of(src) == []


class TestGL015MalformedPartitionSpec:

    def test_duplicate_axis_fires(self):
        src = ("from jax.sharding import PartitionSpec as P\n"
               "spec = P('dp', None, 'dp')\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL015"]
        assert "'dp'" in findings[0].message

    def test_duplicate_through_tuple_entry_fires(self):
        src = ("from jax.sharding import PartitionSpec as P\n"
               "spec = P(('dp', 'tp'), 'tp')\n")
        assert rules_of(src) == ["GL015"]

    def test_spec_longer_than_rank_fires(self):
        src = ("import jax\n"
               "import jax.numpy as jnp\n"
               "from jax.sharding import NamedSharding\n"
               "from jax.sharding import PartitionSpec as P\n"
               "y = jax.device_put(jnp.zeros((4, 8)),\n"
               "                   NamedSharding(mesh, P('a', None, 'b')))\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL015"]
        assert "3 entries" in findings[0].message
        assert "rank 2" in findings[0].message

    def test_distinct_axes_silent(self):
        src = ("from jax.sharding import PartitionSpec as P\n"
               "spec = P('dp', 'tp', None, ('sp', 'ep'))\n")
        assert rules_of(src) == []

    def test_spec_not_longer_than_rank_silent(self):
        # Shorter is fine (trailing dims replicate); equal is fine.
        src = ("import jax\n"
               "import jax.numpy as jnp\n"
               "from jax.sharding import PartitionSpec as P\n"
               "a = jax.lax.with_sharding_constraint(jnp.zeros((4, 8)),"
               " P('x'))\n"
               "b = jax.lax.with_sharding_constraint(jnp.zeros((4, 8)),"
               " P('x', 'y'))\n")
        assert rules_of(src) == []

    def test_axis_ok_sanction(self):
        src = ("from jax.sharding import PartitionSpec as P\n"
               "spec = P('dp', 'dp')  # graftlint: axis-ok\n")
        assert rules_of(src) == []


class TestGL016UnreducedShardMapLeak:

    _HEAD = ("import jax\n"
             "from jax import lax\n"
             "from jax.experimental.shard_map import shard_map\n"
             "from jax.sharding import PartitionSpec as P\n")

    def test_unreduced_body_fires(self):
        src = self._HEAD + (
            "def body(a):\n"
            "    return a * 2\n"
            "def f(mesh, x):\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(P('dp'),),\n"
            "                     out_specs=P())(x)\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL016"]
        assert "'dp'" in findings[0].message
        assert "body" in findings[0].message

    def test_lambda_body_fires(self):
        src = self._HEAD + (
            "def f(mesh, x):\n"
            "    return shard_map(lambda a: a + 1, mesh=mesh,\n"
            "                     in_specs=(P('dp'),),\n"
            "                     out_specs=P())(x)\n")
        assert rules_of(src) == ["GL016"]

    def test_reduction_over_other_axis_fires(self):
        # A psum over 'tp' does not discharge the 'dp' leak.
        src = self._HEAD + (
            "def body(a):\n"
            "    return lax.psum(a, 'tp')\n"
            "def f(mesh, x):\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(P('dp', 'tp'),),\n"
            "                     out_specs=P(None, 'tp'))(x)\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL016"]
        assert "'dp'" in findings[0].message

    def test_psum_body_silent(self):
        # THE negative fixture from the acceptance criteria: a body
        # that reduces over the sharded axis is exactly how psum-style
        # data parallelism is written.
        src = self._HEAD + (
            "def body(a):\n"
            "    return lax.psum(a, 'dp')\n"
            "def f(mesh, x):\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(P('dp'),),\n"
            "                     out_specs=P())(x)\n")
        assert rules_of(src) == []

    def test_axis_kept_in_out_specs_silent(self):
        src = self._HEAD + (
            "def body(a):\n"
            "    return a * 2\n"
            "def f(mesh, x):\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(P('dp'),),\n"
            "                     out_specs=P('dp'))(x)\n")
        assert rules_of(src) == []

    def test_dynamic_axis_reduction_silent(self):
        # A reducing collective over a parameter axis may cover any
        # axis: conservative silence.
        src = self._HEAD + (
            "def body(a, axis):\n"
            "    return lax.psum(a, axis)\n"
            "def f(mesh, x):\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(P('dp'),),\n"
            "                     out_specs=P())(x)\n")
        assert rules_of(src) == []

    def test_reduction_in_local_callee_silent(self):
        # The body delegates to a helper that reduces: the scan
        # follows local calls.
        src = self._HEAD + (
            "def reduce_it(a):\n"
            "    return lax.psum(a, 'dp')\n"
            "def body(a):\n"
            "    return reduce_it(a) * 2\n"
            "def f(mesh, x):\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(P('dp'),),\n"
            "                     out_specs=P())(x)\n")
        assert rules_of(src) == []

    def test_axis_ok_sanction(self):
        src = self._HEAD + (
            "def body(a):\n"
            "    return a * 2\n"
            "def f(mesh, x):\n"
            "    fn = shard_map(body, mesh=mesh,  # graftlint: axis-ok\n"
            "                   in_specs=(P('dp'),),\n"
            "                   out_specs=P())\n"
            "    return fn(x)\n")
        assert rules_of(src) == []


class TestGL017ConflictingNestedSharding:

    _HEAD = ("import jax\n"
             "from jax.sharding import PartitionSpec as P\n")

    def test_nested_jit_repin_fires(self):
        src = self._HEAD + (
            "def outer(x):\n"
            "    x = jax.lax.with_sharding_constraint(x, P('dp'))\n"
            "    @jax.jit\n"
            "    def inner(y):\n"
            "        x2 = jax.lax.with_sharding_constraint(x, P('tp'))\n"
            "        return x2 + y\n"
            "    return inner(x)\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL017"]
        assert "'dp'" in findings[0].message
        assert "'tp'" in findings[0].message
        assert "jit" in findings[0].message

    def test_with_mesh_repin_fires(self):
        src = self._HEAD + (
            "def outer(x, mesh):\n"
            "    x = jax.lax.with_sharding_constraint(x, P('dp'))\n"
            "    with mesh:\n"
            "        x2 = jax.lax.with_sharding_constraint(x, P('tp'))\n"
            "    return x2\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL017"]
        assert "with-mesh" in findings[0].message

    def test_device_put_counts_as_pin(self):
        src = self._HEAD + (
            "from jax.sharding import NamedSharding\n"
            "def outer(x, mesh):\n"
            "    x = jax.device_put(x, NamedSharding(mesh, P('dp')))\n"
            "    @jax.jit\n"
            "    def inner(y):\n"
            "        x2 = jax.device_put(x, NamedSharding(mesh, P('tp')))\n"
            "        return x2 + y\n"
            "    return inner(x)\n")
        assert rules_of(src) == ["GL017"]

    def test_same_spec_silent(self):
        src = self._HEAD + (
            "def outer(x):\n"
            "    x = jax.lax.with_sharding_constraint(x, P('dp'))\n"
            "    @jax.jit\n"
            "    def inner(y):\n"
            "        x2 = jax.lax.with_sharding_constraint(x, P('dp'))\n"
            "        return x2 + y\n"
            "    return inner(x)\n")
        assert rules_of(src) == []

    def test_plain_nested_def_silent(self):
        # A non-jit nested def is a different dynamic extent, not an
        # enclosed sharding scope.
        src = self._HEAD + (
            "def outer(x):\n"
            "    x = jax.lax.with_sharding_constraint(x, P('dp'))\n"
            "    def helper(y):\n"
            "        return jax.lax.with_sharding_constraint(y, P('tp'))\n"
            "    return helper(x)\n")
        assert rules_of(src) == []

    def test_different_names_silent(self):
        src = self._HEAD + (
            "def outer(x, z):\n"
            "    x = jax.lax.with_sharding_constraint(x, P('dp'))\n"
            "    @jax.jit\n"
            "    def inner(y):\n"
            "        z2 = jax.lax.with_sharding_constraint(z, P('tp'))\n"
            "        return z2 + y\n"
            "    return inner(x)\n")
        assert rules_of(src) == []

    def test_axis_ok_sanction(self):
        src = self._HEAD + (
            "def outer(x):\n"
            "    x = jax.lax.with_sharding_constraint(x, P('dp'))\n"
            "    @jax.jit\n"
            "    def inner(y):\n"
            "        x2 = jax.lax.with_sharding_constraint(x, P('tp'))"
            "  # graftlint: axis-ok\n"
            "        return x2 + y\n"
            "    return inner(x)\n")
        assert rules_of(src) == []


class TestGL018AxisDivisibility:

    _HEAD = ("import jax\n"
             "import jax.numpy as jnp\n"
             "from jax.sharding import NamedSharding\n"
             "from jax.sharding import PartitionSpec as P\n"
             "mesh = jax.make_mesh((2, 4), ('dp', 'tp'))\n")

    def test_indivisible_dim_fires(self):
        src = self._HEAD + (
            "y = jax.device_put(jnp.zeros((5, 8)),\n"
            "                   NamedSharding(mesh, P('dp', 'tp')))\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL018"]
        assert "size 5" in findings[0].message
        assert "'dp'" in findings[0].message
        assert "size 2" in findings[0].message

    def test_tuple_entry_uses_axis_product_fires(self):
        # ('dp', 'tp') shards one dim over 2*4=8 devices; 12 % 8 != 0.
        src = self._HEAD + (
            "y = jax.device_put(jnp.zeros((12, 4)),\n"
            "                   NamedSharding(mesh, P(('dp', 'tp'),)))\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL018"]
        assert "size 8" in findings[0].message

    def test_shape_dtype_struct_fires(self):
        src = self._HEAD + (
            "s = jax.ShapeDtypeStruct((6, 3), jnp.float32,\n"
            "    sharding=NamedSharding(mesh, P(None, 'tp')))\n")
        findings = engine.check_source(src)
        assert [f.rule for f in findings] == ["GL018"]
        assert "dimension 1" in findings[0].message

    def test_divisible_silent(self):
        src = self._HEAD + (
            "y = jax.device_put(jnp.zeros((6, 8)),\n"
            "                   NamedSharding(mesh, P('dp', 'tp')))\n")
        assert rules_of(src) == []

    def test_unknown_axis_size_silent(self):
        # A dynamic mesh gives the axis no static size: no opinion.
        src = ("import jax\n"
               "import jax.numpy as jnp\n"
               "from jax.sharding import Mesh, NamedSharding\n"
               "from jax.sharding import PartitionSpec as P\n"
               "mesh = Mesh(devs, ('dp',))\n"
               "y = jax.device_put(jnp.zeros((5,)),\n"
               "                   NamedSharding(mesh, P('dp')))\n")
        assert rules_of(src) == []

    def test_conflicting_mesh_literals_silent(self):
        # Two meshes disagree on 'dp': the size is unusable for
        # divisibility reasoning, not a coin flip.
        src = ("import jax\n"
               "import jax.numpy as jnp\n"
               "from jax.sharding import NamedSharding\n"
               "from jax.sharding import PartitionSpec as P\n"
               "m1 = jax.make_mesh((2,), ('dp',))\n"
               "m2 = jax.make_mesh((3,), ('dp',))\n"
               "y = jax.device_put(jnp.zeros((5,)),\n"
               "                   NamedSharding(m1, P('dp')))\n")
        assert rules_of(src) == []

    def test_axis_ok_sanction(self):
        src = self._HEAD + (
            "y = jax.device_put(jnp.zeros((5, 8)),\n"
            "                   NamedSharding(mesh, P('dp', 'tp')"
            "))  # graftlint: axis-ok\n")
        assert rules_of(src) == []


class TestGL006BlindSpot:
    """GL006 (and its GL014 descendant) reason only over mesh
    LITERALS. An axis registered dynamically — `Mesh(devs,
    tuple(names))` built from a variable — is invisible, so a
    collective over an axis that IS valid at runtime but never appears
    in a literal still fires. Pinned as strict-xfail: if the analyzer
    ever learns to resolve this, the xfail turns into a failure and
    the sanction guidance in the docs must be rewritten."""

    @pytest.mark.xfail(
        strict=True,
        reason="dynamically registered mesh axes are statically "
               "invisible (documented GL006/GL014 blind spot)")
    def test_dynamic_axis_registration_not_resolved(self):
        src = ("import jax\n"
               "from jax import lax\n"
               "from jax.sharding import Mesh\n"
               "names = tuple(['dp'] + ['ep'])\n"
               "static = Mesh(devs, ('dp',))\n"
               "dynamic = Mesh(devs, names)\n"
               "def f(x):\n"
               "    return lax.psum(x, 'ep')\n")
        # 'ep' IS declared at runtime by the dynamic mesh; a smarter
        # analyzer would stay silent.
        assert rules_of(src) == []


class TestSuppression:

    def test_same_line_disable(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return float(x)  # graftlint: disable=GL001\n")
        assert rules_of(src) == []

    def test_disable_wrong_rule_keeps_finding(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return float(x)  # graftlint: disable=GL002\n")
        assert rules_of(src) == ["GL001"]

    def test_disable_all(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return float(x)  # graftlint: disable=all\n")
        assert rules_of(src) == []

    def test_disable_file(self):
        src = (
            "# graftlint: disable-file=GL001\n"
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    print(x)\n"
            "    return float(x)\n")
        assert rules_of(src) == []

    def test_multiple_codes_one_comment(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x, flag):\n"
            "    if flag: x = float(x)  # graftlint: disable=GL001,GL005\n"
            "    return x\n")
        assert rules_of(src) == []


class TestParseError:

    def test_syntax_error_is_gl000(self):
        findings = engine.check_source("def broken(:\n", "bad.py")
        assert [f.rule for f in findings] == [engine.PARSE_ERROR]


class TestCli:

    def _run(self, argv):
        out = io.StringIO()
        code = lint.main(argv, out=out)
        return code, out.getvalue()

    def test_text_output_and_warn_exit(self, tmp_path):
        target = tmp_path / "train.py"
        target.write_text(PITFALL_SCRIPT)
        code, output = self._run([str(target)])
        assert code == 0  # warn mode: report, don't gate
        assert "GL001" in output
        assert "finding(s)" in output

    def test_strict_gates(self, tmp_path):
        target = tmp_path / "train.py"
        target.write_text(PITFALL_SCRIPT)
        code, _ = self._run([str(target), "--strict"])
        assert code == 1
        target.write_text("x = 1\n")
        code, _ = self._run([str(target), "--strict"])
        assert code == 0

    def test_json_schema_stable(self, tmp_path):
        target = tmp_path / "train.py"
        target.write_text(PITFALL_SCRIPT)
        code, output = self._run([str(target), "--format", "json"])
        doc = json.loads(output)
        assert set(doc) == {"version", "files_checked", "counts",
                            "findings"}
        assert doc["version"] == 1
        assert doc["files_checked"] == 1
        assert doc["counts"] == {"GL001": 2}
        assert [set(f) for f in doc["findings"]] == [
            {"path", "line", "col", "rule", "message"}] * 2
        assert all(f["rule"] == "GL001" for f in doc["findings"])

    def test_select_filters_rules(self, tmp_path):
        target = tmp_path / "train.py"
        target.write_text(PITFALL_SCRIPT)
        code, output = self._run([str(target), "--select", "GL004",
                                  "--format", "json"])
        assert json.loads(output)["findings"] == []

    def test_unknown_select_is_usage_error(self, tmp_path):
        target = tmp_path / "train.py"
        target.write_text("x = 1\n")
        code, _ = self._run([str(target), "--select", "GL999"])
        assert code == 2

    def test_missing_path_is_usage_error(self):
        code, _ = self._run(["/no/such/dir"])
        assert code == 2

    def test_directory_walk_skips_pycache(self, tmp_path):
        (tmp_path / "pkg").mkdir()
        (tmp_path / "pkg" / "good.py").write_text("x = 1\n")
        (tmp_path / "pkg" / "__pycache__").mkdir()
        (tmp_path / "pkg" / "__pycache__" / "junk.py").write_text("(((\n")
        code, output = self._run([str(tmp_path / "pkg"), "--strict"])
        assert code == 0
        assert "1 file(s)" in output


# -- preflight: the run() hook ----------------------------------------


@pytest.fixture
def project_env(monkeypatch):
    monkeypatch.setenv("GOOGLE_CLOUD_PROJECT", "my-project")
    monkeypatch.delenv("CLOUD_TPU_RUNNING_REMOTELY", raising=False)
    monkeypatch.delenv("TF_KERAS_RUNNING_REMOTELY", raising=False)
    monkeypatch.delenv("CLOUD_TPU_EVENT_LOG", raising=False)


@pytest.fixture
def pitfall_entry(tmp_path, monkeypatch):
    (tmp_path / "train.py").write_text(PITFALL_SCRIPT)
    monkeypatch.chdir(tmp_path)
    return "train.py"


def _mock_cloud(monkeypatch):
    builder = mock.MagicMock()
    builder.get_docker_image.return_value = "gcr.io/my-project/img:tag"
    builder.get_generated_files.return_value = []
    monkeypatch.setattr(run_module.containerize, "LocalContainerBuilder",
                        mock.MagicMock(return_value=builder))
    deploy_job = mock.MagicMock(return_value="job_123")
    monkeypatch.setattr(run_module.deploy, "deploy_job", deploy_job)
    return deploy_job


class TestPreflight:

    def test_warn_mode_reports_and_proceeds(self, project_env,
                                            pitfall_entry, monkeypatch,
                                            capsys):
        deploy_job = _mock_cloud(monkeypatch)
        job_id = run_module.run(entry_point=pitfall_entry,
                                distribution_strategy=None)
        assert job_id == "job_123"
        deploy_job.assert_called_once()
        err = capsys.readouterr().err
        assert "graftlint preflight" in err
        assert "GL001" in err

    def test_strict_mode_raises_before_containerize(self, project_env,
                                                    pitfall_entry,
                                                    monkeypatch):
        deploy_job = _mock_cloud(monkeypatch)
        with pytest.raises(preflight.GraftlintError, match="GL001"):
            run_module.run(entry_point=pitfall_entry,
                           distribution_strategy=None, lint="strict")
        deploy_job.assert_not_called()

    def test_off_mode_skips(self, project_env, pitfall_entry,
                            monkeypatch, capsys):
        deploy_job = _mock_cloud(monkeypatch)
        run_module.run(entry_point=pitfall_entry,
                       distribution_strategy=None, lint="off")
        deploy_job.assert_called_once()
        assert "graftlint" not in capsys.readouterr().err

    def test_clean_entry_point_is_quiet(self, project_env, tmp_path,
                                        monkeypatch, capsys):
        deploy_job = _mock_cloud(monkeypatch)
        (tmp_path / "ok.py").write_text("print('training')\n")
        monkeypatch.chdir(tmp_path)
        run_module.run(entry_point="ok.py", distribution_strategy=None,
                       lint="strict")
        deploy_job.assert_called_once()
        assert "graftlint" not in capsys.readouterr().err

    def test_invalid_mode_rejected_by_validate(self, project_env,
                                               pitfall_entry):
        with pytest.raises(ValueError, match="Invalid `lint`"):
            run_module.run(entry_point=pitfall_entry, lint="fix")

    def test_findings_land_in_job_event_log(self, project_env,
                                            pitfall_entry, monkeypatch,
                                            tmp_path, capsys):
        _mock_cloud(monkeypatch)
        log_path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("CLOUD_TPU_EVENT_LOG", log_path)
        run_module.run(entry_point=pitfall_entry,
                       distribution_strategy=None)
        records = events.read_job_events(log_path)
        assert len(records) == 1
        assert records[0]["kind"] == "graftlint"
        payload = records[0]["payload"]
        assert payload["mode"] == "warn"
        assert payload["entry_point"] == "train.py"
        assert {f["rule"] for f in payload["findings"]} == {"GL001"}
        capsys.readouterr()

    def test_notebook_entry_point_skipped(self, project_env, tmp_path,
                                          monkeypatch):
        (tmp_path / "nb.ipynb").write_text("{}")
        monkeypatch.chdir(tmp_path)
        assert preflight.resolve_target("nb.ipynb") is None
        assert preflight.preflight_lint("nb.ipynb", "strict") == []

    def test_direct_preflight_rejects_bad_mode(self):
        with pytest.raises(ValueError, match="Invalid `lint`"):
            preflight.preflight_lint("whatever.py", "loud")


class TestSelfRun:
    """The repository lints itself clean — CI enforces this with
    --strict; a rule change that fires on our own tree must either fix
    the code or carry an explicit suppression."""

    def test_tree_is_graftlint_clean(self):
        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(cloud_tpu.__file__)))
        targets = [os.path.join(repo_root, "cloud_tpu")]
        # tests/ is linted too: a pitfall in a test fixture that is
        # real code (not a string) must carry an explicit suppression.
        for extra in ("examples", "tests"):
            path = os.path.join(repo_root, extra)
            if os.path.exists(path):  # absent in installed layouts
                targets.append(path)
        findings, files_checked = engine.check_paths(targets)
        assert files_checked > 50
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_every_rule_has_id_title_and_counter(self):
        assert list(engine.RULES) == [
            "GL001", "GL002", "GL003", "GL004", "GL005", "GL006",
            "GL007", "GL008", "GL009", "GL010", "GL011", "GL012",
            "GL013", "GL014", "GL015", "GL016", "GL017", "GL018"]
        for rule in engine.RULES.values():
            assert rule.title and rule.predicts
