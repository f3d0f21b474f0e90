"""Mixture-of-Experts MLPs with expert parallelism: three forms of
one computation, each for the shapes it suits.

Expert parallelism is another axis the reference never had (SURVEY §2.3
lists EP among the explicitly-absent strategies). In every form the
expert weights carry a leading [experts] dim that
`expert_parallel_rules` shards over the mesh's "ep" axis, and shapes
are static.

1. **One-hot dispatch** (`MoEMLP`, Switch top-1 with GELU experts): the
   GShard/Switch pattern. Routing is dense one-hot dispatch/combine
   einsums over a `[T, E, C]` tensor, and XLA inserts the all-to-alls
   when the dispatch einsum crosses the expert axis — no manual
   collectives. Each expert takes at most
   `capacity = capacity_factor * tokens / num_experts` tokens; overflow
   tokens are dropped (contribute zero, standard Switch behavior) and
   the load-balancing auxiliary loss pushes the router toward uniform
   load. The dispatch tensor grows as T squared: training at modest T.
2. **Grouped** (`routed_expert_ffn`: `TopKMoEMLP`,
   `models.deepseek.DeepseekMoE`): the chosen (token, expert) pairs are
   sorted by expert and each projection is one `jax.lax.ragged_dot`
   over them, a group a held expert. No token-by-expert array at any
   T, and only the chosen pairs are computed: every prefill, every
   training step, any call under a capacity, and a decode tick that
   leaves held experts untouched.
3. **Batched over the held experts** (`routed_expert_ffn`, the same
   parameters): every held expert's projections are applied to all T
   rows as batched products and the gates, zero for a pair that was
   not chosen, select afterwards. It computes `held / chosen` times
   the pairs and reads each expert's matrices exactly once, in order:
   the form for a decode tick whose few rows touch every held expert
   anyway, where the layer's time is its weights' bytes.

`routed_expert_ffn` picks between 2 and 3 from the shapes of the call
(`batched_over_held`); nothing else selects a form.
"""

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


class MoEMLP(nn.Module):
    """Switch-routing MoE feed-forward block, drop-in for a dense MLP.

    Call returns (output, aux_loss); add `aux_loss * aux_weight` to the
    training loss to balance expert load.
    """

    num_experts: int = 8
    d_ff: int = 2048
    capacity_factor: float = 1.25
    compute_dtype: jnp.dtype = jnp.bfloat16
    router_noise: float = 0.0  # jitter std during training (0 = off)

    @nn.compact
    def __call__(self, x, deterministic=True):
        """x: [batch, seq, d_model] -> ([batch, seq, d_model], scalar)."""
        batch, seq, d_model = x.shape
        tokens = batch * seq
        capacity = max(
            1, int(self.capacity_factor * tokens / self.num_experts))

        # --- Router (always f32: tiny matmul, precision matters) ---
        router_kernel = self.param(
            "router", nn.initializers.lecun_normal(),
            (d_model, self.num_experts), jnp.float32)
        logits = jnp.asarray(x, jnp.float32).reshape(
            tokens, d_model) @ router_kernel          # [T, E]
        if self.router_noise and not deterministic:
            rng = self.make_rng("router")
            logits = logits + self.router_noise * jax.random.normal(
                rng, logits.shape)
        probs = jax.nn.softmax(logits, axis=-1)
        expert_index = jnp.argmax(probs, axis=-1)     # [T]
        expert_gate = jnp.max(probs, axis=-1)         # [T]

        # --- Load-balancing aux loss (Switch eq. 4-6) ---
        one_hot = jax.nn.one_hot(expert_index, self.num_experts,
                                 dtype=jnp.float32)   # [T, E]
        fraction_routed = one_hot.mean(axis=0)
        fraction_prob = probs.mean(axis=0)
        aux_loss = self.num_experts * jnp.sum(
            fraction_routed * fraction_prob)

        # --- Capacity assignment: position of each token within its
        # expert's queue; tokens past capacity are dropped ---
        position_in_expert = (jnp.cumsum(one_hot, axis=0) - 1.0) * one_hot
        keep = (position_in_expert < capacity).astype(jnp.float32) * one_hot
        position = jnp.sum(position_in_expert * keep,
                           axis=-1).astype(jnp.int32)           # [T]
        position_oh = jax.nn.one_hot(position, capacity,
                                     dtype=jnp.float32)         # [T, C]

        # dispatch[t, e, c] = 1 iff token t sits in slot c of expert e
        dispatch = keep[:, :, None] * position_oh[:, None, :]   # [T,E,C]
        combine = dispatch * expert_gate[:, None, None]

        # --- Expert FFN: einsum over the (sharded) expert dim; XLA
        # inserts the token all-to-all when "ep" shards E ---
        xf = x.reshape(tokens, d_model).astype(self.compute_dtype)
        expert_in = jnp.einsum("tec,td->ecd",
                               dispatch.astype(self.compute_dtype), xf)
        w_in = self.param(
            "expert_in",
            nn.initializers.lecun_normal(batch_axis=(0,)),
            (self.num_experts, d_model, self.d_ff), jnp.float32)
        w_out = self.param(
            "expert_out",
            nn.initializers.lecun_normal(batch_axis=(0,)),
            (self.num_experts, self.d_ff, d_model), jnp.float32)
        h = jnp.einsum("ecd,edf->ecf", expert_in,
                       w_in.astype(self.compute_dtype))
        h = nn.gelu(h)
        expert_out = jnp.einsum("ecf,efd->ecd", h,
                                w_out.astype(self.compute_dtype))

        out = jnp.einsum("tec,ecd->td",
                         combine.astype(self.compute_dtype), expert_out)
        return (out.reshape(batch, seq, d_model).astype(x.dtype),
                aux_loss)


class TopKMoEMLP(nn.Module):
    """Mixtral-style top-k routed MoE with SwiGLU experts.

    The modern-LLM counterpart of `MoEMLP` (Switch top-1, GELU
    experts): each token is processed by its `top_k` highest-scoring
    experts, whose outputs are combined with the token's renormalized
    router probabilities — softmax over the selected logits, exactly
    HF Mixtral's softmax-then-topk-then-renormalize (the two are
    algebraically identical). Experts are the same gate/up/down SwiGLU
    as `models.llama.SwiGLU`, stacked on a leading [num_experts] dim
    that `expert_parallel_rules` shards over the "ep" mesh axis.

    The chosen (token, expert) pairs are sorted by expert and each
    projection is one grouped product over them (`routed_expert_ffn`:
    static shapes, no `[T, E, C]` dispatch tensor at any T),
    processed slot-major so a token's top-1 choice wins capacity over
    any token's top-2 choice. `capacity_factor=None` disables dropping
    entirely: exact HF-Mixtral inference semantics, at the same cost
    as a factor — a factor only sheds the pairs past it (set one,
    conventionally 1.25-2.0, to bound an expert's share of a training
    batch, and let the aux loss balance load).

    Call returns (output, aux_loss); `LlamaBlock` sows the aux loss
    into the "losses" collection like `TransformerBlock` does.
    """

    num_experts: int = 8
    top_k: int = 2
    d_ff: int = 2048
    capacity_factor: Optional[float] = 2.0  # None = drop-free
    compute_dtype: jnp.dtype = jnp.bfloat16
    activation: str = "silu"
    norm_topk: bool = True  # Qwen3-MoE checkpoints may set False

    @nn.compact
    def __call__(self, x, deterministic=True):
        """x: [batch, seq, d_model] -> ([batch, seq, d_model], scalar)."""
        del deterministic  # no router noise in the Mixtral recipe
        from cloud_tpu.models.llama import _GATE_ACTIVATIONS

        batch, seq, d_model = x.shape
        tokens = batch * seq
        k = self.top_k
        if not 1 <= k <= self.num_experts:
            raise ValueError(
                "top_k={} must be in [1, num_experts={}].".format(
                    k, self.num_experts))
        if self.capacity_factor is None:
            capacity = None
        else:
            capacity = max(1, int(self.capacity_factor * tokens * k
                                  / self.num_experts))
        act = _GATE_ACTIVATIONS[self.activation]

        router_kernel = self.param(
            "router", nn.initializers.lecun_normal(),
            (d_model, self.num_experts), jnp.float32)
        logits = jnp.asarray(x, jnp.float32).reshape(
            tokens, d_model) @ router_kernel              # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top_probs, top_idx = jax.lax.top_k(probs, k)      # [T, k]
        if self.norm_topk:
            gates = top_probs / jnp.sum(top_probs, axis=-1,
                                        keepdims=True)
        else:  # Qwen3-MoE norm_topk_prob=False: raw softmax mass
            gates = top_probs

        # Load-balancing aux loss at HF Mixtral's scale
        # (load_balancing_loss_func): per-expert assignment counts are
        # SUMMED over the k routes (mean over tokens only), so a
        # uniform router scores top_k — coefficients calibrated
        # against HF (router_aux_loss_coef) transfer unchanged.
        counts = jnp.zeros((self.num_experts,), jnp.float32).at[
            top_idx.reshape(-1)].add(1.0)
        aux_loss = self.num_experts * jnp.sum(
            counts / tokens * probs.mean(axis=0))

        out = routed_expert_ffn(self, x.reshape(tokens, d_model),
                                top_idx, gates, self.num_experts,
                                self.d_ff, capacity, act,
                                self.compute_dtype)
        return out.reshape(batch, seq, d_model).astype(x.dtype), aux_loss


#: Declared scopes of an expert layer's three parts (table "Scopes" in
#: monitoring/spans.py): `jax.named_scope`s, so every op the part
#: lowers to carries the name in its `op_name`, whatever program (the
#: serve tick, a prefill, a train step) holds the layer.
MOE_ROUTER = "moe_router"
MOE_ROUTED_EXPERTS = "moe_routed_experts"
MOE_SHARED_EXPERT = "moe_shared_expert"
#: A latent expert layer's two projections, round the routed experts.
MOE_LATENT_DOWN = "moe_latent_down"
MOE_LATENT_UP = "moe_latent_up"

#: Activations of an expert that is NOT gated: `down(act(up(x)))`, two
#: products (`relu2` = relu squared, the Nemotron-H family's). Every
#: other activation name is a gate's: `down(act(gate(x)) * up(x))`.
PLAIN_ACTIVATIONS = {
    "relu2": lambda x: jnp.square(nn.relu(x)),
}

#: Collection an expert layer sows its counters of one call into, for
#: a caller that makes it mutable (the serve tick): `pairs_routed`
#: (token, choice) pairs of real tokens, `pairs_held` those whose
#: expert is held here, `experts_touched` held experts with at least
#: one pair, `expert_load` pairs a held expert, `pairs_dense` the
#: (real token, held expert) products the batched form computed (0 on
#: the grouped one: over `pairs_held` it is the padding that form pays).
MOE_STATS = "moe_stats"

#: Rows up to which a `[T, d] x [d, f]` product whose weights stream
#: from HBM is bound by their bytes, not by the MXU: it does
#: `2 T d f` FLOPs over `d f` elements, so the two times meet at
#: `T = peak FLOP/s x bytes an element / (2 x HBM bytes/s)` =
#: 197e12 x 2 / (2 x 819e9) = 240 rows for bfloat16 on the v5e (peak:
#: `monitoring.telemetry.PEAK_TFLOPS`; HBM: Google Cloud documentation,
#: "TPU v5e"). Under it, multiplying every row by every held expert
#: costs no time the weights' read does not already take.
DENSE_MAX_ROWS = 240
#: Least expected share of the held experts that a call touches for
#: the batched form: it reads every held expert, and each untouched
#: one is bytes the grouped form would not have read.
DENSE_MIN_TOUCHED = 0.95


def batched_over_held(tokens, top_k, num_experts, capacity):
    """Whether `routed_expert_ffn` runs a call of these (static) shapes
    batched over the held experts rather than grouped. All three hold:

    (a) nothing is shed (`capacity` None or at least `tokens`): the
        slot-major shedding order is the grouped form's;
    (b) `tokens <= DENSE_MAX_ROWS`: the products are bytes-bound, so
        the rows the gates zero afterwards are free;
    (c) under an even router a held expert is touched with probability
        `1 - (1 - top_k / num_experts) ** tokens`, and that is at
        least `DENSE_MIN_TOUCHED`: (nearly) every held expert's
        weights are read whatever the form.

    128 slots choosing 22 of 512 (99.6 % touched) run batched; 32
    slots choosing 8 of 128 (87 %) and every prefill or training step
    (rows past the ridge) run grouped.
    """
    if capacity is not None and capacity < tokens:
        return False
    if tokens > DENSE_MAX_ROWS:
        return False
    touched = 1.0 - (1.0 - top_k / num_experts) ** tokens
    return touched >= DENSE_MIN_TOUCHED


def routed_expert_ffn(module, x2d, top_idx, gates, num_experts, d_ff,
                      capacity, act, compute_dtype, held_experts=None,
                      token_mask=None, param_dtype=jnp.float32,
                      gated=True):
    """Top-k expert computation, shared by `TopKMoEMLP` (Mixtral) and
    `models.deepseek.DeepseekMoE`: gated experts
    `down(act(gate(x)) * up(x))`, three products, or with
    `gated=False` plain ones `down(act(up(x)))`, two (no `expert_gate`
    parameter then).

    x2d: [T, d] tokens; top_idx/gates: [T, k] selected experts and
    combine weights over all `num_experts` (any routing recipe).
    `held_experts`: the ids of the experts this module holds (None =
    all): only their weights exist — stacked expert_gate/up/down
    params `[len(held), ...]` on `module` (the caller's @nn.compact
    scope), in `param_dtype`, which `expert_parallel_rules` shards
    over "ep" — and only the chosen pairs whose expert is held are
    computed; what the absent experts would have added is left out
    (the share of an expert-parallel layout that this holder
    computes, before its exchange). `token_mask` [T] marks real
    tokens: a pad's pairs go nowhere.

    Two forms of the same sum, picked from the call's static shapes by
    `batched_over_held(T, k, num_experts, capacity)` and by nothing
    else. **Grouped**: the pairs are sorted by expert and each
    projection is ONE grouped product over them
    (`jax.lax.ragged_dot`: a group a held expert), so no `[T, E, .]`
    dispatch mask exists at any T and nothing is dropped unless
    `capacity` says so. Capacity (None = drop-free) is slot-major: the
    sort is stable over the slot-major list of pairs, so within an
    expert all slot-0 (highest-gate) assignments stand before any
    slot-1 assignment, and when capacity binds the lowest-priority
    routes past it are shed first. **Batched**: where T is at most
    `DENSE_MAX_ROWS` (the products are bound by the weights' bytes),
    nothing is shed and a call of T rows is expected to touch at
    least `DENSE_MIN_TOUCHED` of the held experts, every held
    expert's weights are read anyway and the grouped kernel's many
    small groups only slow that read: each projection is one batched
    product of all T rows with every held expert, and a `[T, held]`
    float32 matrix of gates — zero for an expert the token did not
    choose, for a pad and for an expert not held — weights the sum.
    The chosen pairs see the same operands in the same dtypes on both
    forms; only the order of a token's float32 sum differs.

    Both forms sow the same counters (`MOE_STATS`) from the router's
    choice, not from what was multiplied.
    Returns [T, d] in compute_dtype.
    """
    tokens, d_model = x2d.shape
    k = top_idx.shape[1]
    held = (tuple(range(num_experts)) if held_experts is None
            else tuple(int(e) for e in held_experts))
    n_held = len(held)
    if len(set(held)) != n_held or not all(
            0 <= e < num_experts for e in held):
        raise ValueError(
            "held_experts must be distinct ids in [0, {}); got "
            "{}.".format(num_experts, held))
    init = nn.initializers.lecun_normal(batch_axis=(0,))
    w_gate = module.param("expert_gate", init,
                          (n_held, d_model, d_ff),
                          param_dtype) if gated else None
    w_up = module.param("expert_up", init,
                        (n_held, d_model, d_ff), param_dtype)
    w_down = module.param("expert_down", init,
                          (n_held, d_ff, d_model), param_dtype)

    # A pair's group: its expert's row in the stacked weights, or
    # `n_held` (sorted last, computed by no one) for an expert that
    # is not held and for a pad's pairs.
    if held_experts is None:
        local = top_idx
    else:
        rows = np.full((num_experts,), n_held, np.int32)
        rows[list(held)] = np.arange(n_held, dtype=np.int32)
        local = jnp.asarray(rows)[top_idx]
    if token_mask is not None:
        local = jnp.where(token_mask.reshape(tokens, 1), local, n_held)
    local = local.astype(jnp.int32)                       # [T, k]
    sizes = jnp.zeros((n_held + 1,), jnp.int32).at[
        local.reshape(-1)].add(1)[:n_held]
    batched = batched_over_held(tokens, k, num_experts, capacity)
    if not module.is_initializing():   # counters are no variables
        module.sow(MOE_STATS, "pairs_held", jnp.sum(sizes))
        module.sow(MOE_STATS, "experts_touched",
                   jnp.sum((sizes > 0).astype(jnp.int32)))
        module.sow(MOE_STATS, "expert_load", sizes)
        real = (tokens if token_mask is None
                else jnp.sum(token_mask.astype(jnp.int32)))
        module.sow(MOE_STATS, "pairs_dense",
                   jnp.asarray(real * n_held if batched else 0,
                               jnp.int32))

    weights = (w_gate, w_up, w_down)
    if batched:
        return _batched_experts(x2d, local, gates, weights, act,
                                compute_dtype)
    return _grouped_experts(x2d, local, gates, sizes, weights, act,
                            compute_dtype, capacity)


def _expert_mlp(product, xs, weights, act, compute_dtype):
    """`down(act(gate(xs)) * up(xs))`, or `down(act(up(xs)))` where
    there is no gate, each projection through `product(rows, w)`:
    operands in `compute_dtype`, the result in the dtype it gives."""
    w_gate, w_up, w_down = (
        w if w is None else w.astype(compute_dtype) for w in weights)
    if w_gate is not None:
        hidden = act(product(xs, w_gate)) * product(xs, w_up)
    else:
        hidden = act(product(xs, w_up))
    return product(hidden.astype(compute_dtype), w_down)


def _batched_experts(x2d, local, gates, weights, act, compute_dtype):
    """Every held expert over all T rows, then the gates select.
    `local` [T, k]: a pair's row in the stacked weights, `n_held` for
    a pair no one here computes."""
    tokens, d_model = x2d.shape
    n_held = weights[1].shape[0]
    # The expert is a batch dimension of BOTH operands, so a weight is
    # read where and as it lies: no transpose, no copy.
    xs = jnp.broadcast_to(x2d.astype(compute_dtype)[None],
                          (n_held, tokens, d_model))
    out = _expert_mlp(lambda a, w: jax.lax.dot_general(
        a, w, (((2,), (1,)), ((0,), (0,)))), xs, weights, act,
        compute_dtype)                                    # [E, T, d]
    # A token's gate for each held expert: its chosen pairs scattered
    # (column `n_held` takes what no one here computes), 0 elsewhere.
    gate_of = jnp.zeros((tokens, n_held + 1), jnp.float32).at[
        jnp.arange(tokens)[:, None], local].add(
            gates.astype(jnp.float32))[:, :n_held]
    out = out.astype(jnp.float32) * jnp.transpose(gate_of)[:, :, None]
    return out.sum(axis=0).astype(compute_dtype)


def _grouped_experts(x2d, local, gates, sizes, weights, act,
                     compute_dtype, capacity):
    """The chosen pairs sorted by expert, one `ragged_dot` a
    projection. `sizes` [n_held]: the pairs of each held expert."""
    tokens, d_model = x2d.shape
    k = local.shape[1]
    n_held = sizes.shape[0]
    # Slot-major: pair j * T + t is token t's j-th choice.
    local = jnp.transpose(local).reshape(k * tokens)
    order = jnp.argsort(local, stable=True)
    group = local[order]
    computed = group < n_held
    kept = computed
    if capacity is not None and capacity < tokens:
        starts = jnp.cumsum(sizes) - sizes
        rank = jnp.arange(k * tokens) - starts[
            jnp.minimum(group, n_held - 1)]
        kept = computed & (rank < capacity)

    token_of = order % tokens
    xs = x2d.astype(compute_dtype)[token_of]              # [kT, d]
    out = _expert_mlp(lambda a, w: jax.lax.ragged_dot(a, w, sizes), xs,
                      weights, act, compute_dtype)        # [kT, d]
    # Rows past the held pairs belong to no group: whatever the
    # product left there is not read.
    weight = jnp.transpose(gates).reshape(k * tokens)[order]
    out = jnp.where(kept[:, None],
                    out.astype(jnp.float32) * weight[:, None], 0.0)
    # Back to slot-major order, and a token's k routes summed.
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(k * tokens, dtype=order.dtype))
    return out[back].reshape(k, tokens, d_model).sum(axis=0).astype(
        compute_dtype)


def expert_parallel_rules(ep_axis: str = "ep"):
    """Sharding rules putting the expert dim on the "ep" mesh axis —
    compose with `tensor_parallel_rules` in
    `Trainer(param_sharding_rules=...)`."""
    return [
        (r"expert_(in|out|gate|up|down)$", P(ep_axis, None, None)),
        # Router stays replicated: every token scores every expert.
    ]
