"""Operations and bytes of causal flash attention, one layer and one step,
split by pass (the whole is `flash_attention.py`): the forward's 2 matrix
products (QK^T, PV) and the backward's 4 (dV, dP, dQ, dK), each seq x seq x
depth a head and halved by the causal mask; the backward's recomputed QK^T is
not counted. Bytes at 2 bytes: the forward reads q, k, v and writes o; the
backward reads q, o, do, k, v and writes dq, dk, dv."""

from cellbench.counts import least_seconds


def product_flops(batch, heads, seq, depth):
    return 2 * batch * heads * seq * seq * depth // 2


def q_bytes(batch, heads, seq, depth, itemsize=2):
    return batch * heads * seq * depth * itemsize


def forward_least_seconds(batch, heads, kv_heads, seq, depth, peaks):
    q, kv = q_bytes(batch, heads, seq, depth), 2 * q_bytes(batch, kv_heads, seq, depth)
    return least_seconds(2 * product_flops(batch, heads, seq, depth), 2 * q + kv,
                         peaks)


def backward_least_seconds(batch, heads, kv_heads, seq, depth, peaks):
    q, kv = q_bytes(batch, heads, seq, depth), 2 * q_bytes(batch, kv_heads, seq, depth)
    return least_seconds(4 * product_flops(batch, heads, seq, depth),
                         4 * q + 2 * kv, peaks)
