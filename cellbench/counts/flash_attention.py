"""Operations and bytes of causal flash attention, forward and backward, for
one layer and one step, as the algorithm needs them: 2 matrix products
forward (QK^T, PV) and 4 backward (dV, dP, dQ, dK), each seq x seq x depth a
head and halved by the causal mask; the backward's recomputed QK^T is not
counted. Bytes: q, k, v, o read or written once a pass at 2 bytes."""

from cellbench.counts import least_seconds


def train_flops(batch, heads, seq, depth):
    per_product = 2 * batch * heads * seq * seq * depth // 2
    return 6 * per_product


def train_bytes(batch, heads, kv_heads, seq, depth, itemsize=2):
    q = batch * heads * seq * depth * itemsize
    kv = 2 * batch * kv_heads * seq * depth * itemsize
    forward = 2 * q + kv                 # read q, k, v; write o
    backward = 4 * q + 2 * kv            # read q, o, do, k, v; write dq, dk, dv
    return forward + backward


def train_least_seconds(batch, heads, kv_heads, seq, depth, peaks):
    return least_seconds(train_flops(batch, heads, seq, depth),
                         train_bytes(batch, heads, kv_heads, seq, depth), peaks)
