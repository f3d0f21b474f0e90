"""Operations and bytes of the fused SwiGLU forward for one layer and one
step: three matrix products (gate, up, down) of tokens x hidden x
intermediate, 2 FLOPs a multiply-add; the activation and the product of the
two branches are left out. Bytes at the compute type: x read and the result
written once, each weight matrix read once. The backward is plain lax in the
program and has no kernel to count."""

from cellbench.counts import least_seconds


def forward_flops(tokens, hidden, intermediate):
    return 3 * 2 * tokens * hidden * intermediate


def forward_bytes(tokens, hidden, intermediate, itemsize=2):
    return (2 * tokens * hidden + 3 * hidden * intermediate) * itemsize


def forward_least_seconds(tokens, hidden, intermediate, peaks):
    return least_seconds(forward_flops(tokens, hidden, intermediate),
                         forward_bytes(tokens, hidden, intermediate), peaks)
