"""The flash-attention backward kernels' share of their roofline in a training
step: the least time for the backward's four products over the device time of
the events of the kernels the program declares as `flash_bwd_dq` and
`flash_bwd_dkv` (both run once a layer a step)."""

from cellbench.counts import flash_attention_passes as passes
from cellbench.layer_metrics import flash_fwd_roofline


def read(observed):
    return flash_fwd_roofline.read(
        observed, kernels=("flash_bwd_dq", "flash_bwd_dkv"),
        least_seconds=passes.backward_least_seconds)
