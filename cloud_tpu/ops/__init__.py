"""TPU compute kernels (Pallas) and their jnp reference implementations."""

from cloud_tpu.ops.attention import attention
from cloud_tpu.ops.attention import flash_attention
from cloud_tpu.ops.attention import mha_reference
from cloud_tpu.ops.eva import chunk_summaries
from cloud_tpu.ops.fused_ce import lm_head_loss
from cloud_tpu.ops.fused_ce import lm_head_loss_reference
from cloud_tpu.ops.fused_mlp import fused_swiglu
from cloud_tpu.ops.fused_mlp import swiglu_reference
from cloud_tpu.ops.fused_norm import fused_rmsnorm
from cloud_tpu.ops.fused_norm import rmsnorm_residual_reference
from cloud_tpu.ops.paged_attention import paged_attention
from cloud_tpu.ops.paged_attention import paged_attention_reference
from cloud_tpu.ops.paged_attention import paged_decode_attention
from cloud_tpu.ops.ssm import ssm_decode_update
from cloud_tpu.ops.ssm import ssm_decode_update_reference

__all__ = ["attention", "flash_attention", "mha_reference",
           "chunk_summaries",
           "lm_head_loss", "lm_head_loss_reference",
           "fused_swiglu", "swiglu_reference",
           "fused_rmsnorm", "rmsnorm_residual_reference",
           "paged_attention", "paged_attention_reference",
           "paged_decode_attention",
           "ssm_decode_update", "ssm_decode_update_reference"]
