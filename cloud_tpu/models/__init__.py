from cloud_tpu.models.llama import (GQAttention, LlamaLM, RopeScaling,
                                    llama_tensor_parallel_rules)
from cloud_tpu.models.deepseek import (DeepseekLM, DeepseekMoE,
                                       MLAttention,
                                       deepseek_tensor_parallel_rules)
from cloud_tpu.models.mnist import MLP, ConvNet
from cloud_tpu.models.resnet import (ResNet, ResNet18, ResNet34, ResNet50,
                                     ResNet101, ResNet152)
from cloud_tpu.models.moe import (MoEMLP, TopKMoEMLP,
                                  expert_parallel_rules)
from cloud_tpu.models.pipelined import PipelinedLM, pipelined_lm_rules
from cloud_tpu.models.beam import generate_beam
from cloud_tpu.models.speculative import (SpeculativeBatchError,
                                          SpeculativeShardingError,
                                          generate_speculative)
from cloud_tpu.models.hf_import import (import_hf_deepseek,
                                        import_hf_gpt2, import_hf_llama)
from cloud_tpu.models.transformer import (TransformerEncoder,
                                          TransformerLM, generate,
                                          tensor_parallel_rules)
from cloud_tpu.models.vit import ViT, ViT_B16, ViT_L16, ViT_S16
from cloud_tpu.models.mamba2 import Mamba2Mixer
from cloud_tpu.models.nemotron_h import NemotronHLM
from cloud_tpu.models.evabyte import EvaByteLM
