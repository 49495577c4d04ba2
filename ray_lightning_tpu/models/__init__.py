from ray_lightning_tpu.models.boring import BoringModel, XORModel, XORDataModule
from ray_lightning_tpu.models.mnist import (LightningMNISTClassifier,
                                            MNISTClassifier)
from ray_lightning_tpu.models.transformer import (latch_eos,
                                                  tensor_parallel_rule,
                                                  TransformerConfig,
                                                  TransformerLM,
                                                  TransformerEncoder)
from ray_lightning_tpu.models.gpt import GPTModule, gpt2_config, count_params
from ray_lightning_tpu.models.bert import BertModule, BertClassifier, bert_config
from ray_lightning_tpu.models.resnet import (ResNetModule, resnet10,
                                             resnet18, resnet50)
from ray_lightning_tpu.models.moe import (MoeConfig, MoeModule,
                                          MoeTransformerLM,
                                          expert_parallel_rule, moe_config)
from ray_lightning_tpu.models.pipelined_lm import (PipelinedLMModule,
                                                   PipelinedTransformerLM)
from ray_lightning_tpu.models.vit import (ViTClassifier, ViTModule,
                                          vit_config)
from ray_lightning_tpu.models.seq2seq import (Seq2SeqModule,
                                              Seq2SeqTransformer)
from ray_lightning_tpu.models.lora import (LoraConfig, adapter_bytes,
                                           extract_adapter, install_adapter,
                                           install_lora_bank, zero_adapter)
from ray_lightning_tpu.models.sambay import SambaYConfig, SambaYLM
from ray_lightning_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                                  OlmoHybridLM)
from ray_lightning_tpu.models.afmoe import AfmoeConfig, AfmoeLM
from ray_lightning_tpu.models.falcon_h1 import FalconH1Config, FalconH1LM
from ray_lightning_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                                  DeepseekV2LM)
from ray_lightning_tpu.models.generate import (decode_step, generate,
                                               generate_full_scan, prefill,
                                               sample_logits,
                                               sample_logits_rows)

__all__ = [
    "BoringModel", "XORModel", "XORDataModule", "LightningMNISTClassifier",
    "MNISTClassifier", "TransformerConfig", "TransformerLM",
    "TransformerEncoder", "GPTModule", "gpt2_config", "count_params",
    "BertModule", "BertClassifier", "bert_config", "ResNetModule",
    "resnet10", "resnet18", "resnet50", "MoeConfig", "MoeModule", "MoeTransformerLM",
    "expert_parallel_rule", "moe_config", "PipelinedLMModule",
    "PipelinedTransformerLM", "ViTClassifier", "ViTModule", "vit_config",
    "decode_step", "generate", "generate_full_scan", "prefill",
    "sample_logits", "sample_logits_rows", "latch_eos",
    "tensor_parallel_rule",
    "Seq2SeqModule", "Seq2SeqTransformer",
    "SambaYConfig", "SambaYLM", "OlmoHybridConfig", "OlmoHybridLM",
    "AfmoeConfig", "AfmoeLM", "FalconH1Config", "FalconH1LM",
    "DeepseekV2Config", "DeepseekV2LM",
    "LoraConfig", "adapter_bytes", "extract_adapter", "install_adapter",
    "install_lora_bank", "zero_adapter",
]
