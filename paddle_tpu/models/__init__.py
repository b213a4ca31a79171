"""Model zoo.

Parity intent: the reference ecosystem's model families (PaddleNLP llama/
ernie, PaddleClas resnet, BASELINE.json configs) — here implemented
natively on paddle_tpu layers with mesh-shardable parameters.
"""
from .llama import (LlamaConfig, LlamaModel, LlamaForCausalLM,
                    LlamaPretrainingCriterion, llama_tiny_config,
                    llama_7b_config)
from .resnet import ResNet, resnet18, resnet34, resnet50, resnet101, \
    resnet152
from .bert import BertConfig, BertModel, BertForPretraining, \
    BertForSequenceClassification
from .gpt import GPTConfig, GPTModel, GPTForCausalLM
from .qwen import (Qwen2Config, Qwen2Model, Qwen2ForCausalLM,
                   Qwen2PretrainingCriterion, qwen2_tiny_config)
from .mixtral import (MixtralConfig, MixtralModel, MixtralForCausalLM,
                      MixtralPretrainingCriterion, MixtralSparseMoeBlock,
                      mixtral_tiny_config, shard_mixtral)
from .deepseek_v2 import (DeepseekV2Config, DeepseekV2Model,
                          DeepseekV2ForCausalLM, deepseek_v2_tiny_config)
