from .llama import (
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
    llama_7b_config,
    llama_tiny_config,
)
from .bert import BertConfig, BertForSequenceClassification, BertModel
from .gpt import GPTConfig, GPTForCausalLM
from .cohere2_moe import (
    Cohere2MoeConfig,
    Cohere2MoeForCausalLM,
    cohere2_moe_tiny_config,
)
from .pangu_ultra_moe import (
    PanguUltraMoEConfig,
    PanguUltraMoEForCausalLM,
    pangu_ultra_moe_tiny_config,
)
from .gigachat3_5 import GigaChat35Config, GigaChat35ForCausalLM
from .qwen3_next import Qwen3NextConfig, Qwen3NextForCausalLM

__all__ = [n for n in dir() if not n.startswith("_")]
