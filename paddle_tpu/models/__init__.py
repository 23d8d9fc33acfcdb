"""Flagship model zoo (BASELINE.json configs: GPT-3 family pretraining,
LLaMA hybrid parallel; vision models live in paddle_tpu.vision)."""
from .gpt import (GPTConfig, GPTForCausalLM, GPTModel,
                  GPTPipelineForCausalLM, gpt_tiny, gpt_125m, gpt_1p3b,
                  gpt_6p7b)
from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel,
                    LlamaPipelineForCausalLM, llama_tiny, llama_7b,
                    llama_13b)
from .afmoe import AfmoeConfig, AfmoeForCausalLM, AfmoeModel
from .deepseek_v3 import (DeepseekV3Attention, DeepseekV3Block,
                          DeepseekV3Config, DeepseekV3ForCausalLM,
                          DeepseekV3Model)
from .smallthinker import (SmallThinkerAttention, SmallThinkerBlock,
                           SmallThinkerConfig, SmallThinkerForCausalLM,
                           SmallThinkerModel)
from .bert import (BertConfig, BertModel, BertForSequenceClassification,
                   BertForMaskedLM, ErnieModel, bert_tiny, bert_base,
                   ernie_3_tiny, ernie_3_base)

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM",
           "GPTPipelineForCausalLM", "gpt_tiny", "gpt_125m", "gpt_1p3b",
           "gpt_6p7b",
           "LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaPipelineForCausalLM", "llama_tiny", "llama_7b",
           "llama_13b",
           "AfmoeConfig", "AfmoeModel", "AfmoeForCausalLM",
           "DeepseekV3Config", "DeepseekV3Attention", "DeepseekV3Block",
           "DeepseekV3Model", "DeepseekV3ForCausalLM",
           "SmallThinkerConfig", "SmallThinkerAttention",
           "SmallThinkerBlock", "SmallThinkerModel",
           "SmallThinkerForCausalLM",
           "BertConfig", "BertModel", "BertForSequenceClassification",
           "BertForMaskedLM", "ErnieModel", "bert_tiny", "bert_base",
           "ernie_3_tiny", "ernie_3_base"]
