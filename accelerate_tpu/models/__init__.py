from .bloom import BloomConfig, BloomForCausalLM
from .cohere2_moe import Cohere2MoeConfig, Cohere2MoeForCausalLM
from .bert import BertConfig, BertForSequenceClassification, classification_loss
from .gpt2 import GPT2Config, GPT2LMHeadModel
from .llama import LlamaConfig, LlamaForCausalLM, LlamaModel, PipelinedLlamaForCausalLM, causal_lm_loss
from .mixtral import MixtralConfig, MixtralForCausalLM, mixtral_lm_loss
from .pangu_ultra_moe import PanguUltraMoeConfig, PanguUltraMoeForCausalLM
from .phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM
from .resnet import ResNet, ResNetConfig
from .simple import MLP, RegressionModel
from .t5 import T5Config, T5ForConditionalGeneration, seq2seq_lm_loss
from .vit import ViTConfig, ViTForImageClassification
