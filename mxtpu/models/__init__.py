"""Flagship model families built tpu-first (transformer encoder/decoder).

The reference's transformer story is GluonNLP BERT riding the fused
interleaved-MHA kernels in src/operator/contrib/transformer.cc (SURVEY §2.1
operator library row); its decoder-era models don't exist in MXNet 1.x.
Here both live in-tree: BERT-style encoders (GluonNLP's BERT-base) and a
Llama-style decoder, designed for SPMD execution —
sharding rules for tensor parallel, ring attention for sequence parallel,
bf16-first compute.
"""

from . import transformer
from .transformer import (MultiHeadAttention, TransformerEncoderLayer,
                          TransformerEncoder, BERTModel, bert_base,
                          LlamaDecoderLayer, TransformerLM, llama_tiny,
                          llama_3_8b, transformer_lm_sharding_rules,
                          bert_sharding_rules)
from . import moe
from .moe import SwitchMoE, MoEDecoderLayer, moe_sharding_rules
from . import parts
from . import kimi_linear
from .kimi_linear import KimiLinearLM, kimi_linear_from_config
from . import glm4_moe_lite
from .glm4_moe_lite import Glm4MoeLiteLM, glm4_moe_lite_from_config
from . import keye_vl
from .keye_vl import KeyeVLTextLM, keye_vl_from_config
from . import lfm2_moe
from .lfm2_moe import Lfm2MoeLM, lfm2_moe_from_config
from . import smallthinker
from .smallthinker import SmallThinkerLM, smallthinker_from_config
from . import sampler
from .sampler import (BeamSearchSampler, NGramDrafter, SequenceSampler,
                      beam_search)
