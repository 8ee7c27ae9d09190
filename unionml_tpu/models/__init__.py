"""TPU-native model zoo (BASELINE.json configs #2-#5).

The reference has no model zoo — users bring sklearn/torch/keras objects
(reference: unionml/model.py:931-988 detects the framework only to pick a
serializer). The TPU-native framework ships flax modules whose forward and
train steps are jit/pjit programs, each family paired with tensor-parallel
partition rules for :class:`unionml_tpu.parallel.ShardingConfig`.
"""

from unionml_tpu.models.bert import (
    BERT_PARTITION_RULES,
    BertClassifier,
    BertConfig,
    BertEncoder,
    BertMlm,
    make_mlm_batch,
    mlm_step,
)
from unionml_tpu.models.llama import (
    LLAMA_INT4_PARTITION_RULES,
    LLAMA_LORA_PARTITION_RULES,
    LLAMA_MOE_PARTITION_RULES,
    LLAMA_PARTITION_RULES,
    LLAMA_QUANT_PARTITION_RULES,
    Llama,
    LlamaConfig,
    init_cache,
)
from unionml_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig
from unionml_tpu.models.glm_moe_lite import (
    GLM_MOE_LITE_QUANT_PATTERNS,
    GlmMoeLite,
    GlmMoeLiteConfig,
)
from unionml_tpu.models.keye_vl_moe import (
    KEYE_VL_MOE_QUANT_PATTERNS,
    KeyeVLMoe,
    KeyeVLMoeConfig,
)
from unionml_tpu.models.sdar_moe import (
    SDAR_MOE_QUANT_PATTERNS,
    SdarMoe,
    SdarMoeConfig,
)
from unionml_tpu.models.encdec import (
    ENCDEC_PARTITION_RULES,
    EncDecConfig,
    EncoderDecoder,
    init_decoder_cache,
    make_seq2seq_generator,
    make_seq2seq_predictor,
    seq2seq_step,
)
from unionml_tpu.models.convert import (
    bert_config_from_hf,
    export_bert_safetensors,
    export_llama_safetensors,
    export_vit_safetensors,
    llama_config_from_hf,
    load_bert_checkpoint,
    load_llama_checkpoint,
    load_vit_checkpoint,
    merge_pretrained,
    vit_config_from_hf,
)
from unionml_tpu.models.generate import (
    PrefixCache,
    make_generator,
    make_lm_predictor,
    make_prefix_cache,
    serving_params,
)
from unionml_tpu.models.lora import (
    LORA_PARTITION_RULES,
    LoRADenseGeneral,
    LoRATrainState,
    create_lora_train_state,
    merge_lora,
    merge_param_trees,
    split_lora_params,
)
from unionml_tpu.models.speculative import (
    make_speculative_generator,
    make_speculative_predictor,
)
from unionml_tpu.models.mlp import Mlp, MlpConfig
from unionml_tpu.models.sequence_parallel import (
    sequence_parallel_config,
    sequence_parallel_lm_step,
)
from unionml_tpu.models.pipeline_lm import (
    PIPELINE_PARTITION_RULES,
    create_pipelined_lm_state,
    pipelined_lm_apply,
    pipelined_lm_step,
    to_pipeline_params,
)
from unionml_tpu.models.quantization import LLAMA_QUANT_PATTERNS, QuantizedDenseGeneral, quantize_params
from unionml_tpu.models.train import (
    GradOverlap,
    TrainState,
    adamw,
    classification_step,
    create_train_state,
    grad_overlap_scope,
    lm_step,
    make_evaluator,
    make_predictor,
)
from unionml_tpu.models.vit import VIT_PARTITION_RULES, ViT, ViTConfig

__all__ = [
    "Mlp", "MlpConfig",
    "ViT", "ViTConfig", "VIT_PARTITION_RULES",
    "BertEncoder", "BertClassifier", "BertMlm", "BertConfig",
    "BERT_PARTITION_RULES", "make_mlm_batch", "mlm_step",
    "Llama", "LlamaConfig", "init_cache", "LLAMA_PARTITION_RULES",
    "OlmoHybrid", "OlmoHybridConfig",
    "GlmMoeLite", "GlmMoeLiteConfig", "GLM_MOE_LITE_QUANT_PATTERNS",
    "KeyeVLMoe", "KeyeVLMoeConfig", "KEYE_VL_MOE_QUANT_PATTERNS",
    "SdarMoe", "SdarMoeConfig", "SDAR_MOE_QUANT_PATTERNS",
    "EncoderDecoder", "EncDecConfig", "ENCDEC_PARTITION_RULES",
    "init_decoder_cache", "make_seq2seq_generator", "make_seq2seq_predictor", "seq2seq_step",
    "LLAMA_QUANT_PARTITION_RULES", "LLAMA_MOE_PARTITION_RULES",
    "LLAMA_INT4_PARTITION_RULES",
    "LLAMA_LORA_PARTITION_RULES", "LORA_PARTITION_RULES",
    "LoRADenseGeneral", "LoRATrainState", "create_lora_train_state",
    "merge_lora", "merge_param_trees", "split_lora_params",
    "TrainState", "create_train_state", "classification_step", "lm_step",
    "GradOverlap", "grad_overlap_scope",
    "make_evaluator", "make_predictor",
    "make_speculative_generator", "make_speculative_predictor",
    "make_generator", "make_lm_predictor", "serving_params", "adamw",
    "make_prefix_cache", "PrefixCache",
    "create_pipelined_lm_state", "pipelined_lm_step", "pipelined_lm_apply",
    "to_pipeline_params", "PIPELINE_PARTITION_RULES",
    "sequence_parallel_config", "sequence_parallel_lm_step",
    "QuantizedDenseGeneral", "quantize_params", "LLAMA_QUANT_PATTERNS",
]
