from .adapters import KerasModelAdapter
from .beam import generate_beam
from .fsdp_lm import LMFsdpLayout, build_lm_fsdp_train_step
from .hf_import import lm_from_hf, load_hf_lm
from .moe_tp import (
    build_moe_lm_tp_generate,
    build_moe_lm_tp_train_step,
    moe_tp_specs,
    shard_moe_tp_params,
)
from .pipeline_lm import (
    build_lm_pp_train_step,
    build_lm_pp_tp_train_step,
    lm_pp_specs,
    lm_pp_tp_specs,
)
from .losses import resolve_accuracy, resolve_per_sample_loss
from .optimizers import (
    FusedOptimizer,
    adam_compact,
    fused_adam,
    scale_by_adam_compact,
    to_optax,
)
from .lora import (
    LoRATensor,
    apply_lora,
    build_lora_lm_train_step,
    load_lora,
    lora_mask,
    lora_trainable_count,
    merge_lora,
    save_lora,
)
from .quantize import (
    QuantizedTensor,
    dequantize_params,
    quantize_lm_params,
    quantized_nbytes,
)
from .sharded_generate import build_lm_generate
from .tensor_lm import (
    build_lm_tp_generate,
    build_lm_tp_train_step,
    build_mesh_tp,
    shard_tp_params,
    tp_specs,
)
from .transformer import (
    SEQ_AXIS,
    MoETransformerLM,
    TransformerLM,
    build_lm_eval_step,
    build_lm_train_step,
    build_mesh_sp,
    chunked_summed_xent,
    make_lm_batches,
    ring_psum,
    select_tokens,
    shard_lm_batch,
)

__all__ = [
    "LMFsdpLayout",
    "build_lm_fsdp_train_step",
    "build_lm_pp_train_step",
    "build_lm_pp_tp_train_step",
    "lm_pp_tp_specs",
    "lm_pp_specs",
    "build_moe_lm_tp_generate",
    "build_moe_lm_tp_train_step",
    "moe_tp_specs",
    "shard_moe_tp_params",
    "LoRATensor",
    "apply_lora",
    "build_lora_lm_train_step",
    "load_lora",
    "save_lora",
    "lora_mask",
    "lora_trainable_count",
    "merge_lora",
    "QuantizedTensor",
    "dequantize_params",
    "quantize_lm_params",
    "quantized_nbytes",
    "KerasModelAdapter",
    "generate_beam",
    "lm_from_hf",
    "load_hf_lm",
    "resolve_per_sample_loss",
    "resolve_accuracy",
    "FusedOptimizer",
    "adam_compact",
    "fused_adam",
    "scale_by_adam_compact",
    "to_optax",
    "build_lm_generate",
    "build_lm_tp_generate",
    "build_lm_tp_train_step",
    "build_mesh_tp",
    "shard_tp_params",
    "tp_specs",
    "select_tokens",
    "SEQ_AXIS",
    "TransformerLM",
    "MoETransformerLM",
    "build_mesh_sp",
    "build_lm_train_step",
    "build_lm_eval_step",
    "chunked_summed_xent",
    "make_lm_batches",
    "ring_psum",
    "shard_lm_batch",
]
