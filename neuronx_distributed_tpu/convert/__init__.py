"""HF-checkpoint interoperability (reference:
``examples/training/llama2/convert_checkpoints.py`` HF↔NxD conversion)."""

from neuronx_distributed_tpu.convert.nxd import (  # noqa: F401
    GPT_NEOX_TP_RULES,
    LLAMA_TP_RULES,
    fuse_split_llama,
    load_nxd_checkpoint,
    merge_tp_shards,
    save_nxd_checkpoint,
    shard_for_rank,
    split_fused_llama,
)
from neuronx_distributed_tpu.convert.hf import (  # noqa: F401
    bert_params_from_hf,
    bert_params_to_hf,
    gemma_params_from_hf,
    gemma_params_to_hf,
    gemma2_params_from_hf,
    gemma2_params_to_hf,
    gpt_neox_params_from_hf,
    gpt_neox_params_from_pipelined,
    gpt_neox_params_to_hf,
    llama_params_from_hf,
    llama_params_from_pipelined,
    llama_params_to_hf,
    minicpm_sala_config_from_hf,
    minicpm_sala_params_from_hf,
    minicpm_sala_params_to_hf,
    nemotron_h_config_from_hf,
    nemotron_h_params_from_hf,
    nemotron_h_params_to_hf,
    olmoe_params_from_hf,
    olmoe_params_to_hf,
    llama_stack_layers,
    llama_unstack_layers,
    mistral_params_from_hf,
    mistral_params_to_hf,
)
