"""Model zoo: composable blocks (GQA/MLA attention, SwiGLU, MoE-EP,
Mamba-2 SSD) assembled into decoder-only / enc-dec LMs via scan groups."""

from .blocks import Runtime
from .config import BlockCfg, Group, MLACfg, ModelConfig
from .lm import (count_params, decode_step, forward, init_caches,
                 init_params, loss_and_stats, loss_fn, matrix_mask,
                 model_flops, prefill, update_router_bias)
from .mamba import MambaConfig
from .moe import MoEConfig

__all__ = [
    "Runtime", "BlockCfg", "Group", "MLACfg", "ModelConfig",
    "MambaConfig", "MoEConfig",
    "init_params", "forward", "loss_fn", "loss_and_stats",
    "update_router_bias", "matrix_mask", "prefill", "decode_step",
    "init_caches", "count_params", "model_flops",
]
