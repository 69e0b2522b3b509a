"""Model configuration: heterogeneous layer patterns as scan groups.

A model is a sequence of *groups*; each group is a repeating unit of
block configs executed under one ``lax.scan`` (stacked params), so HLO
size is independent of depth — an 80-layer model compiles like a 2-layer
one.  Heterogeneous architectures express their period as the unit:
gemma-2 scans (local, global) pairs, jamba scans its 8-layer
mamba/attention/MoE period, deepseek scans a dense prefix then the MoE
body.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from .mamba import MambaConfig
from .moe import MoEConfig

__all__ = ["BlockCfg", "Group", "MLACfg", "ModelConfig"]


@dataclasses.dataclass(frozen=True)
class MLACfg:
    q_lora: Optional[int] = 1536   # None: queries from one plain ``wq``
    kv_lora: int = 512
    dh_nope: int = 128
    dh_rope: int = 64
    dh_v: int = 128


@dataclasses.dataclass(frozen=True)
class BlockCfg:
    mixer: str = "attn"            # attn | mla | mamba | none
    ffn: str = "dense"             # dense | moe | none
    causal: bool = True
    window: Optional[int] = None   # sliding-window (local) attention
    cross_attn: bool = False       # decoder block attending to encoder


@dataclasses.dataclass(frozen=True)
class Group:
    name: str
    blocks: Tuple[BlockCfg, ...]
    repeats: int

    @property
    def n_layers(self) -> int:
        return len(self.blocks) * self.repeats


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    vocab: int
    groups: Tuple[Group, ...]
    # attention geometry
    n_heads: int = 8
    n_kv: int = 8
    head_dim: Optional[int] = None
    d_ff: int = 0
    rope_theta: float = 10000.0
    qk_norm: bool = False
    qkv_bias: bool = False
    attn_softcap: Optional[float] = None
    logit_softcap: Optional[float] = None
    norm: str = "rms"              # rms | layer
    norm_eps: float = 1e-6         # epsilon of the block and final RMSNorms
    post_norms: bool = False       # gemma-2 sandwich norms
    pos_embed: str = "rope"        # rope | sinusoidal | learned | none
    tie_embeddings: bool = False
    # sub-configs
    moe: Optional[MoEConfig] = None
    mla: Optional[MLACfg] = None
    mamba: Optional[MambaConfig] = None
    n_shared: int = 0              # deepseek shared experts alongside MoE
    # enc-dec
    encoder_groups: Tuple[Group, ...] = ()
    # modality stub: input embeddings are provided directly for the first
    # `stub_prefix` positions (vision patches / audio frames)
    modality: str = "none"         # none | vision | audio
    stub_prefix: int = 0
    # multi-token prediction (deepseek): extra next-next-token head
    mtp: bool = False
    scale_embed: bool = False      # gemma: embeddings scaled by sqrt(d)
    # execution policy
    unroll_layers: bool = False    # python-loop groups (FLOP calibration)
    attn_impl: str = "blocked"
    q_chunk: int = 512
    remat: str = "full"            # full | dots | none
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    max_seq: int = 8192            # RoPE/learned-position capacity

    @property
    def vocab_padded(self) -> int:
        """Embedding/head tables padded to a 256 multiple so the vocab dim
        divides any production mesh axis; padded logits are masked."""
        return -(-self.vocab // 256) * 256

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def n_layers(self) -> int:
        return sum(g.n_layers for g in self.groups) \
            + sum(g.n_layers for g in self.encoder_groups)

    def param_count(self) -> int:
        """Analytic parameter count (used for 6*N*D roofline flops)."""
        from . import lm
        return lm.count_params(self)

    def active_param_count(self) -> int:
        from . import lm
        return lm.count_params(self, active_only=True)
