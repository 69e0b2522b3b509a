"""moonlight-16b-a3b [moe]: 27L d=2048 16H MLA without q_lora (kv_lora
512, qk 128+64, v 128), 1 dense layer (ff=11264) then 26 expert layers of
64 routed experts top-6 (expert ff=1408) and 2 shared; DeepSeek-V3's
sigmoid router (noaux_tc, 1 group, normalised, scale 2.446, sequence-wise
balance loss); vocab=163840, rope_theta 50000, RMSNorm eps 1e-5, no MTP.
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]

The balance constants are not in the config; the sigmoid router takes
DeepSeek-V3's (``moe.BIAS_RATE``, ``moe.SEQ_AUX_ALPHA``)."""

from repro.models.config import BlockCfg, Group, MLACfg, ModelConfig
from repro.models.moe import MoEConfig

ARCH = "moonlight-16b-a3b"


def _moe(d_model: int, d_ff: int, n_experts: int, top_k: int,
         ep_degree: int) -> MoEConfig:
    return MoEConfig(d_model=d_model, d_ff=d_ff, n_experts=n_experts,
                     top_k=top_k, ep_degree=ep_degree, scoring="sigmoid",
                     n_group=1, topk_group=1, routed_scale=2.446)


def config(ep_degree: int = 16) -> ModelConfig:
    return ModelConfig(
        name=ARCH, d_model=2048, vocab=163840,
        groups=(
            Group("dense", (BlockCfg("mla", "dense"),), 1),
            Group("moe", (BlockCfg("mla", "moe"),), 26),
        ),
        n_heads=16, n_kv=16, head_dim=128, d_ff=11264,
        rope_theta=50000.0, norm_eps=1e-5,
        mla=MLACfg(q_lora=None, kv_lora=512, dh_nope=128, dh_rope=64,
                   dh_v=128),
        moe=_moe(2048, 1408, 64, 6, ep_degree),
        n_shared=2,
        max_seq=8192,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name=ARCH + "-smoke", d_model=64, vocab=512,
        groups=(
            Group("dense", (BlockCfg("mla", "dense"),), 1),
            Group("moe", (BlockCfg("mla", "moe"),), 2),
        ),
        n_heads=4, n_kv=4, head_dim=32, d_ff=128,
        rope_theta=50000.0, norm_eps=1e-5,
        mla=MLACfg(q_lora=None, kv_lora=32, dh_nope=16, dh_rope=8, dh_v=16),
        moe=_moe(64, 32, 16, 4, 1),
        n_shared=2, q_chunk=32,
        max_seq=256,
    )
