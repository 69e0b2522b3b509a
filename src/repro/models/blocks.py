"""Transformer/Mamba block implementations: init, train apply, decode step.

All blocks are pre-norm residual; gemma-2's ``post_norms`` adds the
sandwich norms.  Attention supports GQA, qk-norm, QKV bias, RoPE,
sliding windows, soft-capping and MLA (compressed-KV) — each feature
driven by the :class:`ModelConfig`.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .attention import attention, decode_attention
from .common import apply_rope, dense_init, layer_norm, rms_norm
from .config import BlockCfg, ModelConfig
from .mamba import (mamba_apply, mamba_decode_step, mamba_init_cache,
                    mamba_params)
from .moe import moe_apply, moe_params

__all__ = ["block_params", "block_apply", "block_decode",
           "block_init_cache", "Runtime"]


class Runtime:
    """Execution context handed down from the launcher: mesh + axis roles.

    ``dp_axes``: batch-sharding axes (also the MoE token axes).
    ``seq_axes``: KV-cache sequence-sharding axes for decode (defaults to
    the model axis; long-context cells widen it to (data, model))."""

    def __init__(self, mesh=None, dp_axes: Tuple[str, ...] = (),
                 model_axis: Optional[str] = None,
                 seq_axes: Optional[Tuple[str, ...]] = None,
                 sp: bool = False, decode_pos=None):
        self.mesh = mesh
        self.dp_axes = dp_axes
        self.model_axis = model_axis
        self.sp = sp
        self.seq_axes = tuple(seq_axes) if seq_axes is not None \
            else ((model_axis,) if model_axis else ())
        self.decode_pos = decode_pos  # traced write position for caches

    @property
    def distributed(self) -> bool:
        return self.mesh is not None and self.model_axis is not None


def _norm(x, p, kind: str, plus_one: bool = False, eps: float = 1e-6):
    if kind == "layer":
        return layer_norm(x, p["w"], p["b"])
    return rms_norm(x, p["w"], eps=eps, plus_one=plus_one)


def _norm_params(d: int, kind: str):
    if kind == "layer":
        return {"w": jnp.ones((d,), jnp.float32),
                "b": jnp.zeros((d,), jnp.float32)}
    return {"w": jnp.zeros((d,), jnp.float32)}  # rms stored as (1+w) style


# --------------------------------------------------------------------------
# parameter init
# --------------------------------------------------------------------------

def _attn_params(key, cfg: ModelConfig, dtype) -> dict:
    hd = cfg.hd
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (cfg.d_model, cfg.n_heads * hd), dtype=dtype),
        "wk": dense_init(ks[1], (cfg.d_model, cfg.n_kv * hd), dtype=dtype),
        "wv": dense_init(ks[2], (cfg.d_model, cfg.n_kv * hd), dtype=dtype),
        "wo": dense_init(ks[3], (cfg.n_heads * hd, cfg.d_model), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _mla_params(key, cfg: ModelConfig, dtype) -> dict:
    m = cfg.mla
    ks = jax.random.split(key, 6)
    h = cfg.n_heads
    dq = h * (m.dh_nope + m.dh_rope)
    if m.q_lora:
        p = {"wq_a": dense_init(ks[0], (cfg.d_model, m.q_lora), dtype=dtype),
             "q_norm": jnp.ones((m.q_lora,), jnp.float32),
             "wq_b": dense_init(ks[1], (m.q_lora, dq), dtype=dtype)}
    else:
        p = {"wq": dense_init(ks[0], (cfg.d_model, dq), dtype=dtype)}
    return {
        **p,
        "wkv_a": dense_init(ks[2], (cfg.d_model, m.kv_lora + m.dh_rope),
                            dtype=dtype),
        "kv_norm": jnp.ones((m.kv_lora,), jnp.float32),
        "wkv_b": dense_init(ks[3], (m.kv_lora, h * (m.dh_nope + m.dh_v)),
                            dtype=dtype),
        "wo": dense_init(ks[4], (h * m.dh_v, cfg.d_model), dtype=dtype),
    }


def _mlp_params(key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (cfg.d_model, cfg.d_ff), dtype=dtype),
        "w_up": dense_init(ks[1], (cfg.d_model, cfg.d_ff), dtype=dtype),
        "w_down": dense_init(ks[2], (cfg.d_ff, cfg.d_model), dtype=dtype),
    }


def block_params(key, bcfg: BlockCfg, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {}
    if bcfg.mixer == "attn":
        p["attn"] = _attn_params(ks[0], cfg, dtype)
        p["ln1"] = _norm_params(cfg.d_model, cfg.norm)
    elif bcfg.mixer == "mla":
        p["attn"] = _mla_params(ks[0], cfg, dtype)
        p["ln1"] = _norm_params(cfg.d_model, cfg.norm)
    elif bcfg.mixer == "mamba":
        p["mamba"] = mamba_params(ks[0], cfg.mamba, dtype)
        p["ln1"] = _norm_params(cfg.d_model, cfg.norm)
    if cfg.post_norms and bcfg.mixer != "none":
        p["post_ln1"] = _norm_params(cfg.d_model, cfg.norm)
    if bcfg.cross_attn:
        p["xattn"] = _attn_params(ks[1], cfg, dtype)
        p["ln_x"] = _norm_params(cfg.d_model, cfg.norm)
    if bcfg.ffn == "dense":
        p["mlp"] = _mlp_params(ks[2], cfg, dtype)
        p["ln2"] = _norm_params(cfg.d_model, cfg.norm)
    elif bcfg.ffn == "moe":
        p["moe"] = moe_params(ks[2], cfg.moe, dtype)
        p["ln2"] = _norm_params(cfg.d_model, cfg.norm)
        if cfg.n_shared:
            # the shared experts, each expert-sized (cfg.moe.d_ff), run
            # as one MLP of their summed width
            f = cfg.n_shared * cfg.moe.d_ff
            kk = jax.random.split(ks[3], 3)
            p["shared_mlp"] = {
                "w_gate": dense_init(kk[0], (cfg.d_model, f), dtype=dtype),
                "w_up": dense_init(kk[1], (cfg.d_model, f), dtype=dtype),
                "w_down": dense_init(kk[2], (f, cfg.d_model), dtype=dtype),
            }
    if cfg.post_norms and bcfg.ffn != "none":
        p["post_ln2"] = _norm_params(cfg.d_model, cfg.norm)
    return p


# --------------------------------------------------------------------------
# apply (train / prefill)
# --------------------------------------------------------------------------

def _mlp(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def _attn_fwd(p, h, cfg: ModelConfig, bcfg: BlockCfg, positions,
              kv_override=None):
    B, S, D = h.shape
    hd = cfg.hd
    q = h @ p["wq"]
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
    q = q.reshape(B, S, cfg.n_heads, hd)
    if kv_override is None:
        src = h
    else:
        src = kv_override              # cross attention reads encoder states
    Skv = src.shape[1]
    k = src @ p["wk"]
    v = src @ p["wv"]
    if "bk" in p:
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    k = k.reshape(B, Skv, cfg.n_kv, hd)
    v = v.reshape(B, Skv, cfg.n_kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if cfg.pos_embed == "rope" and kv_override is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    o = attention(q, k, v, impl=cfg.attn_impl,
                  causal=bcfg.causal and kv_override is None,
                  window=bcfg.window, softcap=cfg.attn_softcap,
                  q_chunk=cfg.q_chunk)
    return o.reshape(B, S, cfg.n_heads * hd) @ p["wo"], (k, v)


def _mla_q(p, h):
    """MLA queries: through the low-rank q_lora path, or one plain ``wq``
    where the model has no q_lora."""
    if "wq" in p:
        return h @ p["wq"]
    return rms_norm(h @ p["wq_a"], p["q_norm"]) @ p["wq_b"]


@jax.named_scope("mla")
def _mla_fwd(p, h, cfg: ModelConfig, bcfg: BlockCfg, positions):
    """MLA training/prefill path (decompressed K/V)."""
    m = cfg.mla
    B, S, D = h.shape
    H = cfg.n_heads
    q = _mla_q(p, h)
    q = q.reshape(B, S, H, m.dh_nope + m.dh_rope)
    q_nope, q_rope = q[..., :m.dh_nope], q[..., m.dh_nope:]
    kv = h @ p["wkv_a"]
    c_kv = rms_norm(kv[..., :m.kv_lora], p["kv_norm"])
    k_rope = kv[..., m.kv_lora:].reshape(B, S, 1, m.dh_rope)
    kvb = c_kv @ p["wkv_b"]
    kvb = kvb.reshape(B, S, H, m.dh_nope + m.dh_v)
    k_nope, v = kvb[..., :m.dh_nope], kvb[..., m.dh_nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, m.dh_rope))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = 1.0 / math.sqrt(m.dh_nope + m.dh_rope)
    o = attention(qf, k, v, impl=cfg.attn_impl, causal=bcfg.causal,
                  window=bcfg.window, softcap=cfg.attn_softcap,
                  scale=scale, q_chunk=cfg.q_chunk)
    out = o.reshape(B, S, H * m.dh_v) @ p["wo"]
    return out, (c_kv, k_rope.reshape(B, S, m.dh_rope))


def _ffn_fwd(p, h, cfg: ModelConfig, bcfg: BlockCfg, rt: Runtime):
    """-> (output, aux): aux is the expert layer's balance loss and
    counters (``moe_apply``), empty for a dense MLP."""
    if bcfg.ffn == "dense":
        return _mlp(p["mlp"], h), {}
    out, bal, stats = moe_apply(
        p["moe"], h, cfg.moe, mesh=rt.mesh if rt.distributed else None,
        model_axis=rt.model_axis or "model", dp_axes=rt.dp_axes)
    if cfg.n_shared:
        out = out + _mlp(p["shared_mlp"], h)
    return out, {"bal": bal, **stats}


def block_apply(p: dict, x: jnp.ndarray, bcfg: BlockCfg, cfg: ModelConfig,
                rt: Runtime, positions, enc_out=None) -> tuple:
    """-> (x, aux): aux as ``_ffn_fwd`` gives it."""
    plus_one = cfg.norm == "rms"
    aux = {}
    if bcfg.mixer in ("attn", "mla"):
        h = _norm(x, p["ln1"], cfg.norm, plus_one, cfg.norm_eps)
        if bcfg.mixer == "attn":
            o, _ = _attn_fwd(p["attn"], h, cfg, bcfg, positions)
        else:
            o, _ = _mla_fwd(p["attn"], h, cfg, bcfg, positions)
        if cfg.post_norms:
            o = _norm(o, p["post_ln1"], cfg.norm, plus_one, cfg.norm_eps)
        x = x + o
    elif bcfg.mixer == "mamba":
        h = _norm(x, p["ln1"], cfg.norm, plus_one, cfg.norm_eps)
        x = x + mamba_apply(p["mamba"], h, cfg.mamba)
    if bcfg.cross_attn:
        h = _norm(x, p["ln_x"], cfg.norm, plus_one, cfg.norm_eps)
        o, _ = _attn_fwd(p["xattn"], h, cfg, bcfg, positions,
                         kv_override=enc_out)
        x = x + o
    if bcfg.ffn != "none":
        h = _norm(x, p["ln2"], cfg.norm, plus_one, cfg.norm_eps)
        o, aux = _ffn_fwd(p, h, cfg, bcfg, rt)
        if cfg.post_norms:
            o = _norm(o, p["post_ln2"], cfg.norm, plus_one, cfg.norm_eps)
        x = x + o
    return x, aux


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------

def block_init_cache(bcfg: BlockCfg, cfg: ModelConfig, batch: int,
                     cache_len: int, dtype) -> dict:
    c: Dict[str, Any] = {}
    if bcfg.mixer == "attn":
        S = min(bcfg.window, cache_len) if bcfg.window else cache_len
        c["k"] = jnp.zeros((batch, S, cfg.n_kv, cfg.hd), dtype)
        c["v"] = jnp.zeros((batch, S, cfg.n_kv, cfg.hd), dtype)
    elif bcfg.mixer == "mla":
        m = cfg.mla
        c["ckv"] = jnp.zeros((batch, cache_len, m.kv_lora), dtype)
        c["krope"] = jnp.zeros((batch, cache_len, m.dh_rope), dtype)
    elif bcfg.mixer == "mamba":
        c.update(mamba_init_cache(batch, cfg.mamba, dtype))
    return c


def _attn_decode(p, h, cache, cfg: ModelConfig, bcfg: BlockCfg, rt: Runtime,
                 pos):
    B, D = h.shape
    hd = cfg.hd
    q = h @ p["wq"]
    k = h @ p["wk"]
    v = h @ p["wv"]
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(B, cfg.n_heads, hd)
    k = k.reshape(B, 1, cfg.n_kv, hd)
    v = v.reshape(B, 1, cfg.n_kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if cfg.pos_embed == "rope":
        posb = jnp.broadcast_to(pos, (B, 1))
        q = apply_rope(q[:, None], posb, cfg.rope_theta)[:, 0]
        k = apply_rope(k, posb, cfg.rope_theta)
    if rt.distributed:
        o = decode_attention(q, cache["k"], cache["v"], k, v, mesh=rt.mesh,
                             seq_axes=rt.seq_axes,
                             batch_axes=rt.dp_axes,
                             softcap=cfg.attn_softcap, window=bcfg.window,
                             pos=pos)
    else:
        from .attention import _partial_softmax, merge_partials
        scale = 1.0 / math.sqrt(hd)
        valid = jnp.arange(cache["k"].shape[1]) < pos
        m1, l1, o1 = _partial_softmax(q, cache["k"], cache["v"],
                                      scale, cfg.attn_softcap, valid)
        m2, l2, o2 = _partial_softmax(q, k, v, scale,
                                      cfg.attn_softcap)
        m, l, o = merge_partials(m1, l1, o1, m2, l2, o2)
        o = (o / jnp.maximum(l, 1e-30)).reshape(B, cfg.n_heads, hd)
        o = o.astype(h.dtype)
    out = o.reshape(B, cfg.n_heads * hd) @ p["wo"]
    # rolling write: replace slot (pos % cache_len)
    slot = (pos % cache["k"].shape[1]).astype(jnp.int32)
    newc = {
        "k": lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0)),
        "v": lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0)),
    }
    return out, newc


def _mla_decode(p, h, cache, cfg: ModelConfig, rt: Runtime, pos):
    """Absorbed MLA decode on the compressed cache (c_kv + shared k_rope)."""
    m = cfg.mla
    B, D = h.shape
    H = cfg.n_heads
    q = _mla_q(p, h).reshape(B, H, m.dh_nope + m.dh_rope)
    q_nope, q_rope = q[..., :m.dh_nope], q[..., m.dh_nope:]
    posb = jnp.broadcast_to(pos, (B, 1))
    q_rope = apply_rope(q_rope[:, None], posb, cfg.rope_theta)[:, 0]

    kv = h @ p["wkv_a"]
    c_new = rms_norm(kv[..., :m.kv_lora], p["kv_norm"])          # [B, 512]
    kr_new = apply_rope(kv[..., m.kv_lora:][:, None, None, :], posb,
                        cfg.rope_theta)[:, 0, 0]                  # [B, 64]

    wkv_b = p["wkv_b"].reshape(m.kv_lora, H, m.dh_nope + m.dh_v)
    w_uk = wkv_b[..., :m.dh_nope]        # [kv_lora, H, dh_nope]
    w_uv = wkv_b[..., m.dh_nope:]        # [kv_lora, H, dh_v]
    # absorb W_uk into the query: q_abs [B, H, kv_lora]
    q_abs = jnp.einsum("bhd,chd->bhc", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = 1.0 / math.sqrt(m.dh_nope + m.dh_rope)

    ckv, krope = cache["ckv"], cache["krope"]                     # [B,S,512]
    s = (jnp.einsum("bhc,bsc->bhs", q_abs, ckv.astype(jnp.float32))
         + jnp.einsum("bhr,bsr->bhs", q_rope.astype(jnp.float32),
                      krope.astype(jnp.float32))) * scale
    s = jnp.where((jnp.arange(ckv.shape[1]) < pos)[None, None, :], s,
                  -1e30)
    s_new = (jnp.einsum("bhc,bc->bh", q_abs, c_new.astype(jnp.float32))
             + jnp.einsum("bhr,br->bh", q_rope.astype(jnp.float32),
                          kr_new.astype(jnp.float32))) * scale
    mmax = jnp.maximum(jnp.max(s, axis=-1), s_new)               # [B, H]
    pcache = jnp.exp(s - mmax[..., None])
    pnew = jnp.exp(s_new - mmax)
    denom = jnp.sum(pcache, axis=-1) + pnew
    ctx_c = (jnp.einsum("bhs,bsc->bhc", pcache, ckv.astype(jnp.float32))
             + pnew[..., None] * c_new.astype(jnp.float32)[:, None, :]) \
        / denom[..., None]                                        # [B,H,512]
    o = jnp.einsum("bhc,chd->bhd", ctx_c, w_uv.astype(jnp.float32))
    out = o.reshape(B, H * m.dh_v).astype(h.dtype) @ p["wo"]
    slot = (pos % ckv.shape[1]).astype(jnp.int32)
    newc = {
        "ckv": lax.dynamic_update_slice(ckv, c_new[:, None].astype(ckv.dtype),
                                        (0, slot, 0)),
        "krope": lax.dynamic_update_slice(
            krope, kr_new[:, None].astype(krope.dtype), (0, slot, 0)),
    }
    return out, newc


def block_decode(p: dict, x: jnp.ndarray, cache: dict, bcfg: BlockCfg,
                 cfg: ModelConfig, rt: Runtime, pos, enc_out=None
                 ) -> Tuple[jnp.ndarray, dict]:
    """One-token decode.  x [B, D]."""
    plus_one = cfg.norm == "rms"
    newc = dict(cache)
    if bcfg.mixer in ("attn", "mla"):
        h = _norm(x, p["ln1"], cfg.norm, plus_one, cfg.norm_eps)
        if bcfg.mixer == "attn":
            o, upd = _attn_decode(p["attn"], h, cache, cfg, bcfg, rt, pos)
        else:
            o, upd = _mla_decode(p["attn"], h, cache, cfg, rt, pos)
        newc.update(upd)
        if cfg.post_norms:
            o = _norm(o, p["post_ln1"], cfg.norm, plus_one, cfg.norm_eps)
        x = x + o
    elif bcfg.mixer == "mamba":
        h = _norm(x, p["ln1"], cfg.norm, plus_one, cfg.norm_eps)
        o, upd = mamba_decode_step(p["mamba"], h, cache, cfg.mamba)
        newc.update(upd)
        x = x + o
    if bcfg.cross_attn:
        h = _norm(x, p["ln_x"], cfg.norm, plus_one, cfg.norm_eps)
        o, _ = _attn_fwd(p["xattn"], h[:, None], cfg, bcfg,
                         jnp.zeros((x.shape[0], 1), jnp.int32),
                         kv_override=enc_out)
        x = x + o[:, 0]
    if bcfg.ffn != "none":
        h = _norm(x, p["ln2"], cfg.norm, plus_one, cfg.norm_eps)
        o = _ffn_fwd(p, h[:, None], cfg, bcfg, rt)[0][:, 0]
        if cfg.post_norms:
            o = _norm(o, p["post_ln2"], cfg.norm, plus_one, cfg.norm_eps)
        x = x + o
    return x, newc
