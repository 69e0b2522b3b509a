"""The language model: embed -> scanned block groups -> head, with
training loss, prefill and single-token decode, for decoder-only,
encoder-decoder (audio), and stub-multimodal (vision) architectures.

Batch conventions
-----------------
train:  {"tokens" [B, St] i32, "labels" [B, St] i32 (-1 = ignore),
         optional "embeds" [B, P, D] (vision stub, prepended),
         optional "frames" [B, Se, D] (audio stub -> encoder)}
decode: serve_step(params, token [B] i32, caches, pos scalar, enc_out?)
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .blocks import (Runtime, block_apply, block_decode, block_init_cache,
                     block_params)
from .common import dense_init, layer_norm, rms_norm, sinusoidal_positions
from .config import Group, ModelConfig
from .moe import bias_step

__all__ = ["init_params", "forward", "loss_fn", "loss_and_stats",
           "update_router_bias", "matrix_mask", "init_caches",
           "prefill", "decode_step", "count_params", "model_flops"]


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _group_params(key, g: Group, cfg: ModelConfig, dtype) -> dict:
    def one_layer(k):
        ks = jax.random.split(k, len(g.blocks))
        return {f"b{i}": block_params(ks[i], b, cfg, dtype)
                for i, b in enumerate(g.blocks)}
    keys = jax.random.split(key, g.repeats)
    return jax.vmap(one_layer)(keys)


def init_params(key, cfg: ModelConfig) -> dict:
    dtype = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8 + len(cfg.groups) + len(cfg.encoder_groups))
    p: Dict[str, Any] = {
        "embed": dense_init(ks[0], (cfg.vocab_padded, cfg.d_model),
                            in_axis=1, dtype=dtype),
        "final_norm": {"w": jnp.ones((cfg.d_model,), jnp.float32)}
        if cfg.norm == "rms" else
        {"w": jnp.ones((cfg.d_model,), jnp.float32),
         "b": jnp.zeros((cfg.d_model,), jnp.float32)},
    }
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_padded),
                               dtype=dtype)
    if cfg.pos_embed == "learned":
        p["pos_embed"] = dense_init(ks[2], (cfg.max_seq, cfg.d_model),
                                    in_axis=1, dtype=dtype)
    for i, g in enumerate(cfg.groups):
        p[f"dec_{g.name}"] = _group_params(ks[4 + i], g, cfg, dtype)
    for i, g in enumerate(cfg.encoder_groups):
        p[f"enc_{g.name}"] = _group_params(
            ks[4 + len(cfg.groups) + i], g, cfg, dtype)
    if cfg.encoder_groups:
        p["enc_final_norm"] = {"w": jnp.ones((cfg.d_model,), jnp.float32),
                               "b": jnp.zeros((cfg.d_model,), jnp.float32)} \
            if cfg.norm == "layer" else \
            {"w": jnp.ones((cfg.d_model,), jnp.float32)}
    if cfg.mtp:
        p["mtp_proj"] = dense_init(ks[3], (2 * cfg.d_model, cfg.d_model),
                                   dtype=dtype)
        p["mtp_block"] = block_params(
            ks[3], cfg.groups[-1].blocks[-1], cfg, dtype)
    return p


# --------------------------------------------------------------------------
# scan-group execution
# --------------------------------------------------------------------------

def _cast_params(tree, cdt, in_loop: bool = False):
    """Cast weight matrices to the compute dtype; norms/scalars stay f32,
    and so does an MoE router, whose top-k must not turn on the
    weights' rounding.  Applied per-layer *inside* scan bodies so the
    FSDP all-gather moves bf16 and the backward's reduce-scatter stays
    inside the loop (casting the whole stacked tree outside the scan
    strands an unsharded f32 gradient accumulator).

    ``in_loop``: the tree is one layer's slice of stacked weights.  XLA
    moves a bare narrowing cast ahead of the loop's slicing, which makes
    a compute-dtype copy of every layer's weights at once (1.3 GB of
    Moonlight's one-chip share); rounding to the compute dtype first
    (``reduce_precision``, after which the cast is exact) keeps the cast
    in the loop."""
    def one(path, a):
        if path and getattr(path[-1], "key", None) == "router":
            return a
        if a.dtype == jnp.int8 and a.ndim > 1:
            # serving quantization: int8-at-rest, dequantised at use (the
            # per-tensor scale is folded into the stored values for the
            # dry-run; a production loader carries explicit scales)
            return a.astype(cdt) * jnp.asarray(0.01, cdt)
        if a.dtype in (jnp.float32, jnp.bfloat16) and a.ndim > 1:
            if in_loop and a.dtype != cdt:
                fi = jnp.finfo(cdt)
                a = lax.reduce_precision(a, fi.nexp, fi.nmant)
            return a.astype(cdt)
        return a
    return jax.tree_util.tree_map_with_path(one, tree)


def _scan_group(gp, x, g: Group, cfg: ModelConfig, rt: Runtime, positions,
                enc_out=None):
    """-> (x, aux): aux maps ``b{i}`` of each expert block to its
    ``block_apply`` aux, stacked over the group's repeats."""
    cdt = _dtype(cfg.compute_dtype)

    def body(carry, layer_p):
        h = carry
        layer_p = _cast_params(layer_p, cdt, in_loop=True)
        aux = {}
        for i, b in enumerate(g.blocks):
            h, a = block_apply(layer_p[f"b{i}"], h, b, cfg, rt, positions,
                               enc_out)
            if a:
                aux[f"b{i}"] = a
        # the carry is what remat saves per layer: keep it SP-sharded
        return _constrain_act(h, rt), aux

    if cfg.remat == "full":
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    elif cfg.remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if cfg.unroll_layers:
        # python loop: identical math, layer-count-proportional HLO (used
        # by the dry-run's cost-analysis calibration; see launch/dryrun)
        auxs = []
        for l in range(g.repeats):
            x, a = body(x, jax.tree.map(lambda a: a[l], gp))
            auxs.append(a)
        return x, jax.tree.map(lambda *ls: jnp.stack(ls), *auxs)
    return lax.scan(body, x, gp)


def _constrain_act(x, rt: Runtime):
    """Pin hidden states to the canonical activation sharding at layer and
    group boundaries: batch over the dp axes and — when sequence
    parallelism is on — sequence over the model axis (Megatron-SP: the
    TP all-reduce splits into reduce-scatter + all-gather with identical
    wire bytes, while resident activations and remat-saved layer inputs
    shrink by the TP degree).  Without the pin, GSPMD's propagation
    through scan bodies can drift into a layout that forces large
    re-materialisation at the head."""
    if rt is None or rt.mesh is None or not rt.dp_axes:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    sp = (getattr(rt, "sp", False) and rt.model_axis and x.ndim >= 3
          and x.shape[1] % rt.mesh.shape[rt.model_axis] == 0)
    if sp:
        spec = P(rt.dp_axes, rt.model_axis, *([None] * (x.ndim - 2)))
    else:
        spec = P(rt.dp_axes, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rt.mesh, spec))


def _final_norm(x, p, cfg):
    if cfg.norm == "layer":
        return layer_norm(x, p["w"], p["b"])
    return rms_norm(x, p["w"], eps=cfg.norm_eps, plus_one=True)


def _embed_tokens(params, tokens, cfg: ModelConfig):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embed:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def _head(params, x, cfg: ModelConfig, rt: Optional[Runtime] = None):
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = (x @ w).astype(jnp.float32)
    if rt is not None and rt.mesh is not None and rt.model_axis:
        # keep the vocab dim model-sharded through the loss
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = P(rt.dp_axes or None, *([None] * (logits.ndim - 2)),
                 rt.model_axis)
        logits = jax.lax.with_sharding_constraint(
            logits, NamedSharding(rt.mesh, spec))
    if cfg.logit_softcap is not None:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    if cfg.vocab_padded != cfg.vocab:
        # vocab-padding columns must never win softmax/argmax
        logits = jnp.where(jnp.arange(cfg.vocab_padded) >= cfg.vocab,
                           -1e30, logits)
    return logits


def _run_encoder(params, frames, cfg: ModelConfig, rt: Runtime):
    x = frames.astype(_dtype(cfg.compute_dtype))
    S = x.shape[1]
    x = x + sinusoidal_positions(S, cfg.d_model)[None].astype(x.dtype)
    positions = jnp.broadcast_to(jnp.arange(S)[None], x.shape[:2])
    x = _constrain_act(x, rt)
    for g in cfg.encoder_groups:
        x, _ = _scan_group(params[f"enc_{g.name}"], x, g, cfg, rt, positions,
                           None)
        x = _constrain_act(x, rt)
    return _final_norm(x, params["enc_final_norm"], cfg)


def forward(params, batch: dict, cfg: ModelConfig, rt: Runtime
            ) -> jnp.ndarray:
    """Training/prefill forward -> logits [B, S, V] (f32)."""
    return _forward(params, batch, cfg, rt)[0]


def _forward(params, batch: dict, cfg: ModelConfig, rt: Runtime):
    """-> (logits, or (logits, MTP logits), aux): aux maps the parameter
    keys of expert blocks (``dec_<group>``, ``mtp_block``) to their
    balance losses and counters."""
    cdt = _dtype(cfg.compute_dtype)
    params = {k: (v if k.startswith(("dec_", "enc_")) else
                  _cast_params(v, cdt)) for k, v in params.items()}
    tokens = batch["tokens"]
    x = _embed_tokens(params, tokens, cfg).astype(cdt)
    if cfg.modality == "vision" and "embeds" in batch:
        x = jnp.concatenate([batch["embeds"].astype(cdt), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.pos_embed == "learned":
        x = x + params["pos_embed"][:S][None].astype(cdt)
    elif cfg.pos_embed == "sinusoidal":
        x = x + sinusoidal_positions(S, cfg.d_model)[None].astype(cdt)

    enc_out = None
    if cfg.encoder_groups:
        enc_out = _run_encoder(params, batch["frames"], cfg, rt)

    x = _constrain_act(x, rt)
    aux = {}
    for g in cfg.groups:
        x, a = _scan_group(params[f"dec_{g.name}"], x, g, cfg, rt, positions,
                           enc_out)
        if a:
            aux[f"dec_{g.name}"] = a
        x = _constrain_act(x, rt)
    x = _final_norm(x, params["final_norm"], cfg)
    if cfg.modality == "vision" and "embeds" in batch:
        x = x[:, batch["embeds"].shape[1]:]  # logits over text positions
    logits = _head(params, x, cfg, rt)
    if cfg.mtp:
        # multi-token prediction: combine h_t with embed(token_{t+1})
        emb_next = jnp.roll(_embed_tokens(params, tokens, cfg), -1, axis=1)
        h_mtp = jnp.concatenate([x.astype(cdt), emb_next.astype(cdt)],
                                axis=-1) @ params["mtp_proj"]
        h_mtp, a = block_apply(params["mtp_block"], h_mtp,
                               cfg.groups[-1].blocks[-1], cfg, rt, positions)
        if a:
            aux["mtp_block"] = a
        logits_mtp = _head(params, _final_norm(
            h_mtp, params["final_norm"], cfg), cfg, rt)
        return (logits, logits_mtp), aux
    return logits, aux


def loss_fn(params, batch: dict, cfg: ModelConfig, rt: Runtime):
    """The training loss (``loss_and_stats``)."""
    return loss_and_stats(params, batch, cfg, rt)[0]


def loss_and_stats(params, batch: dict, cfg: ModelConfig, rt: Runtime):
    """-> (loss, stats).  The loss is the mean next-token cross-entropy
    (labels -1 are masked), plus 0.3 of the MTP head's, plus every
    expert layer's balance loss.  ``stats`` holds each expert layer's
    counters (``moe_apply``), keyed as the parameters are."""
    out, aux = _forward(params, batch, cfg, rt)
    logits_mtp = None
    if cfg.mtp:
        logits, logits_mtp = out
    else:
        logits = out
    labels = batch["labels"]

    def xent(lg, lb):
        # one-hot einsum keeps the vocab dim sharded (take_along_axis over
        # a model-sharded vocab would all-gather the logits)
        lse = jax.nn.logsumexp(lg, axis=-1)
        onehot = jax.nn.one_hot(jnp.maximum(lb, 0), lg.shape[-1],
                                dtype=lg.dtype)
        picked = jnp.einsum("bsv,bsv->bs", lg, onehot)
        mask = (lb >= 0).astype(jnp.float32)
        return jnp.sum((lse - picked) * mask) / jnp.maximum(
            jnp.sum(mask), 1.0)

    loss = xent(logits, labels)
    if logits_mtp is not None:
        labels2 = jnp.concatenate(
            [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
        loss = loss + 0.3 * xent(logits_mtp, labels2)
    bal = [a["bal"] for a in jax.tree.leaves(
        aux, is_leaf=lambda t: isinstance(t, dict) and "bal" in t)]
    loss = loss + sum(jnp.sum(b) for b in bal)
    stats = jax.tree.map(lambda t: {k: v for k, v in t.items() if k != "bal"},
                         aux, is_leaf=lambda t: isinstance(t, dict)
                         and "bal" in t)
    return loss, stats


def matrix_mask(params) -> dict:
    """True for each weight matrix, False for norms, biases and the
    routers' selection biases: the leaves AdamW decays.  A scan group's
    leaves carry a leading layer axis, which does not count."""
    def one(path, a):
        stacked = str(getattr(path[0], "key", "")).startswith(("dec_",
                                                                "enc_"))
        return a.ndim - stacked > 1
    return jax.tree_util.tree_map_with_path(one, params)


def update_router_bias(params, stats, cfg: ModelConfig):
    """Move each expert layer's selection bias against the load its
    router counted in the step (``moe.bias_step``); ``stats`` as
    ``loss_and_stats`` returns them.  Models whose router has no bias
    (softmax scoring) pass through."""
    if cfg.moe is None or cfg.moe.scoring != "sigmoid":
        return params

    def block(p, st):
        moe = dict(p["moe"])
        moe["e_score_correction_bias"] = bias_step(
            moe["e_score_correction_bias"], st["load"])
        return {**p, "moe": moe}

    new = dict(params)
    for key, st in stats.items():
        if key == "mtp_block":
            new[key] = block(params[key], st)
        else:
            new[key] = {**params[key], **{b: block(params[key][b], s)
                                          for b, s in st.items()}}
    return new


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, cache_len: int,
                dtype=None) -> dict:
    dtype = dtype or _dtype(cfg.compute_dtype)

    caches: Dict[str, Any] = {}
    for g in cfg.groups:
        def one_layer(_):
            return {f"b{i}": block_init_cache(b, cfg, batch, cache_len,
                                              dtype)
                    for i, b in enumerate(g.blocks)}
        caches[g.name] = jax.vmap(one_layer)(jnp.arange(g.repeats))
    return caches


def decode_step(params, token: jnp.ndarray, caches: dict, pos, cfg: ModelConfig,
                rt: Runtime, enc_out=None):
    """One greedy decode step.  token [B] i32; pos scalar i32 (absolute
    position of the new token; cache writes roll modulo cache length).
    Returns (next_token [B], logits [B, V], new caches)."""
    cdt = _dtype(cfg.compute_dtype)
    params = {k: (v if k.startswith(("dec_", "enc_")) else
                  _cast_params(v, cdt)) for k, v in params.items()}
    x = _embed_tokens(params, token, cfg).astype(cdt)
    if cfg.pos_embed == "learned":
        x = x + params["pos_embed"][
            jnp.minimum(pos, cfg.max_seq - 1)][None].astype(cdt)

    new_caches = {}
    for g in cfg.groups:
        gp = params[f"dec_{g.name}"]
        gc = caches[g.name]

        def body(carry, xs):
            h = carry
            layer_p, layer_c = xs
            layer_p = _cast_params(layer_p, cdt, in_loop=True)
            newc = {}
            for i, b in enumerate(g.blocks):
                h, c = block_decode(layer_p[f"b{i}"], h, layer_c[f"b{i}"],
                                    b, cfg, rt, pos, enc_out)
                newc[f"b{i}"] = c
            return h, newc

        if cfg.unroll_layers:
            ncs = []
            for l in range(g.repeats):
                x, c_l = body(x, (jax.tree.map(lambda a: a[l], gp),
                                  jax.tree.map(lambda a: a[l], gc)))
                ncs.append(c_l)
            nc = jax.tree.map(lambda *ls: jnp.stack(ls), *ncs)
        else:
            x, nc = lax.scan(body, x, (gp, gc))
        new_caches[g.name] = nc
    x = _final_norm(x, params["final_norm"], cfg)
    logits = _head(params, x, cfg)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return nxt, logits, new_caches


def prefill(params, batch: dict, cfg: ModelConfig, rt: Runtime):
    """Prefill = the forward pass producing last-position logits.  (Cache
    population during prefill shares the forward path; the dry-run's
    prefill cell measures exactly this compute.)"""
    out = forward(params, batch, cfg, rt)
    logits = out[0] if cfg.mtp else out
    return logits[:, -1]


# --------------------------------------------------------------------------
# accounting
# --------------------------------------------------------------------------

def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    shapes = jax.eval_shape(
        partial(init_params, cfg=cfg), jax.random.PRNGKey(0))
    total = 0
    moe_total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        total += n
        keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        if "moe" in keys and any(
                str(k).startswith("w_") for k in keys):
            moe_total += n
    if not active_only or cfg.moe is None:
        return total
    frac = cfg.moe.top_k / cfg.moe.n_experts
    return int(total - moe_total + moe_total * frac)


def model_flops(cfg: ModelConfig, tokens: int) -> float:
    """6*N*D useful-training flops (6*N_active*D for MoE); for serve cells
    the caller divides by 3 (forward only)."""
    n = count_params(cfg, active_only=True)
    return 6.0 * n * tokens
