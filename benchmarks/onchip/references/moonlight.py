"""Plain reference for a DeepSeek-V3-family decoder (Moonlight-16B-A3B):
forward pass, training loss and gradients, independent of the program
under test.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no batching
tricks.  It reads the program's parameter tree as data (the same names
and stacking: ``dec_dense``/``dec_moe`` with a leading layer axis) and a
plain dict of sizes, ``c``, with Hugging Face's key names where the
model's config has them:

* MLA without q_lora: ``q = h Wq``; ``[c_kv, k_rope] = h Wkv_a``,
  ``c_kv`` RMS-normalised (eps 1e-6, as Hugging Face's
  ``kv_a_layernorm``); ``[k_nope, v] = c_kv Wkv_b``; RoPE on the rope
  parts, one ``k_rope`` shared by all heads; causal softmax at
  ``1/sqrt(qk_nope + qk_rope)``;
* the router: sigmoid scores over all ``router_experts``; selection on
  score + ``e_score_correction_bias`` (no gradient), the best
  ``topk_group`` of ``n_group`` groups by the sum of each group's two
  highest, then the top ``num_experts_per_tok``; weights the chosen
  scores, normalised, times ``routed_scaling_factor``;
* routed experts: each held expert (``experts_held`` = first, count) as
  a dense SwiGLU MLP over every token, weighted by the token's routing
  weight for it (0 where it was not chosen); assignments to experts not
  held add nothing.  Shared experts: one SwiGLU MLP;
* the loss: mean next-token cross-entropy plus, for every expert layer,
  DeepSeek-V3's sequence-wise balance loss ``seq_aux_alpha * sum_i f_i
  P_i`` (arXiv:2412.19437, §2.1.2);
* the step's update of the state: AdamW (bias-corrected moments,
  decoupled weight decay on weight matrices, none on norms) on every
  parameter that takes a gradient, and DeepSeek-V3's update of each
  selection bias, ``b_i += bias_update_speed * sign(mean load -
  load_i)``, from the loads the router chose (§2.1.2).

Departures from the published model, both fixed reparameterisations:
RMSNorm weights are stored as ``w`` and applied as ``1 + w`` (the
published stores the scale itself); RoPE rotates the two halves of the
rope dims (Hugging Face's DeepSeek-V3 rotates interleaved pairs, which is
the same map under a fixed permutation of those dims of ``Wq`` and
``Wkv_a``).

``low``: a dtype that every matmul input is rounded to in the forward
pass (saturating, straight through in the backward), for the control one
precision step below the program's.  Each layer, each held expert,
each query chunk and each chunk of the head's tokens is checkpointed, so
the gradient at 8k tokens fits beside the parameters and the optimizer
state.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rounder(low):
    if low is None:
        return lambda a: a
    top = float(jnp.finfo(low).max)

    def rnd(a):
        r = jnp.clip(a, -top, top).astype(low).astype(F32)
        return a + jax.lax.stop_gradient(r - a)
    return rnd


def _rms(x, w, eps, plus_one=True):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + w) if plus_one else y * w


def _rope(x, theta):
    """x [B, S, H, d], positions 0..S-1, the two halves rotated."""
    S, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mlp(p, x, mm):
    return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]),
              p["w_down"])


def _attention(p, h, c, mm, chunk):
    B, S, _ = h.shape
    H = c["num_attention_heads"]
    nope, rope, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    r = c["kv_lora_rank"]
    q = mm(h, p["wq"]).reshape(B, S, H, nope + rope)
    kv = mm(h, p["wkv_a"])
    ckv = _rms(kv[..., :r], p["kv_norm"], 1e-6, plus_one=False)
    k_rope = _rope(kv[..., r:].reshape(B, S, 1, rope), c["rope_theta"])
    kvb = mm(ckv, p["wkv_b"]).reshape(B, S, H, nope + dv)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_rope, (B, S, H, rope))], -1)
    v = kvb[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:],
                                              c["rope_theta"])], -1)
    scale = 1.0 / math.sqrt(nope + rope)

    @jax.checkpoint
    def rows(args):
        q_chunk, start = args
        s = jnp.einsum("bqhd,bkhd->bhqk", mm(q_chunk, None), mm(k, None))
        s = s * scale
        qpos = start + jnp.arange(q_chunk.shape[1])
        s = jnp.where(qpos[:, None] >= jnp.arange(S)[None, :], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", mm(w, None), mm(v, None))

    n = S // chunk
    qs = jnp.moveaxis(q.reshape(B, n, chunk, H, nope + rope), 1, 0)
    o = jax.lax.map(rows, (qs, chunk * jnp.arange(n)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * dv)
    return mm(o, p["wo"])


def route(x, router, bias, c):
    """x [T, D] -> (experts [T, k], weights [T, k], scores [T, E])."""
    scores = jax.nn.sigmoid(x @ router)
    choice = jax.lax.stop_gradient(scores + bias)
    T, E = choice.shape
    G = c["n_group"]
    if G > 1:
        g = choice.reshape(T, G, E // G)
        top2 = jnp.sort(g, axis=-1)[..., -2:].sum(-1)
        best = jnp.argsort(-top2, axis=-1)[:, :c["topk_group"]]
        keep = (best[:, :, None] == jnp.arange(G)).any(1)
        choice = jnp.where(keep[:, :, None], g, -jnp.inf).reshape(T, E)
    experts = jnp.argsort(-choice, axis=-1)[:, :c["num_experts_per_tok"]]
    w = jnp.take_along_axis(scores, experts, axis=1)
    if c["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return experts, w * c["routed_scaling_factor"], scores


def _moe(p, x, c, mm):
    """x [B, S, D] -> (routed part + shared experts, balance loss,
    assignments per router expert)."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    m = p["moe"]
    E, k = c["router_experts"], c["num_experts_per_tok"]
    experts, w, scores = route(xt, m["router"],
                               m["e_score_correction_bias"], c)
    first, count = c["experts_held"]

    @jax.checkpoint
    def expert(out, e_w):
        e, weights = e_w
        gate = jnp.sum(jnp.where(experts == first + e, w, 0.0), axis=1)
        return out + gate[:, None] * _mlp(weights, xt, mm), None

    held = {n: m[n][:count] for n in ("w_gate", "w_up", "w_down")}
    out, _ = jax.lax.scan(expert, jnp.zeros_like(xt),
                          (jnp.arange(count), held))
    out = out + _mlp(p["shared_mlp"], xt, mm)
    s = scores.reshape(B, S, E)
    P = jnp.mean(s / s.sum(-1, keepdims=True), axis=1)
    f = jnp.mean(jax.nn.one_hot(experts, E).sum(1).reshape(B, S, E),
                 axis=1) * (E / k)
    bal = c["seq_aux_alpha"] * jnp.mean(jnp.sum(f * P, axis=-1))
    load = jnp.sum(jax.nn.one_hot(experts, E, dtype=jnp.int32), axis=(0, 1))
    return out.reshape(B, S, D), bal, load


def _layer(p, x, c, mm, moe: bool, chunk: int):
    eps = c["rms_norm_eps"]
    x = x + _attention(p["attn"], _rms(x, p["ln1"]["w"], eps), c, mm, chunk)
    h = _rms(x, p["ln2"]["w"], eps)
    if moe:
        o, bal, load = _moe(p, h, c, mm)
    else:
        o, bal = _mlp(p["mlp"], h, mm), jnp.zeros((), F32)
        load = jnp.zeros((c["router_experts"],), jnp.int32)
    return x + o, (bal, load)


def loss(params, tokens, labels, c, *, low=None, chunk: int = 512):
    """-> (mean next-token cross-entropy plus the balance losses, in
    float32; loads [expert layers, router_experts] int32, each expert
    layer's assignments per expert).  tokens, labels [B, S] int32."""
    params = jax.tree.map(lambda a: a.astype(F32), params)
    chunk = min(chunk, tokens.shape[1])
    rnd = _rounder(low)

    def mm(a, b):
        return rnd(a) if b is None else rnd(a) @ rnd(b)

    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0)
        total_bal = jnp.zeros((), F32)
        for group, moe in (("dec_dense", False), ("dec_moe", True)):
            layer = jax.checkpoint(functools.partial(
                _layer, c=c, mm=mm, moe=moe, chunk=chunk))
            x, (bal, loads) = jax.lax.scan(lambda h, p: layer(p, h), x,
                                           params[group]["b0"])
            total_bal = total_bal + jnp.sum(bal)
        B, S, D = x.shape
        V = c["vocab_size"]

        @jax.checkpoint
        def xent(args):
            h, lab = args
            logits = mm(_rms(h, params["final_norm"]["w"], c["rms_norm_eps"]),
                        params["head"])
            logits = jnp.where(jnp.arange(logits.shape[-1]) < V, logits,
                               -jnp.inf)
            picked = jnp.take_along_axis(logits, lab[:, None], -1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

        n = B * S // chunk
        parts = jax.lax.map(xent, (x.reshape(n, chunk, D),
                                   labels.reshape(n, chunk)))
        return jnp.sum(parts) / (B * S) + total_bal, loads


def loss_and_grads(params, tokens, labels, c, *, low=None, chunk: int = 512):
    """(loss, the expert layers' loads, gradients with the parameters'
    tree)."""
    (value, loads), grads = jax.value_and_grad(loss, has_aux=True)(
        params, tokens, labels, c, low=low, chunk=chunk)
    return value, loads, grads


def decays(path, leaf) -> bool:
    """Whether AdamW decays a parameter: weight matrices do; norms and
    the selection biases do not.  ``dec_*`` groups stack their layers on
    a leading axis, which does not count."""
    stacked = str(getattr(path[0], "key", path[0])).startswith("dec_")
    return leaf.ndim - stacked >= 2


def is_selection_bias(path) -> bool:
    return str(getattr(path[-1], "key", path[-1])) == \
        "e_score_correction_bias"


def adamw_step(p, m, v, g, *, t, lr, opt, decay: bool):
    """One AdamW step of one parameter: -> (p, m, v).  ``g`` the
    gradient as the step uses it (clipped), ``t`` the step's number from
    1, ``opt`` the configuration's ``optimizer``."""
    b1, b2 = opt["b1"], opt["b2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["eps"])
    if decay:
        step = step + opt["weight_decay"] * p
    return p - lr * step, m, v


def bias_step(bias, load, speed):
    """The selection bias's update from one step's loads [..., E]."""
    load = load.astype(F32)
    return bias + speed * jnp.sign(jnp.mean(load, axis=-1, keepdims=True)
                                   - load)


def clip_scale(grads, clip_norm):
    """The factor global-norm clipping puts on every gradient."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    return jnp.minimum(1.0, clip_norm / (norm + 1e-9))


def train_update(params, m, v, grads, loads, *, t, lr, opt, speed):
    """The state after one step: AdamW on the clipped gradients, the
    selection biases moved by the loads.  -> (params, m, v)."""
    scale = clip_scale(grads, opt["clip_norm"])

    def one(path, p, m, v, g):
        if is_selection_bias(path):
            return bias_step(p, loads, speed), m, v
        return adamw_step(p, m, v, g * scale, t=t, lr=lr, opt=opt,
                          decay=decays(path, p))
    out = jax.tree_util.tree_map_with_path(one, params, m, v, grads)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)
