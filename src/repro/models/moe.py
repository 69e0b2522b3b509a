"""Mixture-of-Experts layer: route over every expert, compute the ones
held here, dropless.

The router scores all ``n_experts`` experts of the published layer; the
weights of only the experts held here exist (``MoEConfig.held``: a chip's
share of an expert-parallel layer, or all of them).  Each (token, expert)
assignment that falls to a held expert becomes one row.  Rows are sorted
by expert, the held experts run as one grouped matmul over them
(``lax.ragged_dot``), and each result is scatter-added into its token's
output with its routing weight.  Nothing is padded to a capacity and no
row is dropped: the row buffer holds every assignment that can fall here,
``T * min(k, experts held)``.  Assignments to absent experts add nothing
here; on a deployment the chips that hold them add theirs.

Under a mesh the held experts are split over the ``model`` axis (expert
parallelism).  Activations arrive replicated over ``model``, each shard
runs its own experts on the tokens routed to them, and the partial
outputs are summed by the all-reduce the dense MLP pays anyway.  No token
moves, so the communication volume is independent of the routing: the
model-compliance property LPF demands (worst-case h == realised h).

Routers (``scoring``):

* ``softmax``: softmax over all experts, top-k, weights renormalised
  (granite, jamba);
* ``sigmoid``: DeepSeek-V3's ``noaux_tc``.  Sigmoid scores; selection on
  score + ``e_score_correction_bias``, a non-trained bias: first the best
  ``topk_group`` of ``n_group`` groups, ranked by the sum of each group's
  two highest, then the top k.  Weights are the chosen scores (without
  the bias), normalised, times ``routed_scale``.  The bias moves by
  ``BIAS_RATE`` against each expert's load after every step
  (``bias_step``), and a sequence-wise balance loss of weight
  ``SEQ_AUX_ALPHA`` joins the training loss.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import compat

__all__ = ["MoEConfig", "moe_params", "moe_apply", "route", "bias_step",
           "BIAS_RATE", "SEQ_AUX_ALPHA"]

#: DeepSeek-V3's balance constants for the sigmoid router
#: (arXiv:2412.19437, §2.1.2): the selection bias's step per update and
#: the weight of the sequence-wise balance loss
BIAS_RATE = 1e-3
SEQ_AUX_ALPHA = 1e-4


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                 # per-expert hidden
    n_experts: int            # the router's width: the published layer's experts
    top_k: int
    ep_degree: int = 1        # model-axis size at runtime
    #: (first, count) of the experts whose weights live here; None: all
    held: Optional[Tuple[int, int]] = None
    scoring: str = "softmax"  # softmax | sigmoid
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 1.0

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.held or (0, self.n_experts)

    @property
    def padded_experts(self) -> int:
        """Expert weights stored here: the held count padded to a
        multiple of the EP degree (padding experts never get a row)."""
        n = self.held_range[1]
        d = max(self.ep_degree, 1)
        return -(-n // d) * d


def moe_params(key, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    from .common import dense_init
    ep = cfg.padded_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "router": dense_init(k1, (cfg.d_model, cfg.n_experts),
                             dtype=jnp.float32),
        "w_gate": dense_init(k2, (ep, cfg.d_model, cfg.d_ff), in_axis=1,
                             dtype=dtype),
        "w_up": dense_init(k3, (ep, cfg.d_model, cfg.d_ff), in_axis=1,
                           dtype=dtype),
        "w_down": dense_init(k4, (ep, cfg.d_ff, cfg.d_model), in_axis=1,
                             dtype=dtype),
    }
    if cfg.scoring == "sigmoid":
        p["e_score_correction_bias"] = jnp.zeros((cfg.n_experts,),
                                                 jnp.float32)
    return p


def route(x, router, bias, cfg: MoEConfig):
    """x [T, D] -> (experts [T, k] int32, weights [T, k] f32, scores
    [T, E] f32).  ``bias`` [E] is added for the selection only (zeros
    for a router without one).  The chosen weights are always
    renormalised to sum 1 before the scale, as every configured model
    does.  The logits are computed in float32 at full precision:
    a near tie between the k-th and the (k+1)-th expert must not be
    decided by the matmul's rounding."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if cfg.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = lax.stop_gradient(scores + bias.astype(jnp.float32))
    if cfg.n_group > 1:
        T, E = choice.shape
        grouped = choice.reshape(T, cfg.n_group, E // cfg.n_group)
        group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
        _, best = lax.top_k(group_score, cfg.topk_group)
        keep = jnp.any(best[:, :, None] == jnp.arange(cfg.n_group), axis=1)
        choice = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(T, E)
    _, experts = lax.top_k(choice, cfg.top_k)
    weights = jnp.take_along_axis(scores, experts, axis=1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return experts, weights * cfg.routed_scale, scores


def balance_loss(scores, experts, cfg: MoEConfig, batch: int) -> jnp.ndarray:
    """DeepSeek-V3's sequence-wise balance loss, per sequence [batch]:
    ``SEQ_AUX_ALPHA * sum_i f_i P_i`` with ``f_i`` the share of the
    sequence's assignments that chose expert i times E / k and ``P_i``
    the sequence's mean of the normalised scores."""
    E = cfg.n_experts
    s = scores.reshape(batch, -1, E)
    p = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=1)
    chosen = jnp.sum(jax.nn.one_hot(experts, E, dtype=jnp.float32), axis=1)
    f = jnp.mean(chosen.reshape(batch, -1, E), axis=1) * (E / cfg.top_k)
    return SEQ_AUX_ALPHA * jnp.sum(f * p, axis=-1)


def bias_step(bias, load):
    """The aux-loss-free balance update: each expert's selection bias
    moves by ``BIAS_RATE`` towards the mean load, ``b_i += BIAS_RATE *
    sign(mean load - load_i)``.  ``load [..., E]`` counts assignments per
    expert."""
    load = load.astype(jnp.float32)
    mean = jnp.mean(load, axis=-1, keepdims=True)
    return bias + BIAS_RATE * jnp.sign(mean - load)


def _held_experts(xt, experts, weights, wg, wu, wd, first, count):
    """The held experts' part of the layer's output.

    xt [T, D]; experts/weights [T, k]; w* [n, ...] the experts stored
    here, of which the first ``count`` are global experts ``first ..``.
    Returns (out [T, D] f32, rows per stored expert [n] int32, rows
    dropped int32)."""
    T, k = experts.shape
    n = wg.shape[0]
    with jax.named_scope("moe.dispatch"):
        local = experts.reshape(-1) - first
        held = (local >= 0) & (local < count)
        key = jnp.where(held, local, n)
        rows = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
        # every held assignment sorts before every other, and a token
        # holds at most min(k, n) of them: the buffer drops none
        order = jnp.argsort(key, stable=True)[:T * min(k, n)]
        held_rows = held[order]
        dropped = (jnp.sum(held) - jnp.sum(held_rows)).astype(jnp.int32)
        tok = order // k
        row_w = jnp.where(held_rows, weights.reshape(-1)[order], 0.0)
        # on the TPU the grouped matmul leaves the rows after the last
        # group unwritten, in its results and in the gradient of its
        # input: each is selected away (0 * garbage may be NaN)
        live = (jnp.arange(order.shape[0]) < jnp.sum(rows))[:, None]
        xs = jnp.where(live, jnp.take(xt, tok, axis=0), 0).astype(wg.dtype)

    def grouped(a, w):
        # the dense MLP's precision: products in the weights' dtype,
        # accumulated in f32 inside the matmul
        return jnp.where(live, lax.ragged_dot(a, w, rows), 0)

    with jax.named_scope("moe.experts"):
        y = grouped(jax.nn.silu(grouped(xs, wg)) * grouped(xs, wu), wd)
    with jax.named_scope("moe.combine"):
        out = jnp.zeros((T, xt.shape[1]), jnp.float32).at[tok].add(
            y.astype(jnp.float32) * row_w[:, None])
    return out, rows, dropped


def moe_apply(params: dict, x: jnp.ndarray, cfg: MoEConfig, *,
              mesh=None, model_axis: str = "model",
              dp_axes: Tuple[str, ...] = ()) -> tuple:
    """x [B, S, D] -> (out [B, S, D], balance loss, stats).

    ``stats``: ``load`` [E] assignments per router expert (what
    ``bias_step`` reads), ``rows`` [stored experts] rows each held expert
    computed, ``dropped`` rows not computed (0: the layer is dropless).
    With a mesh, runs under shard_map over ``model`` (the held experts)
    and the dp axes (the batch)."""
    B, S, D = x.shape
    E = cfg.n_experts
    first, count = cfg.held_range
    bias = params.get("e_score_correction_bias")
    if bias is None:
        bias = jnp.zeros((E,), jnp.float32)

    def layer(xb, router, bias, wg, wu, wd, shard):
        Bl = xb.shape[0]
        xt = xb.reshape(Bl * S, D)
        with jax.named_scope("moe.route"):
            experts, weights, scores = route(xt, router, bias, cfg)
            bal = balance_loss(scores, experts, cfg, Bl) \
                if cfg.scoring == "sigmoid" else jnp.zeros((Bl,), jnp.float32)
            load = jnp.bincount(experts.reshape(-1), length=E)
        n = wg.shape[0]
        out, rows, dropped = _held_experts(
            xt, experts, weights, wg, wu, wd, first + shard * n,
            jnp.clip(count - shard * n, 0, n))
        return out, bal, {"load": load.astype(jnp.int32), "rows": rows,
                          "dropped": dropped}

    args = (params["router"], bias, params["w_gate"], params["w_up"],
            params["w_down"])
    if mesh is None:
        out, bal, stats = layer(x, *args, 0)
        return out.reshape(B, S, D).astype(x.dtype), jnp.mean(bal), stats

    dp = tuple(dp_axes) or None

    def body(xb, *a):
        out, bal, st = layer(xb, *a, lax.axis_index(model_axis))
        out = lax.psum(out, model_axis)
        if dp:
            st = jax.tree.map(lambda c: lax.psum(c, dp), st)
        st["dropped"] = lax.psum(st["dropped"], model_axis)
        return out.reshape(xb.shape).astype(xb.dtype), bal, st

    experts = P(model_axis, None, None)
    out, bal, stats = compat.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None), P(None),
                  experts, experts, experts),
        out_specs=(P(dp, None, None), P(dp),
                   {"load": P(), "rows": P(model_axis), "dropped": P()}),
        check_vma=False,
    )(x, *args)
    return out, jnp.mean(bal), stats
