"""Train/serve step builders: shardings, optimizer wiring, LPF grad sync.

Two gradient-sync modes:

* ``gspmd`` — pure jit: GSPMD inserts the reduce-scatter/all-reduce
  pattern implied by the parameter shardings (the optimised baseline).
* ``lpf``   — the step runs *manual over the pod axis* (partial
  shard_map): backward produces pod-local gradients, and the DCN hop is
  an explicit LPF superstep program (``bsp.pod_sync``) honouring sync
  attributes (int8 compression; staleness is handled by the local-SGD
  outer loop which simply skips the sync).  Intra-pod reduction stays on
  GSPMD/ICI — a two-level hierarchical all-reduce.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.bsp.pod_sync import pod_allreduce
from repro.core import CostLedger, LPF_SYNC_DEFAULT, SyncAttributes, compat
from repro.models import (Runtime, decode_step, init_caches, init_params,
                          loss_and_stats, matrix_mask, update_router_bias)
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.sharding.rules import batch_specs, cache_specs, param_specs
from repro.launch.mesh import dp_axes_of, model_axis_of

__all__ = ["TrainStep", "build_train_step", "ServeStep", "build_serve_step",
           "build_serve_buckets"]


def _split_scan_layers(grads: dict, cfg: ModelConfig):
    """Split stacked scan-group gradient leaves ``[L, ...]`` into L
    per-layer subtrees, so bucket boundaries (``bucketize`` packs leaves
    greedily, never splitting one) can fall on layer boundaries — the
    granularity at which the backward pass actually materialises
    gradients.  Returns the split tree plus the set of keys to restack.
    Leaves whose leading dim is not the group's repeat count (or groups
    of one repeat) pass through unsplit."""
    repeats = {f"dec_{g.name}": g.repeats for g in cfg.groups}
    repeats.update({f"enc_{g.name}": g.repeats for g in cfg.encoder_groups})
    split, split_keys = {}, set()
    for key, sub in grads.items():
        r = repeats.get(key, 0)
        if r > 1:
            leaves = jax.tree_util.tree_flatten(sub)[0]
            if leaves and all(l.ndim >= 1 and l.shape[0] == r
                              for l in leaves):
                split[key] = [jax.tree.map(lambda l: l[i], sub)
                              for i in range(r)]
                split_keys.add(key)
                continue
        split[key] = sub
    return split, split_keys


def _restack_scan_layers(split: dict, split_keys) -> dict:
    return {key: jax.tree.map(lambda *xs: jnp.stack(xs), *sub)
            if key in split_keys else sub
            for key, sub in split.items()}


@dataclasses.dataclass
class TrainStep:
    """Compiled pieces + specs (also consumed by dryrun/roofline)."""
    # (params, opt, batch) -> (params, opt, metrics); metrics: loss,
    # grad_norm, lr, and for expert models ``moe``, each expert layer's
    # counters (``moe_apply``) keyed as the parameters are
    step_fn: Any
    init_fn: Any                 # (key) -> (params, opt)
    param_sharding: Any
    opt_sharding: Any
    batch_sharding: Any
    rt: Runtime
    ledger: CostLedger


def _shardings(tree_specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


def build_train_step(cfg: ModelConfig, mesh, *,
                     opt_cfg: AdamWConfig = AdamWConfig(),
                     grad_sync: str = "gspmd",
                     sync_attrs: SyncAttributes = LPF_SYNC_DEFAULT,
                     grad_sync_method: str = "auto",
                     grad_bucket_bytes: Optional[int] = None,
                     grad_accum: int = 1,
                     axis_roles: str = "fsdp_tp",
                     donate: bool = True,
                     steps_per_call: int = 1) -> TrainStep:
    dp = dp_axes_of(mesh)
    if axis_roles == "dp_all":
        # axis-role remap for small models: the model axis carries extra
        # data parallelism; params keep ZeRO over `data` only
        batch_axes = tuple(a for a in ("pod", "data", "model")
                           if a in mesh.axis_names)
        param_axes = tuple(a for a in ("data",) if a in mesh.axis_names)
        rt = Runtime(mesh, dp_axes=batch_axes, model_axis=None, sp=False)
    else:
        batch_axes = dp
        param_axes = None
        rt = Runtime(mesh, dp_axes=dp, model_axis=model_axis_of(mesh),
                     sp=True)
    ledger = CostLedger()
    key = jax.random.PRNGKey(0)

    p_shapes = jax.eval_shape(partial(init_params, cfg=cfg), key)
    decay = matrix_mask(p_shapes)
    pspecs = param_specs(p_shapes, mesh, axes=param_axes)
    o_shapes = jax.eval_shape(partial(adamw_init, cfg=opt_cfg), p_shapes)
    ospecs = param_specs(o_shapes, mesh, axes=param_axes)

    npods = mesh.shape.get("pod", 1)

    def constrain_grads(grads):
        # pin gradients to the parameter sharding so the FSDP
        # reduce-scatter happens inside the layer loop, not as a giant
        # unsharded stacked buffer afterwards
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, s)), grads, pspecs)

    def loss_and_grads(params, batch, rt_=None, constrain=True):
        """Microbatched (gradient-accumulated) loss, expert counters and
        grads: activation memory scales by 1/k at unchanged arithmetic —
        how the widest configs fit 16 GB/chip at global batch 256."""
        rt_ = rt_ or rt
        cg = constrain_grads if constrain else (lambda g: g)
        value_and_grad = jax.value_and_grad(
            lambda p, b: loss_and_stats(p, b, cfg, rt_), has_aux=True)
        if grad_accum <= 1:
            (loss, stats), grads = value_and_grad(params, batch)
            return loss, stats, cg(grads)

        micro = jax.tree.map(
            lambda l: l.reshape((grad_accum, l.shape[0] // grad_accum)
                                + l.shape[1:]), batch)

        def acc_step(carry, mb):
            loss_acc, g_acc = carry
            (loss, stats), grads = value_and_grad(params, mb)
            grads = cg(grads)
            g_acc = jax.tree.map(lambda a, g: a + g.astype(a.dtype),
                                 g_acc, grads)
            return (loss_acc + loss, g_acc), stats

        g0 = cg(jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params))
        (loss_sum, g_sum), stats = jax.lax.scan(
            acc_step, (jnp.zeros((), jnp.float32), g0), micro)
        k = float(grad_accum)
        stats = jax.tree.map(lambda c: jnp.sum(c, axis=0), stats)
        return loss_sum / k, stats, jax.tree.map(lambda g: g / k, g_sum)

    def update(params, opt, loss, stats, grads):
        """AdamW, then the routers' selection biases, which take no
        gradient and no decay: each moves against its layer's load."""
        params, opt, metrics = adamw_update(grads, opt, params, opt_cfg,
                                            decay)
        params = update_router_bias(params, stats, cfg)
        metrics["loss"] = loss
        if stats:
            metrics["moe"] = stats
        return params, opt, metrics

    def plain_step(params, opt, batch):
        return update(params, opt, *loss_and_grads(params, batch))

    if grad_sync == "lpf" and npods > 1:
        # XLA workaround: with_sharding_constraint over Auto axes inside a
        # partial-manual (pod) region CHECK-fails in the SPMD partitioner
        # (spmd_partitioner_util.cc:504, verified by bisection), so the
        # loss runs without internal activation constraints here; GSPMD
        # propagates shardings freely.  The gspmd baseline path (and the
        # whole dry-run matrix) keeps the constraints + SP.
        rt_pod = Runtime()

        def pod_body(params, opt, batch):
            loss, stats, grads = loss_and_grads(params, batch, rt_pod,
                                                constrain=False)
            # default ``auto`` picks the overlapped bucket pipeline when
            # ``grad_bucket_bytes`` is set (bucket k+1's reduce-scatter
            # under bucket k's all-gather), one fused reduce-scatter+
            # all-gather pair for uncompressed gradients otherwise, and
            # lax.psum rings under compression
            bucketing = grad_bucket_bytes is not None and \
                grad_sync_method in ("auto", "bucketed", "bucketed_fenced",
                                     "bucketed_overlap")
            if bucketing:
                # thread bucket boundaries through the scan-layer
                # structure: stacked [L, ...] gradient leaves split into
                # per-layer leaves so buckets align with the layers the
                # backward pass produces one by one
                gsplit, keys = _split_scan_layers(grads, cfg)
                gsplit = pod_allreduce(gsplit, npods, "pod",
                                       attrs=sync_attrs, mean=True,
                                       ledger=ledger,
                                       method=grad_sync_method,
                                       bucket_bytes=grad_bucket_bytes)
                grads = _restack_scan_layers(gsplit, keys)
            else:
                grads = pod_allreduce(grads, npods, "pod",
                                      attrs=sync_attrs, mean=True,
                                      ledger=ledger,
                                      method=grad_sync_method,
                                      bucket_bytes=grad_bucket_bytes)
            loss = jax.lax.pmean(loss, "pod")
            stats = jax.lax.psum(stats, "pod")
            return update(params, opt, loss, stats, grads)

        rep = lambda tree: jax.tree.map(lambda _: P(), tree)

        def step_core(params, opt, batch):
            bspecs = jax.tree.map(
                lambda l: P("pod", *([None] * (l.ndim - 1))), batch)
            fn = compat.shard_map(
                pod_body, mesh=mesh,
                in_specs=(rep(params), rep(opt), bspecs),
                out_specs=(rep(params), rep(opt), P()),
                axis_names={"pod"}, check_vma=False)
            return fn(params, opt, batch)
    else:
        step_core = plain_step

    p_shard = _shardings(pspecs, mesh)
    o_shard = _shardings(ospecs, mesh)

    def make_batch_sharding(batch_shapes):
        return _shardings(batch_specs(batch_shapes, mesh,
                                      dp_axes=batch_axes), mesh)

    def init_fn(k):
        params = init_params(k, cfg)
        return params, adamw_init(params, opt_cfg)

    init_jit = jax.jit(init_fn, out_shardings=(p_shard, o_shard))

    if steps_per_call > 1:
        # roll K optimizer steps into ONE jitted call: the batch gains a
        # leading [K] dim and the per-step sync program replays inside a
        # single XLA While — K steps' worth of supersteps at one Python
        # dispatch (and one ledger trace)
        def multi_core(params, opt, batches):
            def one(carry, batch):
                p_, o_ = carry
                p_, o_, m = step_core(p_, o_, batch)
                return (p_, o_), m
            (params, opt), metrics = compat.scan(one, (params, opt),
                                                 batches)
            return params, opt, metrics   # metrics leaves are [K]
        core = multi_core
    else:
        core = step_core

    step_jit = jax.jit(
        core,
        donate_argnums=(0, 1) if donate else (),
        in_shardings=(p_shard, o_shard, None),
        out_shardings=(p_shard, o_shard, None),
    )
    return TrainStep(step_fn=step_jit, init_fn=init_jit,
                     param_sharding=p_shard, opt_sharding=o_shard,
                     batch_sharding=make_batch_sharding, rt=rt,
                     ledger=ledger)


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ServeStep:
    step_fn: Any                 # (params, caches, token, pos[, enc]) -> ...
    param_sharding: Any
    cache_sharding: Any
    rt: Runtime
    # (n_tokens) -> jitted (params, caches, tok0, pos0[, enc]) ->
    # (toks [T, B], caches): the whole decode loop as ONE XLA While
    # instead of a Python-dispatched step per token; memoized per length
    decode_fn: Any = None


def build_serve_step(cfg: ModelConfig, mesh, *, global_batch: int,
                     cache_len: int,
                     batch_axes: Optional[Tuple[str, ...]] = None,
                     seq_axes: Optional[Tuple[str, ...]] = None,
                     param_axes: Optional[Tuple[str, ...]] = None,
                     donate_cache: bool = True) -> ServeStep:
    axes = tuple(mesh.axis_names)
    if batch_axes is None:
        dp = dp_axes_of(mesh)
        total = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
        batch_axes = dp if dp and global_batch % total == 0 else ()
    if seq_axes is None:
        seq_axes = ("model",) if "model" in axes else ()
        if not batch_axes:   # batch can't shard -> widen sequence sharding
            seq_axes = tuple(a for a in ("pod", "data", "model")
                             if a in axes)
    rt = Runtime(mesh, dp_axes=batch_axes, model_axis=model_axis_of(mesh),
                 seq_axes=seq_axes)

    p_shapes = jax.eval_shape(partial(init_params, cfg=cfg),
                              jax.random.PRNGKey(0))
    pspecs = param_specs(p_shapes, mesh, axes=param_axes)
    c_shapes = jax.eval_shape(
        lambda: init_caches(cfg, global_batch, cache_len))
    cspecs = cache_specs(c_shapes, mesh, batch_axes=batch_axes,
                         seq_axes=seq_axes)
    p_shard = _shardings(pspecs, mesh)
    c_shard = _shardings(cspecs, mesh)
    tok_shard = NamedSharding(mesh, P(batch_axes or None))
    pos_shard = NamedSharding(mesh, P())

    def serve(params, caches, token, pos, enc_out=None):
        nxt, logits, new_caches = decode_step(params, token, caches, pos,
                                              cfg, rt, enc_out)
        return nxt, new_caches

    in_sh = [p_shard, c_shard, tok_shard, pos_shard]
    if cfg.encoder_groups:
        in_sh.append(NamedSharding(mesh, P(batch_axes or None, None, None)))
    step_jit = jax.jit(
        serve,
        donate_argnums=(1,) if donate_cache else (),
        in_shardings=tuple(in_sh),
        out_shardings=(tok_shard, c_shard),
    )

    toks_shard = NamedSharding(mesh, P(None, batch_axes or None))
    _decode_cache: dict = {}

    def decode_fn(n_tokens: int):
        """Jitted whole-sequence greedy decode: scan the per-token step
        ``n_tokens`` times in one XLA computation (body traced once)."""
        fn = _decode_cache.get(n_tokens)
        if fn is not None:
            return fn

        def decode(params, caches, tok0, pos0, enc_out=None):
            def one(carry, _):
                tok, caches, pos = carry
                nxt, _, caches = decode_step(params, tok, caches, pos,
                                             cfg, rt, enc_out)
                return (nxt, caches, pos + 1), nxt

            (_, caches, _), toks = compat.scan(
                one, (tok0, caches, pos0), None, length=n_tokens)
            return toks, caches   # toks [n_tokens, B]

        fn = jax.jit(
            decode,
            donate_argnums=(1,) if donate_cache else (),
            in_shardings=tuple(in_sh),
            out_shardings=(toks_shard, c_shard),
        )
        _decode_cache[n_tokens] = fn
        return fn

    return ServeStep(step_fn=step_jit, param_sharding=p_shard,
                     cache_sharding=c_shard, rt=rt, decode_fn=decode_fn)


def build_serve_buckets(cfg: ModelConfig, mesh,
                        buckets: Sequence[Tuple[int, int]],
                        **kwargs) -> Dict[Tuple[int, int], ServeStep]:
    """Build the continuous-batching server's decode buckets: one
    :class:`ServeStep` per ``(global_batch, cache_len)`` shape.  Each
    bucket owns its jitted per-token step and memoized fused decode
    variants (``decode_fn(n)``); the server routes admitted requests to
    the bucket whose shape they fit and replays that bucket's programs.
    Cache state is per-bucket too — buckets never share KV buffers, so
    quarantining one bucket's fused path cannot corrupt another's."""
    out: Dict[Tuple[int, int], ServeStep] = {}
    for batch, cache_len in buckets:
        out[(batch, cache_len)] = build_serve_step(
            cfg, mesh, global_batch=batch, cache_len=cache_len, **kwargs)
    return out
