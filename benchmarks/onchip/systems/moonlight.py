"""Moonlight-16B-A3B's training cell: one chip's share of an 8-way
expert-parallel layer, through ``repro.runtime.train_step``.

Set-up builds the configuration on the program's normal path
(``get_config`` of the file's ``arch``, cut by ``expert_share`` to the
file's layers, held experts and vocabulary), ``build_train_step`` on a
one-chip ``(data, model)`` mesh, and the parameters and AdamW state from
the run's seed (``init_fn``).  It draws a pool of token sequences on the
chip: i.i.d. Zipf over the vocabulary slice, the rank-to-id map permuted
by the seed.  The timed call is one ``step_fn``: one sequence of the
mix's length, parameters and optimizer state carried from call to call;
each step's expert counters are kept.

One step of the window, drawn from the seed among its first
``kept_steps``, is compared with the plain reference
(``references/moonlight.py``, float32 at ``HIGHEST``) after the window:
its parameters and AdamW moments are moved to the host before the step
(after the step before it, or the warm-up's), its batch, parameters and
first moment after it: between calls, so that no step's latency holds
a host copy.

* ``loss_rel``: the loss the step returned against the reference's at
  the same parameters and batch.
* ``grad_rel_l2.<group>``: the gradient the step used, recovered exactly
  as ``(m_k - b1 m_{k-1}) / (1 - b1)``, against the reference's
  gradient, clipped as the program clips; relative L2 per group of
  parameters.
* ``step_rel_l2.<group>``: the step's change of the parameters against
  the reference's AdamW step from the same parameters, moments and
  gradient (the one the step used, which ``grad_rel_l2`` holds to the
  reference's), at the step's number and learning rate; a state the
  step left unchanged reads 1.
* ``bias_step_rel_l2``: the change of the routers' selection biases
  against the reference's update from the loads its own router chose.
* ``moe_dropped_rows``: the rows the expert layers did not compute, over
  every step of the window.

The control puts the reference, with every matmul input rounded to
float8_e4m3fn (one step below the configuration's bfloat16), in the
program's place: its loss and gradient, and the reference's update of
the state from them.
"""

from __future__ import annotations

import functools
import random

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

import cells
from seeding import prng_key

REF = cells.load_module(cells.HERE / "references" / "moonlight.py")

#: the reference's keys, read from the configuration file
REF_KEYS = ("hidden_size", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta", "rms_norm_eps", "router_experts",
            "num_experts_per_tok",
            "n_group", "topk_group", "norm_topk_prob",
            "routed_scaling_factor", "seq_aux_alpha", "vocab_size")

GROUPS = ("router", "mla", "experts", "shared", "dense_mlp", "embed",
          "head", "norms")


def group_of(path) -> str:
    """The group a parameter's gradient and step are compared in (the
    selection biases, which take no gradient, are compared apart)."""
    names = [str(getattr(k, "key", k)) for k in path]
    if "router" in names:
        return "router"
    if names[0] in ("embed", "head"):
        return names[0]
    if {"ln1", "ln2", "final_norm"} & set(names):
        return "norms"
    for key, group in (("attn", "mla"), ("moe", "experts"),
                       ("shared_mlp", "shared"), ("mlp", "dense_mlp")):
        if key in names:
            return group
    raise KeyError(f"no group for parameter {names}")


@functools.partial(jax.jit, static_argnames=("n", "length", "vocab"))
def _zipf_pool(key, s: float, n: int, length: int, vocab: int):
    """n sequences of i.i.d. Zipf(s) ids over ``vocab``, the rank-to-id
    map a permutation drawn from ``key``."""
    k_perm, k_draw = jax.random.split(key)
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    cdf = jnp.cumsum(ranks ** -s)
    u = jax.random.uniform(k_draw, (n, length)) * cdf[-1]
    rank = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
    return jax.random.permutation(k_perm, vocab)[rank].astype(jnp.int32)


class System:
    def __init__(self, config: dict, mix: dict, devices, seed: int,
                 rehearse: bool = False):
        from repro.configs import get_config
        base = get_config(config["arch"], smoke=rehearse, ep_degree=1)
        from repro.configs import expert_share
        from repro.core import compat
        from repro.optim import AdamWConfig, warmup_cosine
        from repro.runtime.train_step import build_train_step

        sizes = {**config, **config["rehearse"]} if rehearse else config
        self.c = {k: sizes[k] for k in REF_KEYS}
        self.c["experts_held"] = (0, sizes["n_routed_experts"])
        self.seq = int(sizes["seq_len"] if rehearse else mix["seq_len"])
        self.batch = int(mix["batch"])
        self.limits = config["limits"]
        self.opt = config["optimizer"]
        self.speed = config["bias_update_speed"]
        self.devices = list(devices[:int(mix["devices"])])
        cfg = expert_share(base, layers=sizes["num_hidden_layers"],
                           experts_held=sizes["n_routed_experts"],
                           vocab=sizes["vocab_size"])
        mesh = compat.make_mesh((1, 1), ("data", "model"),
                                devices=self.devices)
        o = self.opt
        self.ts = build_train_step(cfg, mesh, opt_cfg=AdamWConfig(
            lr=warmup_cosine(o["lr"], o["warmup_steps"], o["total_steps"]),
            b1=o["b1"], b2=o["b2"], eps=o["eps"],
            weight_decay=o["weight_decay"], clip_norm=o["clip_norm"]))
        k_init, k_data = jax.random.split(prng_key(seed, self.devices[0]))
        self.params, self.state = self.ts.init_fn(k_init)
        self.pool = _zipf_pool(k_data, float(mix["zipf_exponent"]),
                               int(mix["pool"]) * self.batch, self.seq + 1,
                               sizes["vocab_size"])
        self.pool.block_until_ready()
        self.kept_step = random.Random(seed).randrange(
            int(sizes["kept_steps"]))
        self.before = self.after = self.kept_batch = None
        self.t = 0                    # steps taken from the initial state
        self.rows, self.dropped, self.steps = [], 0, 0

        c = self.c
        # the control's gradient and its update of the state are two
        # calls: as one, the update's temporaries join the reference's
        # activations (15.7 GB compiled for a v5e chip; 13.5 as two)
        self._control_grads = jax.jit(functools.partial(
            REF.loss_and_grads, c=c, low=ml_dtypes.float8_e4m3fn))
        self._control_update = jax.jit(functools.partial(
            REF.train_update, opt=self.opt, speed=self.speed),
            donate_argnums=(0, 1, 2))
        self._ref = jax.jit(functools.partial(REF.loss_and_grads, c=c))

    def lr(self, t: int) -> float:
        """The learning rate of step ``t``: the warm-up's linear ramp
        (a window lies inside it)."""
        assert t < self.opt["warmup_steps"], t
        return self.opt["lr"] * t / self.opt["warmup_steps"]

    def _batch(self, i: int) -> dict:
        seqs = self.pool.reshape(-1, self.batch, self.seq + 1)
        s = seqs[i % seqs.shape[0]]
        return {"tokens": s[:, :-1], "labels": s[:, 1:]}

    def _count(self, i: int, batch: dict) -> None:
        """Counts the step about to run and holds the kept step's batch."""
        self.t += 1
        if i == self.kept_step:
            if self.before is None:       # no warm-up ran: a later seed
                self._keep_before(i - 1)  # of ``--seeds``
            self.kept_batch, self.kept_t = batch, self.t

    def _keep_before(self, i: int) -> None:
        """After step ``i`` (-1: the warm-up), moves the state to the host
        if the kept step comes next."""
        if i + 1 == self.kept_step:
            self.before = jax.device_get(
                (self.params, self.state["m"], self.state["v"]))

    # -- the window ------------------------------------------------------
    def call(self, i: int):
        batch = self._batch(i)
        self._count(i, batch)
        self.params, self.state, metrics = self.ts.step_fn(
            self.params, self.state, batch)
        if i < 0:
            self._keep_before(i)
        return metrics

    def control(self, i: int):
        """The reference's step, one precision below, in the program's
        place."""
        batch = self._batch(i)
        self._count(i, batch)
        loss, loads, grads = self._control_grads(
            self.params, batch["tokens"], batch["labels"])
        self.params, m, v = self._control_update(
            self.params, self.state["m"], self.state["v"], grads, loads,
            t=self.t, lr=self.lr(self.t))
        self.state = {**self.state, "m": m, "v": v}
        if i < 0:
            self._keep_before(i)
        return {"loss": loss}

    @staticmethod
    def wait(out) -> None:
        jax.block_until_ready(out)

    def keep(self, i: int, out) -> None:
        self.steps += 1
        if i == self.kept_step:
            self.before += (jax.device_get(self.kept_batch),)
            self.after = (jax.device_get((self.params, self.state["m"])),
                          float(out["loss"]))
        self._keep_before(i)
        stats = out.get("moe")
        if stats is not None:
            groups = jax.tree.leaves(
                stats, is_leaf=lambda t: isinstance(t, dict) and "rows" in t)
            self.rows.append(np.concatenate(
                [np.asarray(g["rows"]).reshape(-1, g["rows"].shape[-1])
                 for g in groups]).tolist())
            self.dropped += sum(int(np.asarray(g["dropped"]).sum())
                                for g in groups)

    def info(self) -> dict:
        return {"steps": self.steps, "rows": self.rows,
                "dropped": self.dropped}

    # -- after the window ------------------------------------------------
    def release(self) -> None:
        self.params = self.state = self.pool = None

    def check(self) -> list:
        if self.before is None or self.after is None:
            return [("kept_step_missing", 1.0, 0.0)]
        (p0, m0, v0, batch), ((p1, m1), loss) = self.before, self.after
        self.before = self.after = None
        dev = self.devices[0]
        ref_loss, loads, ref_g = self._ref(
            jax.device_put(p0, dev), jax.device_put(batch["tokens"], dev),
            jax.device_put(batch["labels"], dev))
        ref_loss, loads, ref_g = jax.device_get((ref_loss, loads, ref_g))
        scale = float(REF.clip_scale(ref_g, self.opt["clip_norm"]))
        t = self.kept_t
        sums = {}                     # name -> [error^2, reference^2]
        host = jax.devices("cpu")[0]
        flat = [jax.tree_util.tree_flatten_with_path(x)[0]
                for x in (p0, p1, m0, m1, v0, ref_g)]
        for leaves in zip(*flat):
            path = leaves[0][0]
            a0, a1, ma, mb, va, g = (jax.device_put(x, host)
                                     for _, x in leaves)
            if REF.is_selection_bias(path):
                gaps = {"bias_step_rel_l2": _bias_gap(
                    a0, a1, jax.device_put(loads, host), self.speed)}
            else:
                g_err, step_err = _leaf_gaps(
                    a0, a1, ma, mb, va, g, scale, t, self.lr(t),
                    tuple(sorted(self.opt.items())), REF.decays(path, a0))
                group = group_of(path)
                gaps = {f"grad_rel_l2.{group}": g_err,
                        f"step_rel_l2.{group}": step_err}
            for name, (e2, r2) in gaps.items():
                acc = sums.setdefault(name, [0.0, 0.0])
                acc[0] += float(e2)
                acc[1] += float(r2)
        checks = [("loss_rel", abs(loss - float(ref_loss)) /
                   abs(float(ref_loss)), self.limits["loss_rel"])]
        for name in (*(f"grad_rel_l2.{g}" for g in GROUPS),
                     *(f"step_rel_l2.{g}" for g in GROUPS),
                     "bias_step_rel_l2"):
            e2, r2 = sums[name]
            checks.append((name, float(np.sqrt(e2 / r2)), self.limits[name]))
        checks.append(("moe_dropped_rows", float(self.dropped),
                       self.limits["moe_dropped_rows"]))
        return checks


@functools.partial(jax.jit, static_argnames=("opt", "decay"))
def _leaf_gaps(p0, p1, m0, m1, v0, ref_g, scale, t, lr, opt, decay: bool):
    """One parameter's (squared error, squared reference) of the
    gradient the step used, ``(m1 - b1 m0) / (1 - b1)``, against the
    reference's clipped; and of the step's change ``p1 - p0`` against
    the reference's AdamW step with that gradient."""
    opt = dict(opt)
    b1 = opt["b1"]
    used = (m1 - b1 * m0) / (1.0 - b1)
    g = ref_g * scale
    want, _, _ = REF.adamw_step(p0, m0, v0, used, t=t, lr=lr, opt=opt,
                                decay=decay)
    d, dw = p1 - p0, want - p0
    return ((jnp.sum(jnp.square(used - g)), jnp.sum(jnp.square(g))),
            (jnp.sum(jnp.square(d - dw)), jnp.sum(jnp.square(dw))))


@functools.partial(jax.jit, static_argnames=("speed",))
def _bias_gap(b0, b1, loads, speed: float):
    """The selection biases' change against the reference's update from
    its own loads: (squared error, squared reference)."""
    dw = REF.bias_step(b0, loads, speed) - b0
    return jnp.sum(jnp.square((b1 - b0) - dw)), jnp.sum(jnp.square(dw))
