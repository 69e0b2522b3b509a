"""The DeepSeek-V3 expert layer and MLA without q_lora against the plain
float32 reference of the Moonlight cell (``benchmarks/onchip/references/
moonlight.py``), on seeded random weights at small sizes.

Tolerances: where both sides compute in float32 they differ only in the
order of summation, so they agree to a few float32 ulps amplified by the
depth of the computation; each test also shows that computing the
program's side in bfloat16 breaks that tolerance."""

import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import expert_share, get_config
from repro.core import compat
from repro.models import Runtime, count_params, init_params, loss_and_stats
from repro.models.attention import blocked_attention
from repro.models.blocks import _mla_fwd, _mla_params, _mlp
from repro.models.config import BlockCfg
from repro.models.moe import (SEQ_AUX_ALPHA, MoEConfig, bias_step, moe_apply,
                              moe_params, route)

pytestmark = pytest.mark.slow

_REF_PATH = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
             / "onchip" / "references" / "moonlight.py")


def _load_ref():
    spec = importlib.util.spec_from_file_location("moonlight_ref", _REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_ref()


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _ref_sizes(cfg, held=None):
    """The reference's dict of sizes for a program config."""
    m, moe = cfg.mla, cfg.moe
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "kv_lora_rank": m.kv_lora, "qk_nope_head_dim": m.dh_nope,
            "qk_rope_head_dim": m.dh_rope, "v_head_dim": m.dh_v,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "router_experts": moe.n_experts,
            "num_experts_per_tok": moe.top_k, "n_group": moe.n_group,
            "topk_group": moe.topk_group, "norm_topk_prob": True,
            "routed_scaling_factor": moe.routed_scale,
            "seq_aux_alpha": SEQ_AUX_ALPHA, "vocab_size": cfg.vocab,
            "experts_held": held or moe.held_range}


def _moe_cfg(**kw):
    base = dict(d_model=32, d_ff=16, n_experts=16, top_k=4,
                scoring="sigmoid", n_group=4, topk_group=2,
                routed_scale=2.5)
    return MoEConfig(**{**base, **kw})


def _plain(a, b):
    return a if b is None else a @ b


# -- router --------------------------------------------------------------


def test_router_matches_reference_with_groups():
    cfg = _moe_cfg()
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(k1, (256, 32))
    router = jax.random.normal(k2, (32, 16)) / np.sqrt(32)
    bias = 0.1 * jax.random.normal(k3, (16,))
    c = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 4,
         "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        want_e, want_w, _ = REF.route(x, router, bias, c)
    got_e, got_w, _ = route(x, router, bias, cfg)
    np.testing.assert_array_equal(got_e, want_e)
    # same float32 arithmetic on both sides: a few ulps
    np.testing.assert_allclose(got_w, want_w, rtol=1e-6)
    # every token's experts lie in at most topk_group of the 4 groups
    groups = np.asarray(got_e) // 4
    assert max(len(set(row)) for row in groups) <= 2
    # the weights sum to the routed scale
    np.testing.assert_allclose(np.sum(got_w, axis=1), 2.5, rtol=1e-6)
    # hidden states rounded to bfloat16 move selections and weights
    bf_e, bf_w, _ = route(x.astype(jnp.bfloat16), router, bias, cfg)
    assert (np.any(np.asarray(bf_e) != np.asarray(want_e))
            or not np.allclose(bf_w, want_w, rtol=1e-6))


def test_bias_update_sign_rule():
    bias = jnp.array([0.0, 0.5, -0.5, 0.25])
    load = jnp.array([1, 5, 3, 3])            # mean 3
    np.testing.assert_allclose(bias_step(bias, load),
                               [0.001, 0.499, -0.5, 0.25])
    # stacked over layers, each layer against its own mean
    b2 = bias_step(jnp.zeros((2, 3)), jnp.array([[0, 3, 3], [4, 4, 4]]))
    np.testing.assert_allclose(b2, [[0.001, -0.001, -0.001], [0.0, 0.0, 0.0]])


# -- the expert layer ----------------------------------------------------


def _layer_inputs(cfg, seed=0, B=2, S=32):
    kp, kx = jax.random.split(jax.random.PRNGKey(seed))
    params = moe_params(kp, cfg)
    x = jax.random.normal(kx, (B, S, cfg.d_model))
    return params, x


def _reference_layer(params, x, cfg, held):
    """The reference's expert layer without shared experts."""
    D = cfg.d_model
    zero = {"w_gate": jnp.zeros((D, 1)), "w_up": jnp.zeros((D, 1)),
            "w_down": jnp.zeros((1, D))}
    c = {"router_experts": cfg.n_experts, "num_experts_per_tok": cfg.top_k,
         "n_group": cfg.n_group, "topk_group": cfg.topk_group,
         "norm_topk_prob": True, "routed_scaling_factor": cfg.routed_scale,
         "seq_aux_alpha": SEQ_AUX_ALPHA, "experts_held": held}
    with jax.default_matmul_precision("highest"):
        return REF._moe({"moe": params, "shared_mlp": zero}, x, c, _plain)


@pytest.mark.parametrize("shards", [1, 2])
def test_dropless_when_every_token_picks_one_expert(shards):
    """A selection bias that sends every token to expert 0: all its rows
    are computed, by one shard or split over two, and none is dropped."""
    cfg = _moe_cfg(n_experts=8, n_group=1, topk_group=1, top_k=2,
                   ep_degree=shards)
    params, x = _layer_inputs(cfg)
    params["e_score_correction_bias"] = jnp.zeros(8).at[0].set(10.0)
    T = x.shape[0] * x.shape[1]
    if shards == 1:
        out, _, stats = moe_apply(params, x, cfg)
    else:
        mesh = compat.make_mesh((1, shards), ("data", "model"))
        out, _, stats = jax.jit(lambda p, x: moe_apply(
            p, x, cfg, mesh=mesh, dp_axes=("data",)))(params, x)
    assert int(stats["load"][0]) == T
    assert int(stats["rows"][0]) == T
    assert int(np.sum(stats["rows"])) == T * cfg.top_k
    assert int(stats["dropped"]) == 0
    want, _, want_load = _reference_layer(params, x, cfg, (0, 8))
    np.testing.assert_array_equal(stats["load"], want_load)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)


def test_held_shares_sum_to_the_whole_layer():
    """The 8 shares of 2 experts each, plus the shared experts counted
    once, give what the uncut reference layer gives."""
    cfg = _moe_cfg()
    params, x = _layer_inputs(cfg, seed=3)
    D = cfg.d_model
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    shared = {"w_gate": jax.random.normal(ks[0], (D, 32)) / np.sqrt(D),
              "w_up": jax.random.normal(ks[1], (D, 32)) / np.sqrt(D),
              "w_down": jax.random.normal(ks[2], (32, D)) / np.sqrt(32)}
    total, rows = 0.0, []
    for s in range(8):
        part = {**params, **{n: params[n][2 * s:2 * s + 2]
                             for n in ("w_gate", "w_up", "w_down")}}
        out, bal, stats = moe_apply(
            part, x, dataclasses.replace(cfg, held=(2 * s, 2)))
        total = total + out
        rows.append(np.asarray(stats["rows"]))
    total = total + _mlp(shared, x)
    c = _ref_sizes(get_config("moonlight-16b-a3b", smoke=True), (0, 16))
    c.update({"router_experts": 16, "num_experts_per_tok": 4, "n_group": 4,
              "topk_group": 2, "routed_scaling_factor": 2.5,
              "seq_aux_alpha": 1e-4})
    with jax.default_matmul_precision("highest"):
        want, want_bal, want_load = REF._moe(
            {"moe": params, "shared_mlp": shared}, x, c, _plain)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(bal, want_bal, rtol=1e-5)
    np.testing.assert_array_equal(stats["load"], want_load)
    # every assignment fell to exactly one share
    assert np.concatenate(rows).sum() == x.shape[0] * x.shape[1] * 4


# -- MLA without q_lora --------------------------------------------------


def test_mla_without_q_lora_matches_reference():
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b", smoke=True),
                              compute_dtype="float32")
    assert cfg.mla.q_lora is None
    p = _mla_params(jax.random.PRNGKey(5), cfg, jnp.float32)
    assert "wq" in p and "wq_a" not in p
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 64, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))
    got, _ = _mla_fwd(p, h, cfg, BlockCfg("mla", "dense"), pos)
    with jax.default_matmul_precision("highest"):
        want = REF._attention(p, h, _ref_sizes(cfg), _plain, 16)
    # float32 on both sides, softmax over 64 keys: summation order only
    assert _rel(got, want) < 1e-5
    bf = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
    got_bf, _ = _mla_fwd(bf, h.astype(jnp.bfloat16), cfg,
                         BlockCfg("mla", "dense"), pos)
    assert _rel(got_bf, want) > 1e-5


# -- the whole smoke model -----------------------------------------------


def _smoke(held):
    cfg = get_config("moonlight-16b-a3b", smoke=True)
    if held:
        cfg = expert_share(cfg, layers=3, experts_held=held, vocab=cfg.vocab)
    return cfg


def _program_loss_and_grads(cfg, params, batch):
    return jax.jit(jax.value_and_grad(
        lambda p: loss_and_stats(p, batch, cfg, Runtime())[0]))(params)


@pytest.mark.parametrize("held", [None, 4])
def test_smoke_model_loss_and_grads_match_reference(held):
    """Loss (cross-entropy plus the balance losses) and every gradient
    of the smoke model, all 16 experts held or a share of 4, against the
    reference.  float32 on both sides: the loss to 1e-5, each gradient
    to 1e-4 in relative L2 (summation order through 3 layers, softmax
    and the router).  The configured bfloat16 compute breaks both."""
    cfg = _smoke(held)
    params = init_params(jax.random.PRNGKey(7), cfg)
    rng = np.random.default_rng(8)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (2, 64)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab, (2, 64)), jnp.int32)
    batch = {"tokens": tokens, "labels": labels}
    want_loss, _, want_g = jax.jit(lambda p: REF.loss_and_grads(
        p, tokens, labels, _ref_sizes(cfg), chunk=16))(params)

    def compare(compute):
        c = dataclasses.replace(cfg, compute_dtype=compute)
        loss, g = _program_loss_and_grads(c, params, batch)
        loss_err = abs(float(loss) - float(want_loss)) / float(want_loss)
        flat = jax.tree_util.tree_flatten_with_path(want_g)[0]
        errs = {jax.tree_util.keystr(path): _rel(gg, w)
                for (path, w), gg in zip(flat, jax.tree.leaves(g))
                if "e_score_correction_bias" not in jax.tree_util.keystr(path)}
        return loss_err, errs

    loss_err, errs = compare("float32")
    assert loss_err < 1e-5
    assert max(errs.values()) < 1e-4, max(errs.items(), key=lambda kv: kv[1])
    loss_bf, errs_bf = compare("bfloat16")
    assert loss_bf > 1e-5 and max(errs_bf.values()) > 1e-4


def test_train_step_moves_the_bias_against_the_load():
    """One step through build_train_step: AdamW leaves the selection
    bias alone (it has no gradient), the bias update moves it by 0.001
    against the load the step's counters report, and nothing drops."""
    from repro.launch.mesh import make_mesh
    from repro.runtime.train_step import build_train_step
    cfg = get_config("moonlight-16b-a3b", smoke=True)
    ts = build_train_step(cfg, make_mesh((1, 1)), donate=False)
    params, opt = ts.init_fn(jax.random.PRNGKey(9))
    rng = np.random.default_rng(10)
    batch = {k: jnp.asarray(rng.integers(0, cfg.vocab, (2, 64)), jnp.int32)
             for k in ("tokens", "labels")}
    new, _, metrics = ts.step_fn(params, opt, batch)
    stats = metrics["moe"]["dec_moe"]["b0"]
    load = np.asarray(stats["load"])                      # [layers, E]
    assert (load.sum(axis=1) == 2 * 64 * cfg.moe.top_k).all()
    assert int(np.sum(stats["dropped"])) == 0
    assert (np.asarray(stats["rows"]).sum(axis=1) == load.sum(axis=1)).all()
    before = np.asarray(params["dec_moe"]["b0"]["moe"]
                        ["e_score_correction_bias"])
    after = np.asarray(new["dec_moe"]["b0"]["moe"]["e_score_correction_bias"])
    want = before + 0.001 * np.sign(load.mean(axis=1, keepdims=True) - load)
    np.testing.assert_allclose(after, want, atol=1e-7)


def test_adamw_decays_weight_matrices_only():
    """AdamW's decoupled weight decay reaches the weight matrices, and
    not the norms or the selection biases, which a scan group stacks as
    [layers, n]: with zero gradients only the matrices move."""
    from repro.models import matrix_mask
    from repro.optim import AdamWConfig, adamw_init, adamw_update
    cfg = get_config("moonlight-16b-a3b", smoke=True)
    params = jax.tree.map(lambda a: a + 0.5,
                          init_params(jax.random.PRNGKey(11), cfg))
    mask = matrix_mask(params)
    moe = mask["dec_moe"]["b0"]
    assert moe["attn"]["wq"] and moe["moe"]["w_gate"] and mask["embed"]
    assert not moe["ln1"]["w"] and not moe["moe"]["e_score_correction_bias"]
    assert not mask["final_norm"]["w"]
    opt_cfg = AdamWConfig(lr=0.1, weight_decay=0.5)
    new, _, _ = adamw_update(jax.tree.map(jnp.zeros_like, params),
                             adamw_init(params, opt_cfg), params, opt_cfg,
                             mask)
    for a, b, m in zip(jax.tree.leaves(params), jax.tree.leaves(new),
                       jax.tree.leaves(mask)):
        np.testing.assert_allclose(b, a * (1 - 0.05) if m else a, rtol=1e-6)


# -- chunk-checkpointed attention ----------------------------------------


def test_checkpointed_attention_backward_keeps_one_chunk_of_scores():
    """The backward pass of blocked attention recomputes each query
    chunk's scores instead of keeping all of them: its temporaries stay
    under half of the [H, S, S] float32 scores (kept, they come to 3.6
    times those scores here)."""
    B, S, H, D, qc = 1, 4096, 4, 64, 256
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(blocked_attention(q, k, v, q_chunk=qc))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile()
    all_scores = B * H * S * S * 4
    assert compiled.memory_analysis().temp_size_in_bytes < all_scores / 2


# -- the published geometry ----------------------------------------------


def test_moonlight_published_geometry():
    cfg = get_config("moonlight-16b-a3b")
    assert (cfg.d_model, cfg.vocab, cfg.n_heads, cfg.d_ff) == \
        (2048, 163840, 16, 11264)
    assert [(g.name, g.repeats) for g in cfg.groups] == [("dense", 1),
                                                         ("moe", 26)]
    assert cfg.mla.q_lora is None
    assert (cfg.mla.kv_lora, cfg.mla.dh_nope, cfg.mla.dh_rope,
            cfg.mla.dh_v) == (512, 128, 64, 128)
    m = cfg.moe
    assert (m.n_experts, m.top_k, m.d_ff, cfg.n_shared) == (64, 6, 1408, 2)
    assert (m.scoring, m.n_group, m.topk_group) == ("sigmoid", 1, 1)
    assert m.routed_scale == 2.446
    assert (cfg.rope_theta, cfg.norm_eps, cfg.max_seq, cfg.mtp) == \
        (50000.0, 1e-5, 8192, False)
    assert not cfg.tie_embeddings
    total = count_params(cfg)
    active = count_params(cfg, active_only=True)
    assert abs(total - 15.96e9) / 15.96e9 < 0.01
    assert abs(active - 2.92e9) / 2.92e9 < 0.01
