"""Per-architecture smoke tests: a REDUCED same-family config runs one
train step (finite loss, non-zero finite grads) and one decode step on
CPU, asserting output shapes — the full configs are exercised only via
the dry-run (ShapeDtypeStruct, no allocation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import (Runtime, count_params, decode_step, init_caches,
                          init_params, loss_fn, prefill)

pytestmark = pytest.mark.slow


def _batch(cfg, rng, B=2, S=64):
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                   jnp.int32)}
    if cfg.modality == "vision":
        batch["embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.stub_prefix, cfg.d_model)),
            jnp.float32)
    if cfg.modality == "audio" and cfg.encoder_groups:
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, S, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch, rng):
    cfg = get_config(arch, smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg, rng)
    rt = Runtime()
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg, rt)))(params)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0
    # every leaf finite
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert np.isfinite(np.asarray(g)).all(), \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_smoke(arch, rng):
    cfg = get_config(arch, smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, cache_len = 2, 32
    caches = init_caches(cfg, B, cache_len)
    enc_out = None
    if cfg.encoder_groups:
        enc_out = jnp.asarray(rng.standard_normal((B, 16, cfg.d_model)),
                              jnp.bfloat16)
    rt = Runtime()
    tok = jnp.zeros((B,), jnp.int32)
    nxt, logits, caches2 = jax.jit(
        lambda p, t, c: decode_step(p, t, c, jnp.int32(5), cfg, rt,
                                    enc_out))(params, tok, caches)
    assert logits.shape == (B, cfg.vocab_padded)
    assert np.isfinite(np.asarray(logits[:, :cfg.vocab])).all()
    assert nxt.shape == (B,)
    assert int(nxt.max()) < cfg.vocab      # padded ids can never win
    # cache structure preserved
    assert jax.tree.structure(caches) == jax.tree.structure(caches2)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mamba2-130m",
                                  "whisper-base"])
def test_prefill_matches_decode_logits(arch, rng):
    """Teacher-forced decode over a short prompt must produce the same
    final logits as prefill (cache correctness)."""
    cfg = get_config(arch, smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, S = 1, 8
    batch = _batch(cfg, rng, B=B, S=S)
    rt = Runtime()
    enc_out = None
    if cfg.encoder_groups:
        from repro.models.lm import _run_encoder, _cast_params
        import jax.numpy as jnp2
        cast = _cast_params(params, jnp2.bfloat16)
        enc_out = _run_encoder(
            {k: (v if k.startswith(("dec_", "enc_")) else cast[k])
             for k, v in params.items()}, batch["frames"], cfg, rt)
    want = prefill(params, batch, cfg, rt)          # [B, V]

    caches = init_caches(cfg, B, S)
    logits = None
    for t in range(S):
        _, logits, caches = decode_step(
            params, batch["tokens"][:, t], caches, jnp.int32(t), cfg, rt,
            enc_out)
    got = logits
    wa = np.asarray(want[:, :cfg.vocab])
    ga = np.asarray(got[:, :cfg.vocab])
    # bf16 accumulation differences only
    assert np.abs(wa - ga).max() / (np.abs(wa).max() + 1e-9) < 0.08


def test_param_counts_match_published():
    expect = {
        "qwen1.5-110b": 111e9, "llama3.2-1b": 1.24e9, "qwen3-14b": 14.8e9,
        "gemma2-9b": 9.2e9, "deepseek-v3-671b": 682e9,
        "mamba2-130m": 0.13e9, "llava-next-mistral-7b": 7.2e9,
        "jamba-v0.1-52b": 51.5e9, "whisper-base": 0.106e9,  # +32k learned positions
        "granite-moe-3b-a800m": 3.9e9, "moonlight-16b-a3b": 15.96e9,
    }
    for arch, n in expect.items():
        got = count_params(get_config(arch))
        assert abs(got - n) / n < 0.06, (arch, got, n)


def test_active_params_moe():
    ds = count_params(get_config("deepseek-v3-671b"), active_only=True)
    assert 34e9 < ds < 42e9                 # ~37B active
    ja = count_params(get_config("jamba-v0.1-52b"), active_only=True)
    assert 10e9 < ja < 14e9                 # ~12B active
    ml = count_params(get_config("moonlight-16b-a3b"), active_only=True)
    assert 2.89e9 < ml < 2.95e9             # 2.92B active
