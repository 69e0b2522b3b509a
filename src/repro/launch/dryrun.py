import os
# the dry-run compiles for 512 emulated CPU devices and never touches an
# accelerator, so neither this process nor the ``--all`` children it
# starts ever holds a chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is how the distribution config is proven coherent without hardware:
``jax.jit(step).lower(...).compile()`` runs the full XLA SPMD partitioner
for the production mesh; sharding mismatches, unsupported collectives and
compile-time OOMs all surface here.  The compiled artifact yields

  * ``memory_analysis()``  — per-device bytes (does it fit 16 GB HBM?),
  * ``cost_analysis()``    — FLOPs / bytes for the §Roofline terms,
  * the HLO text          — collective bytes via ``parse_collectives``.

One cell per invocation (isolation against compile OOM); ``--all`` runs
the whole matrix through subprocesses of this same module.

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-110b --shape train_4k \
      --mesh single --out experiments/dryrun
  python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import subprocess
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _attach(sds_tree, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        sds_tree, shardings)


def _lower_cell(cfg, cell, mesh, batch_sds, overrides):
    """Build the right step for the cell kind and return its `lowered`."""
    from repro.models import Runtime, init_caches, init_params, prefill
    from repro.runtime.train_step import build_serve_step, build_train_step
    from repro.sharding.rules import batch_specs, param_specs
    from jax.sharding import NamedSharding, PartitionSpec as P

    def shard_tree(specs):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    if cell.kind == "train":
        from repro.optim import adamw_init
        accum = overrides.get("grad_accum")
        if accum is None:
            # wide configs need microbatching to fit 16 GB/chip
            accum = 8 if cfg.d_model >= 7168 else \
                (4 if cfg.d_model >= 3584 else 1)
        from repro.optim import AdamWConfig
        opt_cfg = AdamWConfig()
        if cfg.param_dtype == "bfloat16":
            # 671B memory policy (DESIGN §7): bf16 moments, as the model's
            # own training recipe uses low-precision optimizer state
            opt_cfg = AdamWConfig(moment_dtype=jnp.bfloat16)
        ts = build_train_step(cfg, mesh, grad_sync=overrides.get(
            "grad_sync", "gspmd"), grad_accum=accum, opt_cfg=opt_cfg,
            axis_roles=overrides.get("axis_roles", "fsdp_tp"))
        p_sds = jax.eval_shape(partial(init_params, cfg=cfg),
                               jax.random.PRNGKey(0))
        o_sds = jax.eval_shape(partial(adamw_init, cfg=opt_cfg), p_sds)
        b_sds = _attach(batch_sds, shard_tree(batch_specs(batch_sds, mesh)))
        return ts.step_fn.lower(p_sds, o_sds, b_sds)
    if cell.kind == "prefill":
        from repro.launch.mesh import dp_axes_of, model_axis_of
        rt = Runtime(mesh, dp_axes=dp_axes_of(mesh),
                     model_axis=model_axis_of(mesh), sp=True)
        p_sds = jax.eval_shape(partial(init_params, cfg=cfg),
                               jax.random.PRNGKey(0))
        p_shard = shard_tree(param_specs(p_sds, mesh))
        b_sds = _attach(batch_sds, shard_tree(batch_specs(batch_sds, mesh)))
        fn = jax.jit(lambda p, b: prefill(p, b, cfg, rt),
                     in_shardings=(p_shard, None))
        return fn.lower(p_sds, b_sds)
    # decode — serving holds parameters in bf16 (inference checkpoints);
    # serve_layout=tp_only replicates weights over `data` (no per-token
    # FSDP gathers); serve_quant=int8 stores weights int8-at-rest
    layout = overrides.get("serve_layout")
    ss = build_serve_step(cfg, mesh, global_batch=cell.global_batch,
                          cache_len=cell.seq_len,
                          param_axes=("model",) if layout == "tp_only"
                          else None)
    p_sds = jax.eval_shape(partial(init_params, cfg=cfg),
                           jax.random.PRNGKey(0))
    wdt = jnp.int8 if overrides.get("serve_quant") == "int8" else jnp.bfloat16
    p_sds = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, wdt)
        if l.dtype == jnp.float32 and len(l.shape) > 1 else l, p_sds)
    c_sds = jax.eval_shape(
        lambda: init_caches(cfg, cell.global_batch, cell.seq_len))
    args = [p_sds, c_sds, batch_sds["token"], batch_sds["pos"]]
    if cfg.encoder_groups:
        args.append(batch_sds["enc_out"])
    return ss.step_fn.lower(*args)


def _measure(compiled, loop_aware: bool = False):
    """flops/bytes from cost_analysis (loop bodies counted ONCE — callers
    extrapolate); collectives + traffic from the HLO census, loop-aware
    for the main scanned compile (exact trip-count multipliers)."""
    from repro.core.hlo_analysis import loop_aware_census, parse_collectives
    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    if loop_aware:
        colls, traffic = loop_aware_census(text)
    else:
        colls = parse_collectives(text)
        traffic = 0.0
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "traffic": float(traffic),
        "coll": float(colls.total_bytes),
        "coll_by_kind": dict(colls.bytes_by_kind),
        "coll_counts": dict(colls.count_by_kind),
    }


def _with_repeats(cfg, reps: dict):
    """cfg with each group's repeat count overridden ({name: n})."""
    import dataclasses as dc
    g2 = tuple(dc.replace(g, repeats=reps.get(g.name, g.repeats))
               for g in cfg.groups)
    e2 = tuple(dc.replace(g, repeats=reps.get(g.name, g.repeats))
               for g in cfg.encoder_groups)
    return dc.replace(cfg, groups=g2, encoder_groups=e2)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             overrides: dict) -> dict:
    from repro.configs import SHAPES, applicable, get_config, input_specs
    from repro.models import count_params
    from repro.launch.mesh import make_production_mesh

    ok, why = applicable(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = int(np.prod(list(mesh.shape.values())))
    cfg = get_config(arch, ep_degree=mesh.shape["model"])
    import dataclasses as dc
    for k, v in overrides.items():
        if k in {f.name for f in dc.fields(cfg)}:
            cfg = dc.replace(cfg, **{k: v})
    cell = SHAPES[shape_name]
    batch_sds = input_specs(cfg, shape_name)

    n_total = count_params(cfg)
    n_active = count_params(cfg, active_only=True)
    if cell.kind == "train":
        model_flops = 6.0 * n_active * cell.global_batch * cell.seq_len
    elif cell.kind == "prefill":
        model_flops = 2.0 * n_active * cell.global_batch * cell.seq_len
    else:
        model_flops = 2.0 * n_active * cell.global_batch

    lowered = _lower_cell(cfg, cell, mesh, batch_sds, overrides)
    t_lower = time.time() - t0
    t1 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t1
    mem = compiled.memory_analysis()
    main = _measure(compiled, loop_aware=True)

    # --- scan-body extrapolation -----------------------------------------
    # XLA's cost analysis counts a while-loop body ONCE (verified
    # empirically), so scanned layer groups are undercounted.  Calibrate
    # with *unrolled* variants: all groups at repeats=1 (baseline c0),
    # then one group at a time bumped to repeats=2; the delta is that
    # group's per-layer cost, and the full-depth cost follows linearly:
    #   cost = c0 + sum_g (R_g - 1) * (c_g - c0).
    # Memory analysis comes from the real scanned compile (scan reuses
    # buffers, so it needs no correction).
    import dataclasses as dc
    all_groups = list(cfg.groups) + list(cfg.encoder_groups)
    multi = [g for g in all_groups if g.repeats > 1]
    extrap = dict(main)
    if multi:
        base_reps = {g.name: 1 for g in all_groups}

        def calib_measure(reps):
            ccfg = dc.replace(_with_repeats(cfg, reps), unroll_layers=True)
            ovr = dict(overrides)
            ovr["grad_accum"] = 1
            return _measure(_lower_cell(ccfg, cell, mesh, batch_sds,
                                        ovr).compile())

        c0 = calib_measure(base_reps)
        extrap["flops"] = c0["flops"]
        for g in multi:
            reps = dict(base_reps)
            reps[g.name] = 2
            c1 = calib_measure(reps)
            delta = max(c1["flops"] - c0["flops"], 0.0)
            extrap["flops"] += delta * (g.repeats - 1)

    per_device_bytes = int(mem.argument_size_in_bytes
                           + mem.temp_size_in_bytes
                           + mem.output_size_in_bytes
                           - mem.alias_size_in_bytes)
    art = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "ok", "chips": chips,
        "params_total": n_total, "params_active": n_active,
        "model_flops": model_flops,
        "hlo_flops_raw": main["flops"],
        "hlo_flops": extrap["flops"],
        "hlo_bytes_raw": main["bytes"],
        "hlo_bytes": main["traffic"],
        "collective_bytes_raw": main["bytes"],
        "collective_bytes": main["coll"],
        "collective_counts": main["coll_counts"],
        "collective_bytes_by_kind": main["coll_by_kind"],
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "per_device_bytes": per_device_bytes,
            "fits_v5e_16g": per_device_bytes <= 16e9,
        },
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "total_s": round(time.time() - t0, 2),
        "memory_note": ("CPU XLA legalises bf16->f32 in several passes "
                        "(verified: duplicate f32 copies of bf16 stacks); "
                        "temp_bytes overstates the TPU figure by up to 2x "
                        "on bf16 buffers."),
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fn = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}.json")
        with open(fn, "w") as f:
            json.dump(art, f, indent=1)
    return art


def _print_result(art: dict):
    if art["status"] == "skipped":
        print(f"SKIP {art['arch']:<24} {art['shape']:<12} {art['mesh']:<7}"
              f" {art['reason']}")
        return
    m = art["memory"]
    print(f"OK   {art['arch']:<24} {art['shape']:<12} {art['mesh']:<7}"
          f" mem/dev={m['per_device_bytes'] / 1e9:7.2f}GB"
          f" fits={str(m['fits_v5e_16g'])[0]}"
          f" flops={art['hlo_flops']:.3e}"
          f" coll={art['collective_bytes'] / 1e6:9.1f}MB"
          f" compile={art['compile_s']:7.1f}s")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--attn-impl", dest="attn_impl")
    ap.add_argument("--remat")
    ap.add_argument("--q-chunk", dest="q_chunk", type=int)
    ap.add_argument("--grad-accum", dest="grad_accum", type=int)
    ap.add_argument("--grad-sync", dest="grad_sync")
    ap.add_argument("--axis-roles", dest="axis_roles")
    ap.add_argument("--serve-layout", dest="serve_layout")
    ap.add_argument("--serve-quant", dest="serve_quant")
    args = ap.parse_args()

    overrides = {k: getattr(args, k) for k in ("attn_impl", "remat",
                                               "q_chunk", "grad_accum",
                                               "grad_sync", "axis_roles",
                                               "serve_layout", "serve_quant")
                 if getattr(args, k) is not None}

    if args.all:
        from repro.configs import ARCHS, SHAPES
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        results = []
        for arch in ARCHS:
            for shape in SHAPES:
                for mk in meshes:
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape, "--mesh", mk,
                           "--out", args.out]
                    r = subprocess.run(cmd, capture_output=True, text=True)
                    tail = (r.stdout or "").strip().splitlines()
                    print(tail[-1] if tail else
                          f"FAIL {arch} {shape} {mk}: {r.stderr[-400:]}")
                    results.append(r.returncode)
        sys.exit(max(results) if results else 0)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for mk in meshes:
        art = run_cell(args.arch, args.shape, mk, args.out, overrides)
        _print_result(art)


if __name__ == "__main__":
    main()
