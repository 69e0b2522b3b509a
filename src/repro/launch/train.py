"""Training launcher: ``python -m repro.launch.train --arch <id> ...``.

On the CPU container this drives reduced (smoke) configs end-to-end; on a
real cluster the same driver runs the full configs (jax.distributed
initialisation happens before mesh construction when JAX_COORDINATOR is
set — the TPU analogue of the paper's lpf_mpi_initialize_over_tcp).
"""

from __future__ import annotations

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default="1x1",
                    help="DxM (data x model), or PxDxM for multi-pod")
    ap.add_argument("--devices", type=int, default=0,
                    help="force host device count (CPU emulation)")
    ap.add_argument("--grad-sync", default="gspmd",
                    choices=["gspmd", "lpf"])
    ap.add_argument("--sync-every", type=int, default=0,
                    help="local-SGD period (0 = synchronous)")
    ap.add_argument("--compress", action="store_true",
                    help="int8 cross-pod gradient compression (lpf mode)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--grad-accum", type=int, default=1)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    if os.environ.get("JAX_COORDINATOR"):
        import jax
        jax.distributed.initialize()

    from repro.configs import get_config
    from repro.core import CompressSpec, SyncAttributes
    from repro.data import DataConfig, SyntheticStream
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.optim import AdamWConfig, warmup_cosine
    from repro.runtime.train_loop import TrainLoopConfig, train_loop
    from repro.runtime.train_step import build_train_step

    enable_compile_cache()
    shape = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_mesh(shape)
    cfg = get_config(args.arch, smoke=args.smoke,
                     ep_degree=mesh.shape.get("model", 1))
    attrs = SyncAttributes(compress=CompressSpec(bits=8)
                           if args.compress else None)
    ts = build_train_step(
        cfg, mesh,
        opt_cfg=AdamWConfig(lr=warmup_cosine(args.lr, 10, args.steps)),
        grad_sync=args.grad_sync, sync_attrs=attrs,
        grad_accum=args.grad_accum)
    ts_nosync = None
    if args.sync_every > 1:
        ts_nosync = build_train_step(
            cfg, mesh, opt_cfg=AdamWConfig(
                lr=warmup_cosine(args.lr, 10, args.steps)),
            grad_sync="gspmd", grad_accum=args.grad_accum)

    stream = SyntheticStream(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch), cfg)

    def on_step(step, loss, verdict):
        if step % 10 == 0 or verdict.straggle:
            flag = f" [{verdict.action}]" if verdict.action != "ok" else ""
            print(f"step {step:>5}  loss {loss:.4f}  "
                  f"{verdict.duration * 1e3:7.1f} ms{flag}")

    out = train_loop(ts, stream, TrainLoopConfig(
        steps=args.steps, ckpt_dir=args.ckpt_dir,
        sync_every=args.sync_every),
        step_fn_nosync=ts_nosync.step_fn if ts_nosync else None,
        on_step=on_step)
    print(f"final loss: {out['final_loss']:.4f}")
    if ts.ledger.records:
        print("\nLPF superstep ledger (first steps):")
        print(ts.ledger.report())


if __name__ == "__main__":
    main()
