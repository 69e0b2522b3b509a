"""Drive the system's main paths once on a TPU, at real sizes, and check
each against its reference.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the multi-chip LPF paths

One chip (everything in this one process):

* serve     llama3.2-1b at full width (random weights from the seed)
            through ``ModelDecodeEngine`` and ``LPFServer``, buckets
            (2, 256) and (4, 256), 8 requests; every completed stream
            must be bit-identical to its solo re-decode;
* lpf_core  a quickstart-shaped ``exec_`` (get, put, sync and a
            recorded program); the ledger must equal the plan;
* fft       the immortal ``bsp_fft`` at n = 2^26 complex64 against
            ``jnp.fft.fft`` (relative L2 <= 1e-4);
* pagerank  ``lpf_pagerank`` on an R-MAT graph of 2^20 vertices, edge
            factor 16, against ``reference_pagerank``;
* kernels   flash attention (llama3.2-1b widths, forward and backward),
            ``fft_stage`` on [2048, 2^15] and ``ssd_scan`` (mamba2-130m
            widths), all compiled, each against its reference.

Four chips (``--chips 4``), one 4-device mesh: a total exchange and an
all-reduce at 64 MiB per chip against numpy, ``bsp_fft`` at n = 2^28
against ``jnp.fft.fft`` on one device, and ``lpf_pagerank`` on 2^22
vertices; each result must be spread over all four devices.

Every phase prints one line with its wall and compile seconds.  The run
fails if any phase fails, or if the server fell back from a fused
decode.  The last line of stdout is
the JSON verdict ``{"ok": true, "device": {...}}``; without a TPU the
script exits nonzero and prints no verdict.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _rel_l2(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


def _rel_max(a, b) -> float:
    import jax.numpy as jnp
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _check_spread(arr, devices) -> None:
    """``arr`` is split over every device of ``devices``, one equal
    shard each — not replicated, not left on one device."""
    shards = arr.addressable_shards
    _check({s.device for s in shards} == set(devices),
           f"result lives on {sorted(str(s.device) for s in shards)}, "
           f"not on all of {len(devices)} devices")
    _check(all(s.data.shape[0] * len(devices) == arr.shape[0]
               for s in shards),
           f"shards {[s.data.shape for s in shards]} do not split "
           f"{arr.shape} {len(devices)} ways")


def _mesh(devices):
    from repro.core import compat
    return compat.make_mesh((len(devices),), ("x",), devices=devices)


# --------------------------------------------------------------------------
# phases: each returns the numbers it compared; a failed check raises
# --------------------------------------------------------------------------

def serve_phase(seed: int) -> dict:
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import ModelDecodeEngine, solo_mismatches
    from repro.runtime.server import LPFServer, synthetic_requests

    buckets = [(2, 256), (4, 256)]
    eng = ModelDecodeEngine(get_config("llama3.2-1b"), make_mesh((1, 1)),
                            buckets)
    srv = LPFServer(eng, max_queue=16)
    # loose deadlines and no deliberately tight ones: every request
    # must be admitted and decoded
    reqs = synthetic_requests(
        8, seed, buckets,
        token_cost_s=max(eng.token_seconds(b) for b in buckets),
        deadline_scale=1e3, tight_frac=0.0, max_tokens=16)
    for r in reqs:
        srv.submit(r)
    health = srv.drain()
    done = [o for o in srv.take_outcomes().values()
            if o.status == "completed"]
    _check(len(done) == len(reqs),
           f"{len(done)}/{len(reqs)} requests completed")
    for k in ("deadline_misses", "decode_fallbacks", "decode_failures",
              "queue_depth"):
        _check(health[k] == 0, f"server {k} = {health[k]}")
    bad = solo_mismatches(eng, reqs, done)
    _check(not bad, f"rids {bad}: batched stream differs from solo decode")
    return {"completed": len(done),
            "tokens": sum(len(o.tokens) for o in done),
            "batches": health["batches"], "solo_mismatches": len(bad),
            "decode_fallbacks": health["decode_fallbacks"]}


def lpf_core_phase(devices) -> dict:
    """The quickstart's shape: fetch dimensions from process 0 (get),
    broadcast an error word (put), then a recorded two-superstep ring
    shift.  The ledger must equal the plan: the eager supersteps ledger
    exactly what ``sync`` planned, and every superstep's h-relation is
    the one its message table defines."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro import core as lpf

    p = len(devices)
    m, n, w = 1024, 512, 1 << 16
    planned = []

    def spmd(ctx, s, p, args):
        ctx.resize_memory_register(5)
        ctx.resize_message_queue(p * p + p)
        lerr = ctx.register_local("lerr", jnp.zeros(1, jnp.int32))
        gerr = ctx.register_global("gerr", jnp.zeros(1, jnp.int32))
        mdim = ctx.register_global("mdim", args["mdim"])
        ctx.get(mdim, mdim, frm=0, size=2)
        planned.append(ctx.sync(label="fetch-dims"))
        dims = ctx.tensor(mdim)
        rows = (dims[0] + p - ctx.pid - 1) // p
        bad = jnp.where((rows <= 0) | (dims[1] <= 0), 1, 0)
        ctx.write(lerr, bad[None].astype(jnp.int32))
        for k in range(p):
            ctx.put(lerr, gerr, to=k, size=1)
        planned.append(ctx.sync(label="error-broadcast"))
        ring = ctx.register_global("ring", args["ring"] + ctx.pid)
        buf = ctx.register_global("buf", jnp.zeros(w, jnp.float32))
        with ctx.program("ring"):
            ctx.put(ring, buf, to=lambda s_: (s_ + 1) % p)
            ctx.sync(label="ring.shift1")
            ctx.put(buf, ring, to=lambda s_: (s_ + 1) % p)
            ctx.sync(label="ring.shift2")
        return (ctx.tensor(gerr)[0], rows[None].astype(jnp.int32),
                ctx.tensor(ring))

    args = {"mdim": jnp.asarray([m, n], jnp.int32),
            "ring": jnp.arange(w, dtype=jnp.float32)}
    (err, rows, ring), ledger = lpf.exec_(
        _mesh(devices), spmd, args, out_specs=(P(), P("x"), P("x")),
        return_ledger=True)
    _check(int(err) == 0, f"error word {int(err)}")
    _check([int(r) for r in rows] == [(m + p - s - 1) // p
                                      for s in range(p)],
           f"rows per process {list(map(int, rows))}")
    want = np.concatenate([np.arange(w, dtype=np.float32) + (s - 2) % p
                           for s in range(p)])
    _check(np.array_equal(np.asarray(ring), want), "ring shift result")
    _check(ledger.records[:2] == planned,
           f"ledger {ledger.records[:2]} != planned {planned}")
    off = (p - 1) if p > 1 else 0
    want_h = [2 * 4 * off, 4 * off] + ([4 * w] * 2 if p > 1 else [0, 0])
    _check(ledger.records[0].h_bytes == want_h[0]
           and ledger.records[1].h_bytes == want_h[1]
           and sum(r.h_bytes for r in ledger.records[2:]) == sum(want_h[2:]),
           f"ledger h-relations {[r.h_bytes for r in ledger.records]} "
           f"!= message tables {want_h}")
    return {"supersteps": ledger.supersteps, "h_bytes": ledger.h_bytes}


def fft_phase(devices, log2n: int, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.algorithms import bsp_fft
    from repro.algorithms.fft import fft_h_bytes

    p, n = len(devices), 1 << log2n
    with jax.default_device(devices[0]):
        re, im = jax.random.normal(jax.random.PRNGKey(seed), (2, n))
        x = jax.lax.complex(re, im)
        del re, im
    y, ledger = bsp_fft(_mesh(devices), x, return_ledger=True)
    _check(ledger.h_bytes == fft_h_bytes(n, p),
           f"ledger h {ledger.h_bytes} != fft_h_bytes {fft_h_bytes(n, p)}")
    if p > 1:
        _check_spread(y, devices)
    ref = jnp.fft.fft(x)                       # one device
    num = den = 0.0
    for sh in y.addressable_shards:
        part = jax.device_put(sh.data, devices[0])
        r = ref[sh.index]
        num += float(jnp.sum(jnp.abs(part - r) ** 2))
        den += float(jnp.sum(jnp.abs(r) ** 2))
    rel = math.sqrt(num / den)
    _check(rel <= 1e-4, f"bsp_fft vs jnp.fft.fft relative L2 {rel:.3e}")
    return {"n": n, "rel_l2_vs_jnp_fft": rel, "h_bytes": ledger.h_bytes}


def pagerank_phase(devices, scale: int, seed: int) -> dict:
    from repro.algorithms import (lpf_pagerank, partition_graph,
                                  reference_pagerank, rmat_graph)

    p, n = len(devices), 1 << scale
    t0 = time.perf_counter()
    edges = rmat_graph(n, 16 * n, seed=seed)
    g = partition_graph(edges, n, p)
    setup_s = time.perf_counter() - t0
    r, iters, res = lpf_pagerank(_mesh(devices), g, tol=1e-7, max_iter=200)
    if p > 1:
        _check_spread(r, devices)
    r = np.asarray(r, np.float64)
    ref, ref_iters = reference_pagerank(edges, n)
    err = float(np.abs(r - ref).max() / ref.max())
    _check(err < 1e-3, f"pagerank vs reference relative max {err:.3e}")
    _check(abs(r.sum() - 1.0) < 1e-3, f"ranks sum to {r.sum()}")
    return {"n": n, "edges": int(edges.shape[0]), "iters": iters,
            "residual": res, "ref_iters": ref_iters,
            "rel_max_vs_reference": err, "graph_setup_s": setup_s}


def exchange_phase(devices, mib: int, seed: int) -> dict:
    """A total exchange and a fused all-reduce over ``mib`` MiB of f32
    per chip, against numpy."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import bsp, core as lpf

    p = len(devices)
    per = mib * (1 << 20) // 4
    host = np.random.default_rng(seed).standard_normal(
        (p * per,), np.float32)
    mesh = _mesh(devices)
    x = jax.device_put(host, NamedSharding(mesh, P("x")))
    _check_spread(x, devices)
    ex, ex_ledger = lpf.exec_(
        mesh, lambda ctx, s, p_, xl: bsp.alltoall(ctx, xl), x,
        in_specs=P("x"), out_specs=P("x"), return_ledger=True)
    _check_spread(ex, devices)
    want = host.reshape(p, p, per // p).transpose(1, 0, 2).reshape(-1)
    _check(np.array_equal(np.asarray(ex), want), "total exchange result")
    w = per // p
    _check(ex_ledger.h_bytes == 4 * w * (p - 1),
           f"exchange ledger h {ex_ledger.h_bytes} != {4 * w * (p - 1)}")
    ar, ar_ledger = lpf.exec_(
        mesh, lambda ctx, s, p_, xl: bsp.allreduce(ctx, xl), x,
        in_specs=P("x"), out_specs=P("x"), return_ledger=True)
    _check_spread(ar, devices)
    total = host.reshape(p, per).sum(axis=0)
    got = np.asarray(ar).reshape(p, per)
    err = float(np.abs(got - total[None]).max())
    _check(err < 1e-4, f"allreduce vs numpy max abs {err:.3e}")
    return {"bytes_per_chip": 4 * per, "exchange_h": ex_ledger.h_bytes,
            "allreduce_supersteps": ar_ledger.supersteps,
            "allreduce_max_abs": err}


def kernels_phase(seed: int, seq: int = 2048, fft_rows: int = 2048
                  ) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels.fft_stage import ops as fft_ops
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.ssd_scan.kernel import ssd_scan
    from repro.models.mamba import _ssd_chunked

    out = {}
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    # references in full f32 matmul precision
    exact = functools.partial(jax.default_matmul_precision, "highest")

    # flash attention, llama3.2-1b widths: H=32, Hkv=8, D=64, bf16
    q = jax.random.normal(keys[0], (1, 32, seq, 64), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, 8, seq, 64), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, 8, seq, 64), jnp.bfloat16)
    o = jax.jit(flash_attention)(q, k, v)
    loss = lambda f: (lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2))
    grads = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))(
        q, k, v)
    with exact():
        o_ref = jax.jit(attention_ref)(q, k, v)
        g_ref = jax.jit(jax.grad(loss(attention_ref), argnums=(0, 1, 2)))(
            q, k, v)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32)
                                - o_ref.astype(jnp.float32))))
    _check(err < 2e-2, f"flash forward max abs {err:.3e}")
    gerr = max(_rel_max(a, b) for a, b in zip(grads, g_ref))
    _check(gerr < 5e-2, f"flash backward relative max {gerr:.3e}")
    out["flash_fwd_max_abs"], out["flash_bwd_rel_max"] = err, gerr

    # fft_stage: [2048, 2^15] complex64
    re, im = jax.random.normal(keys[3], (2, fft_rows, 1 << 15))
    x = jax.lax.complex(re, im)
    y = jax.jit(fft_ops.fft)(x)
    rel = _rel_l2(y, jnp.fft.fft(x))
    _check(rel <= 1e-4, f"fft_stage vs jnp.fft.fft relative L2 {rel:.3e}")
    out["fft_stage_rel_l2"] = rel

    # ssd_scan, mamba2-130m widths: H=24, P=64, N=128, G=1, chunk 128
    mcfg = get_config("mamba2-130m").mamba
    B, S, H, Pd, N = 1, seq, mcfg.n_heads, mcfg.head_dim, mcfg.d_state
    xs = jax.random.normal(keys[4], (B, S, H, Pd))
    dt = jax.random.uniform(keys[5], (B, S, H), minval=1e-3, maxval=0.1)
    a = -jax.random.uniform(keys[6], (H,), minval=0.5, maxval=2.0)
    bc = jax.random.normal(keys[7], (2, B, S, mcfg.n_groups, N))
    ys, st = jax.jit(functools.partial(ssd_scan, chunk=mcfg.chunk))(
        xs, dt, a, bc[0], bc[1])
    with exact():
        y_ref, st_ref = jax.jit(functools.partial(_ssd_chunked, cfg=mcfg))(
            xs, dt, a, bc[0], bc[1])
    yerr, serr = _rel_max(ys, y_ref), _rel_max(st, st_ref)
    _check(max(yerr, serr) < 1e-3,
           f"ssd_scan vs chunked jnp relative max y {yerr:.3e} "
           f"state {serr:.3e}")
    out["ssd_y_rel_max"], out["ssd_state_rel_max"] = yerr, serr
    return out


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.core import hardware_for
    from repro.launch.compile_cache import enable_compile_cache
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"this smoke run needs the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    hw = hardware_for(dev.device_kind)
    cache_dir = enable_compile_cache()
    print(f"device: {dev.device_kind} x{len(devices)}, hardware model "
          f"{hw.name}, jax {jax.__version__}, compile cache {cache_dir}")

    compile_s = [0.0]

    def on_duration(event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    seed = args.seed
    if args.chips == 1:
        one = devices[:1]
        phases = [
            ("serve", lambda: serve_phase(seed)),
            ("lpf_core", lambda: lpf_core_phase(one)),
            ("fft", lambda: fft_phase(one, 26, seed)),
            ("pagerank", lambda: pagerank_phase(one, 20, seed)),
            ("kernels", lambda: kernels_phase(seed)),
        ]
    else:
        four = devices[:4]
        phases = [
            ("exchange", lambda: exchange_phase(four, 64, seed)),
            ("fft", lambda: fft_phase(four, 28, seed)),
            ("pagerank", lambda: pagerank_phase(four, 22, seed)),
        ]

    ok = True
    total_compile = 0.0
    for name, run in phases:
        compile_s[0] = 0.0
        t0 = time.perf_counter()
        try:
            res = run()
            status = "ok"
        except Exception:
            traceback.print_exc()
            res, status, ok = {}, "FAILED", False
        wall = time.perf_counter() - t0
        total_compile += compile_s[0]
        print(f"phase {name}: {status} wall_s={wall:.3f} "
              f"compile_s={compile_s[0]:.3f} "
              + " ".join(f"{k}={v}" for k, v in res.items()), flush=True)
    print(f"total compile_s={total_compile:.3f}")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
