"""Serving launcher: the hardened continuous-batching loop over real
model decode buckets.

``python -m repro.launch.serve --arch llama3.2-1b --requests 8`` runs a
reduced config end-to-end on CPU: requests are admitted by the
model-priced controller (:class:`repro.runtime.server.LPFServer`),
batched continuously into ``(batch, cache_len)`` buckets, and decoded
through each bucket's fused whole-loop XLA computation
(``ServeStep.decode_fn``).  Full configs use the same driver under a
real mesh.

The engine here wraps :func:`repro.runtime.train_step
.build_serve_buckets`; its admission prices are *wall-calibrated* from
a warm-up decode per bucket (the model's transformer step is jax
compute, not an LPF program, so the BSP ledger does not price it —
the pure-LPF :class:`~repro.runtime.server.ProgramDecodeEngine` is
the model-priced variant the chaos soak proves exact).  Greedy decode
is row-independent, so a request's token stream is bit-identical
whether it decodes solo or fully batched; ``--check`` re-decodes every
completed request solo and verifies exactly that.
"""

from __future__ import annotations

import argparse
import functools
import os
import time
from typing import Dict, Sequence, Tuple


class ModelDecodeEngine:
    """Decode-engine protocol (see :class:`repro.runtime.server
    .LPFServer`) over real model buckets: one jitted per-token step and
    memoized fused decode loops per ``(batch, cache_len)`` shape.

    ``quarantine(bucket)`` (or ``--per-token``) drops the bucket to the
    per-token dispatch path — same greedy argmax stream, one jitted
    call per token instead of one XLA ``While`` per sequence."""

    def __init__(self, cfg, mesh, buckets: Sequence[Tuple[int, int]],
                 calibrate_tokens: int = 4):
        import jax
        import jax.numpy as jnp
        from repro.models import init_caches, init_params
        from repro.runtime.train_step import build_serve_buckets

        self._jax, self._jnp = jax, jnp
        self._cfg = cfg
        self._init_caches = init_caches
        self._steps = build_serve_buckets(cfg, mesh, buckets)
        # one device copy of the weights per distinct param sharding:
        # buckets differ in batch and cache length, not in weights
        self._params = {}
        copies = []                      # [(sharding leaves, params)]
        for b, ss in self._steps.items():
            leaves = jax.tree.leaves(ss.param_sharding)
            params = next((p for l, p in copies if l == leaves), None)
            if params is None:
                params = jax.jit(functools.partial(init_params, cfg=cfg),
                                 out_shardings=ss.param_sharding)(
                    jax.random.PRNGKey(0))
                copies.append((leaves, params))
            self._params[b] = params
        self._enc = {}
        for b, ss in self._steps.items():
            self._enc[b] = (jnp.zeros((b[0], 64, cfg.d_model),
                                      jnp.bfloat16),) \
                if cfg.encoder_groups else ()
        self._quarantined: set = set()
        self._token_s: Dict[Tuple[int, int], float] = {}
        self._overhead_s: Dict[Tuple[int, int], float] = {}
        self._calibrate(calibrate_tokens)

    # -- protocol --------------------------------------------------------
    def buckets(self):
        return tuple(sorted(self._steps))

    def token_seconds(self, bucket):
        return self._token_s[tuple(bucket)]

    def overhead_seconds(self, bucket):
        return self._overhead_s[tuple(bucket)]

    def round_tokens(self, bucket, n: int) -> int:
        t = 1
        while t < n:
            t *= 2
        return min(t, bucket[1])

    def ledger_seconds(self, bucket, n_tokens: int) -> float:
        b = tuple(bucket)
        return self._overhead_s[b] + self._token_s[b] * n_tokens

    def quarantine(self, bucket) -> None:
        self._quarantined.add(tuple(bucket))

    def decode(self, bucket, reqs, n_tokens: int
               ) -> Dict[int, Tuple[int, ...]]:
        toks = self._decode_rows(
            tuple(bucket),
            [r.seed % self._cfg.vocab for r in reqs], n_tokens)
        return {r.rid: toks[i] for i, r in enumerate(reqs)}

    # -- internals -------------------------------------------------------
    def _decode_rows(self, bucket, seed_toks, n_tokens: int):
        """Decode ``n_tokens`` greedy tokens for rows seeded with
        ``seed_toks`` (one prompt token each); rows beyond the request
        count pad with token 0.  Returns per-row token tuples."""
        jax, jnp = self._jax, self._jnp
        B, C = bucket
        ss = self._steps[bucket]
        caches = jax.device_put(
            self._init_caches(self._cfg, B, C), ss.cache_sharding)
        row = [int(s) for s in seed_toks] + [0] * (B - len(seed_toks))
        tok = jnp.asarray(row, jnp.int32)
        extra = self._enc[bucket]
        if bucket in self._quarantined:
            seq = []
            for pos in range(n_tokens):
                tok, caches = ss.step_fn(self._params[bucket], caches,
                                         tok, jnp.int32(pos), *extra)
                seq.append(tok)
            out = jnp.stack(seq)            # [T, B]
        else:
            out, _caches = ss.decode_fn(n_tokens)(
                self._params[bucket], caches, tok, jnp.int32(0), *extra)
        jax.block_until_ready(out)
        return [tuple(int(t) for t in out[:, i]) for i in range(B)]

    def _calibrate(self, n_tokens: int) -> None:
        """Wall-calibrate the admission price per bucket: trace+compile
        on the first decode, then time one 1-token and one ``n``-token
        decode — the slope is the per-token price, the intercept the
        per-call overhead."""
        for b in self.buckets():
            n = min(n_tokens, b[1])
            for t in (1, n):                # compile both lengths
                self._decode_rows(b, [0], t)
            t0 = time.perf_counter()
            self._decode_rows(b, [0], 1)
            t1 = time.perf_counter()
            self._decode_rows(b, [0], n)
            t2 = time.perf_counter()
            per_tok = max((t2 - t1) - (t1 - t0), 1e-9) / max(n - 1, 1)
            self._token_s[b] = per_tok
            self._overhead_s[b] = max((t1 - t0) - per_tok, 0.0)


def solo_mismatches(eng, reqs, done) -> list:
    """Re-decode every completed request alone in its bucket; returns
    the rids whose batched stream differs from the solo decode (greedy
    decode is row-independent, so the list must be empty)."""
    by_rid = {r.rid: r for r in reqs}
    bad = []
    for o in sorted(done, key=lambda o: o.rid):
        r = by_rid[o.rid]
        solo = eng.decode(o.bucket, [r],
                          eng.round_tokens(o.bucket, r.n_tokens))
        if tuple(solo[r.rid][:r.n_tokens]) != tuple(o.tokens):
            bad.append(o.rid)
    return bad


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--tokens", type=int, default=32,
                    help="max tokens per request")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--requests", type=int, default=8,
                    help="synthetic requests to serve")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-queue", type=int, default=16)
    ap.add_argument("--deadline-scale", type=float, default=40.0,
                    help="loose deadlines as multiples of the "
                         "calibrated per-token decode cost")
    ap.add_argument("--tight-frac", type=float, default=0.25,
                    help="fraction of deliberately unmeetable deadlines")
    ap.add_argument("--per-token", action="store_true",
                    help="dispatch one jitted call per token (the "
                         "fallback path) instead of the fused decode "
                         "loop")
    ap.add_argument("--check", action="store_true",
                    help="re-decode every completed request solo and "
                         "assert the batched stream is bit-identical")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.runtime.server import LPFServer, synthetic_requests

    enable_compile_cache()

    mesh = make_mesh(tuple(int(x) for x in args.mesh.split("x")))
    cfg = get_config(args.arch, smoke=args.smoke,
                     ep_degree=mesh.shape.get("model", 1))
    cache_len = max(args.cache_len, args.tokens)
    buckets = sorted({(max(1, args.batch // 2), cache_len),
                      (args.batch, cache_len)})
    print(f"building decode buckets {buckets} ...")
    eng = ModelDecodeEngine(cfg, mesh, buckets)
    if args.per_token:
        for b in eng.buckets():
            eng.quarantine(b)
    for b in eng.buckets():
        print(f"  bucket {b}: {eng.token_seconds(b) * 1e3:.2f} ms/token"
              f" + {eng.overhead_seconds(b) * 1e3:.2f} ms/call")

    srv = LPFServer(eng, max_queue=args.max_queue)
    reqs = synthetic_requests(
        args.requests, args.seed, buckets,
        token_cost_s=max(eng.token_seconds(b) for b in buckets),
        deadline_scale=args.deadline_scale, tight_frac=args.tight_frac,
        max_tokens=args.tokens)
    t0 = time.perf_counter()
    for r in reqs:
        out = srv.submit(r)
        if out.status != "admitted":
            print(f"  rid {r.rid}: {out.status} ({out.reason})")
    srv.run_until_idle()
    health = srv.drain()
    dt = time.perf_counter() - t0

    outs = srv.take_outcomes()
    done = [o for o in outs.values() if o.status == "completed"]
    ntok = sum(len(o.tokens) for o in done)
    print(f"\nserved {len(done)}/{args.requests} requests "
          f"({ntok} tokens) in {dt:.3f}s wall "
          f"({ntok / dt:.1f} tok/s), vclock {health['vclock_s']:.3f}s")
    for k in ("admitted", "completed", "rejected_total", "shed",
              "deadline_misses", "batches", "decode_fallbacks",
              "level_peak", "queue_peak"):
        print(f"  {k}: {health[k]}")
    if done:
        o = min(done, key=lambda o: o.rid)
        print(f"sample stream (rid {o.rid}):",
              list(o.tokens[:16]))

    # SLO accounting gates (the CI smoke tripwire): an admitted request
    # must never miss its deadline on the admission clock, a drain must
    # leave nothing queued, and every non-completed request must carry
    # a classified refusal
    if health["deadline_misses"]:
        raise SystemExit(f"SLO violation: {health['deadline_misses']} "
                         f"admitted request(s) missed their deadline")
    if health["queue_depth"] != 0 or not health["draining"]:
        raise SystemExit("drain left work queued")
    unclassified = [o.rid for o in outs.values()
                    if o.status != "completed" and not o.classified]
    if unclassified:
        raise SystemExit(f"unclassified refusals: rids {unclassified}")

    if args.check:
        bad = solo_mismatches(eng, reqs, done)
        for rid in bad:
            print(f"  CHECK FAILED rid {rid}: batched stream "
                  f"differs from solo decode")
        print(f"check: {len(done) - len(bad)}/{len(done)} completed "
              f"requests bit-identical to solo decode")
        if bad:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
