"""The pure-LPF serve engine on the host mesh (slow tier).

What the fast-tier fake cannot prove: the real recorded decode
programs are bit-identical across solo / batched / per-token-fallback
execution, the admission price equals the executed ledger (model
compliance end to end), and the chaos harness's per-request serve
invariant holds under its worst fixed plans.
"""

import pytest

from repro.runtime.faults import FaultPlan, _run_one
from repro.runtime.server import (LPFServer, ProgramDecodeEngine,
                                  ServeRequest, synthetic_requests)

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def program_engine():
    return ProgramDecodeEngine(buckets=((2, 8), (4, 8)))


def req(rid, n=4, seed=0):
    return ServeRequest(rid=rid, n_tokens=n, deadline_s=10.0, seed=seed)


def test_engine_bit_identical_solo_batched_fallback(program_engine):
    eng = program_engine
    a, b = req(0, seed=1234), req(1, seed=777)
    solo = eng.decode((4, 8), [a], 4)[0]
    batched = eng.decode((4, 8), [a, b], 4)[0]
    assert solo == batched
    eng.quarantine((4, 8))
    try:
        assert eng.decode((4, 8), [a], 4)[0] == solo
    finally:
        eng._quarantined.discard((4, 8))


def test_engine_prices_match_ledger_and_serve(program_engine):
    """Model compliance end to end: the admission price equals the
    executed ledger, so the served vclock is exactly the sum of batch
    prices, no admitted request misses its deadline, and every
    admitted request terminates classified or completed."""
    eng = program_engine
    assert eng.token_seconds((2, 8)) > 0
    srv = LPFServer(eng, max_queue=8)
    reqs = synthetic_requests(10, 3, eng.buckets(),
                              token_cost_s=eng.token_seconds((4, 8)))
    for r in reqs:
        srv.submit(r)
    srv.run_until_idle()
    h = srv.drain()
    assert h["deadline_misses"] == 0
    assert h["completed"] > 0
    assert h["completed"] + h["shed"] == h["admitted"]
    assert h["program_pinned"] >= 2          # hot buckets stay pinned
    for out in srv.take_outcomes().values():
        if out.status == "completed":
            assert out.completion_v <= out.predicted_v + 1e-12
        else:
            assert out.classified


def test_serve_chaos_invariant_smoke():
    """One pass of the serve chaos workload under its worst fixed
    plans via the harness's own comparator — the CI-shaped reduction
    of the nightly 100-seed soak."""
    baselines = {}
    for spec in ("serve_admit@0x-1", "serve_decode@0x-1"):
        verdict, detail = _run_one("serve", FaultPlan.parse(spec),
                                   baselines)
        assert verdict in ("identical", "classified"), (spec, detail)


def test_model_engine_shares_weights_across_buckets():
    """Buckets differ in batch and cache length, not in weights: the
    model engine holds one device copy of the params for all buckets
    with the same param sharding, and a request decodes the same stream
    solo and batched."""
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import ModelDecodeEngine

    eng = ModelDecodeEngine(get_config("llama3.2-1b", smoke=True),
                            make_mesh((1, 1)), [(2, 16), (4, 16)],
                            calibrate_tokens=2)
    assert eng._params[(2, 16)] is eng._params[(4, 16)]
    a, b = req(0, seed=1234), req(1, seed=777)
    assert eng.decode((4, 16), [a], 4)[0] == \
        eng.decode((4, 16), [a, b], 4)[0] == eng.decode((2, 16), [a], 4)[0]
