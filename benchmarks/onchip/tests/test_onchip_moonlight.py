"""The Moonlight cell's work count, its metric readers, and faults planted
under whole rehearsed runs, each of which comes out not correct."""

import json

import pytest

import cells
import devtrace
import modelcount
import run
from drive import drive

CELL = "moonlight-16b-a3b-ep8.train8k"
CONFIG = json.loads((cells.HERE / "configs" / "moonlight-16b-a3b-ep8.json")
                    .read_text())


def test_work_count_at_the_published_widths():
    assert modelcount.matmul_params_per_token(CONFIG) == pytest.approx(
        313_327_616, rel=1e-9)
    flops = modelcount.train_step_flops(CONFIG, 8192, 1)
    assert flops == pytest.approx(21.586e12, rel=1e-4)


def test_work_count_matches_the_program_parameters():
    """The matmul parameters a token passes through here are the
    program's active parameters less the embedding table and the norm
    and bias vectors."""
    from repro.configs import expert_share, get_config
    from repro.models import count_params
    cfg = expert_share(get_config(CONFIG["arch"], ep_degree=1),
                       layers=CONFIG["num_hidden_layers"],
                       experts_held=CONFIG["n_routed_experts"],
                       vocab=CONFIG["vocab_size"])
    embed = CONFIG["vocab_size"] * CONFIG["hidden_size"]
    active = count_params(cfg, active_only=True) - embed
    assert active - modelcount.matmul_params_per_token(CONFIG) == \
        pytest.approx(0, abs=1e-4 * active)


def _run(info=None, trace=None, calls=2):
    return run.Run(CELL, CONFIG, {"seq_len": 8192, "batch": 1}, 0.0,
                   [(float(i), i + 0.1, i + 1.0) for i in range(calls)], [],
                   info or {}, trace, None)


def _reader(name):
    return cells.load_module(cells.HERE / "metrics" / f"{name}.py").read


def test_expert_load_reader():
    read = _reader("moonlight.expert_load_max")
    rows = [[[1, 1, 2], [3, 3, 0]], [[2, 2, 2], [0, 0, 0]]]
    # 1.5, 1.5 and 1.0; a layer with no rows counts for nothing
    assert read(_run({"rows": rows})) == pytest.approx(4.0 / 3.0)
    assert read(_run({})) is None


def test_device_readers():
    trace = devtrace.Trace(
        {"tpu": [(0, 500_000_000, "%fusion.1 = f32[4] fusion(), "
                                  "kind=kCustom"),
                 (600_000_000, 1_000_000_000, "%fusion.2 = f32[4] fusion(),"
                                              " kind=kLoop")]},
        [(0, 2_000_000_000, "bench.window", "main")])
    summary = devtrace.reduce(trace)
    r = _run(trace=summary)
    assert _reader("moonlight.gather_scatter_ms")(r) == pytest.approx(250.0)
    assert _reader("moonlight.idle_share")(r) == pytest.approx(25.0)
    assert _reader("moonlight.mfu")(r) is None          # no peak: rehearsal
    for name in ("moonlight.gather_scatter_ms", "moonlight.idle_share"):
        assert _reader(name)(_run()) is None


def test_step_latency_leaves_out_the_kept_steps_copies():
    """``fft_p95_ms`` reads the p95 of any call's latency: here one
    training step's.  The kept step's host copies (in its call) are the
    slowest call of a window and fall above the p95."""
    calls = [(float(i), i + 0.1, i + 0.9) for i in range(40)]
    calls[3] = (3.0, 3.1, 8.0)
    r = run.Run(CELL, CONFIG, {}, 0.0, calls, [], {}, None, None)
    assert _reader("fft_p95_ms")(r) == pytest.approx(900.0)


def test_untraced_run_reports_setup_and_step_latency():
    res = drive(CELL, seconds=1.0)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "fft_p95_ms"}
    assert res["metrics"]["fft_p95_ms"]["value"] > 0.0


def _expert_left_out(moe):
    orig = moe._held_experts

    def held(xt, experts, weights, wg, wu, wd, first, count):
        return orig(xt, experts, weights, wg, wu, wd, first, count - 1)
    return "_held_experts", held


def _drop_reported(moe):
    orig = moe._held_experts

    def held(*args):
        out, rows, dropped = orig(*args)
        return out, rows, dropped + 1
    return "_held_experts", held


def _rope_left_out(blocks):
    return "apply_rope", lambda x, positions, theta=10000.0: x


def _update_not_applied(train_step):
    """AdamW's moments move, the parameters stay."""
    orig = train_step.adamw_update

    def update(grads, state, params, *args):
        _, state, metrics = orig(grads, state, params, *args)
        return params, state, metrics
    return "adamw_update", update


def _bias_sign_flipped(lm):
    orig = lm.bias_step
    return "bias_step", lambda bias, load: 2 * bias - orig(bias, load)


@pytest.mark.parametrize("module,fault", [
    ("repro.models.moe", _expert_left_out),
    ("repro.models.moe", _drop_reported),
    ("repro.models.blocks", _rope_left_out),
    ("repro.runtime.train_step", _update_not_applied),
    ("repro.models.lm", _bias_sign_flipped),
], ids=["expert_left_out", "drop_reported", "rope_left_out",
        "update_not_applied", "bias_sign_flipped"])
def test_fault_is_caught(monkeypatch, module, fault):
    import importlib
    mod = importlib.import_module(module)
    name, patched = fault(mod)
    monkeypatch.setattr(mod, name, patched)
    res = drive(CELL)
    assert res["checks"] and not res["correct"], res["checks"]
