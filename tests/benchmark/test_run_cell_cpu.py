"""A whole run of the chip benchmark's harness at smoke size on the CPU,
with the harness's look for a chip skipped: the served path as the window
drives it, the result line, and ``correct`` coming out false for each
fault the cells can have, planted in the program underneath, and for the
control (the reference in bfloat16 in the program's place)."""
import json
import pathlib
import sys

import numpy as np
import pytest

CHIP = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import calibrate  # noqa: E402
import run_cell  # noqa: E402
from cellbench import correctness, model, spec, work  # noqa: E402

SMOKE = {"name": "resnet-smoke", "model": "resnet_basic",
         "stage_blocks": [1, 1], "widths": [8, 16], "n_classes": 10,
         "in_hw": 16, "in_channels": 3}
# each smoke configuration takes the plan, check and limits of the
# benchmark configuration it stands in for
STANDS_IN = {"hb8": "resnet18-cifar10-hb8", "exact": "resnet18-cifar10-exact"}
TRAFFIC = {"batch": 2, "pool_requests": 2, "scale_for_k": 21, "tenant": "t"}


def _smoke(kind):
    full = spec.config(STANDS_IN[kind])
    plan = full["plan"] if full["plan"] == "exact" else full["plan"][:3]
    return dict(SMOKE, plan=plan, limits=full["limits"], check=full["check"])


@pytest.fixture(params=sorted(STANDS_IN))
def smoke_cell(request, monkeypatch):
    import jax

    cfg = _smoke(request.param)
    cell = {"name": "smoke.b2", "config": "smoke", "traffic": "b2",
            "chips": 1}
    bench = {"workloads": [cell],
             "end_to_end": [{"name": n, "unit": u} for n, u in
                            (("images_per_s", "images/s"),
                             ("latency_p50_s", "s"),
                             ("setup_s", "s"))],
             "per_layer": [{"name": m["name"], "unit": m["unit"]}
                           for m in spec.load_benchmark()["per_layer"]]}
    monkeypatch.setattr(spec, "load_benchmark", lambda: bench)
    monkeypatch.setattr(spec, "config", lambda name: cfg)
    monkeypatch.setattr(spec, "traffic", lambda name: TRAFFIC)
    v5e = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda kind: v5e)
    monkeypatch.setattr(run_cell, "start_jax",
                        lambda chips: (jax, jax.devices()))
    return cfg


def _run(capsys, trace=0):
    rc = run_cell.main(["--workload", "smoke.b2", "--seed", str(2 ** 31 + 9),
                        "--seconds", "0.05", "--trace", str(trace)])
    out = capsys.readouterr()
    assert rc == 0
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_sound_run_is_correct(smoke_cell, capsys):
    res, err = _run(capsys)
    assert res["correct"] is True, res
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"images_per_s", "latency_p50_s",
                                   "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(smoke_cell["limits"])
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert err.strip().splitlines()[-1].startswith("check logit_")


@pytest.mark.parametrize("smoke_cell", ["hb8"], indirect=True)
def test_traced_run_reads_per_layer_metrics(smoke_cell, capsys):
    res, _ = _run(capsys, trace=1)
    assert res["correct"] is True
    m = res["metrics"]
    for name in ("frontend_overhead_ms", "round_wall_ms",
                 "fused_rounds_per_request", "exchange_mb_per_image", "mfu"):
        assert name in m and m[name]["value"] > 0, name
    assert res["device"]["window_s"] > 0
    assert "breakdown" in res


def _exchange_left_out(monkeypatch):
    from repro.core import comm
    monkeypatch.setattr(comm.SimComm, "swap", lambda self, x: x)


def _relu_returns_input(monkeypatch):
    from repro.core import hummingbird
    monkeypatch.setattr(hummingbird.HBLayer, "is_identity",
                        property(lambda self: True))


def _answer_altered(monkeypatch):
    from repro.core import mpc_tensor
    orig = mpc_tensor.MPCTensor.reveal

    def reveal(self):
        y = orig(self)
        return y.at[0, 0].add(1.0)
    monkeypatch.setattr(mpc_tensor.MPCTensor, "reveal", reveal)


def _half_batch_left_out(monkeypatch):
    from repro.core import mpc_tensor
    orig = mpc_tensor.MPCTensor.reveal

    def reveal(self):
        y = orig(self)
        half = y.shape[0] // 2
        return y.at[half:].set(y[:half])
    monkeypatch.setattr(mpc_tensor.MPCTensor, "reveal", reveal)


@pytest.mark.parametrize("fault", [_exchange_left_out, _relu_returns_input,
                                   _answer_altered, _half_batch_left_out])
def test_fault_makes_run_incorrect(smoke_cell, capsys, monkeypatch, fault):
    fault(monkeypatch)
    res, err = _run(capsys)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("seed", [5, 2 ** 32 + 6])
def test_control_fails_the_limit(smoke_cell, seed):
    """The control in the program's place reads above a limit: the
    reference in bfloat16 on the exact ring, one draw of the reference with
    every window one bit narrower on the reduced ring."""
    cfg = smoke_cell
    params = model.make_params(seed, cfg)
    raw = model.make_images(seed, 6, cfg)
    x = np.asarray(model.on_grid(
        raw * model.theorem1_scale(cfg, params, raw, 21)))
    ref = correctness.reference_for(cfg, params, x, seed)
    ctl = calibrate.control_logits(cfg, params, x, seed)
    got = correctness.compare(cfg, ctl, ref)
    assert any(v["value"] > v["limit"] for v in got.values()), got
