"""The chip benchmark's harness as data: every configuration, traffic mix,
metric reader and model family is found by name, a new file is picked up
without an edit, and the work counts match hand counts."""
import json
import pathlib
import shutil
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

from cellbench import client, model, spec, work  # noqa: E402

BENCH = spec.load_benchmark()

SMOKE = {"model": "resnet_basic", "stage_blocks": [1, 1], "widths": [8, 16],
         "n_classes": 10, "in_hw": 16, "in_channels": 3}
FAMILY_API = ("init_params", "forward", "input_shape", "macs_per_image",
              "relu_elements_per_image", "engine")


@pytest.mark.parametrize("name", spec.names("configs"))
def test_config_file_loads(name):
    cfg = spec.config(name)
    for key in ("model", "source", "stage_blocks", "widths", "n_classes",
                "in_hw", "in_channels", "plan", "reduced", "assumed",
                "limits"):
        assert key in cfg, key
    groups = model.family(cfg).relu_elements_per_image(cfg)
    assert sum(groups) == cfg["relu_elements_per_image"]
    assert len(groups) == cfg["relu_groups"] == len(model.plan_groups(cfg))


@pytest.mark.parametrize("name", spec.names("traffic"))
def test_traffic_file_loads(name):
    t = spec.traffic(name)
    assert int(t["batch"]) >= 1 and int(t["pool_requests"]) >= 1


@pytest.mark.parametrize("name", spec.names("metrics"))
def test_metric_reader_loads(name):
    assert callable(spec.metric_reader(name).read)


@pytest.mark.parametrize("name", spec.names("models"))
def test_model_family_loads(name):
    fam = spec.model_family(name)
    for fn in FAMILY_API:
        assert callable(getattr(fam, fn)), fn


def test_unknown_model_family_raises():
    with pytest.raises(KeyError):
        model.family(dict(SMOKE, model="no-such-family"))


def test_benchmark_names_resolve():
    every = {m["name"] for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    assert every == set(spec.names("metrics"))
    for w in BENCH["workloads"]:
        spec.config(w["config"])
        spec.traffic(w["traffic"])
        assert spec.workload(BENCH, w["name"]) is w
    for c in BENCH["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        assert json.loads((spec.ROOT / c["file"]).read_text())["source"] \
            == c["source"]


def test_cell_metrics_by_trace():
    cell = BENCH["workloads"][0]["name"]
    e2e = {m["name"] for m in spec.cell_metrics(BENCH, cell, trace=False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.cell_metrics(BENCH, cell, trace=True)


def test_new_files_are_found_without_edits(tmp_path, monkeypatch):
    root = tmp_path / "chip"
    shutil.copytree(CHIP / "configs", root / "configs")
    shutil.copytree(CHIP / "traffic", root / "traffic")
    shutil.copytree(CHIP / "metrics", root / "metrics")
    shutil.copytree(CHIP / "models", root / "models")
    (root / "traffic" / "b2.json").write_text(json.dumps(
        {"batch": 2, "pool_requests": 4, "scale_for_k": 21, "tenant": "t"}))
    (root / "metrics" / "extra_metric.py").write_text(
        "def read(run):\n    return 7.0\n")
    (root / "models" / "extra_family.py").write_text(
        "def macs_per_image(cfg):\n    return 5\n")
    (root / "configs" / "extra.json").write_text(json.dumps(
        dict(SMOKE, model="extra_family")))
    monkeypatch.setattr(spec, "HERE", root)
    assert "b2" in spec.names("traffic")
    assert spec.traffic("b2")["batch"] == 2
    assert spec.metric_reader("extra_metric").read({}) == 7.0
    assert "extra_family" in spec.names("models")
    assert work.step_ops_per_image(spec.config("extra")) == 20
    with pytest.raises(KeyError):
        spec.traffic("no-such-mix")


def test_macs_per_image_by_hand():
    # stem 8*3*9*256; stage 0 (16x16, 8->8): 2 convs 8*8*9*256;
    # stage 1 (8x8, 8->16, stride 2): 16*8*9*64 + 16*16*9*64 + proj
    # 16*8*64; head 16*10
    hand = (8 * 3 * 9 * 256 + 2 * 8 * 8 * 9 * 256
            + 16 * 8 * 9 * 64 + 16 * 16 * 9 * 64 + 16 * 8 * 64 + 16 * 10)
    assert model.family(SMOKE).macs_per_image(SMOKE) == hand
    assert work.step_ops_per_image(SMOKE) == 4 * hand


def test_resnet18_counts():
    cfg = spec.config("resnet18-cifar10-hb8")
    fam = model.family(cfg)
    assert fam.relu_elements_per_image(cfg) == \
        (65536, 262144, 131072, 65536, 32768)
    assert abs(fam.macs_per_image(cfg) / 1e6 - 555.4) < 1.0


def test_parameters_match_the_config():
    import jax

    cfg = spec.config("resnet18-cifar10-exact")
    shapes = jax.eval_shape(lambda k: model.family(cfg).init_params(k, cfg),
                            jax.random.key(0))
    # BatchNorm's running mean and variance are statistics, not parameters
    trained = [a for path, a in jax.tree_util.tree_leaves_with_path(shapes)
               if getattr(path[-1], "key", None) not in ("mean", "var")]
    assert sum(a.size for a in trained) == cfg["parameters"]


def test_plan_groups_from_the_family():
    exact = model.plan_groups(dict(SMOKE, plan="exact"))
    assert exact == [{"k": 64, "m": 0}] * 3
    assert not model.is_stochastic(exact)
    hb = model.plan_groups(dict(SMOKE, plan=[{"k": 21, "m": 13}] * 3))
    assert model.is_stochastic(hb)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks("TPU v99 imaginary")
    assert work.peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12


def test_seed_handling_beyond_32_bits():
    a = model.base_key(2 ** 31 + 12345)
    b = model.base_key(2 ** 33 + 7)
    assert a.shape == (2,) and not (a == b).all()
    assert 0 <= model.derived_int(2 ** 33 + 7, 3) < 2 ** 31


def _fake_clock():
    t = [0.0]

    def clock():
        return t[0]

    def advance(dt):
        t[0] += dt
    return clock, advance


def test_closed_loop_window():
    clock, advance = _fake_clock()
    sent = []

    def send(i):
        sent.append(i)
        advance(0.4)
        return {"wall_s": 0.3}

    w = client.run_window(send, {"pool_requests": 3}, 1.0, clock=clock)
    # sends at 0.0, 0.4, 0.8; the last issued completes at 1.2
    assert [r["i"] for r in w["requests"]] == [0, 1, 2]
    assert sent == [0, 1, 2]
    assert w["end"] - w["start"] == pytest.approx(1.2)
    assert all(r["latency_s"] == pytest.approx(0.4) for r in w["requests"])


def test_failed_request_is_counted():
    def send(i):
        raise RuntimeError("boom")

    w = client.run_window(send, {"pool_requests": 2}, 0.01)
    assert w["requests"] and all("error" in r and "response" not in r
                                 for r in w["requests"])


def test_compile_watch_counts_by_phase():
    import jax
    import jax.numpy as jnp

    from cellbench import compiles

    watch = compiles.Watch()
    watch.phase = "warm-up 0"
    f = jax.jit(lambda x: x * 3 + 1)
    f(jnp.ones(7)).block_until_ready()
    watch.phase = "window"
    f(jnp.ones(7)).block_until_ready()
    assert watch.counts["warm-up 0"]["traces"] >= 1
    assert watch.counts["warm-up 0"]["compiles"] >= 1
    assert watch.counts["window"]["traces"] == 0
    assert watch.counts["window"]["compiles"] == 0
    assert "compiles" in watch.summary("warm-up 0")
    assert watch.summary("nothing ran") == "nothing"
