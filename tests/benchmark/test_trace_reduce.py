"""The chip benchmark's reduction from a profiler trace to metrics, on a
small synthetic trace and on one recorded on the CPU: idle share as the
union of intervals, device time by program, idle gaps by host span."""
import pathlib
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

from cellbench import model, spec, tracing, work  # noqa: E402

def _trace():
    dev = "/device:TPU:0"
    ops = [
        # overlapping ops: union [100, 400) is 300 ns, not 350
        tracing.DeviceOp(dev, "fusion.1", 100, 200),
        tracing.DeviceOp(dev, "fusion.2", 250, 150),
        tracing.DeviceOp(dev, "custom-call.3", 500, 100, "jit__ks_mask_jit"),
        tracing.DeviceOp(dev, "custom-call.1", 700, 50,
                         "jit__beaver_and_jit"),
        # outside the window: clipped away
        tracing.DeviceOp(dev, "fusion.9", 2000, 100),
    ]
    spans = [tracing.HostSpan("client.request", 0, 600),
             tracing.HostSpan("client.request", 600, 1000)]
    return tracing.Trace(ops, spans)


def test_union_of_intervals():
    assert tracing.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert tracing.union_ns([]) == 0
    assert tracing.union_ns([(0, 10), (0, 10)]) == 10


def test_gaps_cover_the_rest():
    assert tracing.gaps([(2, 4), (3, 6), (8, 9)], 0, 10) == \
        [(0, 2), (6, 8), (9, 10)]


def test_window_busy_and_idle_share():
    t = _trace()
    assert t.window == (0, 1000)
    # busy: [100,400) + [500,600) + [700,750) = 450 ns
    assert t.busy_s == pytest.approx(450e-9)
    idle = spec.metric_reader("device_idle_share").read({"trace": t})
    assert idle == pytest.approx(55.0)


def test_busy_averages_over_devices():
    ops = [tracing.DeviceOp("/device:TPU:0", "a", 0, 100),
           tracing.DeviceOp("/device:TPU:1", "a", 0, 50)]
    t = tracing.Trace(ops, [], window=(0, 100))
    assert t.busy_s == pytest.approx(75e-9)


def test_device_time_by_program():
    t = _trace()
    top = dict(t.top_ops())
    # ops outside any program are named by themselves; the window does
    # not clip this table
    assert top["jit__ks_mask_jit"] == pytest.approx(100e-9)
    assert top["jit__beaver_and_jit"] == pytest.approx(50e-9)
    assert top["fusion.1"] == pytest.approx(200e-9)
    assert [v for _, v in t.top_ops()] == sorted(top.values(), reverse=True)


def test_idle_gaps_labelled_by_host_span():
    t = _trace()
    gaps = t.idle_gaps()
    # gaps: [0,100) [400,500) [600,700) [750,1000)
    assert gaps[0] == ("client.request", pytest.approx(250e-9))
    assert len(gaps) == 4
    bare = tracing.Trace(t.ops, [], window=(0, 1000))
    assert bare.idle_gaps()[0][0] == "unattributed"
    b = t.breakdown()
    assert b["device_ops"][0][0] == "fusion.1"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def _run(requests, batch=1, images_per_s=0.1):
    return {"requests": requests, "batch": batch,
            "images_per_s": images_per_s, "cell": {"chips": 1},
            "config": spec.config("resnet18-cifar10-hb8"),
            "peaks": work.peaks("TPU v5 lite"), "trace": None}


def test_response_readers():
    reqs = [{"latency_s": 12.5, "response": {
        "wall_s": 12.0, "batch": {"measured_rounds": 120,
                                  "measured_bytes": 30e6, "n_requests": 1}}},
            {"latency_s": 13.0, "response": {
                "wall_s": 12.2, "batch": {"measured_rounds": 120,
                                          "measured_bytes": 30e6,
                                          "n_requests": 1}}}]
    run = _run(reqs)
    read = lambda n: spec.metric_reader(n).read(run)  # noqa: E731
    assert read("frontend_overhead_ms") == pytest.approx(650.0)
    assert read("round_wall_ms") == pytest.approx(1e3 * 12.1 / 120)
    assert read("fused_rounds_per_request") == 120
    assert read("exchange_mb_per_image") == pytest.approx(30.0)
    ops = 4 * model.family(run["config"]).macs_per_image(run["config"])
    assert read("mfu") == pytest.approx(100 * ops * 0.1 / 393e12)


def test_load_reads_a_recorded_trace(tmp_path):
    """A trace the JAX profiler writes on the CPU: the benchmark's host
    spans set the window; a CPU has no device plane, so nothing is busy."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        with jax.profiler.TraceAnnotation("client.request"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tracing.load(str(tmp_path), window_host_span="client.request")
    assert len(t.spans) == 2
    assert t.window == (t.spans[0].start_ns, t.spans[-1].end_ns)
    assert t.window_s > 0
    assert t.ops == [] and t.busy_s == 0.0
    assert spec.metric_reader("device_idle_share").read({"trace": t}) \
        is None
