"""The served path's spans (``runtime/spans.py``) in a profile captured on
the CPU around one HTTP request to the smoke ResNet, on the generator
round loop the benchmark's ResNet-18 cells run: every name of the
vocabulary is recorded, the round spans match the engine's round count,
the ReLU phase spans nest in the local steps and never hold an exchange,
and the response carries the engine's queue wait and execute time.
Outputs with the profiler off are covered by the bit-identity tests."""
import glob
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, serve
from repro.configs import RESNET_SMOKE
from repro.core import beaver, comm as comm_lib, gmw, ring, shares
from repro.core.hummingbird import HBConfig, HBLayer
from repro.models import resnet
from repro.runtime import spans


def _events(trace_dir):
    """Host events of the profile: (name, start, end, thread, stats)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    data = ProfileData.from_file(path[-1])
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in spans.NAMES or ev.name == "client.request":
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, thread,
                                dict(ev.stats)))
    return out


def _inside(inner, outer):
    return (inner[3] == outer[3] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


def _relu_calls():
    """ReLU calls of one smoke ResNet request: the stem, then two per
    basic block (after its first convolution, after the residual add)."""
    return 1 + 2 * sum(RESNET_SMOKE.stage_blocks)


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    params = resnet.init(jax.random.PRNGKey(0), RESNET_SMOKE)

    def afn(p, v, relu_fn=None):
        return resnet.apply(p, v, RESNET_SMOKE, relu_fn=relu_fn)

    plan = api.trace_plan(afn, params, (1, 3, 8, 8), name="smoke")
    plan = plan.with_hb(HBConfig((HBLayer(k=21, m=13),) * plan.n_groups,
                                 plan.group_elements))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (1, 3, 8, 8))
                   * 0.5, np.float32)
    body = json.dumps({"tenant": "alice", "x": x.tolist()}).encode()
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("HB_ROUND_LOOP", "python")
        engine = serve.InferenceEngine(afn, params, RESNET_SMOKE, plan,
                                       api.Session(key=0))
        frontend = serve.Frontend(engine)
        host, port = frontend.serve_background("127.0.0.1", 0)

        def post():
            req = urllib.request.Request(f"http://{host}:{port}/infer",
                                         data=body, method="POST")
            with urllib.request.urlopen(req, timeout=600) as resp:
                return json.loads(resp.read())

        try:
            post()                            # warm-up: compiles, traces
            jax.profiler.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation("client.request"):
                resp = post()
            jax.profiler.stop_trace()
        finally:
            frontend.close()
    return resp, _events(trace_dir)


def test_every_span_of_the_vocabulary_is_recorded(profiled):
    _, events = profiled
    assert set(spans.NAMES) <= {e[0] for e in events}
    request = [e for e in events if e[0] == "client.request"]
    assert len(request) == 1
    assert all(request[0][1] <= e[1] and e[2] <= request[0][2]
               for e in events)


def test_round_spans_match_the_measured_rounds(profiled):
    resp, events = profiled
    rounds = resp["batch"]["measured_rounds"]
    (execute,) = [e for e in events if e[0] == "engine.execute"]
    exchanges = [e for e in events if e[0] == "round.exchange"]
    assert rounds > 0 and len(exchanges) == rounds
    assert all(_inside(e, execute) for e in exchanges)
    # one local step per round, plus each ReLU call's (single) stream's
    # step before its first exchange
    calls = [e for e in events if e[0] == "relu.layer"]
    locals_ = [e for e in events if e[0] == "round.local"]
    assert len(calls) == _relu_calls()
    assert len(locals_) == rounds + len(calls)
    assert all(any(_inside(e, c) for c in calls)
               for e in exchanges + locals_)
    assert {c[4]["group"] for c in calls} == set(range(3))
    assert all(c[4]["elements"] > 0 for c in calls)


def test_relu_phase_spans_nest_in_local_steps(profiled):
    _, events = profiled
    locals_ = [e for e in events if e[0] == "round.local"]
    exchanges = [e for e in events if e[0] == "round.exchange"]
    phases = [e for e in events if e[0] in (
        "relu.a2b", "relu.adder", "relu.b2a", "relu.mult")]
    assert {e[0] for e in phases} == {"relu.a2b", "relu.adder", "relu.b2a",
                                      "relu.mult"}
    assert all(any(_inside(p, loc) for loc in locals_) for p in phases)
    assert not any(_inside(x, p) for x in exchanges for p in phases)
    linear = [e for e in events if e[0] == "linear"]
    for child in ("linear.encode", "linear.matmul", "linear.truncate"):
        kids = [e for e in events if e[0] == child]
        assert kids and all(any(_inside(k, lin) for lin in linear)
                            for k in kids)


def test_spans_carry_the_request_and_the_response_its_counters(profiled):
    resp, events = profiled
    (execute,) = [e for e in events if e[0] == "engine.execute"]
    assert str(resp["id"]) in str(execute[4]["request_ids"])
    (submit,) = [e for e in events if e[0] == "engine.submit"]
    assert submit[4]["request_id"] == resp["id"]
    batch = resp["batch"]
    assert batch["queue_s"] >= 0.0
    assert 0.0 < batch["execute_s"] <= resp["wall_s"]
    assert batch["execute_s"] == pytest.approx(
        (execute[2] - execute[1]) / 1e9, rel=0.05, abs=0.005)


def test_relu_scan_device_ops_carry_the_phase_names():
    """The compiled backend: the phases of a ReLU traced into one jitted
    program carry the host spans' names as name scopes."""
    n, k, m = 64, 21, 13
    x = shares.share(jax.random.PRNGKey(0),
                     ring.from_int32(jnp.arange(n, dtype=jnp.int32) - 32))
    tri = beaver.gen_relu_triples(jax.random.PRNGKey(1), n, k - m)

    def f(key, lo, hi, tri):
        out = gmw.relu_scan(key, ring.Ring64(lo, hi), tri,
                            comm_lib.SimComm(), k=k, m=m)
        return out.lo, out.hi

    text = jax.jit(f).lower(jax.random.PRNGKey(2), x.lo, x.hi,
                            tri).as_text(debug_info=True)
    for name in ("relu.a2b", "relu.adder", "relu.b2a", "relu.mult"):
        assert name in text, name
