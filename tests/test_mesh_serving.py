"""Mesh-native round-fused serving (PR 4).

The coalesced ``run_streams`` replay executes *inside* ``shard_map`` over
the party axis with ``MeshComm`` as the ``CoalescingComm`` base, so one
fused protocol round = one ``lax.ppermute`` of one flattened uint32
buffer.  Three layers of validation:

- backend parity: ``MeshComm`` swap/``party_is``/``party_slice`` match
  ``SimComm`` under ``shard_map``, and a ``CoalescingComm`` flush over
  the mesh base returns bit-identical per-handle payloads;
- serving parity: ``PrivateModel.serve_step(mesh)`` is bit-identical to
  the SimComm replay on the same shares/triples (smoke mesh in-process;
  a real two-party axis in a 2-device subprocess);
- HLO-vs-costmodel: the compiled step's collective-permute census
  (``runtime.hlo_analyzer.collective_census``) equals
  ``core.schedule``'s predicted ``(n_rounds, round_bytes)`` exactly —
  count for count, payload for payload, in program order.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import api
from repro.configs import RESNET_SMOKE
from repro.core import beaver, comm as comm_lib, fixed, gmw, ring, shares
from repro.core.hummingbird import HBConfig, HBLayer
from repro.launch.mesh import make_mpc_smoke_mesh, make_smoke_mesh
from repro.models import resnet


# ---------------------------------------------------------------------------
# Backend parity on the 1-device smoke mesh (party axis size 1: both party
# rows on one shard, exchanges degenerate to the sim backend's local flip)
# ---------------------------------------------------------------------------

def _smoke_shard_map(fn, n_out: int = 1):
    mesh = make_mpc_smoke_mesh()
    spec = P("party")
    return jax.shard_map(fn, mesh=mesh, in_specs=spec,
                         out_specs=(spec,) * n_out if n_out > 1 else spec,
                         check_vma=False)


def test_meshcomm_swap_matches_simcomm_on_smoke_mesh():
    x = jax.random.bits(jax.random.PRNGKey(0), (2, 3, 5), dtype=jnp.uint32)
    want = comm_lib.SimComm().swap(x)
    got = _smoke_shard_map(
        lambda a: comm_lib.MeshComm("party", 1).swap(a))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_meshcomm_party_is_and_slice_match_simcomm_on_smoke_mesh():
    x = jax.random.bits(jax.random.PRNGKey(1), (2, 4), dtype=jnp.uint32)
    sim = comm_lib.SimComm()

    def body(a):
        mc = comm_lib.MeshComm("party", 1)
        mask = jnp.broadcast_to(mc.party_is(1, a), a.shape)
        return mask.astype(jnp.uint32), mc.party_slice(a)

    got_mask, got_slice = _smoke_shard_map(body, n_out=2)(x)
    want_mask = jnp.broadcast_to(sim.party_is(1, x), x.shape)
    np.testing.assert_array_equal(np.asarray(got_mask),
                                  np.asarray(want_mask.astype(jnp.uint32)))
    np.testing.assert_array_equal(np.asarray(got_slice), np.asarray(x))


def test_coalescing_flush_over_meshcomm_bit_identical_to_sim():
    """One flattened flush over the mesh base hands every enqueuer back
    exactly the payload the sim base would have."""
    key = jax.random.PRNGKey(2)
    payloads = [
        jax.random.bits(k, shape, dtype=jnp.uint32)
        for k, shape in zip(jax.random.split(key, 3),
                            [(2, 7), (2, 3, 5), (2, 11)])
    ]

    def run(comm_factory):
        def body(a, b, c):
            cc = comm_lib.CoalescingComm(comm_factory())
            ha, hb_, hc = cc.enqueue(a), cc.enqueue(b), cc.enqueue(c)
            opened = cc.flush()
            return opened[ha], opened[hb_], opened[hc]
        return body

    want = run(comm_lib.SimComm)(*payloads)
    mesh = make_mpc_smoke_mesh()
    got = jax.shard_map(run(lambda: comm_lib.MeshComm("party", 1)),
                        mesh=mesh, in_specs=(P("party"),) * 3,
                        out_specs=(P("party"),) * 3,
                        check_vma=False)(*payloads)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_meshcomm_rejects_indivisible_axis():
    with pytest.raises(ValueError, match="divide"):
        comm_lib.MeshComm("party", 3)


# ---------------------------------------------------------------------------
# Mesh-native serve_step on the smoke mesh (in-process, 1 device)
# ---------------------------------------------------------------------------

def _smoke_model():
    params = resnet.init(jax.random.PRNGKey(0), RESNET_SMOKE)

    def afn(p, v, relu_fn=None):
        return resnet.apply(p, v, RESNET_SMOKE, relu_fn=relu_fn)

    plan = api.trace_plan(afn, params, (2, 3, 8, 8), name="smoke")
    hb = HBConfig(tuple([HBLayer(k=21, m=13)] * (plan.n_groups - 1)
                        + [HBLayer(k=13, m=13)]),   # last group culled
                  plan.group_elements)
    model = api.compile(afn, params, RESNET_SMOKE, plan.with_hb(hb),
                        api.Session(key=0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 8, 8)) * 0.5
    X = model.encrypt(jax.random.PRNGKey(2), x)
    pool = beaver.gen_plan_triples(jax.random.PRNGKey(3),
                                   model.plan.triple_specs())
    return model, params, x, X, pool


def test_mesh_serve_step_bit_identical_to_sim_on_smoke_mesh():
    model, params, x, X, pool = _smoke_model()
    key = jax.random.PRNGKey(4)
    sim_lo, sim_hi = model.serve_step()(params, X.data.lo, X.data.hi, pool,
                                        key)
    mesh_step = model.serve_step(make_mpc_smoke_mesh())
    m_lo, m_hi = jax.jit(mesh_step)(params, X.data.lo, X.data.hi, pool, key)
    np.testing.assert_array_equal(np.asarray(m_lo), np.asarray(sim_lo))
    np.testing.assert_array_equal(np.asarray(m_hi), np.asarray(sim_hi))
    served = fixed.decode_np(shares.reconstruct(ring.Ring64(m_lo, m_hi)))
    want = np.argmax(np.asarray(model.plaintext(x)), -1)
    assert (np.argmax(served, -1) == want).all()


def test_mesh_serve_step_requires_triple_pool():
    model, params, _, X, _ = _smoke_model()
    step = model.serve_step(make_mpc_smoke_mesh())
    with pytest.raises(ValueError, match="triple pool"):
        step(params, X.data.lo, X.data.hi, None, jax.random.PRNGKey(0))


def test_mesh_serve_step_rejects_party_axis_free_mesh():
    model = _smoke_model()[0]
    with pytest.raises(ValueError, match="party"):
        model.serve_step(make_smoke_mesh())


# ---------------------------------------------------------------------------
# HLO-vs-costmodel + real two-party exchange (2-device subprocess: the main
# test process keeps the default single CPU device, matching conftest)
# ---------------------------------------------------------------------------

_TWO_PARTY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro import api
from repro.configs import RESNET_SMOKE
from repro.core import beaver, comm as comm_lib, fixed, gmw, ring, \
    schedule as schedule_lib, shares
from repro.core.hummingbird import HBConfig, HBLayer
from repro.models import resnet
from repro.runtime.hlo_analyzer import collective_census

assert jax.device_count() >= 2

# -- 1. multi-group relu_many step: census == schedule, bit-identical -------
for cone in (False, True):
    specs = [(256, 64, 0), (256, 21, 13), (128, 21, 13), (128, 20, 14)]
    keys = [jax.random.PRNGKey(40 + i) for i in range(len(specs))]
    rng = np.random.default_rng(0)
    Xs, trs = [], []
    for i, (n, k, m) in enumerate(specs):
        x = rng.uniform(-3.5, 3.5, n).astype(np.float32)
        Xs.append(shares.share(jax.random.PRNGKey(50 + i),
                               fixed.encode_np(x)))
        trs.append(beaver.gen_relu_triples(jax.random.PRNGKey(60 + i), n,
                                           k - m, cone=cone))
    kms = [(k, m) for _, k, m in specs]
    mesh = jax.make_mesh((2,), ("party",), axis_types=(AxisType.Auto,))

    def replay(lo_list, hi_list, triples):
        cc = comm_lib.CoalescingComm(comm_lib.MeshComm("party", 2))
        xs = [ring.Ring64(lo, hi) for lo, hi in zip(lo_list, hi_list)]
        outs = gmw.relu_many(keys, xs, triples, cc, kms, cone=cone)
        return [o.lo for o in outs], [o.hi for o in outs]

    party = P("party")
    n_g = len(specs)
    fused = jax.shard_map(replay, mesh=mesh,
                          in_specs=([party] * n_g, [party] * n_g,
                                    beaver.pool_party_specs(trs)),
                          out_specs=([party] * n_g, [party] * n_g),
                          check_vma=False)
    compiled = jax.jit(fused).lower([x.lo for x in Xs], [x.hi for x in Xs],
                                    trs).compile()
    census = collective_census(compiled.as_text())
    sched = schedule_lib.simulate([(n, k - m, (n, k, m)) for n, k, m in specs],
                                  cone=cone)
    assert all(c.count == 1 for c in census), census
    assert len(census) == sched.n_rounds, (cone, len(census), sched.n_rounds)
    assert [c.bytes for c in census] == list(sched.round_bytes), (
        cone, [c.bytes for c in census], sched.round_bytes)

    los, his = compiled([x.lo for x in Xs], [x.hi for x in Xs], trs)
    sim = gmw.relu_many(keys, Xs, trs, comm_lib.SimComm(), kms, cone=cone)
    for o, lo, hi in zip(sim, los, his):
        np.testing.assert_array_equal(np.asarray(o.lo), np.asarray(lo))
        np.testing.assert_array_equal(np.asarray(o.hi), np.asarray(hi))
    print(json.dumps({"cone": cone, "rounds": len(census),
                      "bytes": int(sum(c.bytes for c in census))}))

# -- 2. whole-network serve step: the compiled artifact IS the timeline ----
params = resnet.init(jax.random.PRNGKey(0), RESNET_SMOKE)

def afn(p, v, relu_fn=None):
    return resnet.apply(p, v, RESNET_SMOKE, relu_fn=relu_fn)

plan = api.trace_plan(afn, params, (2, 3, 8, 8), name="smoke")
plan = plan.with_hb(HBConfig(
    tuple([HBLayer(k=21, m=13)] * (plan.n_groups - 1)
          + [HBLayer(k=13, m=13)]), plan.group_elements))
model = api.compile(afn, params, RESNET_SMOKE, plan, api.Session(key=0))
x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 8, 8)) * 0.5
X = model.encrypt(jax.random.PRNGKey(2), x)
pool = beaver.gen_plan_triples(jax.random.PRNGKey(3), plan.triple_specs())
key = jax.random.PRNGKey(4)

from repro.launch.mesh import make_mpc_mesh
mesh = make_mpc_mesh()          # (2, 1) on the forced 2-device topology
step = model.serve_step(mesh)
compiled = jax.jit(step).lower(params, X.data.lo, X.data.hi, pool,
                               key).compile()
census = collective_census(compiled.as_text())
sched = model.schedule()
assert len(census) == sched.n_rounds, (len(census), sched.n_rounds)
assert [c.bytes for c in census] == list(sched.round_bytes)

m_lo, m_hi = compiled(params, X.data.lo, X.data.hi, pool, key)
s_lo, s_hi = model.serve_step()(params, X.data.lo, X.data.hi, pool, key)
np.testing.assert_array_equal(np.asarray(m_lo), np.asarray(s_lo))
np.testing.assert_array_equal(np.asarray(m_hi), np.asarray(s_hi))
print(json.dumps({"model_rounds": len(census),
                  "model_bytes": int(sum(c.bytes for c in census))}))
print("TWO_PARTY_OK")
"""


def test_two_party_hlo_census_matches_schedule_and_sim():
    """Acceptance: on a party axis of size 2, the compiled HLO of the
    multi-group relu_many serve step contains exactly the
    schedule-predicted number of collective-permutes with matching
    per-collective bytes, and the mesh replay's outputs are bit-identical
    to the SimComm replay on the same shares/triples."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _TWO_PARTY_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    assert "TWO_PARTY_OK" in out.stdout
