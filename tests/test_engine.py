"""Request-level serving engine (PR 5).

The contract under test, per ISSUE 5's acceptance criteria:

- **Bit-exactness**: for any request mix (shapes, tenants, arrival
  orders), the engine's outputs are bit-identical to serial per-request
  execution on the same shares/triples (default policy: per-request keys
  forked from ``Session.request_key``, per-tenant providers, coalescing
  only).
- **Rounds**: measured fused rounds of every micro-batch equal
  ``core.schedule.simulate_merged``'s prediction exactly and equal
  max-over-requests rounds, not the sum.
- **Reproducibility**: reordering submissions does not change any
  request's output (PRNG forking is by request id, not admission order).
- **Tenancy**: triple consumption is metered per tenant; an over-budget
  request fails its future without executing any protocol round.
- **Data sharding**: ``TriplePool.shard``/``shard_pool`` split triple
  pools per data shard at the bit level (party dim untouched) so
  ``serve_step(mesh, data_axis=...)`` composes with a data axis inside
  ``shard_map``, with the per-shard HLO collective census unchanged.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, errors
from repro.core import MPCTensor, beaver, comm as comm_lib, ring, shares
from repro.core import schedule as schedule_lib
from repro.core.hummingbird import HBConfig, HBLayer
from repro.launch.mesh import make_mpc_smoke_mesh
from repro.serve import BatchPolicy, InferenceEngine

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# A tiny two-ReLU-group model: fast enough for property tests, shaped enough
# (two call sites, ragged batches) to exercise the whole engine
# ---------------------------------------------------------------------------

class TinyCfg:
    name = "tiny-mlp"


def tiny_apply(params, x, relu_fn=None):
    rf = relu_fn if relu_fn is not None else (lambda v, g: jax.nn.relu(v))
    h = rf(x @ params["w1"], 0)
    return rf(h @ params["w2"], 1)


def tiny_forward(params, hs, cfg, relu_fn, comm):
    hs = relu_fn([h.matmul_public(params["w1"]) for h in hs], 0)
    return relu_fn([h.matmul_public(params["w2"]) for h in hs], 1)


api.register_mpc_forward(TinyCfg, tiny_forward)

D_IN, D_HID, D_OUT = 6, 5, 4


@pytest.fixture(scope="module")
def tiny():
    params = {
        "w1": jax.random.normal(jax.random.PRNGKey(0), (D_IN, D_HID)) * 0.4,
        "w2": jax.random.normal(jax.random.PRNGKey(1), (D_HID, D_OUT)) * 0.4,
    }
    plan = api.trace_plan(tiny_apply, params, (2, D_IN), name="tiny")
    plan = plan.with_hb(HBConfig((HBLayer(k=21, m=13), HBLayer(k=21, m=13)),
                                 plan.group_elements))
    return params, plan


def _engine(params, plan, policy=None, **kw):
    return InferenceEngine(tiny_apply, params, TinyCfg(), plan,
                           api.Session(key=0), policy=policy, **kw)


def _request_tensor(i, batch):
    x = jax.random.normal(jax.random.PRNGKey(100 + i), (batch, D_IN))
    return MPCTensor.from_plain(jax.random.PRNGKey(200 + i), x)


def _serial_oracle(params, plan, X, request_id):
    """Serial per-request execution on the same shares/triples: one
    PrivateModel call with the request's forked key and a fresh inline
    provider — what the engine must stay bit-identical to."""
    session = api.Session(key=0)
    model = api.compile(tiny_apply, params, TinyCfg(), plan, session)
    key_iter = iter(jax.random.split(session.request_key(request_id), 256))
    return model._run_streams([X], [key_iter], [beaver.InlineTTP()],
                              comm_lib.CoalescingComm(), params,
                              auto_batch=False)[0]


# ---------------------------------------------------------------------------
# Acceptance: canonical mix — two identical shapes + one ragged shape
# ---------------------------------------------------------------------------

def test_canonical_mix_bit_identical_and_max_over_requests(tiny):
    params, plan = tiny
    engine = _engine(params, plan)
    batches = [2, 2, 3]                       # two identical + one ragged
    Xs = [_request_tensor(i, b) for i, b in enumerate(batches)]
    futs = [engine.submit(t, X) for t, X in zip(["alice", "bob", "alice"],
                                                Xs)]
    outs = [f.result() for f in futs]

    # one micro-batch; measured == simulate_merged prediction, exactly
    assert len(engine.reports) == 1
    rep = engine.reports[0]
    assert rep.n_requests == 3
    sched = schedule_lib.simulate_merged(
        [engine.plan_for_shape((b, D_IN)).call_specs() for b in batches],
        auto_batch=False)
    assert rep.measured_rounds == sched.n_rounds == rep.predicted_rounds
    assert rep.measured_bytes == sched.bytes_tx == rep.predicted_bytes

    # max-over-requests, not the sum: every request replays the same
    # network, so the fused batch pays exactly one request's rounds
    per_request = [engine.plan_for_shape((b, D_IN)).schedule().n_rounds
                   for b in batches]
    assert rep.measured_rounds == max(per_request)
    assert rep.serial_rounds == sum(per_request) > rep.measured_rounds
    assert rep.rounds_saved_ratio == pytest.approx(3.0)

    # bit-identical (share level) to serial per-request execution
    for i, (X, out) in enumerate(zip(Xs, outs)):
        want = _serial_oracle(params, plan, X, i)
        np.testing.assert_array_equal(ring.to_uint64_np(out.data),
                                      ring.to_uint64_np(want.data))


def test_reordered_submissions_do_not_change_outputs(tiny):
    """Randomness regression: a request's output depends on its id, never
    on admission order or on which other requests were in flight."""
    params, plan = tiny
    Xs = {7: _request_tensor(0, 2), 11: _request_tensor(1, 3),
          13: _request_tensor(2, 2)}

    def run(order):
        engine = _engine(params, plan)
        futs = {rid: engine.submit("t", Xs[rid], request_id=rid)
                for rid in order}
        return {rid: ring.to_uint64_np(f.result().data)
                for rid, f in futs.items()}

    a = run([7, 11, 13])
    b = run([13, 7, 11])
    for rid in Xs:
        np.testing.assert_array_equal(a[rid], b[rid])


def test_api_reexports_engine_types():
    import repro.serve as serve

    assert api.InferenceEngine is serve.InferenceEngine
    assert api.BatchPolicy is serve.BatchPolicy
    assert api.RequestFuture is serve.RequestFuture
    with pytest.raises(AttributeError):
        api.NoSuchThing


def test_duplicate_request_id_rejected(tiny):
    params, plan = tiny
    engine = _engine(params, plan)
    engine.submit("t", _request_tensor(0, 2), request_id=3)
    with pytest.raises(ValueError, match="already submitted"):
        engine.submit("t", _request_tensor(1, 2), request_id=3)


# ---------------------------------------------------------------------------
# Property test: random request mixes (hypothesis where available, a
# seeded sweep everywhere — same checker)
# ---------------------------------------------------------------------------

def _check_random_mix(tiny, mix, order):
    """For an arbitrary request mix (batch sizes, tenants) submitted in an
    arbitrary order: engine outputs are bit-identical to serial execution
    and every batch's measured rounds/bytes equal the merged-schedule
    prediction."""
    params, plan = tiny
    engine = _engine(params, plan)
    futs = {}
    for rid in order:
        batch, tenant = mix[rid]
        futs[rid] = engine.submit(tenant, _request_tensor(rid, batch),
                                  request_id=rid)
    outs = {rid: f.result() for rid, f in futs.items()}

    # revealed (indeed share-level) outputs == serial execution
    for rid, (batch, _) in enumerate(mix):
        want = _serial_oracle(params, plan, _request_tensor(rid, batch), rid)
        np.testing.assert_array_equal(ring.to_uint64_np(outs[rid].data),
                                      ring.to_uint64_np(want.data))

    # every executed batch's measured rounds == the simulator's
    # prediction for its merged group set
    for rep in engine.reports:
        sched = schedule_lib.simulate_merged(
            [engine.plan_for_shape(s).call_specs() for s in rep.shapes],
            auto_batch=False)
        assert rep.measured_rounds == sched.n_rounds
        assert rep.measured_bytes == sched.bytes_tx


@pytest.mark.parametrize("seed", range(4))
def test_seeded_random_mix_bit_identical_and_rounds_predicted(tiny, seed):
    rnd = np.random.default_rng(seed)
    mix = [(int(rnd.integers(1, 5)), str(rnd.choice(["a", "b", "c"])))
           for _ in range(int(rnd.integers(1, 6)))]
    order = rnd.permutation(len(mix)).tolist()
    _check_random_mix(tiny, mix, order)


if HAVE_HYPOTHESIS:
    @settings(max_examples=5, deadline=None)
    @given(st.lists(st.tuples(st.integers(1, 4),           # batch size
                              st.sampled_from(["a", "b", "c"])),  # tenant
                    min_size=1, max_size=5),
           st.randoms(use_true_random=False))
    def test_random_mix_bit_identical_and_rounds_predicted(tiny, mix, rnd):
        order = list(range(len(mix)))
        rnd.shuffle(order)                    # random arrival order
        _check_random_mix(tiny, mix, order)


# ---------------------------------------------------------------------------
# Batching policy
# ---------------------------------------------------------------------------

def test_policy_max_batch_splits_queue(tiny):
    params, plan = tiny
    engine = _engine(params, plan, policy=BatchPolicy(max_batch=2))
    futs = [engine.submit("t", _request_tensor(i, 2)) for i in range(5)]
    engine.flush()
    assert [r.n_requests for r in engine.reports] == [2, 2, 1]
    assert all(f.done for f in futs)


def test_policy_min_gain_one_forces_serial_batches(tiny):
    """A gain threshold no merge can meet degenerates to per-request
    batches — the serial baseline expressed as a policy."""
    params, plan = tiny
    engine = _engine(params, plan, policy=BatchPolicy(min_gain=1.0))
    for i in range(3):
        engine.submit("t", _request_tensor(i, 2))
    engine.flush()
    assert [r.n_requests for r in engine.reports] == [1, 1, 1]


def test_poll_respects_deadline_flush_drains(tiny):
    params, plan = tiny
    engine = _engine(params, plan,
                     policy=BatchPolicy(max_wait_s=10.0, max_batch=8))
    engine.submit("t", _request_tensor(0, 2), arrival_s=0.0)
    engine.submit("t", _request_tensor(1, 2), arrival_s=1.0)
    # queue absorbed into one still-open batch, deadline not hit: no run
    assert engine.poll(now_s=5.0) == []
    assert engine.pending == 2
    # head exceeded max_wait_s: the batch closes and runs
    reports = engine.poll(now_s=10.5)
    assert len(reports) == 1 and reports[0].n_requests == 2
    assert engine.pending == 0
    assert reports[0].waits_s == (10.5, 9.5)


def test_merge_identical_one_payload_per_round_reveals_sane(tiny):
    """Opt-in cross-request auto-batching: identical shapes merge into ONE
    protocol stream, so every fused round carries a single payload (the
    CoalescingComm parts counter drops to 1) with rounds/bytes still equal
    to the auto-batched schedule prediction, and the revealed outputs stay
    within the HummingBird approximation's own error of the plaintext."""
    params, plan = tiny
    x = jax.random.normal(jax.random.PRNGKey(42), (2, D_IN))
    X1 = MPCTensor.from_plain(jax.random.PRNGKey(43), x)
    X2 = MPCTensor.from_plain(jax.random.PRNGKey(44), x)

    merged = _engine(params, plan, policy=BatchPolicy(merge_identical=True))
    f1 = merged.submit("a", X1)
    f2 = merged.submit("b", X2)
    out1, out2 = f1.result(), f2.result()
    rep = merged.reports[0]
    assert rep.measured_rounds == rep.predicted_rounds
    assert rep.measured_bytes == rep.predicted_bytes
    # merged prediction uses auto-batched specs: one payload per round
    sched = schedule_lib.simulate_merged(
        [merged.plan_for_shape((2, D_IN)).call_specs()] * 2, auto_batch=True)
    assert rep.measured_rounds == sched.n_rounds
    assert list(merged.comm.round_parts) == [1] * sched.n_rounds
    want = np.asarray(tiny_apply(params, x))
    for out in (out1, out2):
        np.testing.assert_allclose(out.reveal_np(), want, atol=0.6)


def test_pow2_bucketing_pads_and_slices(tiny):
    params, plan = tiny
    engine = _engine(params, plan, policy=BatchPolicy(bucket="pow2"))
    fut = engine.submit("t", _request_tensor(0, 3))    # padded to 4
    out = fut.result()
    assert out.shape == (3, D_OUT)
    assert engine.reports[0].shapes == ((4, D_IN),)
    # batches 3 and 4 share one plan-cache entry (plus the seed plan)
    engine.submit("t", _request_tensor(1, 4))
    engine.flush()
    assert engine.plan_cache_size == 2


def test_pow2_bucket_does_not_reuse_unbucketed_seed_plan(tiny):
    """Regression: a plan traced at a non-power-of-two batch must not be
    served for the padded bucket it maps to — the padded replay has more
    elements, and budgets/predictions sized off the smaller trace would
    let a mid-protocol budget error through."""
    params, plan = tiny
    plan3 = api.trace_plan(tiny_apply, params, (3, D_IN), hb=plan.hb,
                           name="tiny3")
    engine = InferenceEngine(tiny_apply, params, TinyCfg(), plan3,
                             api.Session(key=0),
                             policy=BatchPolicy(bucket="pow2"))
    cached = engine.plan_for_shape((3, D_IN))
    assert tuple(cached.input_shape) == (4, D_IN)      # traced at the bucket
    fut = engine.submit("t", _request_tensor(0, 3))
    assert fut.result().shape == (3, D_OUT)
    assert engine.reports[0].predicted_rounds == engine.reports[0].measured_rounds


def test_fully_culled_plan_batches_without_crashing(tiny):
    """Regression: a zero-round (all-culled) plan has merged latency 0 —
    admission must treat merging as free, not divide by zero."""
    params, plan = tiny
    culled = plan.with_hb(HBConfig((HBLayer(k=0, m=0), HBLayer(k=0, m=0)),
                                   plan.group_elements))
    engine = _engine(params, culled)
    futs = [engine.submit("t", _request_tensor(i, 2)) for i in range(3)]
    outs = [f.result() for f in futs]
    assert all(o is not None for o in outs)
    rep = engine.reports[0]
    assert rep.n_requests == 3 and rep.measured_rounds == 0


def test_unservable_shape_fails_at_submit(tiny):
    """A shape the engine cannot trace fails the submit() call itself —
    queued requests can never be dropped by a later trace error."""
    params, plan = tiny
    engine = InferenceEngine(None, params, TinyCfg(), plan,
                             api.Session(key=0))
    ok = engine.submit("t", _request_tensor(0, 2))     # seed-plan shape
    with pytest.raises(ValueError, match="no traced plan"):
        engine.submit("t", _request_tensor(1, 3))      # untraced shape
    assert engine.pending == 1
    assert ok.result() is not None


def test_plan_cache_reuses_traced_shapes(tiny):
    params, plan = tiny
    engine = _engine(params, plan)
    for i, b in enumerate([2, 3, 2, 3, 2]):
        engine.submit("t", _request_tensor(i, b))
    engine.flush()
    assert engine.plan_cache_size == 2        # (2, D_IN) seeded + (3, D_IN)


# ---------------------------------------------------------------------------
# Tenancy: metered triple budgets
# ---------------------------------------------------------------------------

def test_tenant_budget_fails_future_without_running(tiny):
    params, plan = tiny
    per_request = 2 * D_HID + 2 * D_OUT       # DReLU elements per batch-2
    engine = _engine(params, plan,
                     tenant_budgets={"capped": per_request + 1})
    ok = engine.submit("capped", _request_tensor(0, 2))
    over = engine.submit("capped", _request_tensor(1, 2))
    free = engine.submit("other", _request_tensor(2, 2))
    assert ok.result() is not None
    assert free.result() is not None
    with pytest.raises(beaver.TripleBudgetExceeded, match="capped"):
        over.result()
    usage = engine.tenant_usage("capped")
    assert usage["consumed_elements"] == per_request
    assert usage["remaining_elements"] == 1
    # the failed request never entered the executed batch
    assert all(over.request.id not in r.request_ids for r in engine.reports)


def test_metered_provider_counts_and_caps():
    p = beaver.MeteredProvider(beaver.InlineTTP(), budget_elements=100)
    assert p.relu_triples(0, 8) is None       # empty: not metered
    assert p.relu_triples(64, 0) is None      # culled: not metered
    p.relu_triples(60, 8)
    assert (p.consumed_elements, p.consumed_bundles) == (60, 1)
    with pytest.raises(beaver.TripleBudgetExceeded):
        p.relu_triples(41, 8)
    assert p.remaining_elements == 40


# ---------------------------------------------------------------------------
# Triple-pool data sharding (ROADMAP item) + data-axis serve_step
# ---------------------------------------------------------------------------

def test_shard_relu_triples_is_elementwise_slice():
    """Shards reconstruct exactly the element slices of the unsharded
    bundle: arithmetic members on the element axis, binary members at the
    bit level (word boundaries shift — 96/3 = 32 is exercised alongside
    the non-word-aligned 40/2 = 20 split)."""
    for E, S in [(96, 3), (40, 2)]:
        b = beaver.gen_relu_triples(jax.random.PRNGKey(E), E, 8)
        shards = [beaver.shard_relu_triples(b, i, S) for i in range(S)]
        for field in ("a", "b", "c"):
            full = shares.unpack_bits(getattr(b.bin_init, field), E)
            got = np.concatenate(
                [shares.unpack_bits(getattr(s.bin_init, field), E // S)
                 for s in shards], axis=-1)
            np.testing.assert_array_equal(np.asarray(full), got)
            full_lvl = shares.unpack_bits(getattr(b.bin_levels, field), E)
            got_lvl = np.concatenate(
                [shares.unpack_bits(getattr(s.bin_levels, field), E // S)
                 for s in shards], axis=-1)
            np.testing.assert_array_equal(np.asarray(full_lvl), got_lvl)
            np.testing.assert_array_equal(
                np.asarray(getattr(b.b2a, field).lo),
                np.concatenate([np.asarray(getattr(s.b2a, field).lo)
                                for s in shards], axis=-1))
    with pytest.raises(ValueError, match="divisible"):
        beaver.shard_relu_triples(
            beaver.gen_relu_triples(jax.random.PRNGKey(0), 10, 8), 0, 3)


def test_shard_relu_triples_cone_mode():
    b = beaver.gen_relu_triples(jax.random.PRNGKey(5), 64, 8, cone=True)
    s0, s1 = (beaver.shard_relu_triples(b, i, 2) for i in range(2))
    assert len(s0.bin_levels) == len(b.bin_levels)
    for lvl, (f0, f1) in enumerate(zip(s0.bin_levels, s1.bin_levels)):
        full = shares.unpack_bits(b.bin_levels[lvl].a, 64)
        got = np.concatenate([shares.unpack_bits(f0.a, 32),
                              shares.unpack_bits(f1.a, 32)], axis=-1)
        np.testing.assert_array_equal(np.asarray(full), got)


def test_sharded_relu_reveals_identically(rng):
    """The protocol run per shard with its triple slice reveals exactly
    the element slice of the unsharded run (same shares: DReLU is a
    deterministic function of the input shares, triples never leak into
    the reconstruction)."""
    from repro.core import fixed, gmw

    E, S = 64, 2
    x = rng.uniform(-3.5, 3.5, E).astype(np.float32)
    X = shares.share(jax.random.PRNGKey(1), fixed.encode_np(x))
    tr = beaver.gen_relu_triples(jax.random.PRNGKey(2), E, 8)
    full = gmw.relu(jax.random.PRNGKey(3), X, tr, comm_lib.SimComm(),
                    k=21, m=13)
    want = fixed.decode_np(shares.reconstruct(full))
    per = E // S
    for i in range(S):
        Xi = ring.Ring64(X.lo[:, i * per:(i + 1) * per],
                         X.hi[:, i * per:(i + 1) * per])
        tri = beaver.shard_relu_triples(tr, i, S)
        out = gmw.relu(jax.random.PRNGKey(3), Xi, tri, comm_lib.SimComm(),
                       k=21, m=13)
        np.testing.assert_array_equal(
            fixed.decode_np(shares.reconstruct(out)),
            want[i * per:(i + 1) * per])


def test_triple_pool_shard_slices_remaining_bundles():
    pool = beaver.gen_plan_triples(jax.random.PRNGKey(0),
                                   [(64, 8), (0, 8), (32, 0), (32, 8)])
    base = beaver.TriplePool(pool)
    shards = [base.shard(i, 2) for i in range(2)]   # non-destructive
    for shard in shards:
        first = shard.relu_triples(32, 8)
        assert first.b2a.a.lo.shape[-1] == 32     # 64-element call halved
        assert shard.relu_triples(0, 8) is None   # empty call stays None
        assert shard.relu_triples(32, 0) is None  # culled call stays None
        assert shard.relu_triples(16, 8).b2a.a.lo.shape[-1] == 16
    # the base pool is untouched and shards only cover what remains
    assert base.relu_triples(64, 8) is not None
    assert base.shard(0, 2).relu_triples(0, 8) is None  # skips consumed head


def test_data_axis_serve_step_smoke_mesh_bit_identical(tiny):
    params, plan = tiny
    model = api.compile(tiny_apply, params, TinyCfg(), plan,
                        api.Session(key=0))
    X = _request_tensor(0, 2)
    pool = beaver.gen_plan_triples(jax.random.PRNGKey(3),
                                   plan.triple_specs())
    key = jax.random.PRNGKey(4)
    s_lo, s_hi = model.serve_step()(params, X.data.lo, X.data.hi, pool, key)
    step = model.jit_step(make_mpc_smoke_mesh(), data_axis="data")
    m_lo, m_hi = step(params, X.data.lo, X.data.hi,
                      beaver.shard_pool(pool, 1), key)
    np.testing.assert_array_equal(np.asarray(m_lo), np.asarray(s_lo))
    np.testing.assert_array_equal(np.asarray(m_hi), np.asarray(s_hi))


# ---------------------------------------------------------------------------
# Data-axis mesh lowering: per-shard collective census unchanged
# (2-device subprocess: party axis 1 x data axis 2 keeps the protocol
# exchanges local per shard — the census isolates the data-sharding effect)
# ---------------------------------------------------------------------------

_DATA_AXIS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro import api
from repro.core import MPCTensor, beaver, ring, schedule as schedule_lib
from repro.core.hummingbird import HBConfig, HBLayer
from repro.runtime.hlo_analyzer import collective_census

assert jax.device_count() >= 4

class TinyCfg:
    name = "tiny-mlp"

def tiny_apply(params, x, relu_fn=None):
    rf = relu_fn if relu_fn is not None else (lambda v, g: jax.nn.relu(v))
    h = rf(x @ params["w1"], 0)
    return rf(h @ params["w2"], 1)

def tiny_forward(params, hs, cfg, relu_fn, comm):
    hs = relu_fn([h.matmul_public(params["w1"]) for h in hs], 0)
    return relu_fn([h.matmul_public(params["w2"]) for h in hs], 1)

api.register_mpc_forward(TinyCfg, tiny_forward)
params = {"w1": jax.random.normal(jax.random.PRNGKey(0), (6, 5)) * 0.4,
          "w2": jax.random.normal(jax.random.PRNGKey(1), (5, 4)) * 0.4}
plan = api.trace_plan(tiny_apply, params, (4, 6), name="tiny")
plan = plan.with_hb(HBConfig((HBLayer(k=21, m=13), HBLayer(k=21, m=13)),
                             plan.group_elements))
model = api.compile(tiny_apply, params, TinyCfg(), plan, api.Session(key=0))

x = jax.random.normal(jax.random.PRNGKey(2), (4, 6))
X = MPCTensor.from_plain(jax.random.PRNGKey(3), x)
pool = beaver.gen_plan_triples(jax.random.PRNGKey(4), plan.triple_specs())
key = jax.random.PRNGKey(5)

mesh = jax.make_mesh((2, 2), ("party", "data"),
                     axis_types=(AxisType.Auto,) * 2)

# unsharded two-party reference census
ref_step = model.serve_step(jax.make_mesh((2,), ("party",),
                                          axis_types=(AxisType.Auto,),
                                          devices=jax.devices()[:2]))
ref = collective_census(jax.jit(ref_step).lower(
    params, X.data.lo, X.data.hi, pool, key).compile().as_text())

sharded = beaver.shard_pool(pool, 2)
step = model.serve_step(mesh, data_axis="data")
compiled = jax.jit(step).lower(params, X.data.lo, X.data.hi, sharded,
                               key).compile()
census = collective_census(compiled.as_text())

# per-shard schedule: every call halves its element count, rounds unchanged
shard_plan = api.trace_plan(tiny_apply, params, (2, 6), hb=plan.hb,
                            name="tiny-shard")
shard_sched = shard_plan.schedule()
assert len(census) == len(ref) == shard_sched.n_rounds, (
    len(census), len(ref), shard_sched.n_rounds)
assert [c.bytes for c in census] == list(shard_sched.round_bytes), (
    [c.bytes for c in census], shard_sched.round_bytes)

# revealed outputs equal the unsharded sim replay's
m_lo, m_hi = compiled(params, X.data.lo, X.data.hi, sharded, key)
s_lo, s_hi = model.serve_step()(params, X.data.lo, X.data.hi, pool, key)
import repro.core.shares as shares, repro.core.fixed as fixed
got = fixed.decode_np(shares.reconstruct(ring.Ring64(m_lo, m_hi)))
want = fixed.decode_np(shares.reconstruct(ring.Ring64(s_lo, s_hi)))
np.testing.assert_allclose(got, want, atol=2 ** (13 - 16) + 1e-4)
print("DATA_AXIS_OK")
"""


def test_data_axis_census_unchanged_per_shard():
    """Acceptance for the ROADMAP data-axis item: with the batch sharded
    2-way over a data axis, the compiled step still carries exactly the
    schedule-predicted number of collective-permutes (rounds are
    element-count independent) and each collective's payload equals the
    per-shard schedule's round bytes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _DATA_AXIS_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    assert "DATA_AXIS_OK" in out.stdout


# ---------------------------------------------------------------------------
# Engine resilience (ISSUE 6): deadline shedding, batch retry on comm
# faults, crash + restart hook — failure accounting exact in stats()
# ---------------------------------------------------------------------------

def _chaos_engine(params, plan, fault_plan, *, resilient_retries=3, **kw):
    """An engine whose session comm realizes ``fault_plan`` below a
    ResilientComm; returns (engine, injector, resilient)."""
    from repro.core import faults
    fic = faults.FaultInjectingComm(fault_plan)
    rc = comm_lib.ResilientComm(fic, max_retries=resilient_retries)
    session = api.Session(key=0, comm=rc)
    engine = InferenceEngine(tiny_apply, params, TinyCfg(), plan, session,
                             **kw)
    return engine, fic, rc


def test_deadline_shedding_typed_and_counted(tiny):
    """A request that provably cannot meet its deadline is shed before
    any triple is consumed; the others in the same batch still run."""
    params, plan = tiny
    engine = _engine(params, plan)
    doomed = engine.submit("alice", _request_tensor(0, 2), deadline_s=0.0)
    ok = engine.submit("bob", _request_tensor(1, 2))
    assert ok.result() is not None
    with pytest.raises(errors.DeadlineExceeded) as ei:
        doomed.result()
    assert ei.value.request_id == doomed.request.id
    assert ei.value.tenant == "alice"
    stats = engine.stats()
    assert stats["shed"] == 1 and stats["requests"] == 1
    assert engine.reports[-1].shed == 1
    # shed before execution: alice consumed nothing
    assert engine.tenant_usage("alice")["consumed_elements"] == 0
    # a generous deadline is met normally
    fine = engine.submit("alice", _request_tensor(2, 2), deadline_s=60.0)
    assert fine.result() is not None
    assert engine.stats()["shed"] == 1


def test_batch_retry_on_transient_faults_bit_identical(tiny):
    """Transport retry budget 0 forces every transient up to the engine:
    the whole batch re-executes (providers rolled back, same request
    keys) and the results stay bit-identical to a fault-free engine —
    with STATEFUL StreamingTTP providers, so the rollback is load-bearing."""
    from repro.core import faults
    params, plan = tiny
    factory = lambda tenant: beaver.StreamingTTP(
        jax.random.PRNGKey(len(tenant)))

    clean = _engine(params, plan, provider_factory=factory)
    f_clean = [clean.submit(t, _request_tensor(i, 2))
               for i, t in enumerate(["alice", "bob"])]
    want = [f.result() for f in f_clean]

    fault_plan = faults.FaultPlan.seeded(3, 10, drops=1, corrupts=1)
    engine, fic, rc = _chaos_engine(params, plan, fault_plan,
                                    resilient_retries=0,
                                    provider_factory=factory)
    futs = [engine.submit(t, _request_tensor(i, 2))
            for i, t in enumerate(["alice", "bob"])]
    outs = [f.result() for f in futs]
    for got, ref in zip(outs, want):
        np.testing.assert_array_equal(ring.to_uint64_np(got.data),
                                      ring.to_uint64_np(ref.data))
    stats = engine.stats()
    assert stats["retries"] == 2 == engine.reports[-1].retries
    assert fic.injected["drop"] == 1 and fic.injected["corrupt"] == 1
    # tenants billed exactly once despite the re-executions
    per_request = 2 * D_HID + 2 * D_OUT
    assert engine.tenant_usage("alice")["consumed_elements"] == per_request


def test_transport_absorbs_faults_engine_counts_recovery(tiny):
    """With transport-level retries available the engine never re-runs the
    batch; it reports the rounds the transport healed."""
    from repro.core import faults
    params, plan = tiny
    fault_plan = faults.FaultPlan.seeded(5, 10, drops=2, corrupts=1)
    engine, fic, rc = _chaos_engine(params, plan, fault_plan,
                                    resilient_retries=3)
    fut = engine.submit("alice", _request_tensor(0, 2))
    out = fut.result()
    want = _serial_oracle(params, plan, _request_tensor(0, 2), 0)
    np.testing.assert_array_equal(ring.to_uint64_np(out.data),
                                  ring.to_uint64_np(want.data))
    stats = engine.stats()
    assert stats["retries"] == 0
    assert stats["faults_recovered"] == 3 == engine.reports[-1].faults_recovered
    assert rc.retries == 3 and rc.recovered == 3


def test_party_crash_restart_hook_retries_batch(tiny):
    """A mid-replay crash fails the batch unless on_party_crash revives
    the transport; with the hook, the retried results are bit-identical."""
    from repro.core import faults
    params, plan = tiny

    # no hook: the typed crash propagates and fails the future
    fault_plan = faults.FaultPlan.seeded(0, 10, drops=0, corrupts=0,
                                         crash_round=2)
    engine, fic, rc = _chaos_engine(params, plan, fault_plan)
    fut = engine.submit("alice", _request_tensor(0, 2))
    with pytest.raises(errors.PartyCrashed):
        engine.flush()
    with pytest.raises(errors.PartyCrashed) as ei:
        fut.result()
    assert ei.value.request_id == fut.request.id

    # with the hook: restart + one batch retry, bit-identical output
    fault_plan = faults.FaultPlan.seeded(0, 10, drops=0, corrupts=0,
                                         crash_round=2)
    holder = {}
    engine2, fic2, rc2 = _chaos_engine(
        params, plan, fault_plan,
        on_party_crash=lambda e: holder["fic"].restart())
    holder["fic"] = fic2
    fut2 = engine2.submit("alice", _request_tensor(0, 2))
    out = fut2.result()
    want = _serial_oracle(params, plan, _request_tensor(0, 2), 0)
    np.testing.assert_array_equal(ring.to_uint64_np(out.data),
                                  ring.to_uint64_np(want.data))
    assert engine2.stats()["retries"] == 1
    assert fic2.restarts == 1


def test_result_timeout_raises_instead_of_hanging(tiny):
    """A policy that never closes a solo batch used to make result() spin
    via flush; with timeout_s the caller gets a typed timeout carrying
    the request identity."""
    params, plan = tiny
    engine = _engine(params, plan,
                     policy=BatchPolicy(max_batch=8, min_gain=-1.0))
    fut = engine.submit("alice", _request_tensor(0, 2))
    with pytest.raises(errors.ResultTimeout) as ei:
        fut.result(timeout_s=0.05)
    assert ei.value.request_id == fut.request.id
    assert ei.value.tenant == "alice"
    assert not fut.done                       # still queued, not failed
    assert fut.result() is not None           # blocking drain still works


def test_typed_errors_preserve_builtin_contracts(tiny):
    """The new hierarchy subclasses the builtins it replaced, so every
    historical except/raises call site keeps working."""
    params, plan = tiny
    engine = _engine(params, plan)
    engine.submit("alice", _request_tensor(0, 2))
    with pytest.raises(errors.DuplicateRequest):
        engine.submit("alice", _request_tensor(0, 2), request_id=0)
    assert issubclass(errors.DuplicateRequest, ValueError)
    assert issubclass(errors.ShapeMismatch, ValueError)
    assert issubclass(errors.UnregisteredModel, KeyError)
    assert issubclass(errors.TripleBudgetExceeded, RuntimeError)
    assert beaver.TripleBudgetExceeded is errors.TripleBudgetExceeded
    with pytest.raises(KeyError, match="no MPC forward"):
        class Unknown:
            pass
        api.compile(None, {}, Unknown(), plan, api.Session(key=0))


def test_stats_report_measured_queue_wait_and_execute(tiny):
    """``stats()`` percentiles are measured: each request's queue wait
    (arrival to execution) and each batch's replay wall time."""
    import time

    params, plan = tiny
    engine = _engine(params, plan)
    now = time.monotonic()
    f1 = engine.submit("alice", _request_tensor(0, 2), arrival_s=now - 2.0)
    f2 = engine.submit("bob", _request_tensor(1, 2), arrival_s=now - 1.0)
    engine.flush()
    rep = engine.reports[-1]
    assert rep.request_ids == (f1.request.id, f2.request.id)
    assert rep.queue_s(f1.request.id) >= 2.0
    assert 1.0 <= rep.queue_s(f2.request.id) < rep.queue_s(f1.request.id)
    assert rep.execute_s > 0.0
    stats = engine.stats()
    assert stats["p95_queue_wait_s"] == rep.queue_s(f1.request.id)
    assert stats["p50_queue_wait_s"] == rep.queue_s(f1.request.id)
    assert stats["p50_execute_s"] == stats["p95_execute_s"] == rep.execute_s
    assert not any("sim" in k for k in stats)
