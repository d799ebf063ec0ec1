"""Compiled round loop (PR 9): the ``lax.scan`` backend of
``runtime/loop.py`` must be share-level BIT-IDENTICAL to the generator
round loop and to the frozen seed path (``core/gmw_ref.py``), with
measured rounds/bytes equal to ``core.schedule.simulate`` exactly —
across random (n, k, m) mixes, early dropout, the cone adder, width-0
culling and auto-batched (merged) siblings.  Also pins the env-selected
backend (``HB_ROUND_LOOP``), compiled-replay eligibility, the
PrivateModel whole-replay path and its counter replay onto the caller's
CoalescingComm.
"""
import os

import jax
import numpy as np
import pytest

from repro import api
from repro.core import (MPCTensor, beaver, comm as comm_lib, fixed, gmw,
                        gmw_ref, ring, schedule, shares)
from repro.core.hummingbird import HBConfig, HBLayer
from repro.runtime import loop as loop_lib

try:                                   # optional: property test only
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _make_group(n, k, m, cone, seed):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-3.5, 3.5, n).astype(np.float32)
    X = shares.share(jax.random.PRNGKey(seed), fixed.encode_np(x))
    tri = (None if k == m or n == 0 else
           beaver.gen_relu_triples(jax.random.PRNGKey(seed + 1), n, k - m,
                                   cone=cone))
    return X, tri


def _run_loop(specs, loop, cone=False, auto_batch=True, seed=0):
    """relu_many on the given round-loop backend; returns (outs, comm)."""
    keys, Xs, trs = [], [], []
    for i, (n, k, m) in enumerate(specs):
        X, tri = _make_group(n, k, m, cone, seed + 10 * i)
        keys.append(jax.random.PRNGKey(seed + 1000 + i))
        Xs.append(X)
        trs.append(tri)
    cc = comm_lib.CoalescingComm(comm_lib.SimComm())
    outs = gmw.relu_many(keys, Xs, trs, cc, [(k, m) for _, k, m in specs],
                         cone=cone, auto_batch=auto_batch, loop=loop)
    return outs, cc


def _assert_pair(specs, cone=False, auto_batch=True, seed=0):
    """scan vs python backends: share-level bit-identity AND identical
    measured counters, both equal to the schedule prediction."""
    outs_py, cc_py = _run_loop(specs, "python", cone, auto_batch, seed)
    outs_sc, cc_sc = _run_loop(specs, "scan", cone, auto_batch, seed)
    for a, b in zip(outs_py, outs_sc):
        np.testing.assert_array_equal(np.asarray(a.lo), np.asarray(b.lo))
        np.testing.assert_array_equal(np.asarray(a.hi), np.asarray(b.hi))
    assert cc_sc.n_rounds == cc_py.n_rounds
    assert cc_sc.round_bytes == cc_py.round_bytes
    assert cc_sc.round_parts == cc_py.round_parts
    sched = schedule.simulate([(n, k - m, (n, k, m)) for n, k, m in specs],
                              cone=cone, auto_batch=auto_batch)
    assert cc_sc.n_rounds == sched.n_rounds
    assert cc_sc.round_bytes == list(sched.round_bytes)
    assert cc_sc.round_parts == list(sched.round_parts)
    return outs_sc


# ---------------------------------------------------------------------------
# relu_scan vs generator loop vs the frozen seed path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k,m", [
    (64, 64, 0),      # full exact ring, 6 dense scan levels
    (300, 21, 13),    # the paper's 8-bit reduced ring
    (33, 8, 6),       # w=2: a single scan level after the init AND
    (7, 9, 8),        # w=1: no adder levels at all (scan degenerates)
    (5, 2, 0),        # tiny n, sub-word packing
])
def test_relu_scan_bit_identical_to_seed(n, k, m):
    X, tri = _make_group(n, k, m, False, 11)
    key = jax.random.PRNGKey(99)
    want = gmw_ref.relu(key, X, tri, comm_lib.SimComm(), k=k, m=m)
    got_gen = gmw.relu(key, X, tri, comm_lib.SimComm(), k=k, m=m)
    got_scan = gmw.relu_scan(key, X, tri, comm_lib.SimComm(), k=k, m=m)
    for got in (got_gen, got_scan):
        np.testing.assert_array_equal(np.asarray(got.lo), np.asarray(want.lo))
        np.testing.assert_array_equal(np.asarray(got.hi), np.asarray(want.hi))


def test_relu_scan_under_jit_bit_identical(rng):
    """The point of the scan backend: the whole ReLU jits into one XLA
    program with unchanged shares."""
    n, k, m = 256, 21, 13
    X, tri = _make_group(n, k, m, False, 5)
    key = jax.random.PRNGKey(4)

    @jax.jit
    def run(lo, hi, tr):
        out = gmw.relu_scan(key, ring.Ring64(lo, hi), tr,
                            comm_lib.SimComm(), k=k, m=m)
        return out.lo, out.hi

    lo, hi = run(X.lo, X.hi, tri)
    want = gmw.relu(key, X, tri, comm_lib.SimComm(), k=k, m=m)
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(want.lo))
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(want.hi))


# ---------------------------------------------------------------------------
# relu_many: deterministic scenario coverage, scan vs python
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("specs,cone", [
    # mixed widths: narrow rings drop out of the lockstep early
    ([(96, 64, 0), (160, 21, 13), (64, 20, 14)], False),
    ([(96, 64, 0), (160, 21, 13), (64, 20, 14)], True),
    # w=1 next to a deep ring
    ([(40, 2, 1), (40, 64, 0)], False),
    # width-0 culled + empty-batch streams cost zero rounds
    ([(64, 13, 13), (0, 21, 13), (32, 21, 13)], False),
    # merged siblings: identical (n, k, m) auto-batch into ONE stream,
    # which is exactly the case the scan backend compiles
    ([(50, 21, 13), (50, 21, 13), (30, 21, 13)], False),
    ([(50, 21, 13), (50, 21, 13), (50, 21, 13)], False),
    # solo group: pure relu_scan path
    ([(128, 21, 13)], False),
    ([(128, 5, 0)], True),
])
def test_scan_vs_python_scenarios(specs, cone):
    _assert_pair(specs, cone=cone)


def test_scan_vs_python_without_batching():
    _assert_pair([(50, 21, 13), (50, 21, 13)], auto_batch=False, seed=3)


_KM_POOL = [(64, 0), (21, 13), (20, 14), (8, 0), (5, 3), (2, 1), (13, 13)]

if HAVE_HYPOTHESIS:
    _GROUP = st.tuples(
        st.integers(min_value=0, max_value=80),        # n (0 = empty batch)
        st.sampled_from(_KM_POOL),
    )

    @settings(max_examples=6, deadline=None)
    @given(groups=st.lists(_GROUP, min_size=1, max_size=3),
           cone=st.booleans(), auto_batch=st.booleans())
    def test_scan_property_random_groups(groups, cone, auto_batch):
        specs = [(n, k, m) for n, (k, m) in groups]
        _assert_pair(specs, cone=cone, auto_batch=auto_batch, seed=7)


@pytest.mark.parametrize("case_seed", [0, 1, 2, 3])
def test_scan_random_sweep(case_seed):
    """Deterministic randomized sweep (runs with or without hypothesis):
    duplicates make merged siblings, zeros empty streams, (13, 13)
    culled identities."""
    rng = np.random.default_rng(200 + case_seed)
    n_groups = int(rng.integers(1, 4))
    specs = []
    for _ in range(n_groups):
        n = int(rng.choice([0, 1, 17, 50, 50, 80]))
        k, m = _KM_POOL[int(rng.integers(0, len(_KM_POOL)))]
        specs.append((n, k, m))
    cone = bool(rng.integers(0, 2))
    _assert_pair(specs, cone=cone, seed=300 + case_seed)


# ---------------------------------------------------------------------------
# Backend selection + compiled-replay eligibility (runtime/loop.py)
# ---------------------------------------------------------------------------

def test_round_loop_mode_env(monkeypatch):
    monkeypatch.delenv("HB_ROUND_LOOP", raising=False)
    assert loop_lib.round_loop_mode() == "scan"        # production default
    monkeypatch.setenv("HB_ROUND_LOOP", "python")
    assert loop_lib.round_loop_mode() == "python"
    monkeypatch.setenv("HB_ROUND_LOOP", "scan")
    assert loop_lib.round_loop_mode() == "scan"
    monkeypatch.setenv("HB_ROUND_LOOP", "bogus")
    assert loop_lib.round_loop_mode() == "scan"        # invalid -> default


@pytest.mark.parametrize("calls,auto", [
    (0, "scan"),
    (loop_lib.MAX_COMPILED_RELU_CALLS, "scan"),
    (loop_lib.MAX_COMPILED_RELU_CALLS + 1, "python"),
])
def test_round_loop_mode_by_replay_size(monkeypatch, calls, auto):
    monkeypatch.delenv("HB_ROUND_LOOP", raising=False)
    assert loop_lib.round_loop_mode(lambda: calls) == auto

    def untraced():
        raise AssertionError("size asked for under an override")

    for forced in ("scan", "python"):          # the env var overrides
        monkeypatch.setenv("HB_ROUND_LOOP", forced)
        assert loop_lib.round_loop_mode(untraced) == forced


def test_compiled_eligible_exact_types():
    assert loop_lib.compiled_eligible(comm_lib.SimComm())
    assert loop_lib.compiled_eligible(
        comm_lib.CoalescingComm(comm_lib.SimComm()))
    # anything that observes rounds at the Python layer must keep the
    # generator loop: counters, resilience framing, real sockets
    assert not loop_lib.compiled_eligible(comm_lib.CountingComm())
    assert not loop_lib.compiled_eligible(
        comm_lib.CoalescingComm(comm_lib.CountingComm()))
    assert not loop_lib.compiled_eligible(
        comm_lib.ResilientComm(comm_lib.SimComm()))


# ---------------------------------------------------------------------------
# PrivateModel whole-replay: a tiny 2-group MLP, scan vs python backends
# ---------------------------------------------------------------------------

class LoopCfg:
    name = "loop-mlp"


def loop_apply(params, x, relu_fn=None):
    rf = relu_fn if relu_fn is not None else (lambda v, g: jax.nn.relu(v))
    h = rf(x @ params["w1"], 0)
    return rf(h @ params["w2"], 1)


def loop_forward(params, hs, cfg, relu_fn, comm):
    hs = relu_fn([h.matmul_public(params["w1"]) for h in hs], 0)
    return relu_fn([h.matmul_public(params["w2"]) for h in hs], 1)


api.register_mpc_forward(LoopCfg, loop_forward)

D_IN, D_HID, D_OUT = 6, 5, 4


@pytest.fixture(scope="module")
def tiny_model():
    params = {
        "w1": jax.random.normal(jax.random.PRNGKey(0), (D_IN, D_HID)) * 0.4,
        "w2": jax.random.normal(jax.random.PRNGKey(1), (D_HID, D_OUT)) * 0.4,
    }
    plan = api.trace_plan(loop_apply, params, (2, D_IN), name="loop")
    plan = plan.with_hb(HBConfig((HBLayer(k=21, m=13), HBLayer(k=21, m=13)),
                                 plan.group_elements))
    return params, plan


def _model_run(params, plan, X, mode, monkeypatch):
    monkeypatch.setenv("HB_ROUND_LOOP", mode)
    monkeypatch.setenv("HB_XLA_OPT", "0")      # cap replay compile time
    cc = comm_lib.CoalescingComm(comm_lib.SimComm())
    model = api.compile(loop_apply, params, LoopCfg(), plan,
                        api.Session(key=0, comm=cc))
    out = model(X, key=jax.random.PRNGKey(4))
    return out, cc, model


def test_private_model_scan_vs_python(tiny_model, monkeypatch):
    params, plan = tiny_model
    x = jax.random.normal(jax.random.PRNGKey(7), (2, D_IN))
    X = MPCTensor.from_plain(jax.random.PRNGKey(8), x)
    out_py, cc_py, _ = _model_run(params, plan, X, "python", monkeypatch)
    out_sc, cc_sc, model = _model_run(params, plan, X, "scan", monkeypatch)
    np.testing.assert_array_equal(np.asarray(out_py.data.lo),
                                  np.asarray(out_sc.data.lo))
    np.testing.assert_array_equal(np.asarray(out_py.data.hi),
                                  np.asarray(out_sc.data.hi))
    # counter replay: the compiled path must report the exact generator
    # timeline onto the caller's CoalescingComm
    assert cc_sc.n_rounds == cc_py.n_rounds
    assert cc_sc.round_bytes == cc_py.round_bytes
    assert cc_sc.round_parts == cc_py.round_parts
    stats = model.replay_stats([X])
    assert stats is not None
    assert stats["n_rounds"] == cc_py.n_rounds
    assert stats["trace_s"] > 0 and stats["compile_s"] > 0


def test_private_model_replay_cache_shared(tiny_model, monkeypatch):
    """A second model from the same plan/forward reuses the compiled
    executable (no new cache entry, bit-identical output)."""
    from repro.api.compile import replay_cache_stats
    params, plan = tiny_model
    x = jax.random.normal(jax.random.PRNGKey(17), (2, D_IN))
    X = MPCTensor.from_plain(jax.random.PRNGKey(18), x)
    out1, _, _ = _model_run(params, plan, X, "scan", monkeypatch)
    n_entries = len(replay_cache_stats())
    out2, _, _ = _model_run(params, plan, X, "scan", monkeypatch)
    assert len(replay_cache_stats()) == n_entries
    np.testing.assert_array_equal(np.asarray(out1.data.lo),
                                  np.asarray(out2.data.lo))


def test_ineligible_comm_stays_on_generator_loop(tiny_model, monkeypatch):
    """A counter-observing comm must take the generator path even when
    HB_ROUND_LOOP=scan — same outputs, counters measured live."""
    params, plan = tiny_model
    x = jax.random.normal(jax.random.PRNGKey(27), (2, D_IN))
    X = MPCTensor.from_plain(jax.random.PRNGKey(28), x)
    monkeypatch.setenv("HB_ROUND_LOOP", "scan")
    cc = comm_lib.CoalescingComm(comm_lib.CountingComm())
    model = api.compile(loop_apply, params, LoopCfg(), plan,
                        api.Session(key=0, comm=cc))
    out = model(X, key=jax.random.PRNGKey(4))
    out_py, cc_py, _ = _model_run(params, plan, X, "python", monkeypatch)
    np.testing.assert_array_equal(np.asarray(out.data.lo),
                                  np.asarray(out_py.data.lo))
    assert cc.n_rounds == cc_py.n_rounds


def test_large_replay_takes_generator_loop(tiny_model, monkeypatch):
    """With HB_ROUND_LOOP unset, a replay of more ReLU calls (over its
    sibling streams) than ``MAX_COMPILED_RELU_CALLS`` runs on the
    generator loop: no replay is compiled, outputs and counters match
    the forced generator run.  Up to the limit it is compiled."""
    from repro.api.compile import replay_cache_stats
    params, plan = tiny_model
    xs = [MPCTensor.from_plain(jax.random.PRNGKey(30 + i),
                               jax.random.normal(jax.random.PRNGKey(40 + i),
                                                 (2, D_IN)))
          for i in range(3)]
    model = api.compile(loop_apply, params, LoopCfg(), plan,
                        api.Session(key=0))
    assert model._relu_calls(xs[:1], True, params) == 2
    assert model._relu_calls(xs, True, params) == 6
    monkeypatch.setattr(loop_lib, "MAX_COMPILED_RELU_CALLS", 5)
    monkeypatch.setenv("HB_XLA_OPT", "0")
    monkeypatch.delenv("HB_ROUND_LOOP", raising=False)
    cc = comm_lib.CoalescingComm(comm_lib.SimComm())
    model = api.compile(loop_apply, params, LoopCfg(), plan,
                        api.Session(key=0, comm=cc))
    n_entries = len(replay_cache_stats())
    outs = model(xs, key=jax.random.PRNGKey(5))
    assert len(replay_cache_stats()) == n_entries
    monkeypatch.setenv("HB_ROUND_LOOP", "python")
    cc_py = comm_lib.CoalescingComm(comm_lib.SimComm())
    ref = api.compile(loop_apply, params, LoopCfg(), plan,
                      api.Session(key=0, comm=cc_py))(
        xs, key=jax.random.PRNGKey(5))
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(np.asarray(a.data.lo),
                                      np.asarray(b.data.lo))
        np.testing.assert_array_equal(np.asarray(a.data.hi),
                                      np.asarray(b.data.hi))
    assert cc.round_bytes == cc_py.round_bytes
    monkeypatch.delenv("HB_ROUND_LOOP")
    model(xs[:2], key=jax.random.PRNGKey(5))    # 4 calls: compiled
    assert model.replay_stats(xs[:2]) is not None


def test_traced_replay_stays_on_generator_loop(tiny_model, monkeypatch):
    """Under an outer ``jax.jit`` the replay traces into the caller's
    program (a compiled replay cannot take tracers), bit-identical to
    the eager call, which runs the compiled replay."""
    params, plan = tiny_model
    monkeypatch.setenv("HB_ROUND_LOOP", "scan")
    monkeypatch.setenv("HB_XLA_OPT", "0")
    model = api.compile(loop_apply, params, LoopCfg(), plan,
                        api.Session(key=0))
    X = MPCTensor.from_plain(jax.random.PRNGKey(50),
                             jax.random.normal(jax.random.PRNGKey(51),
                                               (2, D_IN)))
    step = model.serve_step()
    key = jax.random.PRNGKey(52)
    traced = jax.jit(step)(params, X.data.lo, X.data.hi, None, key)
    eager = step(params, X.data.lo, X.data.hi, None, key)
    assert model.replay_stats([X]) is not None
    for a, b in zip(traced, eager):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
