"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

``--quick`` is the CI perf tracker: a CPU-sim measurement of the GMW ReLU
hot path — rounds, wire bytes and wall-clock for the exact (k=64, m=0) vs
the 8-bit reduced ring, the round-fused engine vs the frozen seed path
(core/gmw_ref.py), and the multi-group relu_many swap fusion — written to
``BENCH_relu.json`` so the perf trajectory is tracked PR over PR.  Every
measured entry sits next to the ``core.schedule`` prediction
(``sched_rounds_pred`` / ``sched_bytes_pred`` plus LAN/WAN latency
projections); ``--check`` is the CI round-regression gate that fails when
measured fused swaps exceed the prediction.  ``--transport`` runs the
real two-process deployment (both parties as OS processes over localhost
TCP under an injected RTT, plus an HTTP-frontend throughput probe) and
``--check`` then also enforces exact wire-vs-schedule byte parity,
bit-identity against the SimComm reference, and the wall-clock tolerance
band.
"""
import argparse
import json
import os
import sys
import time

# make `python benchmarks/run.py` work from anywhere: repo root (for the
# benchmarks package) and src/ (for repro) onto sys.path
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)



# the --quick / --gantt multi-group layer: four concurrent ReLU groups of
# mixed widths and element counts, (n_elements, k, m) each
_E = 2048
MULTIGROUP_SPECS = [(_E, 64, 0), (_E, 21, 13), (_E // 2, 21, 13),
                    (_E // 2, 20, 14)]


def _time_best(fn, repeats: int = 3) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def quick(out_path: str = "BENCH_relu.json") -> dict:
    import jax
    import numpy as np

    from repro.api.plan import LAN, WAN
    from repro.core import (beaver, comm as comm_lib, costmodel, fixed, gmw,
                            gmw_ref, ring, schedule as schedule_lib, shares)

    rng = np.random.default_rng(0)
    E = 2048
    results = {"n_elements": E, "configs": {}}

    for name, (k, m) in {"exact_64": (64, 0),
                         "reduced_8of64": (21, 13)}.items():
        w = k - m
        x = rng.uniform(-3.5, 3.5, E).astype(np.float32)
        X = shares.share(jax.random.PRNGKey(1), fixed.encode_np(x))
        tr = beaver.gen_relu_triples(jax.random.PRNGKey(2), E, w)
        cm = comm_lib.CountingComm()

        def run(mod, comm):
            out = mod.relu(jax.random.PRNGKey(3), X, tr, comm, k=k, m=m)
            jax.block_until_ready((out.lo, out.hi))

        run(gmw, cm)  # warmup + counter fill
        wall_python = _time_best(lambda: run(gmw, comm_lib.SimComm()))
        run(gmw_ref, comm_lib.SimComm())  # warmup
        wall_seed = _time_best(lambda: run(gmw_ref, comm_lib.SimComm()))

        # compiled round loop: the whole ReLU as ONE jitted XLA program
        # (scan backend, runtime/loop.py) — no per-round Python dispatch
        @jax.jit
        def run_scan(lo, hi, tri, _k=k, _m=m):
            out = gmw.relu_scan(jax.random.PRNGKey(3), ring.Ring64(lo, hi),
                                tri, comm_lib.SimComm(), k=_k, m=_m)
            return out.lo, out.hi

        want = gmw.relu(jax.random.PRNGKey(3), X, tr, comm_lib.SimComm(),
                        k=k, m=m)
        got = run_scan(X.lo, X.hi, tr)  # warmup (trace + compile)
        assert np.array_equal(np.asarray(got[0]), np.asarray(want.lo)), \
            f"{name}: compiled loop diverged from the generator loop"
        wall_compiled = _time_best(lambda: jax.block_until_ready(
            run_scan(X.lo, X.hi, tr)))
        model = costmodel.relu_cost(E, w)
        sched = schedule_lib.simulate([(E, w, (E, k, m))])
        results["configs"][name] = {
            "k": k, "m": m, "width": w,
            "rounds": cm.n_swaps,
            "bytes_tx": cm.bytes_tx,
            "model_rounds": model.rounds,
            "model_bytes_tx": model.bytes_tx,
            "sched_rounds_pred": sched.n_rounds,
            "sched_bytes_pred": sched.bytes_tx,
            "wall_s_seed": round(wall_seed, 4),
            "wall_s_python_loop": round(wall_python, 4),
            "wall_s_compiled_loop": round(wall_compiled, 6),
            "wall_s_fused": round(wall_compiled, 6),
            "speedup_vs_seed": round(wall_seed / max(wall_compiled, 1e-9), 3),
        }

    # multi-group layer: sibling ReLU groups sharing rounds via relu_many
    specs = MULTIGROUP_SPECS
    keys = [jax.random.PRNGKey(40 + i) for i in range(len(specs))]
    Xs, trs = [], []
    for i, (n, k, m) in enumerate(specs):
        x = rng.uniform(-3.5, 3.5, n).astype(np.float32)
        Xs.append(shares.share(jax.random.PRNGKey(50 + i), fixed.encode_np(x)))
        trs.append(beaver.gen_relu_triples(jax.random.PRNGKey(60 + i), n,
                                           k - m))

    def run_seed(comm):
        for i, (n, k, m) in enumerate(specs):
            out = gmw_ref.relu(keys[i], Xs[i], trs[i], comm, k=k, m=m)
            jax.block_until_ready((out.lo, out.hi))

    def run_fused(comm):
        outs = gmw.relu_many(keys, Xs, trs, comm,
                             [(k, m) for _, k, m in specs])
        jax.block_until_ready([(o.lo, o.hi) for o in outs])

    seed_cm = comm_lib.CountingComm()
    run_seed(seed_cm)
    fused_cc = comm_lib.CoalescingComm()
    run_fused(fused_cc)
    wall_seed = _time_best(lambda: run_seed(comm_lib.SimComm()))
    wall_python = _time_best(lambda: run_fused(comm_lib.SimComm()))
    # schedule-predicted fused timeline (the CI round-regression oracle:
    # measured fused swaps must never exceed this — see --check)
    sched = schedule_lib.simulate([(n, k - m, (n, k, m)) for n, k, m in specs])

    # compiled round loop: the whole multi-group layer as ONE jitted XLA
    # program (the scan backend of runtime/loop.py).  The trace / XLA
    # compile / warm execute split IS the dispatch-overhead breakdown:
    # trace+compile are paid once per signature, warm batches pay only
    # the execute time.
    kms = [(k, m) for _, k, m in specs]
    los, his = [x.lo for x in Xs], [x.hi for x in Xs]

    def compiled_replay(lo_list, hi_list, tris):
        xs2 = [ring.Ring64(lo, hi) for lo, hi in zip(lo_list, hi_list)]
        outs = gmw.relu_many(keys, xs2, tris, comm_lib.SimComm(), kms,
                             loop="scan")
        return [o.lo for o in outs], [o.hi for o in outs]

    t0 = time.perf_counter()
    lowered = jax.jit(compiled_replay).lower(los, his, trs)
    trace_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    exe = lowered.compile()
    compile_s = time.perf_counter() - t0
    got_lo, _ = exe(los, his, trs)
    want = gmw.relu_many(keys, Xs, trs, comm_lib.SimComm(), kms)
    assert all(np.array_equal(np.asarray(a), np.asarray(b.lo))
               for a, b in zip(got_lo, want)), \
        "multigroup: compiled loop diverged from the generator loop"
    wall_compiled = _time_best(lambda: jax.block_until_ready(
        exe(los, his, trs)))
    # per-round host overhead of the generator loop (Python dispatch,
    # pytree flatten/unflatten, per-round device sync) — what compiling
    # the loop removes
    host_s_per_round = (max(wall_python - wall_compiled, 0.0)
                        / max(sched.n_rounds, 1))

    # mesh-lowered census: the same fused replay inside shard_map over a
    # party axis of size 2 must compile to exactly one collective-permute
    # per fused round with the schedule's per-round payloads (--check
    # fails on any divergence).  Needs >= 2 devices (forced on CPU above);
    # with one device the census is recorded as not measured (None).
    mesh_census = {"mesh_collective_permutes": None,
                   "mesh_collective_bytes": None}
    if jax.device_count() >= 2:
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_mpc_mesh

        from repro.runtime.hlo_analyzer import collective_census

        kms = [(k, m) for _, k, m in specs]

        def replay(lo_list, hi_list, triples):
            cc = comm_lib.CoalescingComm(comm_lib.MeshComm("party", 2))
            xs = [ring.Ring64(lo, hi) for lo, hi in zip(lo_list, hi_list)]
            outs = gmw.relu_many(keys, xs, triples, cc, kms)
            return [o.lo for o in outs], [o.hi for o in outs]

        party = P("party")
        n_g = len(specs)
        fused = jax.shard_map(
            replay, mesh=make_mpc_mesh(n_data=1),
            in_specs=([party] * n_g, [party] * n_g,
                      beaver.pool_party_specs(trs)),
            out_specs=([party] * n_g, [party] * n_g), check_vma=False)
        compiled = jax.jit(fused).lower(
            [x.lo for x in Xs], [x.hi for x in Xs], trs).compile()
        census = collective_census(compiled.as_text())
        mesh_census = {
            "mesh_collective_permutes": sum(c.count for c in census),
            "mesh_collective_bytes": sum(c.bytes * c.count for c in census),
        }

    # request-level serving engine: the canonical ISSUE-5 request mix (two
    # identical shapes + one ragged) served as one fused micro-batch over
    # the smoke model — requests/s, simulated latency percentiles, and the
    # rounds saved vs serial per-request execution (--check gates the
    # measured fused rounds against the merged-schedule prediction)
    from repro import api
    from repro.configs import RESNET_SMOKE
    from repro.core.hummingbird import HBConfig, HBLayer
    from repro.models import resnet
    from repro.serve import InferenceEngine

    params = resnet.init(jax.random.PRNGKey(0), RESNET_SMOKE)

    def afn(p, v, relu_fn=None):
        return resnet.apply(p, v, RESNET_SMOKE, relu_fn=relu_fn)

    plan = api.trace_plan(afn, params, (2, 3, 8, 8), name="smoke")
    plan = plan.with_hb(HBConfig(
        tuple([HBLayer(k=21, m=13)] * (plan.n_groups - 1)
              + [HBLayer(k=13, m=13)]), plan.group_elements))
    engine = InferenceEngine(afn, params, RESNET_SMOKE, plan,
                             api.Session(key=0))
    mix = [(2, 3, 8, 8), (2, 3, 8, 8), (1, 3, 8, 8)]
    xs = [rng.uniform(-0.5, 0.5, sh).astype(np.float32) for sh in mix]

    def serve_mix():
        t0 = time.perf_counter()
        futs = [engine.submit(t, x) for t, x in zip("aba", xs)]
        engine.flush()
        jax.block_until_ready([f.result().data.lo for f in futs])
        return time.perf_counter() - t0

    # cold = first batch for this (model, shapes) signature: on the scan
    # round-loop backend it pays the whole-replay trace + XLA compile;
    # warm = every batch after, paying only dispatch + execute.  The
    # steady-state serving number (and the --check wall gate) is warm.
    from repro.api.compile import replay_cache_stats
    n_replays = len(replay_cache_stats())
    wall_cold = serve_mix()
    wall_warm = min(serve_mix(), serve_mix())
    replay_entries = replay_cache_stats()[n_replays:]
    st = engine.stats()
    results["engine"] = {
        "mix": [list(sh) for sh in mix],
        "requests": int(st["requests"]),
        "batches": int(st["batches"]),
        "fused_rounds": int(st["fused_rounds"]),
        "serial_rounds": int(st["serial_rounds"]),
        "sched_rounds_pred": sum(r.predicted_rounds for r in engine.reports),
        "sched_bytes_pred": sum(r.predicted_bytes for r in engine.reports),
        "bytes_fused": sum(r.measured_bytes for r in engine.reports),
        "rounds_saved_ratio": round(st["rounds_saved_ratio"], 3),
        "requests_per_s": round(len(mix) / max(wall_warm, 1e-9), 3),
        "p50_queue_wait_ms": round(st["p50_queue_wait_s"] * 1e3, 3),
        "p50_execute_ms": round(st["p50_execute_s"] * 1e3, 3),
        "round_loop": "scan" if replay_entries else "python",
        "wall_s": round(wall_warm, 4),
        "wall_s_cold": round(wall_cold, 4),
        "replay_trace_s": round(sum(e["trace_s"] for e in replay_entries), 4),
        "replay_compile_s": round(
            sum(e["compile_s"] for e in replay_entries), 4),
        "replay_signatures": len(replay_entries),
    }

    # private LM (reduced-ring transformer): one qwen smoke block through
    # the registered MPC forward — PWL SiLU, ReLU attention, three Beaver
    # opens per layer.  Measured fused rounds/bytes must EQUAL the plan's
    # schedule prediction (--check gates the equality), alongside the
    # per-token sim latency and the LAN/WAN projections.
    import dataclasses

    from repro import configs as configs_lib
    from repro.models import lm as lm_lib

    lm_cfg = dataclasses.replace(configs_lib.get("qwen1.5-0.5b-smoke"),
                                 n_layers=1)
    lm_params = lm_lib.init(jax.random.PRNGKey(0), lm_cfg)
    lm_seq = 8
    lm_h = jax.random.normal(jax.random.PRNGKey(1),
                             (1, lm_seq, lm_cfg.d_model)) * 0.5
    lm_plan = lm_lib.trace(lm_params, lm_cfg, 1, lm_seq)
    lm_cc = comm_lib.CoalescingComm(comm_lib.CountingComm())
    lm_model = api.compile(
        lambda p, v, relu_fn=None: lm_lib.mpc_reference(p, v, lm_cfg,
                                                        relu_fn=relu_fn),
        lm_params, lm_cfg, lm_plan, api.Session(key=0, comm=lm_cc))
    Xh = lm_model.encrypt(jax.random.PRNGKey(2), lm_h)

    def serve_lm():
        t0 = time.perf_counter()
        out = lm_model(Xh, key=jax.random.PRNGKey(3))
        jax.block_until_ready((out.data.lo, out.data.hi))
        return out, time.perf_counter() - t0

    lm_out, lm_wall = serve_lm()
    lm_ref = np.asarray(lm_lib.mpc_reference(lm_params, lm_h, lm_cfg))
    lm_err = float(np.max(np.abs(lm_out.reveal_np() - lm_ref)))
    lm_sched = lm_plan.schedule()
    results["lm"] = {
        "arch": lm_cfg.name, "n_layers": lm_cfg.n_layers, "seq": lm_seq,
        "n_relu_calls": len(lm_plan.calls), "n_opens": len(lm_plan.opens),
        "fused_rounds": lm_cc.n_rounds,
        "bytes_fused": lm_cc.bytes_tx,
        "sched_rounds_pred": lm_sched.n_rounds,
        "sched_bytes_pred": lm_sched.bytes_tx,
        "max_abs_err_vs_plaintext": round(lm_err, 6),
        "wall_s": round(lm_wall, 4),
        "s_per_token": round(lm_wall / lm_seq, 4),
        "sched_latency_lan_ms_pred": round(
            lm_sched.latency(LAN.bandwidth_bps, LAN.rtt_s) * 1e3, 3),
        "sched_latency_wan_s_pred": round(
            lm_sched.latency(WAN.bandwidth_bps, WAN.rtt_s), 4),
    }

    # protocol-safety counters (the hbcheck gate): non-baselined AST-lint
    # + lock-discipline findings over src/tests, and the canonical ResNet
    # serve_step leakage census — zero collectives may carry an unmasked
    # secret share (needs the 2-device party axis; None on 1 device).
    # --check fails on any finding or unmasked collective.
    from repro.analysis import lint as hb_lint
    from repro.analysis import locks as hb_locks
    from repro.analysis import taint as hb_taint

    hb_findings = hb_lint.lint_paths(
        [os.path.join(_ROOT, "src"), os.path.join(_ROOT, "tests")],
        root=_ROOT)
    hb_findings += hb_locks.check_paths(_ROOT)
    hb_baseline = hb_lint.load_baseline(
        os.path.join(_ROOT, "tools", "hbcheck_baseline.json"))
    hb_new = [f for f in hb_findings if f.key() not in hb_baseline]
    taint_summary = {}
    if jax.device_count() >= 2:
        taint_summary = hb_taint.canonical_resnet_census()
    results["hbcheck"] = {
        "hbcheck_findings": len(hb_new),
        "baselined_findings": len(hb_findings) - len(hb_new),
        "unmasked_collectives": taint_summary.get("unmasked_collectives"),
        "taint_collectives": taint_summary.get("collectives"),
        "taint_cross_check_ok": taint_summary.get("cross_check_ok"),
    }

    results["multigroup"] = {
        **mesh_census,
        "groups": [{"n": n, "k": k, "m": m} for n, k, m in specs],
        "swaps_seed": seed_cm.n_swaps,
        "swaps_fused": fused_cc.n_rounds,
        "swap_reduction": round(seed_cm.n_swaps / max(fused_cc.n_rounds, 1), 2),
        "bytes_seed": seed_cm.bytes_tx,
        "bytes_fused": fused_cc.bytes_tx,
        "sched_rounds_pred": sched.n_rounds,
        "sched_bytes_pred": sched.bytes_tx,
        "sched_latency_lan_ms_pred": round(
            sched.latency(LAN.bandwidth_bps, LAN.rtt_s) * 1e3, 3),
        "sched_latency_wan_s_pred": round(
            sched.latency(WAN.bandwidth_bps, WAN.rtt_s), 4),
        "wall_s_seed": round(wall_seed, 4),
        "wall_s_python_loop": round(wall_python, 4),
        "wall_s_compiled_loop": round(wall_compiled, 6),
        "wall_s_fused": round(wall_compiled, 6),
        "trace_s": round(trace_s, 4),
        "compile_s": round(compile_s, 4),
        "host_s_per_round": round(host_s_per_round, 6),
        "speedup_vs_seed": round(wall_seed / max(wall_compiled, 1e-9), 3),
    }
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    print(json.dumps(results, indent=2, sort_keys=True))
    return results


def chaos(out_path: str = "BENCH_relu.json") -> dict:
    """``--chaos``: the canonical engine request mix under a seeded
    ``FaultPlan`` — transient drops + a corrupted payload below the
    resilient transport, one mid-replay party crash healed by the
    engine's restart hook, and one deadline-shed request.  Asserts the
    recovered outputs are bit-identical to a fault-free run of the SAME
    mix, that the engine's failure accounting matches the injected plan
    exactly, and demonstrates journal-based crash/resume.  Results merge
    into BENCH_relu.json under ``"chaos"``; ``--check`` fails on any
    recorded divergence."""
    import jax
    import numpy as np

    from repro import api, errors
    from repro.configs import RESNET_SMOKE
    from repro.core import beaver, comm as comm_lib, faults, fixed, gmw
    from repro.core import ring, shares
    from repro.core.hummingbird import HBConfig, HBLayer
    from repro.models import resnet
    from repro.serve import InferenceEngine

    rng = np.random.default_rng(0)
    params = resnet.init(jax.random.PRNGKey(0), RESNET_SMOKE)

    def afn(p, v, relu_fn=None):
        return resnet.apply(p, v, RESNET_SMOKE, relu_fn=relu_fn)

    plan = api.trace_plan(afn, params, (2, 3, 8, 8), name="smoke")
    plan = plan.with_hb(HBConfig(
        tuple([HBLayer(k=21, m=13)] * (plan.n_groups - 1)
              + [HBLayer(k=13, m=13)]), plan.group_elements))
    mix = [(2, 3, 8, 8), (2, 3, 8, 8), (1, 3, 8, 8)]
    xs = [rng.uniform(-0.5, 0.5, sh).astype(np.float32) for sh in mix]

    def run_mix(session, **engine_kw):
        engine = InferenceEngine(afn, params, RESNET_SMOKE, plan, session,
                                 **engine_kw)
        futs = [engine.submit(t, x) for t, x in zip("aba", xs)]
        shed_fut = engine.submit("a", xs[0], deadline_s=0.0)
        engine.flush()
        outs = [ring.to_uint64_np(f.result().data) for f in futs]
        return engine, outs, shed_fut

    # fault-free baseline: same Session seed => same request keys
    baseline, want, _ = run_mix(api.Session(key=0))
    n_rounds = int(baseline.stats()["fused_rounds"])

    # seeded chaos: transients within the measured fused timeline + one
    # crash at a mid-replay round, healed by restarting the transport
    fault_plan = faults.FaultPlan.seeded(
        17, n_rounds, drops=2, corrupts=1,
        crash_round=max(1, n_rounds // 2))
    fic = faults.FaultInjectingComm(fault_plan)
    rc = comm_lib.ResilientComm(fic, max_retries=3)
    engine, got, shed_fut = run_mix(
        api.Session(key=0, comm=rc),
        on_party_crash=lambda e: fic.restart())

    bit_identical = all(np.array_equal(a, b) for a, b in zip(got, want))
    st = engine.stats()
    shed_typed = False
    try:
        shed_fut.result()
    except errors.DeadlineExceeded:
        shed_typed = True

    # journal-based crash/resume on a raw fused layer: crash mid-replay,
    # snapshot at the barrier, restart with the journal mounted
    import tempfile
    E, k, m = 512, 21, 13
    x = rng.uniform(-3.5, 3.5, E).astype(np.float32)
    X = shares.share(jax.random.PRNGKey(7), fixed.encode_np(x))
    tr = beaver.gen_relu_triples(jax.random.PRNGKey(8), E, k - m)
    key = jax.random.PRNGKey(9)
    ref = gmw.relu(key, X, tr, comm_lib.SimComm(), k=k, m=m)
    crash_plan = faults.FaultPlan.seeded(0, 8, drops=0, corrupts=0,
                                         crash_round=3)
    jc = faults.JournaledComm(comm_lib.ResilientComm(
        faults.FaultInjectingComm(crash_plan)))
    resume_ok, replayed = False, 0
    with tempfile.TemporaryDirectory() as snap_dir:
        try:
            gmw.relu(key, X, tr, comm_lib.CoalescingComm(jc), k=k, m=m)
        except errors.PartyCrashed:
            jc.snapshot(snap_dir)
            journal = faults.RoundJournal.load(snap_dir)
            jc2 = faults.JournaledComm(comm_lib.ResilientComm(),
                                       journal=journal)
            out = gmw.relu(key, X, tr, comm_lib.CoalescingComm(jc2),
                           k=k, m=m)
            replayed = jc2.replayed
            resume_ok = bool(np.array_equal(ring.to_uint64_np(out),
                                            ring.to_uint64_np(ref)))

    entry = {
        "fault_plan_seed": 17,
        "injected": dict(fic.injected),
        "bit_identical": bit_identical,
        "transport_retries": rc.retries,
        "engine_retries": int(st["retries"]),
        "chaos_retries": rc.retries + int(st["retries"]),
        "chaos_recovery_overhead_bytes": rc.resent_bytes,
        "faults_recovered": int(st["faults_recovered"]),
        "shed": int(st["shed"]),
        "shed_typed": shed_typed,
        "restarts": fic.restarts,
        "resume_bit_identical": resume_ok,
        "resume_replayed_rounds": replayed,
    }
    try:
        with open(out_path) as f:
            results = json.load(f)
    except FileNotFoundError:
        results = {}
    results["chaos"] = entry
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    print(json.dumps({"chaos": entry}, indent=2, sort_keys=True))
    assert bit_identical, "chaos run diverged from the fault-free outputs"
    assert resume_ok, "journal resume diverged from the uninterrupted run"
    return entry


def transport(out_path: str = "BENCH_relu.json") -> dict:
    """``--transport``: the real two-process deployment gate.  Writes a
    smoke job directory, launches BOTH parties as their own OS processes
    (``repro.launch.party_host``) over localhost TCP with an injected
    WAN-style RTT, and records:

    - byte-accounting parity: the socket transport's measured DATA
      payload bytes and round count vs the ``Schedule.framed()``
      prediction (``--check`` fails on ANY divergence — the wire is the
      schedule, exactly);
    - bit-identity: the combined party output shares vs an in-process
      SimComm reference run of the same job;
    - wall-clock vs the schedule's latency projection under the injected
      RTT (gated with a timing-noise tolerance band: the shaped floor is
      hard, the ceiling allows compile + interpreter overhead);
    - requests/s through the asyncio HTTP frontend driving a
      leader/follower engine link over a second real socket pair.

    Results merge into BENCH_relu.json under ``"transport"``.

    One process per accelerator: this process stays off JAX until the
    JAX processes it spawns (the job writer and both parties) have exited.
    """
    import json as json_lib
    import subprocess
    import tempfile
    import threading
    import urllib.request

    import numpy as np

    from repro.transport.socket import free_port

    rtt_ms = 4.0
    env = dict(os.environ,
               PYTHONPATH=os.path.join(_ROOT, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    with tempfile.TemporaryDirectory() as tmp:
        job_dir = os.path.join(tmp, "job")
        subprocess.run(
            [sys.executable, "-c", "import sys; from repro.transport import "
             "write_smoke_job; write_smoke_job(sys.argv[1])", job_dir],
            env=env, cwd=_ROOT, check=True, timeout=600)
        port = free_port()

        def spawn(party, *extra):
            link = (["--listen", f"127.0.0.1:{port}"] if party == 0
                    else ["--peer", f"127.0.0.1:{port}"])
            return subprocess.Popen(
                [sys.executable, "-m", "repro.launch.party_host",
                 "--party", str(party), "--job", job_dir,
                 "--rtt-ms", str(rtt_ms)] + link + list(extra),
                env=env, cwd=_ROOT)

        t0 = time.perf_counter()
        procs = [spawn(0), spawn(1)]
        rcs = [p.wait(timeout=600) for p in procs]
        pair_wall = time.perf_counter() - t0
        if any(rcs):
            raise RuntimeError(f"party_host exit codes {rcs}")

        outs, stats = [], []
        for p in (0, 1):
            with np.load(os.path.join(job_dir, f"out{p}.npz")) as npz:
                outs.append((npz["lo"].copy(), npz["hi"].copy()))
            with open(os.path.join(job_dir, f"stats{p}.json")) as f:
                stats.append(json_lib.load(f))

    # every JAX child has exited: this process may use JAX from here on
    import jax

    from repro import api
    from repro.configs import RESNET_SMOKE
    from repro.core import beaver, ring
    from repro.models import resnet
    from repro.serve import Frontend, InferenceEngine
    from repro import transport as transport_lib

    rng = np.random.default_rng(0)
    params = resnet.init(jax.random.PRNGKey(0), RESNET_SMOKE)

    def afn(p, v, relu_fn=None):
        return resnet.apply(p, v, RESNET_SMOKE, relu_fn=relu_fn)

    plan, X, pool = transport_lib.smoke_job_inputs()
    framed = plan.schedule().framed()
    predicted_latency_s = framed.latency(float("inf"), rtt_ms / 1e3)
    # measured-wall acceptance band: schedule floor (hard physics) up to
    # floor + per-round host budget + one-off startup (process spawn,
    # connect handshake, jit warm-up of both parties) — see
    # Schedule.wall_band.  Tightens with the round count, so per-round
    # host regressions fail --check instead of hiding under the old flat
    # 20x+120s ceiling.
    wall_band = framed.wall_band(float("inf"), rtt_ms / 1e3)

    # in-process SimComm reference of the same job: the bit-identity oracle
    ref_model = api.compile(
        afn, params, RESNET_SMOKE, plan,
        api.Session(key=0, provider=beaver.TriplePool(pool)))
    want = ring.to_uint64_np(
        ref_model(X, key=jax.random.PRNGKey(4)).data)
    got = ring.to_uint64_np(ring.Ring64(
        np.concatenate([outs[0][0], outs[1][0]]),
        np.concatenate([outs[0][1], outs[1][1]])))
    bit_identical = bool(np.array_equal(got, want))

    # HTTP frontend over a leader/follower engine link on a second socket
    fport = transport_lib.free_port()
    follower_served = {}

    def follower():
        session = api.Session.connect(
            1, peer=("127.0.0.1", fport), key=0, session_id="bench",
            plan_digest=plan.digest(), handshake_timeout_s=120.0,
            timeout_s=120.0)
        model = api.compile(afn, params, RESNET_SMOKE, plan, session)
        try:
            follower_served["n"] = transport_lib.serve_follower(
                session.transport, model,
                provider_factory=transport_lib.tenant_provider_factory(
                    0, party=1))
        finally:
            session.transport.close()

    fthread = threading.Thread(target=follower, daemon=True)
    fthread.start()
    session = api.Session.connect(
        0, listen=("127.0.0.1", fport), key=0, session_id="bench",
        plan_digest=plan.digest(), handshake_timeout_s=120.0,
        timeout_s=120.0)
    engine = InferenceEngine(
        afn, params, RESNET_SMOKE, plan, session,
        provider_factory=transport_lib.tenant_provider_factory(0, party=0))
    link = transport_lib.EngineLink(engine)
    frontend = Frontend(engine)
    n_http = 3
    try:
        host, hport = frontend.serve_background("127.0.0.1", 0)
        t0 = time.perf_counter()
        for i, tenant in enumerate("aba"[:n_http]):
            xq = rng.uniform(-0.5, 0.5, (2, 3, 8, 8)).astype(np.float32)
            req = urllib.request.Request(
                f"http://{host}:{hport}/infer", method="POST",
                data=json_lib.dumps({"tenant": tenant,
                                     "x": xq.tolist()}).encode())
            with urllib.request.urlopen(req, timeout=600) as resp:
                assert resp.status == 200
                json_lib.loads(resp.read().decode())
        frontend_wall = time.perf_counter() - t0
    finally:
        frontend.close()
        link.shutdown()
        session.transport.close()
    fthread.join(60.0)

    entry = {
        "rtt_ms_injected": rtt_ms,
        "rounds_measured": [int(s["rounds"]) for s in stats],
        "sched_rounds_pred": framed.n_rounds,
        "payload_bytes_measured": [int(s["payload_bytes"]) for s in stats],
        "sched_bytes_pred": framed.bytes_tx,
        "header_bytes": [int(s["header_bytes"]) for s in stats],
        "bit_identical": bit_identical,
        "wall_s": round(max(float(s["wall_s"]) for s in stats), 4),
        "pair_wall_s": round(pair_wall, 4),
        "predicted_latency_s": round(predicted_latency_s, 4),
        # schedule-derived tolerance band (Schedule.wall_band): the
        # shaped floor is hard; the ceiling is floor + n_rounds x host
        # budget + startup, so it scales with the timeline instead of
        # being a flat multiplier
        "wall_band_s": [round(wall_band[0], 4), round(wall_band[1], 4)],
        "frontend": {
            "requests": n_http,
            "requests_per_s": round(n_http / max(frontend_wall, 1e-9), 3),
            "wall_s": round(frontend_wall, 4),
            "follower_batches": int(follower_served.get("n", 0)),
        },
    }
    try:
        with open(out_path) as f:
            results = json.load(f)
    except FileNotFoundError:
        results = {}
    results["transport"] = entry
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    print(json.dumps({"transport": entry}, indent=2, sort_keys=True))
    assert bit_identical, "two-process run diverged from SimComm reference"
    return entry


def check(path: str = "BENCH_relu.json") -> int:
    """Round-regression gate: fail (non-zero) when the measured fused
    engine used MORE swaps than the round schedule predicts — i.e. the
    engine stopped coalescing/batching the way ``core.schedule`` says it
    should.  (Fewer is also a model bug, but the gate is one-sided so a
    future engine improvement can land before its model update.)

    When the BENCH file carries a mesh-lowered census (>= 2 devices at
    --quick time), the gate is also two-sided on the compiled artifact:
    the mesh replay's collective-permute count must EQUAL the schedule's
    fused-round prediction and its summed payload bytes the predicted
    wire bytes — the compiled HLO is the timeline, not an upper bound."""
    with open(path) as f:
        data = json.load(f)
    failures = []
    entries = [("multigroup", data.get("multigroup", {}), "swaps_fused"),
               ("engine", data.get("engine", {}), "fused_rounds")]
    entries += [(name, c, "rounds")
                for name, c in data.get("configs", {}).items()]
    for name, entry, measured_key in entries:
        measured = entry.get(measured_key)
        pred = entry.get("sched_rounds_pred")
        if measured is None or pred is None:
            failures.append(
                f"{name}: missing {measured_key!r}/'sched_rounds_pred' — "
                f"stale BENCH file? regenerate with --quick")
        elif measured > pred:
            failures.append(
                f"{name}: measured {measured} {measured_key} > "
                f"schedule-predicted {pred}")
    mg = data.get("multigroup", {})
    # wall-clock gates (the compiled round loop's reason to exist): the
    # multi-group layer must beat the frozen seed path by >= 1.5x, and a
    # warm engine batch of the canonical mix must clear 5s / 1 req/s.
    wf, ws = mg.get("wall_s_fused"), mg.get("wall_s_seed")
    if wf is not None and ws is not None and wf * 1.5 > ws:
        failures.append(
            f"multigroup: wall_s_fused={wf}s not >= 1.5x faster than "
            f"wall_s_seed={ws}s (speedup {ws / max(wf, 1e-9):.2f}x) — the "
            f"compiled round loop stopped paying for itself")
    eng_entry = data.get("engine", {})
    eng_wall = eng_entry.get("wall_s")
    if eng_wall is not None and eng_wall >= 5.0:
        failures.append(
            f"engine: warm canonical-mix batch took {eng_wall}s >= 5.0s "
            f"(cold {eng_entry.get('wall_s_cold')}s, replay compile "
            f"{eng_entry.get('replay_compile_s')}s)")
    eng_rps = eng_entry.get("requests_per_s")
    if eng_rps is not None and eng_rps < 1.0:
        failures.append(
            f"engine: warm throughput {eng_rps} requests/s < 1.0 floor")
    mesh_rounds = mg.get("mesh_collective_permutes")
    mesh_bytes = mg.get("mesh_collective_bytes")
    if mesh_rounds is not None:
        if mesh_rounds != mg.get("sched_rounds_pred"):
            failures.append(
                f"multigroup: mesh-lowered HLO has {mesh_rounds} "
                f"collective-permutes != schedule-predicted "
                f"{mg.get('sched_rounds_pred')} fused rounds")
        if mesh_bytes != mg.get("sched_bytes_pred"):
            failures.append(
                f"multigroup: mesh-lowered collective bytes {mesh_bytes} "
                f"!= schedule-predicted {mg.get('sched_bytes_pred')}")
    # private-LM gate (present once --quick ran): the transformer block's
    # measured fused rounds AND bytes must EQUAL the plan's schedule
    # prediction — Beaver opens included, equality not a bound — and the
    # forward must stay within fixed-point tolerance of the plaintext
    # reference
    lm_entry = data.get("lm")
    if lm_entry is not None:
        for meas_key, pred_key, unit in (
                ("fused_rounds", "sched_rounds_pred", "rounds"),
                ("bytes_fused", "sched_bytes_pred", "bytes")):
            meas, pred = lm_entry.get(meas_key), lm_entry.get(pred_key)
            if meas is None or pred is None:
                failures.append(f"lm: missing {meas_key!r}/{pred_key!r} — "
                                f"stale BENCH file? regenerate with --quick")
            elif meas != pred:
                failures.append(
                    f"lm: measured {meas} {unit} != schedule-predicted "
                    f"{pred} — the LM replay and its plan diverged")
        lm_err = lm_entry.get("max_abs_err_vs_plaintext")
        if lm_err is None or lm_err > 0.05:
            failures.append(
                f"lm: max |MPC - plaintext| = {lm_err} exceeds the "
                f"fixed-point tolerance 0.05")
    # hbcheck gate (present once --quick ran with the analysis suite):
    # zero non-baselined protocol-safety findings and zero unmasked-secret
    # collectives in the canonical serve_step lowering
    hb = data.get("hbcheck")
    if hb is not None:
        if hb.get("hbcheck_findings", 0) != 0:
            failures.append(
                f"hbcheck: {hb.get('hbcheck_findings')} non-baselined "
                f"protocol-safety findings (run `python -m "
                f"repro.analysis.hbcheck src tests` for the list)")
        unmasked = hb.get("unmasked_collectives")
        if unmasked not in (None, 0):
            failures.append(
                f"hbcheck: {unmasked} collective(s) in the serve_step "
                f"lowering carry an unmasked secret share")
        if hb.get("taint_cross_check_ok") is False:
            failures.append(
                "hbcheck: taint census walked a different collective set "
                "than collective_census (parser drift)")
    # chaos gate (present once --chaos ran): recovery must be invisible —
    # bit-identical outputs, and every recovery action accounted against
    # the injected plan exactly (transients healed by re-send, the crash
    # by exactly one engine batch retry after restart)
    ch = data.get("chaos")
    if ch is not None:
        inj = ch.get("injected", {})
        transient = sum(inj.get(k, 0) for k in ("drop", "stall", "corrupt"))
        if not ch.get("bit_identical"):
            failures.append("chaos: recovered engine outputs diverged from "
                            "the fault-free run")
        if not ch.get("resume_bit_identical"):
            failures.append("chaos: journal crash/resume outputs diverged "
                            "from the uninterrupted run")
        if ch.get("transport_retries") != transient:
            failures.append(
                f"chaos: {ch.get('transport_retries')} transport re-sends "
                f"!= {transient} injected transient faults")
        if ch.get("engine_retries") != inj.get("crash", 0):
            failures.append(
                f"chaos: {ch.get('engine_retries')} engine batch retries "
                f"!= {inj.get('crash', 0)} injected crashes")
        if ch.get("shed") != 1 or not ch.get("shed_typed"):
            failures.append("chaos: deadline shed not counted/typed "
                            f"(shed={ch.get('shed')}, "
                            f"typed={ch.get('shed_typed')})")
    # transport gate (present once --transport ran): the real two-process
    # wire must MATCH the schedule exactly — byte-accounting parity is an
    # equality, not a bound — and the shaped wall-clock must sit inside
    # the recorded timing-noise tolerance band
    tr = data.get("transport")
    if tr is not None:
        if not tr.get("bit_identical"):
            failures.append("transport: two-process outputs diverged from "
                            "the SimComm reference")
        for party, rounds in enumerate(tr.get("rounds_measured", [])):
            if rounds != tr.get("sched_rounds_pred"):
                failures.append(
                    f"transport: party {party} measured {rounds} rounds "
                    f"!= schedule-predicted {tr.get('sched_rounds_pred')}")
        for party, nbytes in enumerate(tr.get("payload_bytes_measured", [])):
            if nbytes != tr.get("sched_bytes_pred"):
                failures.append(
                    f"transport: party {party} measured {nbytes} payload "
                    f"bytes != framed-schedule {tr.get('sched_bytes_pred')}")
        lo_s, hi_s = tr.get("wall_band_s", (0.0, float("inf")))
        if not (lo_s <= tr.get("wall_s", -1.0) <= hi_s):
            failures.append(
                f"transport: shaped wall {tr.get('wall_s')}s outside the "
                f"tolerance band [{lo_s}, {hi_s}]s (predicted "
                f"{tr.get('predicted_latency_s')}s under "
                f"{tr.get('rtt_ms_injected')}ms injected RTT)")
        fe = tr.get("frontend", {})
        if fe.get("requests_per_s", 0) <= 0 or fe.get("follower_batches",
                                                      0) < 1:
            failures.append(
                f"transport: HTTP frontend served no traffic "
                f"(requests_per_s={fe.get('requests_per_s')}, "
                f"follower_batches={fe.get('follower_batches')})")
    if failures:
        for msg in failures:
            print(f"ROUND-REGRESSION: {msg}", file=sys.stderr)
        return 1
    eng = data.get("engine", {})
    print(f"round gate OK: multigroup swaps_fused={mg.get('swaps_fused')} "
          f"<= sched_rounds_pred={mg.get('sched_rounds_pred')}; engine "
          f"fused_rounds={eng.get('fused_rounds')} <= "
          f"sched_rounds_pred={eng.get('sched_rounds_pred')} "
          f"({eng.get('rounds_saved_ratio')}x rounds saved vs serial)"
          + (f"; mesh HLO census {mesh_rounds} collective-permutes / "
             f"{mesh_bytes} B == schedule" if mesh_rounds is not None
             else " (no mesh census: single device)"))
    if lm_entry is not None:
        print(f"lm gate OK: {lm_entry.get('fused_rounds')} fused rounds / "
              f"{lm_entry.get('bytes_fused')} B == schedule (opens "
              f"included), max err "
              f"{lm_entry.get('max_abs_err_vs_plaintext')} vs plaintext, "
              f"{lm_entry.get('s_per_token')} s/token (sim)")
    if hb is not None:
        print(f"hbcheck gate OK: {hb.get('hbcheck_findings')} findings, "
              f"{hb.get('unmasked_collectives')} unmasked collectives "
              f"of {hb.get('taint_collectives')} in the serve_step "
              f"lowering")
    if ch is not None:
        print(f"chaos gate OK: bit-identical under "
              f"{sum(ch['injected'].values())} injected faults "
              f"({ch['injected']}), {ch['chaos_retries']} retries, "
              f"{ch['chaos_recovery_overhead_bytes']} B recovery overhead, "
              f"resume replayed {ch['resume_replayed_rounds']} rounds")
    if tr is not None:
        print(f"transport gate OK: 2-process wire == schedule "
              f"({tr['sched_rounds_pred']} rounds / "
              f"{tr['sched_bytes_pred']} B exactly), bit-identical, wall "
              f"{tr['wall_s']}s in band {tr['wall_band_s']} under "
              f"{tr['rtt_ms_injected']}ms RTT; HTTP frontend "
              f"{tr['frontend']['requests_per_s']} req/s")
    return 0


def gantt() -> None:
    """Print the fused-round Gantt of the --quick multi-group layer and
    the per-layer Gantt of the smoke-model serving plan."""
    from repro.core import schedule as schedule_lib

    specs = MULTIGROUP_SPECS
    sched = schedule_lib.simulate([(n, k - m, (n, k, m)) for n, k, m in specs])
    print("multi-group relu_many layer "
          f"({', '.join(f'{n}el k={k} m={m}' for n, k, m in specs)}):\n")
    print(sched.gantt())

    import jax
    import jax.numpy as jnp

    from repro.configs import RESNET_SMOKE
    from repro.core.hummingbird import HBConfig, HBLayer
    from repro.models import resnet

    params = jax.eval_shape(lambda k: resnet.init(k, RESNET_SMOKE),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    plan = resnet.trace(params, RESNET_SMOKE, batch=2)
    hb = HBConfig(tuple([HBLayer(k=21, m=13)] * (plan.n_groups - 1)
                        + [HBLayer(k=13, m=13)]), plan.group_elements)
    print(f"\n\nper-layer replay of the {RESNET_SMOKE.name} serving plan "
          "(last group culled):\n")
    print(plan.with_hb(hb).gantt())


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("filter", nargs="?", default=None,
                    help="only run benchmark modules whose name contains this")
    ap.add_argument("--quick", action="store_true",
                    help="CPU-sim ReLU perf tracker; writes BENCH_relu.json")
    ap.add_argument("--chaos", action="store_true",
                    help="chaos gate: re-run the engine mix under a seeded "
                         "FaultPlan (drops, a corrupt payload, a mid-replay "
                         "crash), assert bit-identical recovery, and merge "
                         "the accounting into BENCH_relu.json['chaos']")
    ap.add_argument("--transport", action="store_true",
                    help="real two-process deployment gate: both parties "
                         "as OS processes over localhost TCP under an "
                         "injected RTT + an HTTP-frontend throughput "
                         "probe; merges byte-accounting parity and wall "
                         "vs predicted latency into "
                         "BENCH_relu.json['transport']")
    ap.add_argument("--check", action="store_true",
                    help="round-regression gate over an existing "
                         "BENCH_relu.json: exit 1 when measured fused swaps "
                         "exceed the schedule prediction")
    ap.add_argument("--gantt", action="store_true",
                    help="print the fused-round Gantt of the --quick "
                         "multi-group layer and the smoke serving plan")
    ap.add_argument("--out", default="BENCH_relu.json",
                    help="output path for --quick / input for --check")
    args = ap.parse_args()
    if args.transport and (args.gantt or args.quick or args.chaos):
        # --transport spawns JAX processes; a parent that already ran JAX
        # would hold the accelerator they need
        ap.error("run --transport on its own (with --check at most)")
    if args.quick and ("--xla_force_host_platform_device_count"
                       not in os.environ.get("XLA_FLAGS", "")):
        # the --quick mesh-lowering census needs a party axis of size 2;
        # force two host devices before the first jax init (quick-mode
        # only — classic benchmarks keep the ambient topology; no effect
        # on real accelerators)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2")
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.gantt:
        gantt()
    if args.quick:
        quick(args.out)
    if args.chaos:
        chaos(args.out)
    if args.transport:
        transport(args.out)
    if args.check:
        sys.exit(check(args.out))
    if args.gantt or args.quick or args.chaos or args.transport:
        return
    from benchmarks import (bench_accuracy, bench_breakdown, bench_comm,
                            bench_e2e, bench_roofline, bench_search)
    mods = [bench_comm, bench_e2e, bench_breakdown, bench_search,
            bench_accuracy, bench_roofline]
    print("name,us_per_call,derived")
    failed = []
    for mod in mods:
        if args.filter and args.filter not in mod.__name__:
            continue
        try:
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}")
        except Exception as e:
            print(f"{mod.__name__}_ERROR,0,{type(e).__name__}:{e}")
            failed.append(mod.__name__)
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
