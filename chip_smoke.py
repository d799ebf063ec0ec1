#!/usr/bin/env python3
"""Private ResNet-18 inference on a TPU: the repository's chip smoke test.

    python chip_smoke.py [--seed N]       # phases A and B on one chip
    python chip_smoke.py --four-chips     # the party x data mesh phase only

Phase A serves ResNet-18 (CIFAR widths 64-512, 32x32 input, random
weights from ``--seed``) through ``launch.serve.make_inference_engine``
on a default ``api.Session`` (``SimComm``: both parties on the one chip):
three requests from two tenants at the 8-bit window ``HBLayer(k=21,
m=13)``, with the library's defaults (batch policy, round loop), then one
request at the exact 64-bit ring.  It checks measured rounds ==
schedule-predicted rounds for every batch, the revealed logits against
the float32 plaintext forward within a per-logit bound derived from
Theorem 2 and the fixed-point scale (``logit_error_bound``), and one
8-bit request's output shares bit for bit against the same request
replayed serially on the frozen seed protocol ``core.gmw_ref``.

Phase B runs ``relu_many`` on ResNet-18's largest ReLU group over
``CoalescingComm(CountingComm(SimComm))`` -- the generator round loop,
which calls the compiled Pallas ``ks_mask``/``ks_combine``/``beaver_and``
-- and checks it bit for bit against the compiled replay
(``loop="scan"``) and the frozen seed protocol ``core.gmw_ref``, with
counted rounds and bytes equal to ``core.schedule``.  Each kernel of
``kernels.ops`` is also checked bit for bit against ``kernels.ref``.

``--four-chips`` runs only the mesh phase: ``serve_step`` on a
party=2 x data=2 mesh, for ResNet-18's widths with one basic block per
stage (``MESH_DEPTH``), bit for bit against the one-chip ``SimComm``
replay of the same shares, pool and key, and the compiled HLO's
collective-permute census against ``plan.schedule()``.

The script refuses to run without a TPU, or with ``HB_PALLAS_INTERPRET``
set.  The last line of its output is one JSON object with ``"ok"`` and
the device; it is printed only when every check passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

FRAC = 16                    # fixed-point fractional bits (core.fixed)
TRUNC_EPS = 2.0 ** (1 - FRAC)  # |error| of one local share truncation
HB_WINDOW = (21, 13)         # the README quickstart's 8-bit window
MIX = (("alice", 2), ("bob", 2), ("alice", 1))  # canonical request mix
# The mesh serve_step compiles the whole network into one unrolled XLA
# program, whose compile grows with depth (ROADMAP 3.5).  The four-chip
# phase keeps ResNet-18's widths and input with one block per stage: 63
# fused rounds, compiled in 62 s on a v5e 2x2 host.
MESH_DEPTH = (1, 1, 1, 1)


class CheckFailed(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


# ---------------------------------------------------------------------------
# Reference model: random weights on the fixed-point grid
# ---------------------------------------------------------------------------

def fixed_point_params(params):
    """Random weights moved onto the 2^-16 grid the MPC encodes them on,
    with BatchNorm scales set so that folding BN into the conv multiplies
    by exactly 1.  The MPC forward then computes the plaintext forward's
    linear layers exactly, and only share truncation and Theorem 2 can
    separate the two."""
    import jax.numpy as jnp

    def fix(tree):
        if isinstance(tree, dict):
            if {"gamma", "var"} <= set(tree):
                return dict(tree, gamma=jnp.sqrt(tree["var"] + 1e-5))
            return {k: fix(v) for k, v in tree.items()}
        if isinstance(tree, list):
            return [fix(v) for v in tree]
        return jnp.round(tree * 2.0 ** FRAC) / 2.0 ** FRAC

    return fix(params)


def on_grid(x):
    import jax.numpy as jnp
    return jnp.round(x * 2.0 ** FRAC) / 2.0 ** FRAC


def theorem1_scale(params, cfg, x, k: int) -> float:
    """Input scale that keeps every ReLU input inside half of Theorem 1's
    exact range |v| < 2^(k-1-FRAC) (the other half is headroom for the
    MPC's fixed-point drift).  1.0 where random-init activations already
    fit; the network has no biases, so scaling the input scales every
    activation by the same factor."""
    import jax
    import jax.numpy as jnp
    from repro.models import resnet

    def peak(params, x):
        pre = []

        def record(v, g):
            pre.append(jnp.max(jnp.abs(v)))
            return jax.nn.relu(v)

        resnet.apply(params, x, cfg, relu_fn=record)
        return jnp.max(jnp.stack(pre))

    with jax.default_matmul_precision("float32"):
        top = float(jax.jit(peak)(params, x))
    return min(1.0, 2.0 ** (k - 2 - FRAC) / top)


def logit_error_bound(params, cfg, x, layers):
    """Per-logit bound on |MPC logits - plaintext logits|, shape (B, C).

    First order in the per-element errors e_i at each ReLU output a_i:
    bound = sum_i |d logit / d a_i| * e_i, plus the pooling and classifier
    truncations.  The e_i come from:

    - the fixed-point scale: the linear layers before a ReLU (a conv, and
      a projection conv on a residual) truncate shares locally, each off
      by less than ``TRUNC_EPS``; weights and input sit on the grid
      (``fixed_point_params``), so nothing else is lost;
    - Theorem 2: with m dropped bits only inputs in [0, 2^(m-FRAC)) can
      lose their sign, each by less than 2^(m-FRAC).  The window is
      widened to |v| < 2^(m-FRAC+1) to cover the drift between the MPC's
      and the plaintext's ReLU inputs.
    """
    import jax
    import jax.numpy as jnp
    from repro.models import resnet

    n_classes = cfg.n_classes

    def bound(params, x):
        pre, groups = [], []

        def record(v, g):
            pre.append(v)
            groups.append(g)
            return jax.nn.relu(v)

        resnet.apply(params, x, cfg, relu_fn=record)

        def perturbed(perts):
            it = iter(perts)
            return resnet.apply(params, x, cfg,
                                relu_fn=lambda v, g: jax.nn.relu(v) + next(it))

        logits, vjp = jax.vjp(perturbed, [jnp.zeros_like(v) for v in pre])
        eps = []
        for v, g in zip(pre, groups):
            m = layers[g].m
            delta = 2.0 ** (m - FRAC) if m else 0.0
            eps.append(2 * TRUNC_EPS
                       + delta * (jnp.abs(v) < 2 * delta).astype(v.dtype))
        cols = []
        for j in range(n_classes):
            (grads,) = vjp(jnp.zeros_like(logits).at[:, j].set(1.0))
            cols.append(sum(jnp.sum(jnp.abs(gr) * e,
                                    axis=tuple(range(1, gr.ndim)))
                            for gr, e in zip(grads, eps)))
        fc_w = params["fc"]["w"]
        head = TRUNC_EPS * (jnp.sum(jnp.abs(fc_w), axis=0) + 1.0)
        return logits, jnp.stack(cols, axis=1) + head[None, :]

    with jax.default_matmul_precision("float32"):
        return jax.jit(bound)(params, x)


# ---------------------------------------------------------------------------
# Phase A: serving through the engine
# ---------------------------------------------------------------------------

def host_peak_gib() -> float:
    """Peak resident memory of this process so far (Linux ru_maxrss)."""
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20


def _check_logits(tag, got, want, tol, log) -> float:
    import numpy as np

    err = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    tol = np.asarray(tol, np.float64)
    log(f"{tag}: max |plaintext logit| {np.abs(want).max():.6g}, max "
        f"|mpc - plaintext| {err.max():.6g}, tolerance "
        f"[{tol.min():.6g}, {tol.max():.6g}], worst err/tol "
        f"{(err / tol).max():.4g}")
    check(bool(np.all(err <= tol)),
          f"{tag}: logits off the plaintext beyond the derived bound")
    return float(err.max())


def gmw_ref_request(params, cfg, hb, x, seed: int, request_id: int):
    """Output shares of request ``request_id`` of ``Session(key=seed)``
    replayed alone with every ReLU on the frozen seed protocol
    ``core.gmw_ref``: the request key, input shares, protocol keys and
    inline triples the serving engine draws for that request."""
    import math

    import jax
    from repro import api
    from repro.core import beaver, comm as comm_lib, gmw_ref
    from repro.core.mpc_tensor import MPCTensor

    key = api.Session(key=seed).request_key(request_id)
    enc_key, key = jax.random.split(key)
    X = MPCTensor.from_plain(enc_key, jax.numpy.asarray(x))
    keys = iter(jax.random.split(key, 256))

    def relu(hs, g):
        layer = hb.layers[g]
        outs = []
        for h in hs:
            n = math.prod(h.shape)
            kt, k = jax.random.split(next(keys))
            tri = beaver.gen_relu_triples(kt, n, layer.width, n_parties=2)
            out = gmw_ref.relu(k, h.data.reshape((2, n)), tri,
                               comm_lib.SimComm(), k=layer.k, m=layer.m)
            outs.append(MPCTensor(out.reshape(h.data.shape), h.frac_bits))
        return outs

    forward = api.resolve_mpc_forward(cfg)
    (out,) = forward(params, [X], cfg, relu, comm_lib.SimComm())
    return out


def phase_serving(cfg, seed: int, log) -> dict:
    """ResNet-``cfg`` through ``make_inference_engine`` on a default
    Session and batch policy: the canonical 3-request mix at the 8-bit
    window (served twice: cold, then warm), and one exact 64-bit
    request."""
    import jax
    import numpy as np
    from repro import api
    from repro.api.compile import replay_cache_stats
    from repro.core import ring
    from repro.core.hummingbird import HBConfig, HBLayer
    from repro.launch.serve import make_inference_engine
    from repro.models import resnet

    out: dict = {}
    params = fixed_point_params(resnet.init(jax.random.PRNGKey(seed), cfg))
    n_groups = resnet.n_relu_groups(cfg)
    hw = cfg.in_hw
    k, m = HB_WINDOW
    xs = [jax.random.normal(jax.random.PRNGKey(seed + 1 + i), (b, 3, hw, hw))
          for i, (_, b) in enumerate(MIX)]
    scale = theorem1_scale(params, cfg, jax.numpy.concatenate(xs), k)
    xs = [on_grid(x * scale) for x in xs]
    log(f"phase A: input scale {scale:.6g} keeps ReLU inputs in Theorem 1's "
        f"range for k={k}")
    out["input_scale"] = scale

    session = api.Session(key=seed)
    log(f"phase A: both parties run on this one device: "
        f"{type(session.comm).__name__} on {jax.devices()[0].device_kind}")
    hb = HBConfig(tuple([HBLayer(k=k, m=m)] * n_groups), (0,) * n_groups)
    engine = make_inference_engine(params, cfg, hb, example_batch=2,
                                   session=session)
    refs = [logit_error_bound(params, cfg, x, hb.layers) for x in xs]
    log(f"phase A: plaintext references and bounds ready; host peak "
        f"{host_peak_gib():.2f} GiB")
    out["hb_max_abs_err"] = 0.0
    n_replays = len(replay_cache_stats())
    for run in ("cold", "warm"):
        t0 = time.perf_counter()
        futures = [engine.submit(t, x, request_id=i + len(MIX) * (run == "warm"))
                   for i, ((t, _), x) in enumerate(zip(MIX, xs))]
        served = [f.result() for f in futures]
        got = [np.asarray(y.reveal_np()) for y in served]
        wall = time.perf_counter() - t0
        if run == "cold":
            loop = ("compiled replay"
                    if len(replay_cache_stats()) > n_replays
                    else "generator loop")
            log(f"phase A: round loop chosen by runtime.loop: {loop}")
            out["round_loop"] = loop
            cold = served
        out[f"hb_{run}_wall_s"] = wall
        log(f"phase A: {run} mix of {len(MIX)} requests: {wall:.4f} s "
            f"wall ({wall / len(MIX):.4f} s per request); host peak "
            f"{host_peak_gib():.2f} GiB")
        for i, (g, (want, tol)) in enumerate(zip(got, refs)):
            e = _check_logits(f"phase A {run} request {i} (k={k}, m={m})",
                              g, want, tol, log)
            out["hb_max_abs_err"] = max(out["hb_max_abs_err"], e)
    for rep in engine.reports:
        log(f"phase A: micro-batch of {rep.n_requests} requests: "
            f"{rep.measured_rounds} rounds measured, "
            f"{rep.predicted_rounds} predicted; {rep.measured_bytes} bytes "
            f"measured, {rep.predicted_bytes} predicted")
        check(rep.measured_rounds == rep.predicted_rounds,
              "phase A: measured rounds != schedule prediction")
    out["hb_rounds"] = max(r.measured_rounds for r in engine.reports)

    # request 2 is the only batch-1 stream
    # of the mix, so batching merged nothing into it: its shares must
    # equal the seed protocol's, bit for bit
    t0 = time.perf_counter()
    ref = gmw_ref_request(params, cfg, hb, xs[2], seed, request_id=2)
    same = np.array_equal(ring.to_uint64_np(cold[2].data),
                          ring.to_uint64_np(ref.data))
    log(f"phase A: request 2 (k={k}, m={m}) output shares "
        f"{'bit-identical' if same else 'DIFFER'} to core.gmw_ref replayed "
        f"alone ({time.perf_counter() - t0:.4f} s)")
    check(same, "phase A: served shares differ from core.gmw_ref")

    exact = make_inference_engine(params, cfg, None, example_batch=1,
                                  session=api.Session(key=seed))
    x1 = xs[2]
    t0 = time.perf_counter()
    got = np.asarray(exact.submit("alice", x1).result().reveal_np())
    out["exact_wall_s"] = time.perf_counter() - t0
    want, tol = logit_error_bound(params, cfg, x1,
                                  (HBLayer(),) * n_groups)
    out["exact_max_abs_err"] = _check_logits(
        "phase A exact request (k=64, m=0)", got, want, tol, log)
    rep = exact.reports[0]
    log(f"phase A: exact request: {rep.measured_rounds} rounds measured, "
        f"{rep.predicted_rounds} predicted ({out['exact_wall_s']:.4f} s "
        f"wall incl. compile); host peak {host_peak_gib():.2f} GiB")
    check(rep.measured_rounds == rep.predicted_rounds,
          "phase A exact: measured rounds != schedule prediction")
    out["exact_rounds"] = rep.measured_rounds
    return out


# ---------------------------------------------------------------------------
# Phase B: compiled Pallas kernels on the generator round loop
# ---------------------------------------------------------------------------

def largest_group_elements(cfg, batch: int) -> int:
    import jax
    from repro.models import resnet

    params = jax.eval_shape(lambda k: resnet.init(k, cfg),
                            jax.ShapeDtypeStruct((2,), jax.numpy.uint32))
    plan = resnet.trace(params, cfg, batch)
    return max(plan.group_elements)


def _kernel_parity(n: int, seed: int, log) -> None:
    """Every ``kernels.ops`` wrapper bit for bit against ``kernels.ref``
    at the group's packed width, for the exact and the 8-bit planes."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ring
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    words = (n + 31) // 32

    def u32(*shape):
        return jnp.asarray(rng.integers(0, 2 ** 32, shape, dtype=np.uint64)
                           .astype(np.uint32))

    def same(tag, got, want):
        for gx, wx in zip(got, want):
            check(np.array_equal(np.asarray(gx), np.asarray(wx)),
                  f"phase B: kernel {tag} differs from kernels.ref")

    for w in (64, HB_WINDOW[0] - HB_WINDOW[1]):
        g, p = u32(2, w, words), u32(2, w, words)
        a, b, c = u32(2, 2 * w, words), u32(2, 2 * w, words), \
            u32(2, 2 * w, words)
        sel = jnp.where(jnp.arange(2)[:, None, None] == 0,
                        jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
        d, e = ops.ks_mask(g, p, a, b, 4)
        same(f"ks_mask w={w}", (d, e), ref.ks_mask(g, p, a, b, 4))
        do, eo = u32(2, 2 * w, words), u32(2, 2 * w, words)
        same(f"ks_combine w={w}",
             ops.ks_combine(d, do, e, eo, a, b, c, sel, g),
             ref.ks_combine(d, do, e, eo, a, b, c, sel, g))
        same(f"beaver_and w={w}", (ops.beaver_and(d, e, a, b, c, sel),),
             (ref.beaver_and(d, e, a, b, c, sel),))
        v = u32(n) & jnp.uint32((1 << min(w, 32)) - 1 if w < 32
                                else 0xFFFFFFFF)
        wp = min(w, 32)
        packed = ops.pack(v, wp)
        vp = jnp.pad(v, (0, (-n) % 32))
        same(f"pack w={wp}", (packed,), (ref.pack(vp, wp)[:, :words],))
        same(f"unpack w={wp}", (ops.unpack(packed, wp, n),), (v,))
    x = ring.Ring64(u32(256, 1152), u32(256, 1152))
    wt = jnp.asarray(rng.integers(-2 ** 15, 2 ** 15, (1152, 128))
                     .astype(np.int32))
    got = ops.ring_matmul(x, wt)
    lo, hi = ref.ring_matmul(ring.balanced_digits(x),
                             ring.balanced_digits_i32(wt))
    same("ring_matmul", (got.lo, got.hi), (lo, hi))
    log("phase B: ks_mask, ks_combine, beaver_and, pack, unpack and "
        "ring_matmul are bit-identical to kernels.ref")


def phase_kernels(cfg, seed: int, log, batch: int = 2) -> dict:
    """``relu_many`` on the largest ReLU group: generator loop (Pallas
    kernels on TPU) == compiled replay == ``gmw_ref``, and counters ==
    schedule, exact and at the 8-bit window."""
    import jax
    import numpy as np
    from repro.core import beaver, comm as comm_lib, fixed, gmw, gmw_ref, \
        ring, schedule, shares
    from repro.runtime import loop as loop_lib

    out: dict = {}
    n = largest_group_elements(cfg, batch)
    log(f"phase B: largest ReLU group: {n} elements (batch {batch})")
    _kernel_parity(n, seed, log)
    rng = np.random.default_rng(seed)
    x = rng.uniform(-4.0, 4.0, n).astype(np.float32)
    X = shares.share(jax.random.PRNGKey(seed), fixed.encode_np(x))
    key = jax.random.PRNGKey(seed + 1)
    for k, m in ((64, 0), HB_WINDOW):
        w = k - m
        tr = beaver.gen_relu_triples(jax.random.PRNGKey(seed + 2), n, w)
        counting = comm_lib.CountingComm(comm_lib.SimComm())
        comm = comm_lib.CoalescingComm(counting)
        check(not loop_lib.compiled_eligible(comm),
              "phase B: the counting comm must run the generator loop")
        t0 = time.perf_counter()
        gen = gmw.relu_many([key], [X], [tr], comm, [(k, m)])[0]
        gen_np = ring.to_uint64_np(gen)
        t_gen = time.perf_counter() - t0

        def scan(key, X, tr, k=k, m=m):
            return gmw.relu_many([key], [X], [tr],
                                 comm_lib.CoalescingComm(comm_lib.SimComm()),
                                 [(k, m)], loop="scan")[0]

        t0 = time.perf_counter()
        scan_np = ring.to_uint64_np(jax.jit(scan)(key, X, tr))
        t_scan = time.perf_counter() - t0
        ref_np = ring.to_uint64_np(
            gmw_ref.relu(key, X, tr, comm_lib.SimComm(), k=k, m=m))
        sched = schedule.simulate([(n, w)])
        log(f"phase B k={k} m={m}: generator loop {t_gen:.4f} s, compiled "
            f"replay {t_scan:.4f} s (both incl. compile); "
            f"{counting.n_swaps} rounds / {counting.bytes_tx} bytes "
            f"counted, {sched.n_rounds} / {sched.bytes_tx} predicted")
        check(np.array_equal(gen_np, scan_np),
              f"phase B k={k} m={m}: generator loop != compiled replay")
        check(np.array_equal(gen_np, ref_np),
              f"phase B k={k} m={m}: generator loop != gmw_ref")
        check(counting.n_swaps == sched.n_rounds
              and list(counting.round_bytes) == list(sched.round_bytes),
              f"phase B k={k} m={m}: counted rounds/bytes != schedule")
        out[f"rounds_k{k}_m{m}"] = counting.n_swaps
    log("phase B: generator loop, compiled replay and gmw_ref are "
        "bit-identical; counters equal the schedule")
    return out


# ---------------------------------------------------------------------------
# Four chips: party x data mesh
# ---------------------------------------------------------------------------

def phase_mesh(cfg, seed: int, mesh, log, batch: int = 2) -> dict:
    """``serve_step`` on a party x data mesh, bit for bit against the
    one-device SimComm replay of each data shard (same shares, pool
    slice and key), and the HLO collective census against the per-shard
    schedule."""
    import jax
    import numpy as np
    from repro import api
    from repro.core import beaver, ring
    from repro.core.hummingbird import HBConfig, HBLayer
    from repro.models import resnet
    from repro.runtime.hlo_analyzer import collective_census

    n_data = mesh.shape["data"]
    params = fixed_point_params(resnet.init(jax.random.PRNGKey(seed), cfg))
    n_groups = resnet.n_relu_groups(cfg)
    hb = HBConfig(tuple([HBLayer(*HB_WINDOW)] * n_groups), (0,) * n_groups)
    plan = resnet.trace(params, cfg, batch)
    plan = plan.with_hb(HBConfig(hb.layers, plan.group_elements))
    model = api.compile(None, params, cfg, plan, api.Session(key=seed))
    hw = cfg.in_hw
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (batch, 3, hw, hw))
    X = model.encrypt(jax.random.PRNGKey(seed + 2), on_grid(x))
    pool = beaver.gen_plan_triples(jax.random.PRNGKey(seed + 3),
                                   plan.triple_specs())
    sharded = beaver.shard_pool(pool, n_data)
    key = jax.random.PRNGKey(seed + 4)

    log(f"mesh: compiling serve_step for {dict(mesh.shape)}")
    t0 = time.perf_counter()
    compiled = jax.jit(model.serve_step(mesh, data_axis="data")).lower(
        params, X.data.lo, X.data.hi, sharded, key).compile()
    t_compile = time.perf_counter() - t0
    log(f"mesh: {dict(mesh.shape)} serve_step compile {t_compile:.4f} s")

    per = batch // n_data
    census = collective_census(compiled.as_text())
    shard_plan = resnet.trace(params, cfg, per)
    sched = shard_plan.with_hb(HBConfig(hb.layers,
                                        shard_plan.group_elements)).schedule()
    got_bytes = [c.bytes * c.count for c in census]
    log(f"mesh: HLO census {len(census)} collectives, "
        f"{sum(got_bytes)} bytes; schedule {sched.n_rounds} rounds, "
        f"{sched.bytes_tx} bytes per shard")
    if [c.bytes for c in census] != list(sched.round_bytes) \
            or any(c.count != 1 for c in census):
        diff = [(i, c.kind, c.bytes, c.count, s) for i, (c, s) in
                enumerate(zip(census, sched.round_bytes))
                if c.bytes != s or c.count != 1]
        log(f"mesh: census differs from the schedule; first "
            f"differences (index, kind, bytes, count, predicted): "
            f"{diff[:10]}")
        check(False, "mesh: HLO collective census != schedule")
    log("mesh: one collective-permute per fused round, bytes as predicted")

    t0 = time.perf_counter()
    m_lo, m_hi = jax.block_until_ready(
        compiled(params, X.data.lo, X.data.hi, sharded, key))
    log(f"mesh: first run {time.perf_counter() - t0:.4f} s")

    ref = model.serve_step()
    t0 = time.perf_counter()
    parts = []
    for d in range(n_data):
        rows = slice(d * per, (d + 1) * per)
        lo, hi = ref(params, X.data.lo[:, rows], X.data.hi[:, rows],
                     jax.tree_util.tree_map(lambda a: a[d], sharded), key)
        parts.append(ring.to_uint64_np(ring.Ring64(lo, hi)))
    want = np.concatenate(parts, axis=1)
    log(f"mesh: one-device SimComm replay of {n_data} shards "
        f"{time.perf_counter() - t0:.4f} s")
    got = ring.to_uint64_np(ring.Ring64(m_lo, m_hi))
    check(np.array_equal(got, want),
          "mesh: serve_step differs from the one-device SimComm replay")
    log("mesh: output shares bit-identical to the one-device SimComm "
        "replay of each data shard")
    return {"rounds": len(census), "compile_s": t_compile}


# ---------------------------------------------------------------------------

def _log(msg: str) -> None:
    print(msg, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the party=2 x data=2 mesh phase")
    args = ap.parse_args(argv)
    if os.environ.get("HB_PALLAS_INTERPRET") is not None:
        _log("HB_PALLAS_INTERPRET is set: refusing to run kernels in "
             "anything but compiled mode")
        return 2

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _log(f"no TPU: JAX sees {dev.platform} devices; nothing was run")
        return 1

    from repro.configs import RESNET18
    from repro.runtime.compile_cache import cache_stats, enable_compile_cache

    _log(f"compile cache: {enable_compile_cache()}")
    _log(f"device: {dev.device_kind}, {jax.device_count()} devices, "
         f"jax {jax.__version__}")
    count = 1
    phases = []
    if args.four_chips:
        if jax.device_count() < 4:
            _log(f"--four-chips needs 4 devices, found {jax.device_count()}")
            return 1
        from repro.launch.mesh import make_mpc_mesh
        phases.append(("mesh", lambda: phase_mesh(
            dataclasses.replace(RESNET18, stage_blocks=MESH_DEPTH),
            args.seed, make_mpc_mesh(n_data=2), _log)))
        count = 4
    else:
        phases.append(("A", lambda: phase_serving(RESNET18, args.seed, _log)))
        phases.append(("B", lambda: phase_kernels(RESNET18, args.seed, _log)))

    from repro.api.compile import replay_cache_stats

    for name, run in phases:
        t0 = time.perf_counter()
        try:
            result = run()
        except Exception as e:
            _log(f"phase {name} FAILED: {type(e).__name__}: {e}")
            return 1
        stats = dev.memory_stats() or {}
        _log(f"phase {name}: {time.perf_counter() - t0:.4f} s wall; "
             f"replay compiles so far {replay_cache_stats()}; compile "
             f"cache {cache_stats()}; peak_bytes_in_use "
             f"{stats.get('peak_bytes_in_use', 'not reported')}; "
             f"{json.dumps(result, sort_keys=True)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
