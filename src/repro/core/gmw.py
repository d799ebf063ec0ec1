"""GMW protocol: A2B, DReLU, B2A, exact ReLU (Eq. 2) and HummingBird's
reduced-ring approximate ReLU (Eq. 3) — round-fused engine.

All functions operate on arrays with a leading party dimension and a
``Comm`` backend (SimComm on one host, MeshComm inside shard_map), so the
same protocol code runs in the search simulator and on the production mesh.

Communication structure (matches §2.2/§2.3 of the paper):
  - A2B prep: each party XOR-shares its arithmetic share      (1 round)
  - adder "Circuit": initial AND + ceil(log2 w) batched ANDs  (1+L rounds)
  - B2A of the sign bit: one Beaver mult on Z/2^64            (1 round)
  - final Mult x*DReLU(x): one Beaver mult on Z/2^64          (1 round)
HummingBird only shrinks the Circuit/prep terms (w = k-m instead of 64),
exactly as the paper's Figure 3/4 describe.

Round-fused engine
------------------
Every protocol primitive here is a *round generator* (``*_rounds``): it
yields exactly one wire payload per communication round and is sent back
the peer's payload.  Two drivers execute the generators:

  - ``drive(gen, comm)``: one ``comm.swap`` per yield — the classic
    single-stream path; rounds and wire bytes are identical to the seed
    implementation (``core/gmw_ref.py``), and exact-path (k=64, m=0)
    outputs are bit-identical to it.
  - ``run_streams(comm, streams)`` / ``relu_many``: N generators advance
    in lockstep and each round's heterogeneous payloads (different widths,
    element counts, even different protocol phases) are coalesced by
    ``comm.CoalescingComm`` into ONE flattened exchange.  Sibling ReLU
    groups therefore share rounds: total rounds = max over groups, not the
    sum, with unchanged total bytes.

Per-round local compute is fused: the dense Kogge-Stone level uses
``kernels.ops.ks_mask`` (plane-shift + Beaver (d, e) masking in one VMEM
pass) before the exchange and ``kernels.ops.ks_combine`` (opening XOR +
Beaver local evaluation + g/p level combine in one pass) after it, instead
of the ~6 separate jnp ops per round the seed path issued.  The
cone-pruned path keeps a compile-time-static position layout: per-plane
tensors tracked in Python dicts at trace time, so XLA sees only static
slices/concats — no runtime ``.at[].set`` scatter.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.runtime.spans import span

from . import beaver, comm as comm_lib, ring, ring_linalg, \
    schedule as schedule_lib, shares
from .schedule import cone_sets  # noqa: F401  (canonical home: core.schedule)

_U32 = jnp.uint32


# ---------------------------------------------------------------------------
# Round-generator drivers
# ---------------------------------------------------------------------------

def drive(gen, comm):
    """Run one round generator to completion: one ``comm.swap`` per round.

    Host spans: ``round.local`` around each step of the generator (the
    local work that makes round ``r``'s payload; for the step that
    returns, ``r`` is one past the last round) and ``round.exchange``
    around each swap."""
    r = 0
    try:
        with span("round.local", round=r):
            payload = gen.send(None)
        while True:
            with span("round.exchange", round=r):
                opened = comm.swap(payload)
            r += 1
            with span("round.local", round=r):
                payload = gen.send(opened)
    except StopIteration as e:
        return e.value


def run_streams(comm, streams: Sequence, on_round=None) -> List:
    """Advance N round generators in lockstep, coalescing each round.

    Every round, all pending streams' payloads are enqueued on a
    ``CoalescingComm`` and fired as ONE flattened exchange; streams that
    finish early (narrower rings -> fewer levels) simply drop out.  Returns
    each stream's result, in order.

    ``on_round(r)``, if given, fires after fused round ``r`` completes —
    i.e. at the round barrier, once every live stream has absorbed the
    exchange.  This is the snapshot/watchdog seam: a
    ``JournaledComm.snapshot`` here makes the execution resumable from
    round ``r``, and the serving engine hangs straggler detection off it.

    Host spans: ``round.local`` around each stream's step (the local work
    that makes its round-``r`` payload; for the step that returns, ``r``
    is one past the stream's last round) and ``round.exchange`` around
    each round's enqueue and flush.
    """
    cc = (comm if isinstance(comm, comm_lib.CoalescingComm)
          else comm_lib.CoalescingComm(comm))
    results: List = [None] * len(streams)
    live = {}
    for i, s in enumerate(streams):
        try:
            with span("round.local", round=0):
                live[i] = (s, s.send(None))
        except StopIteration as e:  # zero-round stream
            results[i] = e.value
    r = 0
    while live:
        with span("round.exchange", round=r):
            handles = {i: cc.enqueue(payload)
                       for i, (_, payload) in live.items()}
            opened = cc.flush()
        nxt = {}
        for i, (s, _) in live.items():
            try:
                with span("round.local", round=r + 1):
                    nxt[i] = (s, s.send(opened[handles[i]]))
            except StopIteration as e:
                results[i] = e.value
        live = nxt
        if on_round is not None:
            on_round(r)
        r += 1
    return results


def _sel_mask(comm, template: jax.Array) -> jax.Array:
    """All-ones on party 0, zeros on party 1 (Beaver open correction)."""
    return jnp.where(comm.party_is(0, template),
                     jnp.uint32(0xFFFFFFFF), jnp.uint32(0))


# ---------------------------------------------------------------------------
# Secure AND on packed binary shares (one communication round)
# ---------------------------------------------------------------------------

def _and_open_rounds(x, y, triple: beaver.BinTriple, comm):
    """Round generator for z = x & y on XOR-shared packed words."""
    from repro.kernels import ops as kops  # lazy: kernels import core.ring

    with span("relu.adder"):
        d = x ^ triple.a
        e = y ^ triple.b
        payload = jnp.stack([d, e], axis=1)
    opened = yield payload                    # single exchange
    with span("relu.adder"):
        d_open = d ^ opened[:, 0]
        e_open = e ^ opened[:, 1]
        sel = _sel_mask(comm, x)
        # local evaluation fused in one VMEM pass (kernels/gmw_round.py)
        return kops.beaver_and(d_open, e_open, triple.a, triple.b, triple.c,
                               sel)


def and_open(x, y, triple: beaver.BinTriple, comm) -> jax.Array:
    """z = x & y on XOR-shared packed words. One swap (round) of (d, e)."""
    return drive(_and_open_rounds(x, y, triple, comm), comm)


# ---------------------------------------------------------------------------
# Kogge-Stone adder over packed bitplanes -> MSB (sign) of x + y mod 2^w
# ---------------------------------------------------------------------------

def _adder_msb_rounds(xw, yw, triples: beaver.ReluTriples, comm, w: int,
                      cone: bool):
    """Round generator for the MSB of (x + y mod 2^w).

    Dense path: one fused pre-exchange pass (plane-shift + (d, e) masking)
    and one fused post-exchange pass (open + Beaver eval + g/p combine) per
    level.  Cone path: compile-time-static layout — positions live in
    trace-time dicts of per-plane (P, W) tensors, so pruned levels are pure
    static stack/slice, never a runtime scatter.
    """
    from repro.kernels import ops as kops

    with span("relu.adder"):
        p0 = xw ^ yw                  # initial propagate (local)
        if w == 1:
            return p0[..., 0, :]
    L = beaver.n_levels(w)
    if not cone:
        g = yield from _and_open_rounds(xw, yw, triples.bin_init, comm)
        p = p0
        with span("relu.adder"):
            sel = _sel_mask(comm, xw)
        for lvl in range(L):
            d = 1 << lvl
            with span("relu.adder"):
                tri = jax.tree_util.tree_map(lambda t: t[lvl],
                                             triples.bin_levels)
                # fused: shift + lhs/rhs build + triple masking, one pass
                d_half, e_half = kops.ks_mask(g, p, tri.a, tri.b, d)
                payload = jnp.stack([d_half, e_half], axis=1)
            opened = yield payload                              # one round
            with span("relu.adder"):
                # fused: opening XOR + Beaver eval + level combine, one pass
                g, p = kops.ks_combine(d_half, opened[:, 0], e_half,
                                       opened[:, 1], tri.a, tri.b, tri.c, sel,
                                       g)
        # carry into bit (w-1) is prefix-generate of bit (w-2)
        with span("relu.adder"):
            return p0[..., w - 1, :] ^ g[..., w - 2, :]

    init_pos, level_sets = cone_sets(w)
    # static cone layout: dense sub-plane tensors per level, positions are
    # Python-side metadata (g_map/p_map) resolved entirely at trace time
    with span("relu.adder"):
        x_init = jnp.stack([xw[..., i, :] for i in init_pos], axis=-2)
        y_init = jnp.stack([yw[..., i, :] for i in init_pos], axis=-2)
    g_sub = yield from _and_open_rounds(x_init, y_init, triples.bin_init,
                                        comm)
    with span("relu.adder"):
        g_map = {i: g_sub[..., j, :] for j, i in enumerate(init_pos)}
        p_map = {i: p0[..., i, :] for i in range(w)}
    for lvl in range(L):
        d = 1 << lvl
        pos = level_sets[lvl]
        if not pos:
            continue
        n = len(pos)
        with span("relu.adder"):
            lhs = jnp.stack([p_map[i] for i in pos] * 2, axis=-2)
            rhs = jnp.stack([g_map[i - d] for i in pos] +
                            [p_map[i - d] for i in pos], axis=-2)
        out = yield from _and_open_rounds(lhs, rhs, triples.bin_levels[lvl],
                                          comm)                # one round
        with span("relu.adder"):
            for j, i in enumerate(pos):
                g_map[i] = g_map[i] ^ out[..., j, :]
                p_map[i] = out[..., n + j, :]
    with span("relu.adder"):
        return p0[..., w - 1, :] ^ g_map[w - 2]


def _shift_planes_dyn(x: jax.Array, d) -> jax.Array:
    """Plane shift by a *traced* distance: plane i of the result is plane
    (i - d) of the input, zeros below.  Bit-identical to the static
    ``kernels.ref._shift_planes`` for every d in [0, w]."""
    w = x.shape[-2]
    rolled = jnp.roll(x, d, axis=-2)
    keep = jnp.arange(w, dtype=jnp.int32)[:, None] >= d
    return jnp.where(keep, rolled, jnp.uint32(0))


def _adder_msb_scan(xw, yw, triples: beaver.ReluTriples, comm, w: int):
    """Dense Kogge-Stone MSB extraction with the level loop as ONE
    ``lax.scan`` instead of L unrolled rounds.

    The carry is the (g, p) plane pair — two (P, w, W) uint32 buffers that
    XLA double-buffers (donates) across trips — and the scanned xs are the
    per-level shift distances plus ``triples.bin_levels`` (whose leaves
    already carry the stacked leading L axis).  The exchange stays on the
    ``Comm`` seam *inside* the body: one ``comm.swap`` of the stacked
    (d, e) halves per trip, exactly like the generator path, so wire
    layout and bytes are unchanged.  Level compute reuses the
    ``kernels.ref`` math with the only twist that the plane shift distance
    is traced (``_shift_planes_dyn``) rather than static.

    A scan body fires Python-side comm bookkeeping only once (at trace
    time); ``CoalescingComm.note_rounds`` accounts the remaining L-1
    uniform rounds so measured counters still equal ``schedule.simulate``.
    Its device ops carry the name scope ``relu.adder``.
    """
    from repro.kernels import ref as kref

    with jax.named_scope("relu.adder"):
        p0 = xw ^ yw
        if w == 1:
            return p0[..., 0, :]
        L = beaver.n_levels(w)
        g = and_open(xw, yw, triples.bin_init, comm)
        sel = _sel_mask(comm, xw)
        shifts = jnp.left_shift(jnp.int32(1),
                                jnp.arange(L, dtype=jnp.int32))

        def level(carry, xs):
            g, p = carry
            d_lvl, tri = xs
            lhs = jnp.concatenate([p, p], axis=-2)
            rhs = jnp.concatenate([_shift_planes_dyn(g, d_lvl),
                                   _shift_planes_dyn(p, d_lvl)], axis=-2)
            d_half = lhs ^ tri.a
            e_half = rhs ^ tri.b
            # one round
            opened = comm.swap(jnp.stack([d_half, e_half], axis=1))
            g2, p2 = kref.ks_combine(d_half, opened[:, 0], e_half,
                                     opened[:, 1], tri.a, tri.b, tri.c, sel,
                                     g)
            return (g2, p2), None

        (g, _p), _ = jax.lax.scan(level, (g, p0),
                                  (shifts, triples.bin_levels))
        note = getattr(comm, "note_rounds", None)
        if note is not None:
            note(L - 1)
        return p0[..., w - 1, :] ^ g[..., w - 2, :]


def adder_msb(xw: jax.Array, yw: jax.Array, triples: beaver.ReluTriples,
              comm, w: int, cone: bool = False) -> jax.Array:
    """XOR shares of the MSB of (x + y mod 2^w).

    xw, yw: (P, w, W) packed plane shares of the two addends.
    Returns (P, W) packed shares of the sign plane.

    cone=True prunes every AND outside the backward cone of G[w-2]
    (same round count, ~log(w)/2 x fewer gate-bits on the wire — a
    beyond-paper optimization, see EXPERIMENTS.md §Perf iteration C2).
    """
    return drive(_adder_msb_rounds(xw, yw, triples, comm, w, cone), comm)


# ---------------------------------------------------------------------------
# A2B prep: XOR-share each party's (reduced-ring) arithmetic share
# ---------------------------------------------------------------------------

def _a2b_prepare_rounds(key, v_packed: jax.Array, comm):
    # party-dependent randomness: every party derives the FULL (n_parties,
    # ...) mask array from the shared key and keeps only its own rows via
    # ``comm.party_slice`` — identity on the sim backend (local party dim
    # is already all parties), the local shard on the mesh backend.  The
    # masks are therefore bit-identical across backends by construction.
    with span("relu.a2b"):
        full = jax.random.bits(key, (comm.n_parties,) + v_packed.shape[1:],
                               dtype=_U32)
        # The mask is data-independent, so a compiler may hoist its
        # exchange ahead of earlier rounds.  XOR-ing in ``s & ~s`` (always
        # 0, not folded by XLA) makes it depend on the value it masks: the
        # compiled program keeps the schedule's round order.
        # ``lax.optimization_barrier`` does not: XLA removes it before
        # scheduling, and the exchange is hoisted again (the HLO census
        # tests catch either regression).
        r = comm.party_slice(full)
        if v_packed.size:
            s = v_packed.reshape(-1)[0]
            r = r ^ (s & ~s)
        masked = v_packed ^ r
    other_mask = yield r
    with span("relu.a2b"):
        p0 = comm.party_is(0, v_packed)
        x0_shares = jnp.where(p0, masked, other_mask)   # party0's value
        x1_shares = jnp.where(p0, other_mask, masked)   # party1's value
        return x0_shares, x1_shares


def a2b_prepare(key, v_packed: jax.Array, comm) -> Tuple[jax.Array, jax.Array]:
    """From each party's packed plaintext planes (P, w, W) of its own
    arithmetic share, produce XOR shares of party0's and party1's values
    held by both parties.  One round (mask exchange)."""
    return drive(_a2b_prepare_rounds(key, v_packed, comm), comm)


# ---------------------------------------------------------------------------
# Beaver multiplication on Z/2^64 (one round)
# ---------------------------------------------------------------------------

def _beaver_mul_rounds(x: ring.Ring64, y: ring.Ring64,
                       triple: beaver.ArithTriple, comm,
                       span_name: str = "relu.mult"):
    with span(span_name):
        e = ring.sub(x, triple.a)
        f = ring.sub(y, triple.b)
        ef = ring.Ring64(jnp.stack([e.lo, f.lo], 1),
                         jnp.stack([e.hi, f.hi], 1))
    other = yield ef                                 # single exchange
    with span(span_name):
        e_open = ring.add(e, ring.Ring64(other.lo[:, 0], other.hi[:, 0]))
        f_open = ring.add(f, ring.Ring64(other.lo[:, 1], other.hi[:, 1]))
        z = ring.add(triple.c, ring.add(ring.mul(e_open, triple.b),
                                        ring.mul(f_open, triple.a)))
        p0 = comm.party_is(0, z.lo)
        corr = ring.mul(e_open, f_open)
        return ring.Ring64(jnp.where(p0, ring.add(z, corr).lo, z.lo),
                           jnp.where(p0, ring.add(z, corr).hi, z.hi))


def beaver_mul(x: ring.Ring64, y: ring.Ring64, triple: beaver.ArithTriple,
               comm) -> ring.Ring64:
    return drive(_beaver_mul_rounds(x, y, triple, comm), comm)


def _beaver_matmul_rounds(x: ring.Ring64, y: ring.Ring64,
                          triple: beaver.ArithTriple, comm):
    """Round generator for Z = X @ Y on Ring64 additive shares.

    Beaver matmul (the transformer's secret-by-secret product): with a
    matrix triple (A, B, C = A @ B) of matching shapes, both parties open
    E = X - A and F = Y - B in ONE exchange (the flattened concatenation
    of both differences — (M*K + K*N) ring elements per batch cell, the
    open payload ``schedule.open_timeline`` prices) and combine locally
    with the mod-2^64 plane matmul:

        Z_p = C_p + E @ B_p + A_p @ F + [p == 0] E @ F
    """
    P = x.shape[0]
    e = ring.sub(x, triple.a)
    f = ring.sub(y, triple.b)
    ne = int(jnp.size(e.lo) // P)
    ef = ring.Ring64(
        jnp.concatenate([e.lo.reshape(P, -1), f.lo.reshape(P, -1)], axis=1),
        jnp.concatenate([e.hi.reshape(P, -1), f.hi.reshape(P, -1)], axis=1))
    other = yield ef                                 # single exchange
    e_open = ring.add(e, ring.Ring64(other.lo[:, :ne].reshape(e.lo.shape),
                                     other.hi[:, :ne].reshape(e.hi.shape)))
    f_open = ring.add(f, ring.Ring64(other.lo[:, ne:].reshape(f.lo.shape),
                                     other.hi[:, ne:].reshape(f.hi.shape)))
    z = ring.add(triple.c,
                 ring.add(ring_linalg.matmul_ring(e_open, triple.b),
                          ring_linalg.matmul_ring(triple.a, f_open)))
    p0 = comm.party_is(0, z.lo)
    corr = ring_linalg.matmul_ring(e_open, f_open)
    return ring.Ring64(jnp.where(p0, ring.add(z, corr).lo, z.lo),
                       jnp.where(p0, ring.add(z, corr).hi, z.hi))


def beaver_matmul(x: ring.Ring64, y: ring.Ring64, triple: beaver.ArithTriple,
                  comm) -> ring.Ring64:
    """Z = X @ Y on additive shares; one communication round."""
    return drive(_beaver_matmul_rounds(x, y, triple, comm), comm)


def products_many(specs: Sequence[Tuple[str, ring.Ring64, ring.Ring64,
                                        beaver.ArithTriple]],
                  comm) -> List[ring.Ring64]:
    """Round-shared Beaver products over sibling streams.

    ``specs`` is one ``(kind, x, y, triple)`` per stream with ``kind`` in
    {"mul", "matmul"}; every stream's single opening is coalesced into ONE
    exchange (``comm.CoalescingComm``), so N concurrent secret products —
    across streams and across kinds — cost exactly one fused round.  The
    open payload per stream is 2n ring elements for "mul" and
    ``size(x) + size(y)`` for "matmul" (what ``schedule.open_timeline``
    prices).  Returns per-stream Ring64 results in order.
    """
    gens = []
    for kind, x, y, tri in specs:
        if kind == "mul":
            gens.append(_beaver_mul_rounds(x, y, tri, comm))
        elif kind == "matmul":
            gens.append(_beaver_matmul_rounds(x, y, tri, comm))
        else:
            raise ValueError(f"products_many: unknown kind {kind!r}")
    return run_streams(comm, gens)


# ---------------------------------------------------------------------------
# B2A of a single packed bit plane -> arithmetic shares of the bit
# ---------------------------------------------------------------------------

def _b2a_bit_rounds(bits: jax.Array, triple: beaver.ArithTriple, comm):
    with span("relu.b2a"):
        zeros = jnp.zeros_like(bits)
        p0 = comm.party_is(0, bits)
        x = ring.Ring64(jnp.where(p0, bits, zeros), zeros)
        y = ring.Ring64(jnp.where(p0, zeros, bits), zeros)
    xy = yield from _beaver_mul_rounds(x, y, triple, comm,
                                       span_name="relu.b2a")
    with span("relu.b2a"):
        s = ring.add(ring.Ring64(bits, zeros), ring.neg(ring.lshift(xy, 1)))
        # NB: x + y == (b0, b1) == Ring64(bits, 0) summed across parties
        return s


def b2a_bit(bits: jax.Array, triple: beaver.ArithTriple, comm) -> ring.Ring64:
    """bits: (P, E) XOR shares in {0,1}. Returns Ring64 additive shares.

    b = b0 xor b1 = b0 + b1 - 2*b0*b1; the cross term uses one Beaver mult
    with X = (b0, 0), Y = (0, b1) as trivially-valid arithmetic shares.
    """
    return drive(_b2a_bit_rounds(bits, triple, comm), comm)


# ---------------------------------------------------------------------------
# DReLU / ReLU (exact and reduced-ring)
# ---------------------------------------------------------------------------

def _drelu_rounds(key, x: ring.Ring64, triples: beaver.ReluTriples, comm,
                  k: int, m: int, cone: bool):
    w = k - m
    n = x.shape[-1]
    with span("relu.a2b"):
        if w <= 32:
            v = ring.extract_bits(x, k, m)          # (P, E) uint32, local
            planes = ring.bitplanes_u32(v, w)       # (w, P, E)
        else:
            planes = ring.extract_planes(x, k, m)   # (w, P, E)
        planes = jnp.moveaxis(planes, 0, 1)         # (P, w, E)
        packed = shares.pack_bits(planes)           # (P, w, W)
    x0s, x1s = yield from _a2b_prepare_rounds(key, packed, comm)    # 1 round
    sign_packed = yield from _adder_msb_rounds(x0s, x1s, triples, comm, w,
                                               cone)
    with span("relu.b2a"):
        sign_bits = shares.unpack_bits(sign_packed, n)  # (P, E)
    s = yield from _b2a_bit_rounds(sign_bits, triples.b2a, comm)    # 1 round
    with span("relu.b2a"):
        one = ring.from_int32(jnp.ones((), jnp.int32))
        p0 = comm.party_is(0, s.lo)
        d = ring.Ring64(jnp.where(p0, ring.sub(one, s).lo, ring.neg(s).lo),
                        jnp.where(p0, ring.sub(one, s).hi, ring.neg(s).hi))
        return d


def drelu(key, x: ring.Ring64, triples: beaver.ReluTriples, comm,
          k: int = 64, m: int = 0, cone: bool = False) -> ring.Ring64:
    """Arithmetic shares of DReLU(x) evaluated on the reduced ring [k:m].

    k = 64, m = 0 reproduces the exact CrypTen baseline; k - m << 64 is
    HummingBird's approximation (Eq. 3).  x: Ring64 shares (P, E).
    """
    return drive(_drelu_rounds(key, x, triples, comm, k, m, cone), comm)


def relu_rounds(key, x: ring.Ring64, triples: beaver.ReluTriples, comm,
                k: int = 64, m: int = 0, cone: bool = False):
    """Round generator for one full ReLU — compose with ``run_streams`` to
    share rounds across concurrent ReLU groups."""
    d = yield from _drelu_rounds(key, x, triples, comm, k, m, cone)
    out = yield from _beaver_mul_rounds(x, d, triples.mult, comm)
    return out


def relu(key, x: ring.Ring64, triples: beaver.ReluTriples, comm,
         k: int = 64, m: int = 0, cone: bool = False) -> ring.Ring64:
    """ReLU(x) = x * DReLU(x[k:m])  (Eq. 3; Eq. 2 when k=64, m=0).

    The final multiplication always uses the full-ring share x, only the
    sign estimation is approximated - exactly the paper's formulation.
    """
    return drive(relu_rounds(key, x, triples, comm, k, m, cone), comm)


def relu_scan(key, x: ring.Ring64, triples: beaver.ReluTriples, comm,
              k: int = 64, m: int = 0, cone: bool = False) -> ring.Ring64:
    """One full ReLU with no Python round loop: the ``scan`` backend of
    the compiled round engine (``runtime/loop.py``).

    Same protocol, same wire layout, bit-identical shares to
    ``relu``/``relu_rounds``: prep, the initial AND, B2A and the final
    Beaver mult are single-round primitives (one ``comm.swap`` each), and
    the dense Kogge-Stone level segment — the only multi-round stretch —
    runs as a single ``lax.scan`` (``_adder_msb_scan``).  Under ``jax.jit``
    the whole call is therefore one XLA program whose round structure
    matches ``schedule.stream_timeline`` exactly.  The cone-pruned adder
    keeps its static per-level layout (ragged positions cannot scan) but
    still traces straight through jit as unrolled rounds.  Each phase's
    device ops carry the name scope of its host span (``relu.a2b``,
    ``relu.adder``, ``relu.b2a``, ``relu.mult``).
    """
    w = k - m
    n = x.shape[-1]
    with jax.named_scope("relu.a2b"):
        if w <= 32:
            v = ring.extract_bits(x, k, m)
            planes = ring.bitplanes_u32(v, w)
        else:
            planes = ring.extract_planes(x, k, m)
        planes = jnp.moveaxis(planes, 0, 1)
        packed = shares.pack_bits(planes)
        x0s, x1s = a2b_prepare(key, packed, comm)                   # 1 round
    if cone:
        with jax.named_scope("relu.adder"):
            sign_packed = adder_msb(x0s, x1s, triples, comm, w, cone=True)
    else:
        sign_packed = _adder_msb_scan(x0s, x1s, triples, comm, w)
    with jax.named_scope("relu.b2a"):
        sign_bits = shares.unpack_bits(sign_packed, n)
        s = b2a_bit(sign_bits, triples.b2a, comm)                   # 1 round
        one = ring.from_int32(jnp.ones((), jnp.int32))
        p0 = comm.party_is(0, s.lo)
        d = ring.Ring64(jnp.where(p0, ring.sub(one, s).lo, ring.neg(s).lo),
                        jnp.where(p0, ring.sub(one, s).hi, ring.neg(s).hi))
    with jax.named_scope("relu.mult"):
        return beaver_mul(x, d, triples.mult, comm)                 # 1 round


def relu_many(keys, xs: Sequence[ring.Ring64],
              triples_list: Sequence[Optional[beaver.ReluTriples]], comm,
              kms: Sequence[Tuple[int, int]], cone: bool = False,
              auto_batch: bool = True, loop: str = "python") -> List[ring.Ring64]:
    """Round-shared evaluation of N concurrent ReLU groups.

    Each group may have its own element count and reduced ring (k, m);
    every protocol round across all groups is ONE coalesced exchange, so
    total rounds = max over groups (vs. the sum when evaluated serially)
    with unchanged total bytes.  Width-0 groups (k == m) are the culled
    identity and zero-element groups the empty batch: both cost nothing.

    With ``auto_batch`` (default), sibling groups of identical
    (n_elements, k, m) are merged into ONE stream on the element axis
    before coalescing — one payload and one fused kernel pass per round
    instead of N, with the combined element vector repacked so per-group
    packing padding disappears (bytes can only drop).  Their Beaver
    triples are merged bit-exactly (``beaver.concat_relu_triples``); the
    protocol randomness comes from the first member's key, so *revealed*
    outputs are unchanged (the protocol's internal masks never affect the
    reconstruction) while output share splits differ from per-group
    evaluation.  Ragged groups keep per-payload coalescing.  The timeline
    either way is exactly ``core.schedule.simulate``'s prediction.

    ``loop`` selects the round-loop backend (``runtime/loop.py``): with
    ``"scan"``, a layer that collapses to a single (possibly merged)
    stream runs through ``relu_scan`` — dense adder levels as one
    ``lax.scan`` — instead of the generator driver; heterogeneous sibling
    streams must advance in lockstep to share rounds, so they stay on the
    generator path (which still traces straight through ``jax.jit``).
    Both backends are share-level bit-identical.

    Returns per-group Ring64 results in order.
    """
    if not (len(keys) == len(xs) == len(triples_list) == len(kms)):
        raise ValueError(
            f"relu_many: mismatched lengths keys={len(keys)} xs={len(xs)} "
            f"triples={len(triples_list)} kms={len(kms)}")
    cc = (comm if isinstance(comm, comm_lib.CoalescingComm)
          else comm_lib.CoalescingComm(comm))
    results: List[Optional[ring.Ring64]] = [None] * len(xs)
    groups: dict = {}                     # batch key -> [(i, key, x, tri)]
    for i, (key, x, tr, (k, m)) in enumerate(
            zip(keys, xs, triples_list, kms)):
        n = x.shape[-1]
        if k == m or n == 0:             # culled identity / empty batch
            results[i] = x
            continue
        bkey = (n, k, m) if auto_batch else i
        groups.setdefault(bkey, []).append((i, key, x, tr, k, m))
    stream_args, placements = [], []
    for members in groups.values():
        i0, key0, x0, tr0, k, m = members[0]
        if len(members) == 1:
            stream_args.append((key0, x0, tr0, k, m))
            placements.append([(i0, 0, x0.shape[-1])])
            continue
        n = x0.shape[-1]
        xcat = ring.Ring64(
            jnp.concatenate([e[2].lo for e in members], axis=-1),
            jnp.concatenate([e[2].hi for e in members], axis=-1))
        tcat = beaver.concat_relu_triples([e[3] for e in members],
                                          [n] * len(members), k - m,
                                          cone=cone)
        stream_args.append((key0, xcat, tcat, k, m))
        placements.append([(e[0], j * n, n) for j, e in enumerate(members)])
    if loop == "scan" and len(stream_args) == 1:
        # solo (possibly merged) stream: nothing to coalesce across, so the
        # lockstep generator driver buys nothing — run the scan backend.
        key0, x0, tr0, k, m = stream_args[0]
        outs = [relu_scan(key0, x0, tr0, cc, k=k, m=m, cone=cone)]
    else:
        outs = run_streams(cc, [relu_rounds(key0, x0, tr0, cc, k=k, m=m,
                                            cone=cone)
                                for key0, x0, tr0, k, m in stream_args])
    for slices, out in zip(placements, outs):
        if len(slices) == 1:
            results[slices[0][0]] = out
        else:
            for i, off, n in slices:
                results[i] = out[..., off:off + n]
    return results


def n_rounds(w: int) -> int:
    """Communication rounds for one ReLU: prep + init-AND + levels + B2A +
    mult; 0 for a culled (width-0) identity layer.  Delegates to the
    round-schedule simulator (``core.schedule``)."""
    return schedule_lib.stream_rounds(w)
