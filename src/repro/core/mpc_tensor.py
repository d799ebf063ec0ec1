"""MPCTensor: the user-facing secret-shared tensor (CrypTen-equivalent).

Carries Ring64 additive shares with a leading party dimension plus the
fixed-point scale.  Linear ops with public weights are local (no
communication); ReLU runs the GMW protocol with an optional HummingBird
reduced-ring config.  The same object works on the sim backend (party dim
materialised) and inside shard_map on the mesh backend.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.spans import span

from . import beaver, comm as comm_lib, fixed, gmw, ring, ring_linalg, shares
from .hummingbird import HBLayer


def encode_weights(w_f, frac_bits: int = fixed.DEFAULT_FRAC_BITS) -> jax.Array:
    """Public float weights -> fixed-point int32 (|w * 2^f| < 2^31)."""
    return jnp.round(jnp.asarray(w_f, jnp.float32) * (2.0 ** frac_bits)).astype(jnp.int32)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class MPCTensor:
    data: ring.Ring64            # shares, party dim leading
    frac_bits: int = fixed.DEFAULT_FRAC_BITS

    def tree_flatten(self):
        return (self.data,), self.frac_bits

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)

    # -- construction / reveal ------------------------------------------------
    @staticmethod
    def from_plain(key, x_f: jax.Array, n_parties: int = 2,
                   frac_bits: int = fixed.DEFAULT_FRAC_BITS) -> "MPCTensor":
        return MPCTensor(shares.share(key, fixed.encode(x_f, frac_bits), n_parties),
                         frac_bits)

    def reveal(self) -> jax.Array:
        return fixed.decode(shares.reconstruct(self.data), self.frac_bits)

    def reveal_np(self) -> np.ndarray:
        return fixed.decode_np(shares.reconstruct(self.data), self.frac_bits)

    @property
    def shape(self):
        return self.data.shape[1:]          # without the party dim

    # -- local linear ops ------------------------------------------------------
    def __add__(self, other: "MPCTensor") -> "MPCTensor":
        assert self.frac_bits == other.frac_bits
        return MPCTensor(ring.add(self.data, other.data), self.frac_bits)

    def __sub__(self, other: "MPCTensor") -> "MPCTensor":
        assert self.frac_bits == other.frac_bits
        return MPCTensor(ring.sub(self.data, other.data), self.frac_bits)

    # The linear layers on public weights below run inside a ``linear``
    # host span, with ``linear.encode`` (the weights to fixed point),
    # ``linear.matmul`` (the ring product) and ``linear.truncate`` under it.

    def add_public(self, b_f, comm=None) -> "MPCTensor":
        """Add a public constant: only party 0 adds it to its share."""
        with span("linear"):
            comm = comm or comm_lib.SimComm()
            enc = fixed.encode(jnp.broadcast_to(jnp.asarray(b_f, jnp.float32),
                                                self.shape), self.frac_bits)
            p0 = comm.party_is(0, self.data.lo)
            zero = ring.zeros(self.shape)
            lo = jnp.where(p0, enc.lo, zero.lo)
            hi = jnp.where(p0, enc.hi, zero.hi)
            return MPCTensor(ring.add(self.data, ring.Ring64(lo, hi)),
                             self.frac_bits)

    def truncate(self, n: Optional[int] = None) -> "MPCTensor":
        """Fixed-point rescale: arithmetic shift of each signed share
        (SecureML-style local truncation, +-1 LSB error, rare wrap)."""
        n = self.frac_bits if n is None else n
        with span("linear.truncate"):
            return MPCTensor(ring.rshift_arith(self.data, n), self.frac_bits)

    def _public_product(self, w_f, product) -> "MPCTensor":
        """``product(shares, encoded weights)``, truncated back.  The
        products are ``ring_linalg``'s compiled programs, so
        ``linear.matmul`` bounds one dispatch."""
        with span("linear"):
            with span("linear.encode"):
                w = encode_weights(w_f, self.frac_bits)
            with span("linear.matmul"):
                prod = product(self.data, w)
            return MPCTensor(prod, self.frac_bits).truncate()

    def mul_public(self, c_f) -> "MPCTensor":
        return self._public_product(c_f, ring_linalg.mul_pub)

    def matmul_public(self, w_f: jax.Array) -> "MPCTensor":
        """x @ W with public float weights [K, N]; local + truncation."""
        return self._public_product(w_f, ring_linalg.matmul_pub)

    def conv2d_public(self, w_f: jax.Array, stride: int = 1,
                      padding: int = 0) -> "MPCTensor":
        """NCHW conv with public float weights [Cout, Cin, kh, kw]."""
        return self._public_product(w_f, lambda x, w: ring_linalg.conv2d_pub(
            x, w, stride, padding))

    def avg_pool(self, window: int) -> "MPCTensor":
        """Non-overlapping average pooling on [..., C, H, W] (MPC-friendly
        replacement for max pooling, as in the paper's §2.3 setup)."""
        h, w = self.shape[-2], self.shape[-1]
        oh, ow = h // window, w // window

        def _pool(a):
            a = a[..., : oh * window, : ow * window]
            a = a.reshape(a.shape[:-2] + (oh, window, ow, window))
            return a

        with span("linear"):
            lo, hi = _pool(self.data.lo), _pool(self.data.hi)
            acc = ring.zeros(lo.shape[:-4] + (oh, ow))
            for i in range(window):
                for j in range(window):
                    acc = ring.add(acc, ring.Ring64(lo[..., :, i, :, j],
                                                    hi[..., :, i, :, j]))
            summed = MPCTensor(acc, self.frac_bits)
            return summed.mul_public(1.0 / (window * window))

    def global_avg_pool(self) -> "MPCTensor":
        """[..., C, H, W] -> [..., C] mean over spatial dims."""
        with span("linear"):
            h, w = self.shape[-2], self.shape[-1]
            flat = self.data.reshape(self.data.shape[:-2] + (h * w,))
            acc = ring.zeros(flat.shape[:-1])
            for i in range(h * w):
                acc = ring.add(acc, flat[..., i])
            return MPCTensor(acc, self.frac_bits).mul_public(1.0 / (h * w))

    def reshape(self, *shape) -> "MPCTensor":
        return MPCTensor(self.data.reshape((self.data.shape[0],) + tuple(shape)),
                         self.frac_bits)

    def transpose(self, *perm) -> "MPCTensor":
        """Permute the logical axes (the party dim stays leading)."""
        if len(perm) == 1 and isinstance(perm[0], (tuple, list)):
            perm = tuple(perm[0])
        nd = len(self.shape)
        p = (0,) + tuple(a % nd + 1 for a in perm)
        return MPCTensor(ring.Ring64(jnp.transpose(self.data.lo, p),
                                     jnp.transpose(self.data.hi, p)),
                         self.frac_bits)

    def swapaxes(self, a1: int, a2: int) -> "MPCTensor":
        nd = len(self.shape)
        perm = list(range(nd))
        perm[a1 % nd], perm[a2 % nd] = perm[a2 % nd], perm[a1 % nd]
        return self.transpose(*perm)

    def repeat(self, reps: int, axis: int) -> "MPCTensor":
        """``jnp.repeat`` along a logical axis (public structural op)."""
        ax = axis % len(self.shape) + 1
        return MPCTensor(ring.Ring64(jnp.repeat(self.data.lo, reps, axis=ax),
                                     jnp.repeat(self.data.hi, reps, axis=ax)),
                         self.frac_bits)

    def __getitem__(self, idx) -> "MPCTensor":
        """Index/slice the logical axes (party dim untouched)."""
        if not isinstance(idx, tuple):
            idx = (idx,)
        full = (slice(None),) + idx
        return MPCTensor(ring.Ring64(self.data.lo[full], self.data.hi[full]),
                         self.frac_bits)

    # -- secret * secret products ---------------------------------------------
    def mul(self, other: "MPCTensor", key, comm=None,
            triple: Optional[beaver.ArithTriple] = None) -> "MPCTensor":
        """Elementwise secret*secret product (one Beaver open round)."""
        return products_many(["mul"], [key], [self], [other], comm=comm,
                             triples_list=[triple])[0]

    def matmul(self, other: "MPCTensor", key, comm=None,
               triple: Optional[beaver.ArithTriple] = None) -> "MPCTensor":
        """Secret@secret matmul (one matrix-Beaver open round)."""
        return products_many(["matmul"], [key], [self], [other], comm=comm,
                             triples_list=[triple])[0]

    # -- the nonlinear op ------------------------------------------------------
    def relu(self, key, comm=None, hb: HBLayer = HBLayer(),
             triples: Optional[beaver.ReluTriples] = None,
             cone: bool = False) -> "MPCTensor":
        """GMW ReLU; `hb` selects the HummingBird reduced ring (k, m);
        cone=True uses the MSB-cone-pruned adder (beyond-paper).  A width-0
        `hb` (k == m) is the paper's culling mode: ReLU degrades to the
        identity at zero communication."""
        if hb.is_identity:
            return self
        comm = comm or comm_lib.SimComm()
        n = int(np.prod(self.shape))
        flat = self.data.reshape((self.data.shape[0], n))
        if triples is None:
            kt, key = jax.random.split(key)
            triples = beaver.gen_relu_triples(kt, n, hb.width,
                                              n_parties=self.data.shape[0],
                                              cone=cone)
        out = gmw.relu(key, flat, triples, comm, k=hb.k, m=hb.m, cone=cone)
        out = out.reshape((self.data.shape[0],) + tuple(self.shape))
        return MPCTensor(out, self.frac_bits)


def relu_many(keys, tensors: Sequence["MPCTensor"], comm=None,
              hbs: Optional[Sequence[HBLayer]] = None,
              triples_list: Optional[Sequence] = None,
              cone: bool = False, auto_batch: bool = True,
              loop: str = "python") -> list:
    """Round-shared GMW ReLU over sibling MPCTensors.

    All tensors advance through the protocol in lockstep; each round's
    payloads are coalesced into ONE exchange (comm.CoalescingComm), so the
    layer pays max-over-groups rounds instead of the per-tensor sum, with
    no byte increase.  `keys[i]` is consumed exactly like
    ``tensors[i].relu(keys[i], ...)`` would, so ragged groups stay
    bit-identical to per-tensor evaluation.  With ``auto_batch`` (default)
    sibling tensors of identical (element count, k, m) are additionally
    merged into one batched protocol stream (see ``gmw.relu_many``) —
    revealed values unchanged, one payload per round instead of N.
    Identity (width-0) layers and empty tensors pass through.  ``loop``
    selects the round-loop backend (see ``gmw.relu_many`` /
    ``runtime.loop``); both backends are share-level bit-identical.
    """
    comm = comm or comm_lib.SimComm()
    n_t = len(tensors)
    hbs = list(hbs) if hbs is not None else [HBLayer()] * n_t
    triples_list = (list(triples_list) if triples_list is not None
                    else [None] * n_t)
    keys = list(keys)
    if not (len(keys) == n_t == len(hbs) == len(triples_list)):
        raise ValueError(
            f"relu_many: mismatched lengths keys={len(keys)} "
            f"tensors={n_t} hbs={len(hbs)} triples={len(triples_list)}")
    out: list = [None] * n_t
    flats, run_keys, tris, kms, order = [], [], [], [], []
    for i, (t, hb, key, tri) in enumerate(zip(tensors, hbs, keys,
                                              triples_list)):
        if hb.is_identity:
            out[i] = t
            continue
        n = int(np.prod(t.shape))
        if tri is None:
            with span("beaver.triples"):
                kt, key = jax.random.split(key)
                tri = beaver.gen_relu_triples(kt, n, hb.width,
                                              n_parties=t.data.shape[0],
                                              cone=cone)
        flats.append(t.data.reshape((t.data.shape[0], n)))
        run_keys.append(key)
        tris.append(tri)
        kms.append((hb.k, hb.m))
        order.append(i)
    rets = gmw.relu_many(run_keys, flats, tris, comm, kms, cone=cone,
                         auto_batch=auto_batch, loop=loop)
    for j, i in enumerate(order):
        t = tensors[i]
        data = rets[j].reshape((t.data.shape[0],) + tuple(t.shape))
        out[i] = MPCTensor(data, t.frac_bits)
    return out


def stack(tensors: Sequence["MPCTensor"], axis: int = 0) -> "MPCTensor":
    """Stack sibling MPCTensors along a new *logical* axis."""
    fb = tensors[0].frac_bits
    assert all(t.frac_bits == fb for t in tensors)
    ax = axis % (len(tensors[0].shape) + 1) + 1
    lo = jnp.stack([t.data.lo for t in tensors], axis=ax)
    hi = jnp.stack([t.data.hi for t in tensors], axis=ax)
    return MPCTensor(ring.Ring64(lo, hi), fb)


def concat(tensors: Sequence["MPCTensor"], axis: int = 0) -> "MPCTensor":
    """Concatenate sibling MPCTensors along an existing *logical* axis."""
    fb = tensors[0].frac_bits
    assert all(t.frac_bits == fb for t in tensors)
    ax = axis % len(tensors[0].shape) + 1
    lo = jnp.concatenate([t.data.lo for t in tensors], axis=ax)
    hi = jnp.concatenate([t.data.hi for t in tensors], axis=ax)
    return MPCTensor(ring.Ring64(lo, hi), fb)


def products_many(kinds: Sequence[str], keys, xs: Sequence["MPCTensor"],
                  ys: Sequence["MPCTensor"], comm=None,
                  triples_list: Optional[Sequence] = None) -> list:
    """Round-shared secret*secret products over sibling MPCTensor pairs.

    ``kinds[i]`` selects ``"mul"`` (elementwise, equal shapes) or
    ``"matmul"`` (batched, contraction on the trailing pair) for pair i;
    every pair advances through its Beaver protocol in lockstep and the
    single open of each is coalesced into ONE protocol round
    (``gmw.products_many``).  ``keys[i]`` deterministically derives the
    pair's triple when ``triples_list`` leaves it None — the same
    inline-TTP convention as ``MPCTensor.relu``.  Products of two
    ``frac_bits`` operands carry ``2*frac_bits``; the results are locally
    truncated back, so each product costs one +-1 LSB truncation error.
    """
    comm = comm or comm_lib.SimComm()
    n_t = len(xs)
    triples_list = (list(triples_list) if triples_list is not None
                    else [None] * n_t)
    keys = list(keys)
    if not (len(kinds) == n_t == len(ys) == len(keys) == len(triples_list)):
        raise ValueError(
            f"products_many: mismatched lengths kinds={len(kinds)} "
            f"xs={n_t} ys={len(ys)} keys={len(keys)} "
            f"triples={len(triples_list)}")
    specs = []
    for kind, key, x, y, tri in zip(kinds, keys, xs, ys, triples_list):
        assert x.frac_bits == y.frac_bits
        if tri is None:
            n_parties = x.data.shape[0]
            if kind == "matmul":
                tri = beaver.gen_matmul(key, x.shape, y.shape,
                                        n_parties=n_parties)
            elif kind == "mul":
                assert x.shape == y.shape, (x.shape, y.shape)
                tri = beaver.gen_arith(key, x.shape, n_parties=n_parties)
            else:
                raise ValueError(f"unknown product kind {kind!r}")
        specs.append((kind, x.data, y.data, tri))
    rets = gmw.products_many(specs, comm)
    return [MPCTensor(r, x.frac_bits).truncate()
            for r, x in zip(rets, xs)]


MPCTensor.relu_many = staticmethod(relu_many)
MPCTensor.products_many = staticmethod(products_many)
MPCTensor.stack = staticmethod(stack)
