"""Z/2^64 ring arithmetic in 2xuint32 limbs (TPU-native, no jax_enable_x64).

CrypTen stores secret shares as int64 tensors. TPUs have no fast 64-bit
integer datapath, so we represent every ring element as a pair of uint32
limbs (lo, hi) and implement add/sub/neg/mul/shift with explicit carries.
All operations are elementwise, vectorizable on the 8x128 VPU, and keep the
exact mod-2^64 wraparound semantics that the GMW protocol relies on.

Representation invariant: value = hi * 2^32 + lo  (mod 2^64), both uint32.
Signed interpretation (two's complement over 64 bits) is only applied at
fixed-point decode time; the ring itself is unsigned-modular.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

_U32 = jnp.uint32
_MASK16 = np.uint32(0xFFFF)   # numpy: importing must not touch a device


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Ring64:
    """An array of Z/2^64 elements stored as two uint32 limbs."""

    lo: jax.Array
    hi: jax.Array

    def tree_flatten(self):
        return (self.lo, self.hi), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.lo.shape

    @property
    def ndim(self) -> int:
        return self.lo.ndim

    def reshape(self, *shape) -> "Ring64":
        return Ring64(self.lo.reshape(*shape), self.hi.reshape(*shape))

    def __getitem__(self, idx) -> "Ring64":
        return Ring64(self.lo[idx], self.hi[idx])

    def flatten(self) -> "Ring64":
        return Ring64(self.lo.reshape(-1), self.hi.reshape(-1))


# ---------------------------------------------------------------------------
# Construction
# ---------------------------------------------------------------------------

def zeros(shape, _=None) -> Ring64:
    z = jnp.zeros(shape, _U32)
    return Ring64(z, z)


def from_limbs(lo, hi) -> Ring64:
    return Ring64(jnp.asarray(lo, _U32), jnp.asarray(hi, _U32))


def from_int32(x) -> Ring64:
    """Embed signed 32-bit values into Z/2^64 (two's-complement extend)."""
    x = jnp.asarray(x, jnp.int32)
    lo = x.astype(_U32)
    hi = jnp.where(x < 0, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    return Ring64(lo, hi)


def from_uint64_np(x: np.ndarray) -> Ring64:
    """Host-side constructor from numpy uint64 (tests / checkpoint IO)."""
    x = np.asarray(x, np.uint64)
    lo = (x & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (x >> np.uint64(32)).astype(np.uint32)
    return Ring64(jnp.asarray(lo), jnp.asarray(hi))


def to_uint64_np(x: Ring64) -> np.ndarray:
    lo = np.asarray(jax.device_get(x.lo), np.uint64)
    hi = np.asarray(jax.device_get(x.hi), np.uint64)
    return lo | (hi << np.uint64(32))


def uniform(key, shape) -> Ring64:
    """Uniformly random ring elements (PRG shares / Beaver masks)."""
    k1, k2 = jax.random.split(key)
    lo = jax.random.bits(k1, shape, dtype=_U32)
    hi = jax.random.bits(k2, shape, dtype=_U32)
    return Ring64(lo, hi)


# ---------------------------------------------------------------------------
# Arithmetic (mod 2^64)
# ---------------------------------------------------------------------------

def add(a: Ring64, b: Ring64) -> Ring64:
    lo = a.lo + b.lo
    carry = (lo < a.lo).astype(_U32)
    hi = a.hi + b.hi + carry
    return Ring64(lo, hi)


def sub(a: Ring64, b: Ring64) -> Ring64:
    lo = a.lo - b.lo
    borrow = (a.lo < b.lo).astype(_U32)
    hi = a.hi - b.hi - borrow
    return Ring64(lo, hi)


def neg(a: Ring64) -> Ring64:
    return sub(zeros(a.shape), a)


def _shift64_of_u32(v: jax.Array, s: int) -> Ring64:
    """(uint32 value v) << s as a 64-bit ring element, static s in {0,16,32,48}."""
    if s == 0:
        return Ring64(v, jnp.zeros_like(v))
    if s < 32:
        return Ring64(v << s, v >> (32 - s))
    if s == 32:
        return Ring64(jnp.zeros_like(v), v)
    return Ring64(jnp.zeros_like(v), v << (s - 32))


def mul(a: Ring64, b: Ring64) -> Ring64:
    """Elementwise a*b mod 2^64 via 16-bit half-limb products."""
    a_h = (a.lo & _MASK16, a.lo >> 16, a.hi & _MASK16, a.hi >> 16)
    b_h = (b.lo & _MASK16, b.lo >> 16, b.hi & _MASK16, b.hi >> 16)
    acc = zeros(a.shape)
    for i in range(4):
        for j in range(4 - i):  # i + j <= 3, shift 16*(i+j) < 64
            p = a_h[i] * b_h[j]  # < 2^32, wraps are impossible
            acc = add(acc, _shift64_of_u32(p, 16 * (i + j)))
    return acc


def mul_pub(a: Ring64, w) -> Ring64:
    """Multiply shares by a public signed int32 value (broadcasts)."""
    return mul(a, from_int32(w))


# ---------------------------------------------------------------------------
# Shifts / bit extraction
# ---------------------------------------------------------------------------

def lshift(a: Ring64, n: int) -> Ring64:
    assert 0 <= n < 64
    if n == 0:
        return a
    if n < 32:
        lo = a.lo << n
        hi = (a.hi << n) | (a.lo >> (32 - n))
        return Ring64(lo, hi)
    return Ring64(jnp.zeros_like(a.lo), a.lo << (n - 32) if n > 32 else a.lo)


def rshift_logical(a: Ring64, n: int) -> Ring64:
    assert 0 <= n < 64
    if n == 0:
        return a
    if n < 32:
        lo = (a.lo >> n) | (a.hi << (32 - n))
        hi = a.hi >> n
        return Ring64(lo, hi)
    return Ring64(a.hi >> (n - 32), jnp.zeros_like(a.hi))


def rshift_arith(a: Ring64, n: int) -> Ring64:
    """Arithmetic (sign-extending) right shift of the 64-bit value."""
    if n == 0:
        return a
    sign = (a.hi >> 31).astype(_U32)  # 0 or 1
    shifted = rshift_logical(a, n)
    # fill the top n bits with the sign
    fill = sub(zeros(a.shape), Ring64(sign, jnp.zeros_like(sign)))  # 0 or all-ones
    fill = lshift(fill, 64 - n) if n < 64 else fill
    return Ring64(shifted.lo | fill.lo, shifted.hi | fill.hi)


def bit(a: Ring64, i: int) -> jax.Array:
    """The i-th bit (0 = LSB) as uint32 in {0,1}. Static i."""
    assert 0 <= i < 64
    if i < 32:
        return (a.lo >> i) & jnp.uint32(1)
    return (a.hi >> (i - 32)) & jnp.uint32(1)


def extract_bits(a: Ring64, k: int, m: int) -> jax.Array:
    """x[k:m] per the paper's notation: bits m..k-1, as uint32 (k-m <= 32).

    This is the HummingBird bit-drop: the result is a valid element of the
    reduced ring Z/2^(k-m)Z.  Requires k - m <= 32 so the reduced-ring value
    fits a single native limb (always true for HummingBird configs; use
    extract_planes for the exact w=64 baseline).
    """
    w = k - m
    assert 0 < w <= 32 and 0 <= m and k <= 64
    shifted = rshift_logical(a, m)
    mask = jnp.uint32(0xFFFFFFFF) if w == 32 else jnp.uint32((1 << w) - 1)
    return shifted.lo & mask


def bitplanes_u32(v: jax.Array, w: int) -> jax.Array:
    """(..., ) uint32 -> (w, ...) planes of {0,1} uint32, LSB first."""
    idx = jnp.arange(w, dtype=_U32).reshape((w,) + (1,) * v.ndim)
    return (v[None] >> idx) & jnp.uint32(1)


def extract_planes(a: Ring64, k: int, m: int) -> jax.Array:
    """Bits m..k-1 of a Ring64 as (k-m, ...) {0,1} planes (w up to 64)."""
    assert 0 <= m < k <= 64
    shifted = rshift_logical(a, m)
    w = k - m
    planes = []
    for i in range(w):
        planes.append(bit(shifted, i))
    return jnp.stack(planes, axis=0)


def from_planes(planes: jax.Array) -> Ring64:
    """(w, ...) {0,1} planes, LSB first -> Ring64 (upper bits zero)."""
    w = planes.shape[0]
    lo = jnp.zeros(planes.shape[1:], _U32)
    hi = jnp.zeros(planes.shape[1:], _U32)
    for i in range(min(w, 32)):
        lo = lo | (planes[i].astype(_U32) << i)
    for i in range(32, w):
        hi = hi | (planes[i].astype(_U32) << (i - 32))
    return Ring64(lo, hi)


def is_negative(a: Ring64) -> jax.Array:
    """Sign bit of the 64-bit two's-complement interpretation."""
    return (a.hi >> 31).astype(_U32)


def where(pred: jax.Array, a: Ring64, b: Ring64) -> Ring64:
    return Ring64(jnp.where(pred, a.lo, b.lo), jnp.where(pred, a.hi, b.hi))


# ---------------------------------------------------------------------------
# Balanced 8-bit digit decomposition (for MXU s8 x s8 -> s32 plane matmuls)
# ---------------------------------------------------------------------------

def balanced_digits(a: Ring64, n_digits: int = 8) -> jax.Array:
    """Decompose into n_digits signed digits d_i in [-128, 127] with
    value = sum_i d_i * 2^(8i)  (mod 2^64).  Returns (n_digits, ...) int8.

    Standard balanced-radix-256 rewrite: digits >= 128 borrow one from the
    next byte.  The final carry out of digit 7 is congruent to 0 mod 2^64.
    """
    assert 1 <= n_digits <= 8
    out = []
    carry = jnp.zeros(a.shape, _U32)
    for i in range(n_digits):
        limb = a.lo if i < 4 else a.hi
        byte = (limb >> (8 * (i % 4))) & jnp.uint32(0xFF)
        t = byte + carry  # in [0, 256]
        ge = (t >= 128).astype(_U32)
        d = t.astype(jnp.int32) - 256 * ge.astype(jnp.int32)
        carry = ge
        out.append(d.astype(jnp.int8))
    return jnp.stack(out, axis=0)


def balanced_digits_i32(w: jax.Array) -> jax.Array:
    """Signed int32 public weights -> 5 digits int8 with
    w = sum_{j<5} e_j 2^(8j) (mod 2^64); e_4 in {-1,0,1} absorbs both the
    balanced carry out of byte 3 and the sign extension of w into 64 bits.
    """
    w = jnp.asarray(w, jnp.int32)
    u = w.astype(_U32)
    out = []
    carry = jnp.zeros(w.shape, _U32)
    for j in range(4):
        byte = (u >> (8 * j)) & jnp.uint32(0xFF)
        t = byte + carry
        ge = (t >= 128).astype(_U32)
        d = t.astype(jnp.int32) - 256 * ge.astype(jnp.int32)
        carry = ge
        out.append(d.astype(jnp.int8))
    # w = u - 2^32 * [w < 0]  and  u = sum_{j<4} e_j 2^(8j) + carry*2^32
    e4 = carry.astype(jnp.int32) - (w < 0).astype(jnp.int32)
    out.append(e4.astype(jnp.int8))
    return jnp.stack(out, axis=0)
