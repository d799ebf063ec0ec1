"""Linear algebra on Z/2^64 with public integer weights.

mod-2^64 matmul via *balanced 8-bit plane decomposition*: shares become 8
signed int8 digit planes, public weights 5 digit planes; the product is a
sum of s8 x s8 -> s32 plane matmuls (MXU-native on TPU) recombined with
64-bit shifts and carries.  This file is the pure-jnp reference; the Pallas
kernel in repro/kernels/ring_matmul.py implements the same contraction with
explicit VMEM blocking.

int32 accumulation safety: |sum_s| <= pairs(s) * K * 128 * 128 with
pairs(s) <= 5, so K <= 2^31 / (5 * 2^14) = 26214 per chunk; larger K is
chunked and the partial results are added in the ring.

The products by public weights (``conv2d_pub``, ``matmul_pub``,
``mul_pub``) each run as one compiled program per operand shapes (and
conv stride and padding): eager, they are hundreds of small dispatches.
The weights are arguments, never constants of the program, so new
weights reuse it.  ``program_counts()`` says how often they were called
and how many programs were traced; ``__wrapped__`` is the eager function.
"""
from __future__ import annotations

import functools
import threading
from typing import Dict

import jax
import jax.numpy as jnp

from . import ring

_MAX_K = 16384  # safe chunk (power of two below the 26214 bound)


def _signed_to_ring64(s32: jax.Array) -> ring.Ring64:
    lo = s32.astype(jnp.uint32)
    hi = jnp.where(s32 < 0, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    return ring.Ring64(lo, hi)


def _matmul_chunk(x: ring.Ring64, w_i32: jax.Array) -> ring.Ring64:
    """x: Ring64 [..., M, K]; w: int32 [K, N] -> Ring64 [..., M, N]."""
    dx = ring.balanced_digits(x)               # (8, ..., M, K) int8
    dw = ring.balanced_digits_i32(w_i32)       # (5, K, N) int8
    # all plane products at int32 accumulation; drop s = i+j >= 8 (2^64 | shift)
    prods = jnp.einsum(
        "i...mk,jkn->ij...mn",
        dx.astype(jnp.int8), dw.astype(jnp.int8),
        preferred_element_type=jnp.int32,
    )
    out = ring.zeros(prods.shape[2:])
    for s in range(8):
        acc = None
        for i in range(8):
            j = s - i
            if 0 <= j < 5:
                p = prods[i, j]
                acc = p if acc is None else acc + p
        if acc is None:
            continue
        out = ring.add(out, ring.lshift(_signed_to_ring64(acc), 8 * s))
    return out


_counts_lock = threading.Lock()
_counts = {"calls": 0, "compiled": 0}


def _count(key: str) -> None:
    with _counts_lock:
        _counts[key] += 1


def program_counts() -> Dict[str, int]:
    """Process totals of the compiled public-weight products: ``calls``
    (every call, traced ones included) and ``compiled`` (programs traced:
    jit cache misses)."""
    with _counts_lock:
        return dict(_counts)


def _compiled(fn, static_argnames=()):
    """``fn`` as one jitted program per operand shapes, counted."""

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        _count("compiled")          # runs only while jit traces
        return fn(*args, **kwargs)

    jitted = jax.jit(traced, static_argnames=static_argnames)

    @functools.wraps(fn)
    def call(*args, **kwargs):
        _count("calls")
        return jitted(*args, **kwargs)

    return call


@_compiled
def matmul_pub(x: ring.Ring64, w_i32: jax.Array) -> ring.Ring64:
    """mod-2^64 matmul of ring values by public int32 weights.

    Linear over shares: applying this to each party's share yields valid
    shares of W @ x (additive homomorphism of the ring).
    """
    k = x.shape[-1]
    assert w_i32.shape[0] == k, (x.shape, w_i32.shape)
    if k <= _MAX_K:
        return _matmul_chunk(x, w_i32)
    out = None
    for start in range(0, k, _MAX_K):
        end = min(k, start + _MAX_K)
        part = _matmul_chunk(x[..., start:end], w_i32[start:end])
        out = part if out is None else ring.add(out, part)
    return out


@_compiled
def mul_pub(x: ring.Ring64, w_i32: jax.Array) -> ring.Ring64:
    """Elementwise mod-2^64 product of ring values by public int32 weights
    that broadcast to ``x``'s shape."""
    return ring.mul_pub(x, jnp.broadcast_to(w_i32, x.shape))


# Secret x secret: both operands decompose to 8 planes, so pairs(s) <= 8
# and |sum_s| <= 8 * K * 128 * 128 -> K <= 2^31 / (8 * 2^14) = 16384; chunk
# one power of two below so the bound is strict even in the worst case.
_MAX_K_RING = 8192


def _matmul_ring_chunk(x: ring.Ring64, y: ring.Ring64) -> ring.Ring64:
    """x: Ring64 [..., M, K]; y: Ring64 [..., K, N] -> Ring64 [..., M, N]."""
    dx = ring.balanced_digits(x)               # (8, ..., M, K) int8
    dy = ring.balanced_digits(y)               # (8, ..., K, N) int8
    prods = jnp.einsum(
        "i...mk,j...kn->ij...mn",
        dx.astype(jnp.int8), dy.astype(jnp.int8),
        preferred_element_type=jnp.int32,
    )
    out = ring.zeros(prods.shape[2:])
    for s in range(8):
        acc = None
        for i in range(8):
            j = s - i
            if 0 <= j < 8:
                p = prods[i, j]
                acc = p if acc is None else acc + p
        if acc is None:
            continue
        out = ring.add(out, ring.lshift(_signed_to_ring64(acc), 8 * s))
    return out


def matmul_ring(x: ring.Ring64, y: ring.Ring64) -> ring.Ring64:
    """mod-2^64 matmul of two ring-valued tensors (batch dims aligned).

    The secret-by-secret counterpart of ``matmul_pub``: both operands are
    full 64-bit ring values, so each decomposes into 8 balanced digit
    planes and the product is the 8x8 plane contraction recombined with
    64-bit shifts.  This is NOT a protocol — it is the local modular
    arithmetic that Beaver-triple matmul reduces to (``gmw`` opens
    ``x - a`` / ``y - b`` and combines public-by-share products with this
    function).
    """
    k = x.shape[-1]
    assert y.shape[-2] == k, (x.shape, y.shape)
    if k <= _MAX_K_RING:
        return _matmul_ring_chunk(x, y)
    out = None
    for start in range(0, k, _MAX_K_RING):
        end = min(k, start + _MAX_K_RING)
        xs = ring.Ring64(x.lo[..., start:end], x.hi[..., start:end])
        ys = ring.Ring64(y.lo[..., start:end, :], y.hi[..., start:end, :])
        part = _matmul_ring_chunk(xs, ys)
        out = part if out is None else ring.add(out, part)
    return out


def im2col(x: ring.Ring64, kh: int, kw: int, stride: int = 1,
           padding: int = 0) -> ring.Ring64:
    """Ring64 [..., C, H, W] -> [..., OH*OW, C*kh*kw] patch matrix (local op)."""

    def _one(a: jax.Array) -> jax.Array:
        if padding:
            pad = [(0, 0)] * (a.ndim - 2) + [(padding, padding)] * 2
            a = jnp.pad(a, pad)
        h, w = a.shape[-2], a.shape[-1]
        oh = (h - kh) // stride + 1
        ow = (w - kw) // stride + 1
        cols = []
        for di in range(kh):
            for dj in range(kw):
                sl = a[..., di:di + stride * oh:stride, dj:dj + stride * ow:stride]
                cols.append(sl.reshape(a.shape[:-2] + (oh * ow,)))
        # (..., C, kh*kw, OH*OW) -> (..., OH*OW, C*kh*kw)
        stacked = jnp.stack(cols, axis=-2)
        moved = jnp.moveaxis(stacked, -1, -3)
        return moved.reshape(moved.shape[:-2] + (moved.shape[-2] * moved.shape[-1],))

    return ring.Ring64(_one(x.lo), _one(x.hi))


@functools.partial(_compiled, static_argnames=("stride", "padding"))
def conv2d_pub(x: ring.Ring64, w_i32: jax.Array, stride: int = 1,
               padding: int = 0) -> ring.Ring64:
    """Ring64 [..., C, H, W] conv by public int32 [Cout, C, kh, kw]."""
    cout, cin, kh, kw = w_i32.shape
    h, w = x.shape[-2], x.shape[-1]
    oh = (h + 2 * padding - kh) // stride + 1
    ow = (w + 2 * padding - kw) // stride + 1
    patches = im2col(x, kh, kw, stride, padding)        # (..., OH*OW, C*kh*kw)
    wmat = w_i32.reshape(cout, cin * kh * kw).T          # (C*kh*kw, Cout)
    out = matmul_pub.__wrapped__(patches, wmat)          # (..., OH*OW, Cout)
    out = ring.Ring64(jnp.moveaxis(out.lo, -1, -2), jnp.moveaxis(out.hi, -1, -2))
    return out.reshape(out.shape[:-1] + (oh, ow))
