"""Jit'd public wrappers for the Pallas kernels.

Dispatch policy: on TPU the Pallas kernels always run compiled — only an
explicit ``interpret=True`` argument to a ``*_pallas`` function puts a
kernel in interpret mode there.  Off TPU the pure-jnp reference runs
unless ``HB_PALLAS_INTERPRET`` selects the Pallas path:

- ``HB_PALLAS_INTERPRET=1``: the Pallas kernels in interpret mode (tests
  use this for the parity sweeps);
- ``HB_PALLAS_INTERPRET=0``: the *non-interpret* Pallas lowering (raises
  on backends without one — CPU today — which the kernel parity tests
  attempt and skip-mark).

Tuning knob (env, read per call):

- ``HB_BLOCK_WORDS=<n>`` overrides the word-dim VMEM tile of the fused
  Kogge-Stone level kernels (multiple of 128; default
  ``gmw_round.BLOCK_WORDS``) — the v5e/v6e BLOCK_WORDS sweep is a config
  sweep, not an edit.  The tile and the kernel mode enter the jit'd
  wrappers as static arguments, so flipping either mid-process retraces
  instead of hitting a stale cache.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.core import ring
from . import bitpack as _bitpack
from . import gmw_round as _gmw_round
from . import ring_matmul as _ring_matmul
from . import ref

_U32 = jnp.uint32


def _mode() -> str:
    """How the kernels run: ``"compiled"`` Pallas (always on TPU, and off
    TPU with ``HB_PALLAS_INTERPRET=0``), ``"interpret"`` Pallas (off TPU
    with ``HB_PALLAS_INTERPRET=1``), else the ``"ref"`` jnp path.  It
    enters every jit'd wrapper as a static argument, so a change retraces."""
    if jax.default_backend() == "tpu":
        return "compiled"
    return {"0": "compiled", "1": "interpret"}.get(
        os.environ.get("HB_PALLAS_INTERPRET", ""), "ref")


def block_words() -> int:
    """The word-dim tile of the fused GMW round kernels: the
    ``HB_BLOCK_WORDS`` override when set and valid (positive multiple of
    128 — the TPU lane count), else ``gmw_round.BLOCK_WORDS``."""
    raw = os.environ.get("HB_BLOCK_WORDS", "")
    try:
        n = int(raw)
    except ValueError:
        return _gmw_round.BLOCK_WORDS
    if n > 0 and n % 128 == 0:
        return n
    return _gmw_round.BLOCK_WORDS


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pack(v: jax.Array, w: int) -> jax.Array:
    """(E,) uint32 -> (w, ceil(E/32)) packed words."""
    return _pack_jit(v, w, _mode())


@functools.partial(jax.jit, static_argnums=(1, 2))
def _pack_jit(v, w, mode):
    n_out = (v.shape[0] + 31) // 32
    if mode != "ref":
        vp = _pad_to(v, 0, 32 * _bitpack.BLOCK_WORDS)
        bw = min(_bitpack.BLOCK_WORDS, vp.shape[0] // 32)
        out = _bitpack.pack_pallas(vp, w, interpret=mode == "interpret",
                                   block_words=bw)
    else:
        vp = _pad_to(v, 0, 32)
        out = ref.pack(vp, w)
    return out[:, :n_out]


def unpack(words: jax.Array, w: int, n_elements: int) -> jax.Array:
    """(w, W) packed words -> (n_elements,) uint32."""
    return _unpack_jit(words, w, n_elements, _mode())


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _unpack_jit(words, w, n_elements, mode):
    if mode != "ref":
        wp = _pad_to(words, 1, _bitpack.BLOCK_WORDS)
        bw = min(_bitpack.BLOCK_WORDS, wp.shape[1])
        out = _bitpack.unpack_pallas(wp, w, interpret=mode == "interpret",
                                     block_words=bw)
    else:
        out = ref.unpack(words, w)
    return out[:n_elements]


@functools.partial(jax.jit, static_argnums=(6, 7))
def _beaver_and_jit(d_open, e_open, a, b, c, sel, mode, bw):
    if mode != "ref":
        # the kernel tiles 2-D (planes, W); fold the party dim into planes
        shape = d_open.shape
        words = shape[-1]
        blk = (_gmw_round.BLOCK[0], min(bw, words + (-words) % 128))
        args = [jnp.broadcast_to(x, shape).reshape(-1, words)
                for x in (d_open, e_open, a, b, c, sel)]
        padded = [_pad_to(_pad_to(x, 0, blk[0]), 1, blk[1]) for x in args]
        out = _gmw_round.beaver_and_pallas(*padded,
                                           interpret=mode == "interpret",
                                           block=blk)
        return out[: args[0].shape[0], :words].reshape(shape)
    return ref.beaver_and(d_open, e_open, a, b, c, sel)


def beaver_and(d_open, e_open, a, b, c, sel):
    """Fused local Beaver-AND evaluation on packed (..., W) words."""
    return _beaver_and_jit(d_open, e_open, a, b, c, sel, _mode(),
                           block_words())


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _ks_mask_jit(g, p, a, b, shift, mode, block):
    if mode != "ref":
        words = g.shape[-1]
        bw = min(block, words + (-words) % 128)
        args = [_pad_to(x, 2, bw) for x in (g, p, a, b)]
        d, e = _gmw_round.ks_mask_pallas(*args, shift,
                                         interpret=mode == "interpret",
                                         block_words=bw)
        return d[..., :words], e[..., :words]
    return ref.ks_mask(g, p, a, b, shift)


def ks_mask(g, p, a, b, shift: int):
    """Fused pre-exchange Kogge-Stone level: plane-shift + lhs/rhs assembly
    + Beaver triple masking in one pass.  Returns the (d, e) wire halves."""
    return _ks_mask_jit(g, p, a, b, shift, _mode(), block_words())


@functools.partial(jax.jit, static_argnums=(9, 10))
def _ks_combine_jit(d, d_other, e, e_other, a, b, c, sel, g, mode, block):
    if mode != "ref":
        words = g.shape[-1]
        bw = min(block, words + (-words) % 128)
        sel_b = jnp.broadcast_to(sel, d.shape)
        args = [_pad_to(x, 2, bw)
                for x in (d, d_other, e, e_other, a, b, c, sel_b, g)]
        g2, p2 = _gmw_round.ks_combine_pallas(*args,
                                              interpret=mode == "interpret",
                                              block_words=bw)
        return g2[..., :words], p2[..., :words]
    return ref.ks_combine(d, d_other, e, e_other, a, b, c, sel, g)


def ks_combine(d, d_other, e, e_other, a, b, c, sel, g):
    """Fused post-exchange Kogge-Stone level: opening XOR + Beaver local
    evaluation + g/p level combine in one pass.  Returns (g', p')."""
    return _ks_combine_jit(d, d_other, e, e_other, a, b, c, sel, g,
                           _mode(), block_words())


def ring_matmul(x: ring.Ring64, w_i32: jax.Array) -> ring.Ring64:
    """Ring64 [M, K] @ public int32 [K, N] -> Ring64 [M, N] (mod 2^64)."""
    return _ring_matmul_jit(x, w_i32, _mode())


@functools.partial(jax.jit, static_argnums=(2,))
def _ring_matmul_jit(x, w_i32, mode):
    dx = ring.balanced_digits(x)            # (8, M, K)
    dw = ring.balanced_digits_i32(w_i32)    # (5, K, N)
    if mode != "ref":
        bm, bk, bn = ((8, 128, 128) if mode == "interpret"
                      else _ring_matmul.DEFAULT_BLOCK)
        m, k = x.shape
        n = w_i32.shape[1]
        dxp = _pad_to(_pad_to(dx, 1, bm), 2, bk)
        dwp = _pad_to(_pad_to(dw, 1, bk), 2, bn)
        lo, hi = _ring_matmul.ring_matmul_pallas(
            dxp, dwp, block=(bm, bk, bn), interpret=mode == "interpret")
        return ring.Ring64(lo[:m, :n], hi[:m, :n])
    lo, hi = ref.ring_matmul(dx, dw)
    return ring.Ring64(lo, hi)
