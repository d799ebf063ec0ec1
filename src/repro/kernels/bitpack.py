"""Pallas TPU kernel: HummingBird bitpacking (paper §4.2).

Packs the w reduced-ring bitplanes of a batch of uint32 share values into
dense uint32 wire words so the collective payload is exactly w bits per
element.  Layout: value v[32*j + t] contributes bit t of word (i, j) for
plane i.  The inverse (unpack) restores per-element values after the
exchange.

TPU mapping: each grid step loads a (BW, 32) tile of values into VMEM,
emits a (w, BW) tile of packed words.  The shift/mask ladder runs on the
VPU.  Packing loops over the w planes (k - m from the HummingBird config)
with ``fori_loop``: unrolled, a 64-plane pack takes the TPU compiler over
a minute.  Lane-dim tiles are multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_U32 = jnp.uint32
BLOCK_WORDS = 256  # words per grid step; 256*32 = 8192 elements per tile


def _pack_kernel(v_ref, out_ref, *, w: int):
    v = v_ref[...]                                    # (BW, 32) uint32
    shifts = jnp.arange(32, dtype=_U32)[None, :]      # bit position per lane

    def plane(i, carry):
        bits = (v >> i.astype(_U32)) & _U32(1)
        # the 32 lane bits are disjoint, so their int32 sum is their OR
        # (Mosaic has no unsigned reductions); bitcast back to uint32
        word = jax.lax.bitcast_convert_type(bits << shifts, jnp.int32)
        out_ref[pl.ds(i, 1), :] = jax.lax.bitcast_convert_type(
            word.sum(axis=-1, dtype=jnp.int32), _U32)[None, :]
        return carry

    jax.lax.fori_loop(0, w, plane, 0)


def _unpack_kernel(words_ref, out_ref, *, w: int):
    words = words_ref[...]                            # (w, BW)
    shifts = jnp.arange(32, dtype=_U32)[None, :]
    acc = jnp.zeros(words.shape[1:] + (32,), _U32)    # (BW, 32)
    for i in range(w):
        bits = (words[i][:, None] >> shifts) & _U32(1)
        acc = acc | (bits << _U32(i))
    out_ref[...] = acc


def pack_pallas(v: jax.Array, w: int, *, interpret: bool = True,
                block_words: int = BLOCK_WORDS) -> jax.Array:
    """(E,) uint32 values -> (w, W) packed words. E must be a multiple of
    32*block_words (ops.py pads)."""
    n_words = v.shape[0] // 32
    grid = (n_words // block_words,)
    return pl.pallas_call(
        functools.partial(_pack_kernel, w=w),
        out_shape=jax.ShapeDtypeStruct((w, n_words), _U32),
        grid=grid,
        in_specs=[pl.BlockSpec((block_words, 32), lambda j: (j, 0))],
        out_specs=pl.BlockSpec((w, block_words), lambda j: (0, j)),
        interpret=interpret,
    )(v.reshape(n_words, 32))


def unpack_pallas(words: jax.Array, w: int, *, interpret: bool = True,
                  block_words: int = BLOCK_WORDS) -> jax.Array:
    """(w, W) packed words -> (E,) uint32 values (E = 32*W)."""
    n_words = words.shape[1]
    grid = (n_words // block_words,)
    out = pl.pallas_call(
        functools.partial(_unpack_kernel, w=w),
        out_shape=jax.ShapeDtypeStruct((n_words, 32), _U32),
        grid=grid,
        in_specs=[pl.BlockSpec((w, block_words), lambda j: (0, j))],
        out_specs=pl.BlockSpec((block_words, 32), lambda j: (j, 0)),
        interpret=interpret,
    )(words)
    return out.reshape(n_words * 32)
