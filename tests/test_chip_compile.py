"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler that ships with jaxlib compiles for
a v5e:2x2 topology it is only told about, and refuses what the chip would
refuse (unaligned tiles, too much VMEM, unsupported reductions) -- faults
the interpret-mode tests cannot see.  Shapes are ResNet-18's: 8 and 64
bit planes over the word count of its largest ReLU group (262,144
elements per image, batch 2), and its conv contractions for
``ring_matmul``.  The wrappers of ``kernels.ops`` are compiled with the
kernel mode they take on a TPU (``"compiled"``), padding included.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import gmw_round, ops

WORDS = 262144 * 2 // 32       # largest group, batch 2, packed
PLANES = [8, 64]               # the 8-bit window and the exact ring


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _u32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("w", PLANES)
def test_ks_mask_compiles_for_v5e(one_chip, w):
    g = _u32((2, w, WORDS), one_chip)
    a = _u32((2, 2 * w, WORDS), one_chip)
    c = ops._ks_mask_jit.lower(g, g, a, a, 4, "compiled",
                               gmw_round.BLOCK_WORDS).compile()
    assert _custom_calls(c) >= 1


@pytest.mark.parametrize("w", PLANES)
def test_ks_combine_compiles_for_v5e(one_chip, w):
    g = _u32((2, w, WORDS), one_chip)
    d = _u32((2, 2 * w, WORDS), one_chip)
    sel = _u32((2, 1, 1), one_chip)
    c = ops._ks_combine_jit.lower(d, d, d, d, d, d, d, sel, g, "compiled",
                                  gmw_round.BLOCK_WORDS).compile()
    assert _custom_calls(c) >= 1


@pytest.mark.parametrize("w", PLANES)
def test_beaver_and_compiles_for_v5e(one_chip, w):
    x = _u32((2, w, WORDS), one_chip)           # (party, planes, words)
    sel = _u32((2, 1, 1), one_chip)
    c = ops._beaver_and_jit.lower(x, x, x, x, x, sel, "compiled",
                                  gmw_round.BLOCK_WORDS).compile()
    assert _custom_calls(c) >= 1


@pytest.mark.parametrize("w", PLANES)
def test_pack_unpack_compile_for_v5e(one_chip, w):
    v = _u32((WORDS * 32,), one_chip)
    c = ops._pack_jit.lower(v, w, "compiled").compile()
    assert _custom_calls(c) >= 1
    words = _u32((w, WORDS), one_chip)
    c = ops._unpack_jit.lower(words, w, WORDS * 32, "compiled").compile()
    assert _custom_calls(c) >= 1


@pytest.mark.parametrize("m,k,n", [(2048, 576, 64),     # stage-1 conv
                                   (32, 4608, 512)])    # stage-4 conv
def test_ring_matmul_compiles_for_v5e(one_chip, m, k, n):
    from repro.core import ring
    x = ring.Ring64(_u32((m, k), one_chip), _u32((m, k), one_chip))
    w = jax.ShapeDtypeStruct((k, n), jnp.int32, sharding=one_chip)
    c = ops._ring_matmul_jit.lower(x, w, "compiled").compile()
    assert _custom_calls(c) >= 1
