"""The public-weight ring products as compiled programs (``ring_linalg``):
each is bit-identical, limb for limb, to its eager function, and a served
request after the first compiles nothing new and calls one program per
linear layer."""
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, serve
from repro.configs import RESNET_SMOKE
from repro.core import ring, ring_linalg
from repro.core.hummingbird import HBConfig, HBLayer
from repro.models import resnet


def _shares(seed, shape):
    return ring.uniform(jax.random.PRNGKey(seed), shape)


def _weights(seed, shape):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, -2 ** 31,
                              2 ** 31 - 1, dtype=jnp.int32)


def _conv(stride, padding, kernel, batch):
    x = _shares(batch, (2, batch, 3, 7, 7))
    w = _weights(kernel, (4, 3, kernel, kernel))
    return ring_linalg.conv2d_pub, (x, w, stride, padding)


def _matmul(k):
    return ring_linalg.matmul_pub, (_shares(0, (2, 3, 2, k)),
                                    _weights(1, (k, 3)))


def _mul(w_shape):
    return ring_linalg.mul_pub, (_shares(0, (2, 3, 4, 5)),
                                 _weights(1, w_shape))


CASES = {
    **{f"conv-s{s}-p{p}-k{k}-b{b}": functools.partial(_conv, s, p, k, b)
       for s, p, k, b in itertools.product((1, 2), (0, 1), (1, 3), (1, 3))},
    "matmul": functools.partial(_matmul, 24),
    "matmul-chunked-k": functools.partial(_matmul, ring_linalg._MAX_K + 40),
    "mul-scalar": functools.partial(_mul, ()),
    "mul-broadcast": functools.partial(_mul, (5,)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiled_product_is_bit_identical_to_eager(case):
    fn, args = CASES[case]()
    got = fn(*args)
    want = fn.__wrapped__(*args)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got.lo), np.asarray(want.lo))
    np.testing.assert_array_equal(np.asarray(got.hi), np.asarray(want.hi))


def _linear_layers(params):
    """Public-weight products of one smoke ResNet request: every conv and
    projection, the global pool's scale and the classifier."""
    convs = 1 + sum(1 for stage in params["stages"] for block in stage
                    for name in block if name.startswith("conv")
                    or name == "proj")
    return convs + 2


def test_second_request_compiles_nothing_and_calls_each_layer_once(
        monkeypatch):
    """On the generator round loop the benchmark cells run, the first
    request compiles the linear programs and the second only calls them."""
    monkeypatch.setenv("HB_ROUND_LOOP", "python")
    params = resnet.init(jax.random.PRNGKey(0), RESNET_SMOKE)

    def afn(p, v, relu_fn=None):
        return resnet.apply(p, v, RESNET_SMOKE, relu_fn=relu_fn)

    plan = api.trace_plan(afn, params, (1, 3, 8, 8), name="smoke")
    plan = plan.with_hb(HBConfig((HBLayer(k=21, m=13),) * plan.n_groups,
                                 plan.group_elements))
    engine = serve.InferenceEngine(afn, params, RESNET_SMOKE, plan,
                                   api.Session(key=0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, 8, 8)) * 0.5
    counts = []
    for tenant in ("alice", "bob"):
        engine.submit(tenant, x)
        engine.flush()
        stats = engine.stats()
        counts.append((stats["linear_program_calls"],
                       stats["linear_programs_compiled"]))
    (calls1, compiled1), (calls2, compiled2) = counts
    assert compiled1 > 0
    assert compiled2 == compiled1
    assert calls2 - calls1 == _linear_layers(params)
