"""ResNet with basic blocks (ResNet-18, -34): the plain reference forward,
the seeded weights, the work counts and the program's engine for it.

A configuration names this file by its ``model`` key.  Everything but
``engine`` is the benchmark's own and imports nothing of the program.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 1e-5


def block_layout(cfg: Dict) -> List[List[Dict]]:
    """Per stage, per block: input/output channels and stride."""
    cin = cfg["widths"][0]
    stages = []
    for si, (n_blocks, cout) in enumerate(zip(cfg["stage_blocks"],
                                              cfg["widths"])):
        blocks = []
        for bi in range(n_blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            blocks.append({"cin": cin, "cout": cout, "stride": stride,
                           "proj": stride != 1 or cin != cout})
            cin = cout
        stages.append(blocks)
    return stages


def input_shape(cfg: Dict) -> Tuple[int, ...]:
    """One image, channels first."""
    return (cfg["in_channels"], cfg["in_hw"], cfg["in_hw"])


# ---------------------------------------------------------------------------
# Work counts
# ---------------------------------------------------------------------------

def macs_per_image(cfg: Dict) -> int:
    """Multiply-accumulates of one plaintext forward: every convolution
    (3x3 with padding 1, 1x1 projections), and the linear head."""
    hw = cfg["in_hw"]
    total = cfg["widths"][0] * cfg["in_channels"] * 9 * hw * hw     # stem
    for stage in block_layout(cfg):
        for b in stage:
            hw_out = hw // b["stride"]
            total += b["cout"] * b["cin"] * 9 * hw_out * hw_out    # conv1
            total += b["cout"] * b["cout"] * 9 * hw_out * hw_out   # conv2
            if b["proj"]:
                total += b["cout"] * b["cin"] * hw_out * hw_out
            hw = hw_out
    total += block_layout(cfg)[-1][-1]["cout"] * cfg["n_classes"]
    return total


def relu_elements_per_image(cfg: Dict) -> Tuple[int, ...]:
    """ReLU elements per group (stem, then one per stage)."""
    hw = cfg["in_hw"]
    groups = [cfg["widths"][0] * hw * hw]
    for stage in block_layout(cfg):
        n = 0
        for b in stage:
            hw //= b["stride"]
            n += 2 * b["cout"] * hw * hw                # after conv1, after add
        groups.append(n)
    return tuple(groups)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _conv_w(key, cout, cin, k):
    return (jax.random.normal(key, (cout, cin, k, k), jnp.float32)
            * (2.0 / (cin * k * k)) ** 0.5)


def _bn(c):
    # gamma = sqrt(var + eps): BatchNorm multiplies by exactly 1, so
    # folding it into the convolution leaves the weights on the grid
    return {"gamma": jnp.full((c,), np.sqrt(1.0 + BN_EPS), jnp.float32),
            "beta": jnp.zeros((c,), jnp.float32),
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32)}


def init_params(key, cfg: Dict) -> Dict:
    """He-normal weights in the program's pytree layout (stem, bn_stem,
    stages of blocks, fc)."""
    layout = block_layout(cfg)
    ks = iter(jax.random.split(key, 2 + 3 * sum(map(len, layout))))
    params = {"stem": _conv_w(next(ks), cfg["widths"][0],
                              cfg["in_channels"], 3),
              "bn_stem": _bn(cfg["widths"][0]), "stages": []}
    for stage in layout:
        blocks = []
        for b in stage:
            p = {"conv1": _conv_w(next(ks), b["cout"], b["cin"], 3),
                 "bn1": _bn(b["cout"]),
                 "conv2": _conv_w(next(ks), b["cout"], b["cout"], 3),
                 "bn2": _bn(b["cout"])}
            pk = next(ks)
            if b["proj"]:
                p["proj"] = _conv_w(pk, b["cout"], b["cin"], 1)
                p["bn_proj"] = _bn(b["cout"])
            blocks.append(p)
        params["stages"].append(blocks)
    cin = layout[-1][-1]["cout"]
    params["fc"] = {"w": jax.random.normal(next(ks), (cin, cfg["n_classes"]),
                                           jnp.float32) * cin ** -0.5,
                    "b": jnp.zeros((cfg["n_classes"],), jnp.float32)}
    return params


# ---------------------------------------------------------------------------
# The plain reference forward
# ---------------------------------------------------------------------------

def _conv(x, w, stride, padding):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(padding, padding)] * 2,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _batchnorm(x, p):
    inv = p["gamma"] / jnp.sqrt(p["var"] + BN_EPS)
    return (x * inv[:, None, None]
            + (p["beta"] - p["mean"] * inv)[:, None, None])


def forward(params, x, relu: Optional[Callable] = None,
            linear: Optional[Callable] = None):
    """Logits.  ``relu(v, group)`` replaces ReLU (group 0 the stem, group
    s+1 stage s); ``linear(v)`` is applied to the output of every
    convolution (BatchNorm included), of the average pool and of the
    head's matmul: the points where the ring truncates."""
    relu = relu or (lambda v, g: jnp.maximum(v, 0.0))
    linear = linear or (lambda v: v)

    def conv_bn(h, w, bn, stride, padding):
        return linear(_batchnorm(_conv(h, w, stride, padding), bn))

    h = relu(conv_bn(x, params["stem"], params["bn_stem"], 1, 1), 0)
    for si, stage in enumerate(params["stages"]):
        for block in stage:
            stride = 2 if ("proj" in block and si > 0) else 1
            y = relu(conv_bn(h, block["conv1"], block["bn1"], stride, 1),
                     si + 1)
            y = conv_bn(y, block["conv2"], block["bn2"], 1, 1)
            if "proj" in block:
                h = conv_bn(h, block["proj"], block["bn_proj"], stride, 0)
            h = relu(h + y, si + 1)
    h = linear(h.mean(axis=(2, 3)))
    return linear(h @ params["fc"]["w"]) + params["fc"]["b"]


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def engine(cfg: Dict, params, hb, example_batch: int, session):
    """The program's ``InferenceEngine`` for this configuration (default
    batch policy and round loop), with the ReLU plan ``hb``."""
    from repro.configs.resnet import ResNetConfig
    from repro.launch.serve import make_inference_engine

    rcfg = ResNetConfig(cfg["name"], "basic", tuple(cfg["stage_blocks"]),
                        tuple(cfg["widths"]), cfg["n_classes"], cfg["in_hw"])
    return make_inference_engine(params, rcfg, hb,
                                 example_batch=example_batch, session=session)
