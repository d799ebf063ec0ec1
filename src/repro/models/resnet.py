"""ResNet-18/50 — the paper's own workload (CIFAR-sized stem).

Two evaluation paths over one weight pytree:
  - `apply`: plaintext JAX forward (training, search simulator).
  - `mpc_apply`: secret-shared forward on MPCTensors (GMW conv/ReLU), with
    BatchNorm folded into the preceding conv (inference-time standard) and
    max-pool removed per the paper's §2.3 setup.

ReLU layers are organised into the paper's five groups (stem + 4 stages);
each group takes one HummingBird (k, m) assignment.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.api import register_mpc_forward
from repro.configs.resnet import ResNetConfig
from repro.core import MPCTensor, beaver
from repro.core.hummingbird import HBConfig
from repro.runtime.spans import span


def _conv_init(key, cout, cin, k):
    scale = (2.0 / (cin * k * k)) ** 0.5
    return jax.random.normal(key, (cout, cin, k, k), jnp.float32) * scale


def _bn_init(c):
    return {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,)),
            "mean": jnp.zeros((c,)), "var": jnp.ones((c,))}


def _block_init(key, cin, cout, cfg, stride):
    ks = jax.random.split(key, 4)
    if cfg.block == "basic":
        p = {
            "conv1": _conv_init(ks[0], cout, cin, 3), "bn1": _bn_init(cout),
            "conv2": _conv_init(ks[1], cout, cout, 3), "bn2": _bn_init(cout),
        }
    else:  # bottleneck (expansion 4)
        mid = cout // 4
        p = {
            "conv1": _conv_init(ks[0], mid, cin, 1), "bn1": _bn_init(mid),
            "conv2": _conv_init(ks[1], mid, mid, 3), "bn2": _bn_init(mid),
            "conv3": _conv_init(ks[2], cout, mid, 1), "bn3": _bn_init(cout),
        }
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(ks[3], cout, cin, 1)
        p["bn_proj"] = _bn_init(cout)
    return p


def init(key, cfg: ResNetConfig):
    expansion = 1 if cfg.block == "basic" else 4
    ks = jax.random.split(key, 3 + len(cfg.stage_blocks))
    params: Dict = {
        "stem": _conv_init(ks[0], cfg.widths[0], 3, 3),
        "bn_stem": _bn_init(cfg.widths[0]),
        "stages": [],
    }
    cin = cfg.widths[0]
    for si, (n_blocks, width) in enumerate(zip(cfg.stage_blocks, cfg.widths)):
        cout = width * expansion
        stage = []
        bkeys = jax.random.split(ks[1 + si], n_blocks)
        for bi in range(n_blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            stage.append(_block_init(bkeys[bi], cin, cout, cfg, stride))
            cin = cout
        params["stages"].append(stage)
    params["fc"] = {
        "w": jax.random.normal(ks[-1], (cin, cfg.n_classes)) * cin ** -0.5,
        "b": jnp.zeros((cfg.n_classes,)),
    }
    return params


# ---------------------------------------------------------------------------
# Plaintext path
# ---------------------------------------------------------------------------

def _conv(x, w, stride=1, padding=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(padding, padding)] * 2,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _bn(x, p, eps=1e-5):
    inv = p["gamma"] / jnp.sqrt(p["var"] + eps)
    return x * inv[:, None, None] + (p["beta"] - p["mean"] * inv)[:, None, None]


def fold_bn(conv_w, bn, eps=1e-5):
    """Fold BN into conv: returns (w', b') with conv(x, w') + b' == bn(conv)."""
    inv = bn["gamma"] / jnp.sqrt(bn["var"] + eps)
    w = conv_w * inv[:, None, None, None]
    b = bn["beta"] - bn["mean"] * inv
    return w, b


def apply(params, x, cfg: ResNetConfig, relu_fn=None,
          collect_acts: bool = False):
    """x: (B, 3, H, W) -> logits.  `relu_fn(x, group_idx)` lets the search
    simulator substitute the HummingBird approximate ReLU per group."""
    relu = relu_fn or (lambda v, g: jax.nn.relu(v))
    acts: List[jax.Array] = []

    def _relu(v, g):
        if collect_acts:
            acts.append(v)
        return relu(v, g)

    h = _bn(_conv(x, params["stem"]), params["bn_stem"])
    h = _relu(h, 0)
    for si, stage in enumerate(params["stages"]):
        for block in stage:
            stride = 2 if ("proj" in block and si > 0) else 1
            if "conv3" in block:  # bottleneck
                y = _relu(_bn(_conv(h, block["conv1"], 1, 0), block["bn1"]), si + 1)
                y = _relu(_bn(_conv(y, block["conv2"], stride, 1), block["bn2"]), si + 1)
                y = _bn(_conv(y, block["conv3"], 1, 0), block["bn3"])
            else:
                y = _relu(_bn(_conv(h, block["conv1"], stride, 1), block["bn1"]), si + 1)
                y = _bn(_conv(y, block["conv2"], 1, 1), block["bn2"])
            if "proj" in block:
                h = _bn(_conv(h, block["proj"], stride, 0), block["bn_proj"])
            h = _relu(h + y, si + 1)
    h = h.mean(axis=(2, 3))
    logits = h @ params["fc"]["w"] + params["fc"]["b"]
    return (logits, acts) if collect_acts else logits


def n_relu_groups(cfg: ResNetConfig) -> int:
    return 1 + len(cfg.stage_blocks)


def relu_group_elements(params, cfg: ResNetConfig, in_hw: int = 0) -> List[int]:
    """Activation counts per ReLU group for one sample (budget weighting)."""
    hw = in_hw or cfg.in_hw
    x = jnp.zeros((1, 3, hw, hw))
    counts = [0] * n_relu_groups(cfg)

    def counting_relu(v, g):
        counts[g] += int(v.size)
        return jax.nn.relu(v)

    _ = apply(params, x, cfg, relu_fn=counting_relu)
    return counts


# ---------------------------------------------------------------------------
# MPC path
# ---------------------------------------------------------------------------

def hb_or_exact(hb: Optional[HBConfig], cfg: ResNetConfig) -> HBConfig:
    return hb if hb is not None else HBConfig.exact((0,) * n_relu_groups(cfg))


def trace(params, cfg: ResNetConfig, batch: int, hw: int = 0,
          hb: Optional[HBConfig] = None, cone: bool = False):
    """Trace this model into a ``repro.api.Plan`` (the generic planner)."""
    from repro import api

    hw = hw or cfg.in_hw
    return api.trace_plan(
        lambda p, x, relu_fn=None: apply(p, x, cfg, relu_fn=relu_fn),
        params, (batch, 3, hw, hw), hb=hb,
        n_groups=n_relu_groups(cfg) if hb is None else None,
        cone=cone, name=cfg.name)


def relu_plan(params, cfg: ResNetConfig, batch: int, hw: int = 0):
    """Deprecated shim over ``repro.api.trace_plan``: (n_elements, group)
    per ReLU call, in call order."""
    plan = trace(params, cfg, batch, hw)
    return [(c.n_elements, c.group) for c in plan.calls]


def gen_mpc_triples(key, plan, hb: Optional[HBConfig], cfg: ResNetConfig,
                    cone: bool = False):
    """Deprecated shim over ``beaver.gen_plan_triples``: one ReluTriples
    bundle per ReLU call (None for culled width-0 groups).  ``plan`` is the
    (n_elements, group) list from ``relu_plan``."""
    hb_layers = hb_or_exact(hb, cfg).layers
    return beaver.gen_plan_triples(
        key, [(n, hb_layers[g].width) for n, g in plan], cone=cone)


def _fold_bn_linear(conv_w, bn):
    """``fold_bn`` of the private forward, in a ``linear`` host span: the
    folding runs on every request, beside the linear layer it feeds."""
    with span("linear"):
        return fold_bn(conv_w, bn)


def _mpc_forward(params, hs: List[MPCTensor], cfg: ResNetConfig, relu_fn,
                 comm) -> List[MPCTensor]:
    """Shared MPC forward over sibling streams.

    ``relu_fn(tensors, group) -> tensors`` is invoked once per ReLU point
    with the sibling tensors of every stream, so implementations can share
    protocol rounds across streams (see mpc_apply_many)."""
    w, b = _fold_bn_linear(params["stem"], params["bn_stem"])
    hs = [h.conv2d_public(w, 1, 1).add_public(b[:, None, None], comm)
          for h in hs]
    hs = relu_fn(hs, 0)
    for si, stage in enumerate(params["stages"]):
        for block in stage:
            stride = 2 if ("proj" in block and si > 0) else 1
            if "conv3" in block:
                w1, b1 = _fold_bn_linear(block["conv1"], block["bn1"])
                ys = relu_fn([h.conv2d_public(w1, 1, 0)
                              .add_public(b1[:, None, None], comm)
                              for h in hs], si + 1)
                w2, b2 = _fold_bn_linear(block["conv2"], block["bn2"])
                ys = relu_fn([y.conv2d_public(w2, stride, 1)
                              .add_public(b2[:, None, None], comm)
                              for y in ys], si + 1)
                w3, b3 = _fold_bn_linear(block["conv3"], block["bn3"])
                ys = [y.conv2d_public(w3, 1, 0)
                      .add_public(b3[:, None, None], comm) for y in ys]
            else:
                w1, b1 = _fold_bn_linear(block["conv1"], block["bn1"])
                ys = relu_fn([h.conv2d_public(w1, stride, 1)
                              .add_public(b1[:, None, None], comm)
                              for h in hs], si + 1)
                w2, b2 = _fold_bn_linear(block["conv2"], block["bn2"])
                ys = [y.conv2d_public(w2, 1, 1)
                      .add_public(b2[:, None, None], comm) for y in ys]
            if "proj" in block:
                wp, bp = _fold_bn_linear(block["proj"], block["bn_proj"])
                hs = [h.conv2d_public(wp, stride, 0)
                      .add_public(bp[:, None, None], comm) for h in hs]
            hs = relu_fn([h + y for h, y in zip(hs, ys)], si + 1)
    hs = [h.global_avg_pool() for h in hs]
    return [h.matmul_public(params["fc"]["w"])
            .add_public(params["fc"]["b"], comm) for h in hs]


# the generic compiler resolves this forward from the config type
register_mpc_forward(ResNetConfig, _mpc_forward)


def _compiled(params, cfg: ResNetConfig, hb, comm, triples, cone):
    """Shared shim body: bind the old threaded arguments into a Plan +
    Session and compile (see repro.api for the first-class entry point)."""
    from repro import api

    provider = beaver.TriplePool(triples) if triples is not None else None
    session = api.Session(comm=comm, provider=provider)
    plan = api.Plan.from_hb(hb_or_exact(hb, cfg), cone=cone, name=cfg.name)
    return api.compile(
        lambda p, x, relu_fn=None: apply(p, x, cfg, relu_fn=relu_fn),
        params, cfg, plan, session)


def mpc_apply(params, x: MPCTensor, cfg: ResNetConfig, key,
              hb: Optional[HBConfig] = None, comm=None,
              triples: Optional[list] = None, cone: bool = False) -> MPCTensor:
    """Deprecated shim over ``repro.api.compile``: secret-shared inference.

    BN folded into convs; ReLU via GMW with the HummingBird (k, m) of each
    group.  When `triples` is given (mesh serving), they are consumed in
    call order; otherwise generated inline (sim backend).  Outputs are
    bit-identical to the pre-Plan/Session implementation (asserted in
    tests/test_api.py)."""
    return _compiled(params, cfg, hb, comm, triples, cone)(x, key=key)


def mpc_apply_many(params, xs: Sequence[MPCTensor], cfg: ResNetConfig, key,
                   hb: Optional[HBConfig] = None, comm=None,
                   triples: Optional[list] = None,
                   cone: bool = False) -> List[MPCTensor]:
    """Deprecated shim over ``repro.api.compile``: N sibling inference
    streams share ReLU rounds (max-over-streams protocol rounds per layer,
    one coalesced exchange per round — see PrivateModel.__call__).

    ``triples`` keeps the offline TTP split: one entry per ReLU call (in
    call order), each a sequence with one ReluTriples bundle (or None for
    culled groups) per stream."""
    flat = ([b for call in triples for b in call]
            if triples is not None else None)
    return _compiled(params, cfg, hb, comm, flat, cone)(list(xs), key=key)
