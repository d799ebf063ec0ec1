"""The benchmark's own model: seeded weights, inputs and the plain reference.

Nothing here imports the program.  The model's layers, weights and work
counts are its family's (``models/<model>.py``, named by the
configuration); what follows is common to every family.  The weights and images are made from
``--seed`` on the device in one jitted call each, in float32 (the type the
program's ``resnet.apply`` / ``PrivateModel`` take them in), and both the
program and the reference are given the same arrays.

The input preparation is copied from the repository's chip smoke test so
that a later change to that script cannot move the yardstick:
``fixed_point_params`` (weights on the 2^-16 grid, BatchNorm folding to a
factor of 1), ``theorem1_scale`` (input scale keeping every ReLU input in
half of Theorem 1's exact range) and ``on_grid``.

The plain reference is the family's straightforward float32 forward in
``jax.numpy`` under ``default_matmul_precision("highest")``, with the
ring's fixed-point truncation and each group's reduced-ring ReLU modelled
from their statements (see "The ring's arithmetic" below).
"""
from __future__ import annotations

from types import ModuleType
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import spec

FRAC = 16                      # fixed-point fractional bits of the ring


def base_key(seed: int) -> jax.Array:
    """A raw threefry key from any whole seed (also beyond 32 bits)."""
    s0, s1 = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray([s0, s1], dtype=jnp.uint32)


def derived_int(seed: int, salt: int) -> int:
    """A 31-bit integer derived from ``seed`` (for APIs that take an int)."""
    return int(np.random.SeedSequence([int(seed), salt])
               .generate_state(1, np.uint32)[0] >> 1)


# ---------------------------------------------------------------------------
# The model family, and its weights
# ---------------------------------------------------------------------------

def family(cfg: Dict) -> ModuleType:
    """The configuration's model family, ``models/<cfg['model']>.py``:
    ``init_params``, ``forward``, ``input_shape``, ``macs_per_image``,
    ``relu_elements_per_image`` and ``engine``."""
    return spec.model_family(cfg["model"])


def make_params(seed: int, cfg: Dict) -> Dict:
    """The cell's weights, made on the device in one jitted call and put on
    the ring's grid."""
    init = family(cfg).init_params
    return jax.jit(lambda k: fixed_point_params(init(k, cfg)))(
        jax.random.fold_in(base_key(seed), 1))


def fixed_point_params(params):
    """Weights moved onto the 2^-16 grid the ring encodes them on; the
    BatchNorm dicts are left as made (they fold to a factor of 1).  The
    private forward then computes the reference's linear layers exactly,
    and only share truncation and the ReLU protocol separate the two."""
    def fix(tree):
        if isinstance(tree, dict):
            if {"gamma", "var"} <= set(tree):
                return tree
            return {k: fix(v) for k, v in tree.items()}
        if isinstance(tree, list):
            return [fix(v) for v in tree]
        return on_grid(tree)

    return fix(params)


def on_grid(x):
    return jnp.round(x * 2.0 ** FRAC) / 2.0 ** FRAC


# ---------------------------------------------------------------------------
# The ring's arithmetic, in float32
# ---------------------------------------------------------------------------
#
# The configuration states fixed point with FRAC fractional bits on the
# 64-bit ring, and for each ReLU group a window [m, k) of bits on which the
# sign is taken.  Two effects of that arithmetic are modelled here, from
# their statements alone (nothing of the program is used):
#
# - Truncation.  Each party shifts its own share right by FRAC after a
#   product.  With z = z0 + z1 and z0 uniform, floor(z0/2^f) +
#   floor(z1/2^f) = floor(z/2^f) - c with P(c = 1) = 1 - (z mod 2^f + 1)
#   / 2^f, so the expected result is z/2^f - 1 + 2^-f units of the last
#   place: every truncating linear op lowers its output by one ULP
#   (2^-FRAC) on average.
# - The reduced ring (Theorem 2).  Each party takes bits [m, k) of its
#   share; the parties' halves add to F - c with F = floor(V/2^m), V the
#   ring value, and the same carry rule at 2^m: c = 1 with probability
#   1 - (V mod 2^m + 1) / 2^m.  The sign is the top bit of (F - c) mod
#   2^(k-m).  So a value in [0, 2^m) keeps its sign with probability
#   (V + 1) / 2^m, and every other value within Theorem 1's range is
#   exact.

ULP = 2.0 ** -FRAC


def ring_linear(v):
    return v - ULP


def _window_keeps(s, w: int):
    """Whether the (w-bit window) sum ``s`` reads as non-negative."""
    return jnp.mod(s, 2.0 ** w) < 2.0 ** (w - 1)


def _carry_free_share(v, m: int):
    """(floor(V / 2^m), P(no carry)) of the ring value V of ``v``."""
    big = jnp.round(v * 2.0 ** FRAC)
    return jnp.floor(big / 2.0 ** m), (jnp.mod(big, 2.0 ** m) + 1) / 2.0 ** m


def hb_relu_mean(plan: Sequence[Dict]) -> Callable:
    """ReLU of each group's window, in expectation over the shares."""
    def relu(v, g):
        k, m = plan[g]["k"], plan[g]["m"]
        if k >= 64 and m == 0:
            return jnp.maximum(v, 0.0)
        f, p0 = _carry_free_share(v, m)
        keep = (p0 * _window_keeps(f, k - m)
                + (1 - p0) * _window_keeps(f - 1, k - m))
        return v * keep
    return relu


def hb_relu_draw(plan: Sequence[Dict], key) -> Callable:
    """ReLU of each group's window for one draw of the shares' carries,
    from ``key`` (the reference's own randomness)."""
    keys = iter(jax.random.split(key, 64))

    def relu(v, g):
        k, m = plan[g]["k"], plan[g]["m"]
        if k >= 64 and m == 0:
            return jnp.maximum(v, 0.0)
        f, p0 = _carry_free_share(v, m)
        carry = jax.random.uniform(next(keys), v.shape) >= p0
        return jnp.where(_window_keeps(f - carry, k - m), v, 0.0)
    return relu


def plan_groups(cfg: Dict) -> List[Dict]:
    """The configuration's (k, m) per ReLU group."""
    n = len(family(cfg).relu_elements_per_image(cfg))
    if cfg["plan"] == "exact":
        return [{"k": 64, "m": 0}] * n
    return [{"k": int(g["k"]), "m": int(g["m"])} for g in cfg["plan"]]


def is_stochastic(plan: Sequence[Dict]) -> bool:
    return any(g["m"] > 0 for g in plan)


def _block(x, i: int, rows: int, dtype):
    """Rows ``[i, i + rows)`` of ``x``, padded with zeros to ``rows`` so
    that every block has one shape and each program compiles once per
    checkout, whatever the number of requests a window serves."""
    v = np.asarray(x[i:i + rows])
    n = v.shape[0]
    pad = np.zeros((rows - n,) + v.shape[1:], v.dtype)
    return n, jnp.asarray(np.concatenate([v, pad]), dtype)


def reference(cfg: Dict, params, x, plan: Sequence[Dict], *,
              precision: str = "highest", draws: int = 0, key=None,
              block_rows: int = 16) -> Dict:
    """The reference over inputs ``x`` (one row per image), in blocks of
    rows.

    ``mean``: the forward with every ReLU window and truncation in
    expectation (``hb_relu_mean``, ``ring_linear``).  With ``draws`` > 0
    also ``mc_mean`` and ``mc_std`` of the logits over that many draws of
    the shares' carries from ``key`` (``hb_relu_draw``).

    ``precision``: ``"highest"`` is float32 throughout, the reference;
    ``"bfloat16"`` casts the weights and images to bfloat16 and computes
    in it, on any backend.
    """
    forward = family(cfg).forward
    dtype, prec = jnp.float32, precision
    if precision == "bfloat16":
        dtype, prec = jnp.bfloat16, "default"
        params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)

    @jax.jit
    def mean_fn(p, v):
        return forward(p, v, hb_relu_mean(plan),
                       ring_linear).astype(jnp.float32)

    @jax.jit
    def draws_fn(p, v, keys):
        def one(k):
            return forward(p, v, hb_relu_draw(plan, k),
                           ring_linear).astype(jnp.float32)
        ys = jax.lax.map(one, keys)
        return ys.mean(0), ys.std(0, ddof=1)

    out: Dict[str, list] = {"mean": [], "mc_mean": [], "mc_std": []}
    with jax.default_matmul_precision(prec):
        for i in range(0, x.shape[0], block_rows):
            n, v = _block(x, i, block_rows, dtype)
            out["mean"].append(np.asarray(mean_fn(params, v))[:n])
            if draws:
                keys = jax.random.split(jax.random.fold_in(key, i), draws)
                mu, sd = draws_fn(params, v, keys)
                out["mc_mean"].append(np.asarray(mu)[:n])
                out["mc_std"].append(np.asarray(sd)[:n])
    return {k: np.concatenate(v).astype(np.float64)
            for k, v in out.items() if v}


def draw_logits(cfg: Dict, params, x, plan: Sequence[Dict], key,
                block_rows: int = 16) -> np.ndarray:
    """One draw of the ring's forward (``hb_relu_draw``) over ``x``."""
    forward = family(cfg).forward
    fn = jax.jit(lambda p, v, k: forward(p, v, hb_relu_draw(plan, k),
                                         ring_linear))
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, x.shape[0], block_rows):
            n, v = _block(x, i, block_rows, jnp.float32)
            out.append(np.asarray(fn(params, v,
                                     jax.random.fold_in(key, i)))[:n])
    return np.concatenate(out).astype(np.float64)


def theorem1_scale(cfg: Dict, params, x, k: int) -> float:
    """Input scale that keeps every ReLU input inside half of Theorem 1's
    exact range |v| < 2^(k-1-FRAC) (the other half is headroom for the
    ring's fixed-point drift).  1.0 where the activations already fit;
    the network has no biases, so scaling the input scales every
    activation by the same factor."""
    def peak(params, x):
        pre = []

        def record(v, g):
            pre.append(jnp.max(jnp.abs(v)))
            return jnp.maximum(v, 0.0)

        family(cfg).forward(params, x, relu=record)
        return jnp.max(jnp.stack(pre))

    with jax.default_matmul_precision("highest"):
        top = float(jax.jit(peak)(params, x))
    return min(1.0, 2.0 ** (k - 2 - FRAC) / top)


def make_images(seed: int, n: int, cfg: Dict) -> jax.Array:
    """``n`` standard-normal images (before scaling), from the seed."""
    shape = (n,) + tuple(family(cfg).input_shape(cfg))
    return jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32))(
        jax.random.fold_in(base_key(seed), 2))
