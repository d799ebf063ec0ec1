"""In-model sharding hints.

XLA's sharding propagation gives up at scan carries (flash-attention
accumulators, layer-scan activations) and silently replicates — on the
16x16 mesh that replicated attention 16x over the model axis before these
hints existed (see EXPERIMENTS.md §Perf, iteration 1).  ``shard(x, ...)``
applies a with_sharding_constraint against the *context* mesh, dropping
any axis that is absent or does not divide the dimension, so model code
can state intent once and run unchanged on the 1-device smoke mesh, the
16x16 pod, and the 2x16x16 multi-pod mesh.

Axis aliases: "dp" expands to the data axes ("pod", "data"); "tp" to
"model".
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P


def context_mesh():
    """The mesh set by ``jax.set_mesh``, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    if m is not None and m.axis_names:
        return m
    return None


def _expand(axis, mesh) -> Tuple[str, ...]:
    if axis is None:
        return ()
    if axis == "dp":
        return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if axis == "tp":
        return ("model",) if "model" in mesh.axis_names else ()
    if isinstance(axis, (tuple, list)):
        out = ()
        for a in axis:
            out += _expand(a, mesh)
        return out
    return (axis,) if axis in mesh.axis_names else ()


def shard(x, *axes):
    """Constrain x's sharding; silently drops non-dividing/absent axes."""
    mesh = context_mesh()
    if mesh is None:
        return x
    sizes = dict(mesh.shape)
    spec = []
    for dim, axis in zip(x.shape, axes):
        names = _expand(axis, mesh)
        total = int(np.prod([sizes[n] for n in names])) if names else 1
        if names and total > 1 and dim % total == 0:
            spec.append(names if len(names) > 1 else names[0])
        else:
            spec.append(None)
    spec += [None] * (x.ndim - len(spec))
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except Exception:
        return x


def axis_divides(axis, dim: int) -> bool:
    """True if `dim` is divisible by the context-mesh size of `axis`."""
    mesh = context_mesh()
    if mesh is None:
        return False
    names = _expand(axis, mesh)
    if not names:
        return False
    total = int(np.prod([dict(mesh.shape)[n] for n in names]))
    return total > 1 and dim % total == 0
