"""How ``correct`` is decided: every served logit against the reference.

The logits of every request served in the window come back through the
HTTP frontend, reconstructed from both parties' shares.  Once the window
has closed and the program is shut down, the reference
(``model.reference``: float32 under ``highest``, the ring's truncation and
each ReLU window modelled from their statements) runs over each request's
images in blocks of rows, and each number of the configuration's
``limits`` is compared with its limit:

- ``logit_gap``: the largest |served - reference| over every logit served
  in the window, the reference taken in expectation over the shares.
  Where every window is exact the ring's only randomness is truncation's
  carry, one unit of the last place, and this is the number.
- ``logit_rms_z``: where a reduced ring's sign rule makes the result
  random (Theorem 2: a value in [0, 2^m) keeps its sign with probability
  (V + 1) / 2^m), each served logit is set against the distribution of
  that logit over ``check.draws`` draws of the shares' carries: z =
  (served - mean) / std, and the root mean square of z over every logit
  served in the window is compared.

The limits, the readings they were set from and the controls that fail
them are in ``PERF.md``.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import numpy as np

from cellbench import model


def _finite(served: np.ndarray, ref: np.ndarray) -> bool:
    return served.shape == ref.shape and bool(np.all(np.isfinite(served)))


def logit_gap(served: np.ndarray, ref: Dict) -> float:
    if not _finite(served, ref["mean"]):
        return float("inf")
    return float(np.max(np.abs(served - ref["mean"])))


def _z(served: np.ndarray, ref: Dict) -> np.ndarray:
    return (served - ref["mc_mean"]) / np.maximum(ref["mc_std"], model.ULP)


def logit_rms_z(served: np.ndarray, ref: Dict) -> float:
    if not _finite(served, ref["mc_mean"]):
        return float("inf")
    return float(np.sqrt(np.mean(_z(served, ref) ** 2)))


NUMBERS = {"logit_gap": logit_gap, "logit_rms_z": logit_rms_z}

WHAT = {
    "logit_gap": "max |served - reference| over the served logits",
    "logit_rms_z": "rms of (served - mean) / std over the served logits, "
                   "against the reference's draws of the shares",
}


def reference_for(cfg: Dict, params, images: np.ndarray, seed: int,
                  precision: str = "highest") -> Dict:
    """The reference of ``images`` for the configuration's numbers."""
    plan = model.plan_groups(cfg)
    draws = int(cfg["check"]["draws"]) if model.is_stochastic(plan) else 0
    key = jax.random.fold_in(model.base_key(seed), 4)
    return model.reference(cfg, params, images, plan, precision=precision,
                           draws=draws, key=key)


def compare(cfg: Dict, served: np.ndarray, ref: Dict) -> Dict[str, Dict]:
    """Each number of ``cfg['limits']``, over all served logits at once
    (``served`` and the reference's arrays row for row)."""
    served = np.asarray(served, np.float64)
    return {name: {"value": NUMBERS[name](served, ref),
                   "limit": float(limit), "what": WHAT[name]}
            for name, limit in cfg["limits"].items()}


def check(cfg: Dict, params, pool: np.ndarray, done: List[Dict],
          seed: int) -> Dict[str, Dict]:
    """The numbers of the window's served requests beside their limits."""
    if not done:
        return {name: {"value": float("inf"), "limit": float(limit),
                       "what": WHAT[name]}
                for name, limit in cfg["limits"].items()}
    images = np.concatenate([pool[r["pool"]] for r in done])
    classes = int(cfg["n_classes"])
    served = np.concatenate([_logits(r["response"], len(pool[r["pool"]]),
                                     classes) for r in done])
    return compare(cfg, served, reference_for(cfg, params, images, seed))


def _logits(response: Dict, rows: int, classes: int) -> np.ndarray:
    """A response's logits; NaN where it has none of the right shape."""
    try:
        y = np.asarray(response["y"], np.float64)
        if y.shape == (rows, classes):
            return y
    except (KeyError, TypeError, ValueError):
        pass
    return np.full((rows, classes), np.nan)
