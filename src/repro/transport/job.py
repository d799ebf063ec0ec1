"""Job bundles: the offline artifact a deployed party process loads.

A *job directory* is everything the two party hosts need to run the same
private inference without any shared memory — the deployment analogue of
the arguments a single-process test passes around:

    job.json     config name, params seed, protocol/infer keys, TTP seed
    plan.json    the traced ``api.Plan`` (handshake-checked by digest)
    party0.npz   party 0's input share rows + its slice of the triple pool
    party1.npz   party 1's rows/slices (same keys, other index)

Shares and triples are generated ONCE (by ``write_job``, typically on the
machine playing trusted dealer / client) and split by party with
``beaver.slice_party_pool`` — each process only ever sees its own rows,
which is the whole point of the two-server model.  Model *weights* are
public in this threat model (both parties re-derive them from
``params_seed``), matching the paper's setup where only activations are
secret-shared.

The triple pool's pytree structure is reconstructed via ``jax.eval_shape``
over ``gen_plan_triples`` (no triple material is generated at load time),
so the flat npz leaves round-trip losslessly for dense and cone layouts
alike.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import RESNET18, RESNET50, RESNET_SMOKE
from repro.core import beaver, fixed, ring
from repro.core.mpc_tensor import MPCTensor
from repro.api.plan import Plan

CONFIGS = {"smoke": RESNET_SMOKE, "resnet18": RESNET18,
           "resnet50": RESNET50}


def resolve_config(name: str):
    try:
        return CONFIGS[name]
    except KeyError:
        raise KeyError(f"unknown job config {name!r}; expected one of "
                       f"{sorted(CONFIGS)}") from None


def pool_treedef(plan: Plan):
    """The triple pool's pytree structure for ``plan`` — derived
    abstractly (``eval_shape``), no triples are generated."""
    template = jax.eval_shape(
        lambda k: beaver.gen_plan_triples(k, plan.triple_specs(),
                                          cone=plan.cone),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    return jax.tree_util.tree_structure(template)


def write_job(job_dir, *, plan: Plan, config: str, params_seed: int,
              infer_key: int, session_seed: int, ttp_seed: int = 0,
              x: Optional[MPCTensor] = None,
              pool: Optional[List] = None) -> pathlib.Path:
    """Materialise a job directory (see module docstring).

    ``x`` is the full 2-party secret-shared input and ``pool`` the full
    offline triple pool; both are split by party here.  Omit them for a
    serving-mode job (the engine leader shares inputs per request and
    triples stream from the shared ``ttp_seed``).
    """
    path = pathlib.Path(job_dir)
    path.mkdir(parents=True, exist_ok=True)
    plan.save(path / "plan.json")
    job = {"config": str(config), "params_seed": int(params_seed),
           "infer_key": int(infer_key), "session_seed": int(session_seed),
           "ttp_seed": int(ttp_seed)}
    resolve_config(job["config"])               # fail at write time, loudly
    if x is not None:
        job["frac_bits"] = int(x.frac_bits)
        job["input_shape"] = [int(s) for s in x.shape]
        for p in (0, 1):
            arrs = {"x_lo": np.asarray(x.data.lo[p:p + 1]),
                    "x_hi": np.asarray(x.data.hi[p:p + 1])}
            if pool is not None:
                leaves = jax.tree_util.tree_leaves(
                    beaver.slice_party_pool(pool, p))
                arrs.update({f"t{i:04d}": np.asarray(leaf)
                             for i, leaf in enumerate(leaves)})
            np.savez(path / f"party{p}.npz", **arrs)
    (path / "job.json").write_text(json.dumps(job, indent=1))
    return path


def load_job(job_dir) -> Dict:
    """job.json + the plan (every party-agnostic piece)."""
    path = pathlib.Path(job_dir)
    job = json.loads((path / "job.json").read_text())
    job["plan"] = Plan.load(path / "plan.json")
    job["cfg"] = resolve_config(job["config"])
    return job


def load_party(job_dir, party: int) -> Dict:
    """One party's view: job + its input share rows + its triple slice."""
    path = pathlib.Path(job_dir)
    job = load_job(path)
    npz_path = path / f"party{party}.npz"
    if npz_path.exists():
        with np.load(npz_path) as npz:
            job["X"] = MPCTensor(
                ring.Ring64(jnp.asarray(npz["x_lo"]),
                            jnp.asarray(npz["x_hi"])),
                int(job.get("frac_bits", fixed.DEFAULT_FRAC_BITS)))
            tkeys = sorted(k for k in npz.files if k.startswith("t"))
            if tkeys:
                leaves = [jnp.asarray(npz[k]) for k in tkeys]
                job["pool"] = jax.tree_util.tree_unflatten(
                    pool_treedef(job["plan"]), leaves)
    return job


def smoke_job_inputs(seed: int = 0):
    """``(plan, X, pool)`` of the smoke deployment job, all from ``seed``:
    ``RESNET_SMOKE`` weights (``PRNGKey(seed)``, as ``params_seed``
    re-derives them), 8-bit windows with the last group culled, one
    (2, 3, 8, 8) secret-shared input, and its offline triple pool.
    Deterministic, so a caller can rebuild the job's inputs for a
    reference run without reading the job back."""
    from repro import api
    from repro.models import resnet

    params = resnet.init(jax.random.PRNGKey(seed), RESNET_SMOKE)

    def afn(p, v, relu_fn=None):
        return resnet.apply(p, v, RESNET_SMOKE, relu_fn=relu_fn)

    plan = api.trace_plan(afn, params, (2, 3, 8, 8), name="smoke")
    plan = plan.with_hb(api.HBConfig(
        tuple([api.HBLayer(k=21, m=13)] * (plan.n_groups - 1)
              + [api.HBLayer(k=13, m=13)]), plan.group_elements))
    k_x, k_share, k_pool = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    x = jax.random.normal(k_x, (2, 3, 8, 8)) * 0.5
    X = MPCTensor.from_plain(k_share, x)
    pool = beaver.gen_plan_triples(k_pool, plan.triple_specs())
    return plan, X, pool


def write_smoke_job(job_dir, seed: int = 0) -> pathlib.Path:
    """Write the smoke job (``smoke_job_inputs(seed)``); the protocol key
    is ``PRNGKey(seed + 4)``."""
    plan, X, pool = smoke_job_inputs(seed)
    return write_job(job_dir, plan=plan, config="smoke", params_seed=seed,
                     infer_key=seed + 4, session_seed=seed, x=X, pool=pool)
