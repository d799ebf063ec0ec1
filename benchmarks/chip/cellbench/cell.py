"""A cell's set-up, shared by the benchmark run and the calibration: the
seeded weights and request pool, and the program's serving stack."""
from __future__ import annotations

import sys
from typing import Dict, Tuple

import numpy as np

from cellbench import client, model, spec


def prepare(cfg: Dict, traffic: Dict, seed: int) -> Dict:
    """Weights, the request pool (``pool[i]``: the images of request i)
    and its HTTP bodies, from ``seed``."""
    batch, n_pool = int(traffic["batch"]), int(traffic["pool_requests"])
    params = model.make_params(seed, cfg)
    raw = model.make_images(seed, n_pool * batch, cfg)
    scale = model.theorem1_scale(cfg, params, raw,
                                 int(traffic["scale_for_k"]))
    images = np.asarray(model.on_grid(raw * scale))
    pool = images.reshape((n_pool, batch) + images.shape[1:])
    bodies = [client.request_body(x.tolist(), traffic["tenant"])
              for x in pool]
    return {"params": params, "pool": pool, "bodies": bodies,
            "scale": scale, "batch": batch}


def hb_plan(cfg: Dict):
    """The configuration's ReLU plan as the program takes it (None: the
    exact 64-bit ring)."""
    if cfg["plan"] == "exact":
        return None
    from repro.core.hummingbird import HBConfig, HBLayer
    layers = tuple(HBLayer(k=int(g["k"]), m=int(g["m"]))
                   for g in cfg["plan"])
    return HBConfig(layers, (0,) * len(layers))


def serve(cfg: Dict, params, batch: int, seed: int) -> Tuple:
    """The program's serving stack for this cell: ``serve.Frontend`` over
    the model family's ``InferenceEngine`` (background pump, default batch
    policy and round loop) on a ``Session`` whose key comes from ``seed``;
    returns (frontend, host, port)."""
    src = str(spec.ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro import api, serve as serving

    session = api.Session(key=model.derived_int(seed, 3))
    engine = model.family(cfg).engine(cfg, params, hb_plan(cfg), batch,
                                      session)
    frontend = serving.Frontend(engine)
    host, port = frontend.serve_background("127.0.0.1", 0)
    return frontend, host, port
