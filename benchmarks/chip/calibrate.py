#!/usr/bin/env python3
"""Readings for the correctness limits of a cell, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 101,102,... --requests 4 [--out chiprun_out/cal.jsonl]

For each seed, in one process: the cell's weights and request pool as a
benchmark run makes them, ``--requests`` requests served through the same
HTTP frontend and engine, and each number of the configuration's
``limits`` read three ways against the reference:

- ``program``: the served logits (the lower reading);
- ``control``: the reference in the program's place, one step below the
  configuration's precision (``control_logits``; the upper reading);
- ``bf16``: the reference in bfloat16 in the program's place, for the
  record where it is not the control.

The benchmark's own runs never run this.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from cellbench import cell as cell_lib  # noqa: E402
from cellbench import client, correctness, model, spec  # noqa: E402


def control_logits(cfg, params, x, seed: int):
    """The reference in the program's place, one step below the precision
    the configuration states: on the exact ring, float32 -> bfloat16; on a
    reduced ring, every window one bit narrower (m + 1), one draw of the
    shares' carries."""
    import jax

    plan = model.plan_groups(cfg)
    if not model.is_stochastic(plan):
        return correctness.reference_for(cfg, params, x, seed,
                                         precision="bfloat16")["mean"]
    narrower = [dict(g, m=g["m"] + 1) if g["m"] else g for g in plan]
    return model.draw_logits(cfg, params, x, narrower,
                             jax.random.fold_in(model.base_key(seed), 6))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--serve", type=int, choices=(0, 1), default=1,
                    help="0: the controls alone, on the images that "
                         "--requests requests would send")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(spec.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibration readings come from the chip")
    import numpy as np

    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        prep = cell_lib.prepare(cfg, traffic, seed)
        pool = prep["pool"]
        used = [i % len(pool) for i in range(args.requests)]
        x = np.concatenate([pool[i] for i in used])
        ref = correctness.reference_for(cfg, prep["params"], x, seed)
        row = {"workload": cell["name"], "seed": seed, "images": len(x),
               "control": correctness.compare(
                   cfg, control_logits(cfg, prep["params"], x, seed), ref),
               "bf16": correctness.compare(
                   cfg, correctness.reference_for(
                       cfg, prep["params"], x, seed,
                       precision="bfloat16")["mean"], ref)}
        if args.serve:
            frontend, host, port = cell_lib.serve(cfg, prep["params"],
                                                  prep["batch"], seed)
            served, lat = [], []
            for i in used:
                t = time.monotonic()
                resp = client.post_infer(host, port, prep["bodies"][i])
                lat.append(time.monotonic() - t)
                served.append(np.asarray(resp["y"]))
            frontend.close()
            served = np.concatenate(served)
            row["program"] = correctness.compare(cfg, served, ref)
            row["latency_s"] = lat
            row["rounds"] = resp["batch"]["measured_rounds"]
            row["plain_relu_gap"] = float(np.max(np.abs(
                served - model.reference(
                    cfg, prep["params"], x,
                    model.plan_groups(dict(cfg, plan="exact")))["mean"])))
        row["seconds"] = time.monotonic() - t0
        for k in ("program", "control", "bf16"):
            if k in row:
                row[k] = {n: v["value"] for n, v in row[k].items()}
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
