import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import: jax locks the
# device count at first init, and the dry-run needs 512 host devices to
# build the production meshes.  (Smoke tests / benches see 1 device.)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the right step (train_step / prefill_step /
serve_step), compiles it for the 16x16 single-pod and 2x16x16 multi-pod
meshes, records memory_analysis / cost_analysis / HLO-derived roofline
terms (trip-count corrected), and writes one JSON artifact per cell under
results/dryrun/.  `--mpc` additionally dry-runs the paper's MPC ResNet
serving step on the (party=2, data=256) mesh, baseline vs HummingBird.

Usage:
  python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k
  python -m repro.launch.dryrun --all [--multipod-only|--singlepod-only]
  python -m repro.launch.dryrun --mpc
"""
import argparse
import functools
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get as get_arch, all_names, shape_applicable
from repro.configs.resnet import RESNET18, RESNET50
from repro.core.hummingbird import HBConfig, HBLayer
from repro.launch import serve as serve_lib, specs as specs_lib
from repro.launch import train as train_lib
from repro.launch.mesh import make_mpc_mesh, make_production_mesh
from repro.models import encdec, lm
from repro.runtime.hlo_analyzer import analyze, normalize_cost_analysis
from repro.runtime.roofline import roofline_terms
from repro.train import optimizer as opt_lib

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _step_fn(cfg, shape):
    if shape.kind == "train":
        opt = opt_lib.AdamW()
        return train_lib.make_train_step(
            cfg, opt, n_microbatches=cfg.train_microbatches)
    if shape.kind == "prefill":
        return serve_lib.make_prefill_step(cfg, max_len=shape.seq_len)
    return serve_lib.make_decode_step(cfg)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides=None) -> dict:
    cfg = get_arch(arch)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with jax.set_mesh(mesh):
        args, kwargs = specs_lib.input_specs(cfg, shape, mesh)
        fn = _step_fn(cfg, shape)
        lowered = jax.jit(fn).lower(*args, **kwargs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ca = normalize_cost_analysis(compiled.cost_analysis())
        ma = compiled.memory_analysis()
        hlo = analyze(compiled.as_text())
    n_chips = 512 if multi_pod else 256
    terms = roofline_terms(cfg, shape, hlo, n_chips)
    out = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok", "n_chips": n_chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "cost_analysis": {"flops": ca.get("flops"),
                          "bytes": ca.get("bytes accessed")},
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "total_bytes": (ma.argument_size_in_bytes +
                            ma.output_size_in_bytes + ma.temp_size_in_bytes),
        },
        "hlo": {"flops": hlo.flops, "bytes": hlo.bytes,
                "collective_bytes": hlo.collective_bytes,
                "collectives": hlo.collective_counts},
        "roofline": terms,
    }
    return out


def run_mpc_cell(rcfg, hb, tag: str, cone: bool = False) -> dict:
    mesh = make_mpc_mesh()
    batch = 512  # the paper's Figure 1 setup: 512 CIFAR inferences
    t0 = time.time()
    with mesh:
        params, lo, hi, triples, key = serve_lib.mpc_input_specs(
            rcfg, batch, mesh, hb, cone=cone)
        step = serve_lib.make_mpc_serve_step(rcfg, hb, cone=cone)
        lowered = jax.jit(step).lower(params, lo, hi, triples, key)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        hlo = analyze(compiled.as_text())
    from repro.runtime.roofline import mpc_roofline_terms
    terms = mpc_roofline_terms(hlo, n_chips=512)
    return {
        "arch": f"{rcfg.name}-mpc-{tag}", "shape": "cifar_b512",
        "multi_pod": True, "status": "ok", "n_chips": 512,
        "compile_s": round(time.time() - t0, 2),
        "memory": {"argument_bytes": ma.argument_size_in_bytes,
                   "temp_bytes": ma.temp_size_in_bytes},
        "hlo": {"flops": hlo.flops, "bytes": hlo.bytes,
                "collective_bytes": hlo.collective_bytes,
                "collectives": hlo.collective_counts},
        "roofline": terms,
    }


def run_lm_mpc_cell(acfg, budget: str, batch: int = 1, seq: int = 32) -> dict:
    """Trace-only dry-run of the private LM: the reduced-ring plan (PWL
    activations, ReLU attention, Beaver opens) and its exact schedule
    prediction.  The LM serves through the sim engine rather than the
    mesh-native step, so the cell reports the round/byte/latency economy
    instead of lowered HLO."""
    from repro.api.plan import LAN, WAN
    t0 = time.time()
    key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)   # abstract PRNG key
    params = jax.eval_shape(functools.partial(lm.init, cfg=acfg), key_spec)
    plan = lm.trace(params, acfg, batch, seq)
    if budget != "baseline":
        k, m = (21, 0) if budget == "eco" else (21, 13)
        hb = HBConfig(tuple(HBLayer(k=k, m=m)
                            for _ in range(plan.hb.n_groups)),
                      plan.hb.group_elements)
        plan = lm.trace(params, acfg, batch, seq, hb=hb)
    sched = plan.schedule()
    return {
        "arch": f"{acfg.name}-mpc-lm-{budget}", "shape": f"b{batch}_s{seq}",
        "multi_pod": False, "status": "ok", "n_chips": 1,
        "compile_s": round(time.time() - t0, 2),
        "lm": {"n_relu_calls": len(plan.calls), "n_opens": len(plan.opens),
               "rounds": sched.n_rounds, "bytes_tx": sched.bytes_tx,
               "budget_fraction": plan.hb.budget_fraction(),
               "latency_lan_s": sched.latency(LAN.bandwidth_bps, LAN.rtt_s),
               "latency_wan_s": sched.latency(WAN.bandwidth_bps, WAN.rtt_s)},
    }


def hb_config_for(rcfg, budget: str):
    """Representative found configs (search engine output, see §Perf)."""
    n_groups = 1 + len(rcfg.stage_blocks)
    if budget == "baseline":
        return None
    if budget == "eco":
        layers = tuple(HBLayer(k=21, m=0) for _ in range(n_groups))
    else:  # 8/64
        layers = tuple(HBLayer(k=21, m=13) for _ in range(n_groups))
    return HBConfig(layers, tuple(1 for _ in range(n_groups)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mpc", action="store_true")
    ap.add_argument("--mpc-arch", default=None,
                    help="registry arch name for a private-LM MPC cell "
                         "(e.g. qwen1.5-0.5b-smoke); default: the paper's "
                         "ResNet pair")
    ap.add_argument("--mpc-budget", default="8of64",
                    choices=["baseline", "eco", "8of64", "8of64cone"])
    ap.add_argument("--multipod-only", action="store_true")
    ap.add_argument("--singlepod-only", action="store_true")
    ap.add_argument("--override", default=None,
                    help="JSON dict of ArchConfig overrides (perf iteration)")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    RESULTS.mkdir(parents=True, exist_ok=True)
    meshes = [False, True]
    if args.multipod_only:
        meshes = [True]
    if args.singlepod_only:
        meshes = [False]

    if args.mpc:
        if args.mpc_arch:
            # LM family resolves by registry name — same idiom as the
            # ResNet pair below, but through configs.get
            acfg = get_arch(args.mpc_arch)
            budget = args.mpc_budget.replace("cone", "")
            try:
                out = run_lm_mpc_cell(acfg, budget)
            except Exception as e:
                out = {"arch": f"{acfg.name}-mpc-lm-{budget}",
                       "status": "error",
                       "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
            name = f"mpc_lm_{acfg.name}_{budget}{args.tag}.json"
            (RESULTS / name).write_text(json.dumps(out, indent=2))
            print(json.dumps({k: v for k, v in out.items()
                              if k not in ("trace",)}, indent=2))
            return
        for rcfg in (RESNET18, RESNET50):
            cone = args.mpc_budget.endswith("cone")
            hb = hb_config_for(rcfg, args.mpc_budget.replace("cone", ""))
            tag = args.mpc_budget
            try:
                out = run_mpc_cell(rcfg, hb, tag, cone=cone)
            except Exception as e:
                out = {"arch": f"{rcfg.name}-mpc-{tag}", "status": "error",
                       "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
            name = f"mpc_{rcfg.name}_{tag}{args.tag}.json"
            (RESULTS / name).write_text(json.dumps(out, indent=2))
            print(json.dumps({k: v for k, v in out.items()
                              if k not in ("trace",)}, indent=2))
        return

    cells = []
    archs = [args.arch] if args.arch else all_names()
    shapes = [args.shape] if args.shape else list(SHAPES)
    overrides = json.loads(args.override) if args.override else None
    for arch in archs:
        for shape_name in shapes:
            for multi_pod in meshes:
                cells.append((arch, shape_name, multi_pod))

    for arch, shape_name, multi_pod in cells:
        tag = "multi" if multi_pod else "single"
        try:
            out = run_cell(arch, shape_name, multi_pod, overrides)
        except Exception as e:
            out = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        fname = f"{arch}_{shape_name}_{tag}{args.tag}.json"
        (RESULTS / fname).write_text(json.dumps(out, indent=2))
        brief = {k: out.get(k) for k in
                 ("arch", "shape", "multi_pod", "status", "compile_s",
                  "error", "reason")}
        brief["roofline"] = out.get("roofline", {})
        print(json.dumps(brief))


if __name__ == "__main__":
    main()
