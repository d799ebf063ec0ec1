#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 benchmarks/chip/run_cell.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``configs/<name>.json``) and a traffic mix (``traffic/<name>.json``).
Set-up makes the weights and the request pool from ``--seed`` on the
device, builds the program's serving stack -- ``serve.Frontend`` over
HTTP on 127.0.0.1, ``InferenceEngine`` with its background pump, the
model's ``PrivateModel`` with the round loop ``runtime.loop`` chooses --
and serves ``WARMUP_REQUESTS`` requests of the cell's shape through it.
Both parties run on the one chip (``SimComm``).  The window then sends
requests over HTTP in a closed loop for ``--seconds``, and waits for the
last request issued.  With ``--trace 1`` the window
runs under the JAX profiler and the per-layer metrics are read from the
responses and the device trace.

After the window the program is shut down and every served logit is
compared with the plain float32 reference (``cellbench.correctness``).
The last line on standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` (traced
runs) and ``checks`` (each compared number beside its limit).  Without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

from cellbench import cell as cell_lib  # noqa: E402
from cellbench import client, compiles, correctness, spec  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
# requests served before the window opens, all of the cell's shape: a
# process's second request is slower than the later ones (12.3-14.1 s
# against 12.0 s on a v5e, with nothing traced or compiled in it), so it
# must not fall inside the window
WARMUP_REQUESTS = 2


def log(msg: str) -> None:
    print(f"[{time.monotonic() - T_START:9.3f} s] {msg}", file=sys.stderr,
          flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def start_jax(chips: int):
    """Import JAX with the compile cache in the checkout, and refuse to go
    on without ``chips`` TPU devices."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache every program, also the small per-round ones the generator
    # loop compiles, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size cap: with one, every read and write of the cache locks it and
    # rescans the directory, which made a cached set-up take 84 s
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"no TPU for this cell: JAX sees {len(devices)} "
            f"{devices[0].platform} device(s), the cell needs {chips} TPU")
    return jax, devices


def _finite_or_none(x: float):
    return x if math.isfinite(x) else None


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    jax, devices = start_jax(int(cell["chips"]))
    watch = compiles.Watch()
    from cellbench import tracing, work

    peak = work.peaks(devices[0].device_kind)
    log(f"cell {cell['name']}: {len(devices)} x {devices[0].device_kind}, "
        f"seed {args.seed}, {args.seconds} s, trace {args.trace}")

    # -- set-up: weights, request pool, serving stack, warm-up ------------
    prep = cell_lib.prepare(cfg, traffic, args.seed)
    params, pool, bodies, batch = (prep["params"], prep["pool"],
                                   prep["bodies"], prep["batch"])
    log(f"weights and {len(pool)} requests of {batch} image(s) made; input "
        f"scale {prep['scale']:.6g}")
    frontend, host, port = cell_lib.serve(cfg, params, batch, args.seed)
    log(f"set-up: {watch.summary('setup')}")
    for w in range(WARMUP_REQUESTS):
        watch.phase = f"warm-up {w}"
        t0 = time.monotonic()
        warm = client.post_infer(host, port, bodies[w % len(bodies)])
        log(f"warm-up request {w} served in {time.monotonic() - t0:.3f} s, "
            f"{warm['batch']['measured_rounds']} rounds: "
            f"{watch.summary(watch.phase)}")

    # -- the measured window -----------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="cellbench-trace-") if args.trace \
        else None
    setup = {}

    def on_start() -> None:
        if trace_dir:
            # device ops and annotated host spans only: the Python tracer
            # would slow the host-bound round loop more than twofold
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        setup["s"] = time.monotonic() - T_START
        watch.phase = "window"

    def send(i: int):
        with jax.profiler.TraceAnnotation("client.request"):
            return client.post_infer(host, port, bodies[i])

    window = client.run_window(send, traffic, args.seconds,
                               on_start=on_start)
    watch.phase = "after"
    if trace_dir:
        jax.profiler.stop_trace()
    log(f"traced or compiled in the window: {watch.summary('window')}")
    mem_peak = int((devices[0].memory_stats() or {}).get("peak_bytes_in_use",
                                                          0))
    frontend.close()
    del frontend
    gc.collect()
    reqs = window["requests"]
    done = [r for r in reqs if "response" in r]
    log(f"window: {len(reqs)} requests, {len(done)} served, "
        f"{window['end'] - window['start']:.3f} s")

    # -- correctness: every served logit against the plain reference -------
    watch.phase = "check"
    t0 = time.monotonic()
    checks = correctness.check(cfg, params, pool, done, args.seed)
    log(f"reference and check in {time.monotonic() - t0:.3f} s: "
        f"{watch.summary('check')}")
    correct = (len(done) == len(reqs) and len(done) > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    # -- metrics -------------------------------------------------------------
    run = {"cell": cell, "config": cfg, "traffic": traffic, "peaks": peak,
           "requests": done, "batch": batch, "setup_s": setup.get("s"),
           "window_s": window["end"] - window["start"], "trace": None}
    run["images_per_s"] = spec.metric_reader("images_per_s").read(run)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct), "attempted": len(reqs),
              "failed": len(reqs) - len(done), "metrics": {},
              "device": device}
    if trace_dir:
        trace = tracing.load(trace_dir, window_host_span="client.request")
        shutil.rmtree(trace_dir, ignore_errors=True)
        run["trace"] = trace
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["breakdown"] = trace.breakdown()
    for m in spec.cell_metrics(bench, cell["name"], bool(args.trace)):
        got = spec.metric_reader(m["name"]).read(run)
        entry = got if isinstance(got, dict) else {"value": got}
        if entry["value"] is None or not math.isfinite(entry["value"]):
            continue
        result["metrics"][m["name"]] = dict(entry, unit=m["unit"])
    log("latencies (s): " + ", ".join(f"{r['latency_s']:.4f}" for r in done))
    for r in reqs:
        if "error" in r:
            log(f"request {r['i']} failed: {r['error']}")
    result["checks"] = {k: {"value": _finite_or_none(v["value"]),
                            "limit": v["limit"]} for k, v in checks.items()}
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r} "
              f"({v['what']})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
