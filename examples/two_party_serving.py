"""Full two-party deployment: party processes + HTTP frontend, end to end.

Drives everything ``docs/deployment.md`` describes, in one command:

1. writes a smoke job directory (plan, seeds, per-party input share rows
   and triple slices);
2. launches party 1 as its OWN OS process in follower mode
   (``repro.launch.party_host --follow``);
3. launches party 0 as a second process: the ``InferenceEngine`` leader
   behind the asyncio HTTP frontend (``repro.serve.frontend``);
4. POSTs a mixed-tenant batch of requests to ``/infer``, polls
   ``/healthz`` / ``/stats``, and prints measured wall-clock latency
   next to the ``core.schedule`` prediction for the injected RTT.

    PYTHONPATH=src python examples/two_party_serving.py
    PYTHONPATH=src python examples/two_party_serving.py --rtt-ms 4

``--make-job DIR`` only writes the job directory (the two-terminal
quickstart in docs/deployment.md starts from this) and exits.

One process per accelerator: this process never runs JAX itself.  The
job is written by a child process, and the request inputs come from
numpy, so the party processes are free to take the device.
"""
import argparse
import json
import os
import pathlib
import subprocess
import sys
import time
import urllib.request

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from repro.transport import free_port, write_smoke_job  # noqa: E402

HOST = "127.0.0.1"


def make_job(job_dir) -> None:
    """Smoke job: traced plan + seeds + party-split shares and triples."""
    write_smoke_job(job_dir)
    print(f"wrote job directory {job_dir}")


def _http(method, url, body=None, timeout=600.0):
    req = urllib.request.Request(
        url, method=method,
        data=json.dumps(body).encode() if body is not None else None)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read().decode())


def _wait_healthy(base, deadline_s=300.0) -> dict:
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        try:
            status, health = _http("GET", f"{base}/healthz", timeout=5.0)
            if status == 200 and health.get("ok"):
                return health
        except OSError:
            pass
        time.sleep(0.25)
    raise TimeoutError(f"frontend at {base} never became healthy")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--make-job", default=None, metavar="DIR",
                    help="only write the job directory and exit")
    ap.add_argument("--job", default=None,
                    help="reuse an existing job directory")
    ap.add_argument("--rtt-ms", type=float, default=0.0,
                    help="injected link RTT for both parties")
    args = ap.parse_args()

    if args.make_job:
        make_job(args.make_job)
        return 0

    import tempfile
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    tmp = None
    if args.job is None:
        tmp = tempfile.TemporaryDirectory()
        args.job = os.path.join(tmp.name, "job")
        subprocess.run([sys.executable, __file__, "--make-job", args.job],
                       env=env, check=True)

    link_port, http_port = free_port(), free_port()
    shaping = (["--rtt-ms", str(args.rtt_ms)] if args.rtt_ms > 0 else [])
    follower = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.party_host", "--party", "1",
         "--job", args.job, "--peer", f"{HOST}:{link_port}", "--follow"]
        + shaping, env=env, cwd=ROOT)
    leader = subprocess.Popen(
        [sys.executable, "-m", "repro.serve.frontend", "--job", args.job,
         "--listen", f"{HOST}:{link_port}",
         "--http", f"{HOST}:{http_port}"] + shaping, env=env, cwd=ROOT)

    base = f"http://{HOST}:{http_port}"
    try:
        _wait_healthy(base)
        print(f"frontend healthy at {base}; POSTing mixed-tenant batch")

        mix = [("alice", 10), ("bob", 11), ("alice", 12)]
        t0 = time.perf_counter()
        for tenant, seed in mix:
            x = (np.random.default_rng(seed).standard_normal((2, 3, 8, 8))
                 * 0.5).astype(np.float32)
            status, resp = _http("POST", f"{base}/infer",
                                 {"tenant": tenant, "x": x.tolist()})
            assert status == 200, resp
            y = np.asarray(resp["y"], np.float32)
            print(f"  {tenant}: argmax {np.argmax(y, -1).tolist()} "
                  f"({resp['wall_s']:.3f}s wall, "
                  f"{resp['batch']['measured_rounds']} rounds vs "
                  f"{resp['batch']['predicted_rounds']} predicted)")
        wall = time.perf_counter() - t0

        status, stats = _http("GET", f"{base}/stats")
        assert status == 200
        tr = stats.get("transport", {})
        print(f"\n{len(mix)} requests in {wall:.2f}s "
              f"({len(mix) / wall:.2f} req/s) across {stats['batches']} "
              f"batches; transport: {tr.get('rounds')} rounds, "
              f"{tr.get('payload_bytes')} payload B, "
              f"{tr.get('retries')} retries")
        if args.rtt_ms > 0:
            print(f"injected RTT {args.rtt_ms}ms -> per-round floor "
                  f"{args.rtt_ms / 1e3:.4f}s x {tr.get('rounds')} rounds = "
                  f"{args.rtt_ms / 1e3 * (tr.get('rounds') or 0):.3f}s "
                  f"minimum comm wall")
        return 0
    finally:
        leader.terminate()
        follower.terminate()
        for p in (leader, follower):
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
        if tmp is not None:
            tmp.cleanup()


if __name__ == "__main__":
    sys.exit(main())
