"""The one traffic generator: a closed-loop HTTP client that never
touches JAX.

A traffic file (``traffic/<name>.json``) holds only parameters:

- ``batch``: images per request;
- ``pool_requests``: distinct requests drawn from the seed; request ``i``
  of a window sends pool entry ``i % pool_requests``;
- ``scale_for_k``: the ReLU window top bit ``k`` whose Theorem-1 range the
  images are scaled into (the same images for every plan);
- ``tenant``: the tenant name the requests carry.

``run_window`` sends one request at a time, the next when the previous
one returns, until ``seconds`` have passed; the window ends at the last
completion.
"""
from __future__ import annotations

import http.client
import json
import time
from typing import Callable, Dict, List, Optional


def post_infer(host: str, port: int, body: bytes,
               timeout_s: float = 1200.0) -> Dict:
    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    try:
        conn.request("POST", "/infer", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {payload}")
        return payload
    finally:
        conn.close()


def request_body(x_nested: list, tenant: str,
                 timeout_s: float = 1100.0) -> bytes:
    """An ``/infer`` body; ``timeout_s`` lets the frontend wait for a cold
    first request, which compiles (a checkout's first run may take 1200 s)."""
    return json.dumps({"tenant": tenant, "x": x_nested,
                       "timeout_s": timeout_s}).encode()


def run_window(send: Callable[[int], Dict], traffic: Dict, seconds: float,
               on_start: Optional[Callable[[], None]] = None,
               clock: Callable[[], float] = time.perf_counter) -> Dict:
    """Call ``send(pool_index)`` in a closed loop for ``seconds``; returns
    ``{"start", "end", "requests": [...]}``.

    Each request record has ``i``, ``pool``, ``sent``, ``done``,
    ``latency_s`` and ``response`` or ``error``.
    """
    pool = int(traffic["pool_requests"])
    records: List[Dict] = []
    if on_start is not None:
        on_start()
    start = clock()
    while clock() < start + seconds:
        i = len(records)
        rec = {"i": i, "pool": i % pool, "sent": clock()}
        try:
            rec["response"] = send(i % pool)
        except Exception as e:                 # noqa: BLE001 -- counted
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["done"] = clock()
        rec["latency_s"] = rec["done"] - rec["sent"]
        records.append(rec)
    end = records[-1]["done"] if records else clock()
    return {"start": start, "end": end, "requests": records}

