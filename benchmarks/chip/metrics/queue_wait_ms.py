"""Engine (serve/engine.py): how long a request waited in the engine's
queue before its micro-batch ran, as the engine counts it (the response's
``batch.queue_s``); the median over the window's requests, in ms."""
import statistics


def read(run):
    waits = [r["response"]["batch"]["queue_s"] for r in run["requests"]
             if "queue_s" in r["response"].get("batch", {})]
    return 1e3 * statistics.median(waits) if waits else None
