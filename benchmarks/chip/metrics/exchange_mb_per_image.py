"""Exchange (core/comm.py): bytes the parties exchange per image, from the
engine's exact count (``batch.measured_bytes``) over the images of the
micro-batch; the median over the window's requests, in MB (10^6 bytes)."""
import statistics


def read(run):
    per = [r["response"]["batch"]["measured_bytes"] / 1e6
           / (run["batch"] * r["response"]["batch"]["n_requests"])
           for r in run["requests"] if "batch" in r["response"]]
    return statistics.median(per) if per else None
