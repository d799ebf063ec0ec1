"""Round loop: the fused protocol rounds of a request's micro-batch, as
the engine counts them (``batch.measured_rounds``); the median over the
window's requests."""
import statistics


def read(run):
    rounds = [r["response"]["batch"]["measured_rounds"]
              for r in run["requests"] if "batch" in r["response"]]
    return statistics.median(rounds) if rounds else None
