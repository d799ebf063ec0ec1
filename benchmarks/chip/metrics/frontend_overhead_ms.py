"""HTTP frontend (serve/frontend.py): median over the window's requests of
the client's latency less the response's ``wall_s`` (which runs from the
engine's ``submit`` to the revealed logits): HTTP, JSON and submit."""
import statistics


def read(run):
    gaps = [r["latency_s"] - r["response"]["wall_s"] for r in run["requests"]
            if "wall_s" in r["response"]]
    return 1e3 * statistics.median(gaps) if gaps else None
