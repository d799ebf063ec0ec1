"""Round loop (runtime/loop.py, core/gmw.py): median over the window's
requests of the response's ``wall_s`` over its batch's measured fused
rounds."""
import statistics


def read(run):
    per = [r["response"]["wall_s"] / r["response"]["batch"]["measured_rounds"]
           for r in run["requests"]
           if r["response"].get("batch", {}).get("measured_rounds")]
    return 1e3 * statistics.median(per) if per else None
