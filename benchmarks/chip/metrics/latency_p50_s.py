"""Median client-side latency over every request served in the window
(host clock, each request timed from when it was sent)."""
import statistics


def read(run):
    lat = [r["latency_s"] for r in run["requests"]]
    return statistics.median(lat) if lat else None
