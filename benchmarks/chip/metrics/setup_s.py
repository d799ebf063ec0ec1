"""Set-up: process start to the first request of the window (weights,
request pool, serving stack, one warm-up request; compilation where the
cache is cold)."""


def read(run):
    return run["setup_s"]
