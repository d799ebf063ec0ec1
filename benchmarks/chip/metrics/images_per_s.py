"""Images whose logits came back, over the whole window: from the first
send to the last completion (host clock)."""


def read(run):
    span = run["window_s"]
    if not run["requests"] or span <= 0:
        return None
    return run["batch"] * len(run["requests"]) / span
