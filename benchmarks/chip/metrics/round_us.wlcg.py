"""Device time per engine round in the dashboard frames: the device's busy
seconds in the traced stretch over the rounds the engine ran in it."""


def read(run):
    n = run["counters"].get("rounds_traced")
    if not run["trace"] or not n:
        return None
    return run["trace"]["busy_s_mean"] / n * 1e6
