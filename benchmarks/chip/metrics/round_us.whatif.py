"""Device time per bucket round in what-if batches: the devices' mean busy
seconds in the traced stretch over the rounds their bucket loops stepped
(each bucket steps until its slowest lane drains)."""


def read(run):
    n = run["counters"].get("bucket_rounds_traced")
    if not run["trace"] or not n:
        return None
    return run["trace"]["busy_s_mean"] / n * 1e6
