"""Lock-step waste of the ensemble, an exact count: the share of lane rounds
stepped by a bucket after the lane had drained,
``1 - sum(lane rounds) / sum(lanes x rounds of their bucket)``."""


def read(run):
    c = run["counters"]
    if not c.get("lockstep_rounds"):
        return None
    return 100.0 * (1.0 - c["lane_rounds"] / c["lockstep_rounds"])
