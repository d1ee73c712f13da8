"""Share of the traced dashboard frames in which no operation ran on the
device: segment re-entry and the host snapshot between frames."""


def read(run):
    if not run["trace"]:
        return None
    return 100.0 * run["trace"]["idle_share"]
