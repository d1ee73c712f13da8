"""Share of the traced what-if batches in which no operation ran on the
device (on several chips, the largest share over the chips): per-bucket
dispatch, the bucket merge and the host read between batches."""


def read(run):
    if not run["trace"]:
        return None
    return 100.0 * run["trace"]["idle_share"]
