"""Device idle time inside each of the program's ``advance_sim`` spans in the
traced dashboard frames: segment re-entry on the host (``scopes.py``)."""
import scopes


def read(run):
    return scopes.reentry_idle_us(run)
