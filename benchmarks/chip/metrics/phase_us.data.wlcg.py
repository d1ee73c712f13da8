"""Device time per engine round in the round loop's ``data`` scope, over the
traced dashboard frames (``scopes.py``)."""
import scopes


def read(run):
    return scopes.phase_us(run, "data")
