"""Device time per engine round in the round loop's ``start`` scope, over the
traced dashboard frames (``scopes.py``)."""
import scopes


def read(run):
    return scopes.phase_us(run, "start")
