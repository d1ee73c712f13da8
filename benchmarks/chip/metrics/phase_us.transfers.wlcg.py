"""Device time per engine round in the transfer queues' hooks (the
``transfers`` scope: byte progress, landing, admission, pricing), over the
traced dashboard frames (``scopes.py``)."""
import scopes


def read(run):
    return scopes.phase_us(run, "transfers")
