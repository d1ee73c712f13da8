"""Pieces every traffic driver shares: the measured window's host clock, the
draw of the unit the check compares, and the count of job events."""
from __future__ import annotations

import time

import numpy as np

import scenario
from reference.gridsim import DONE, RUNNING

DRAIN_ROUNDS = 10**7  # "until drained": far above any lane's need


class Window:
    """Host clock of the measured window, and the traced stretch at its start."""

    def __init__(self, ctx):
        import jax

        self.ctx, self.jax = ctx, jax
        self.tracing = ctx.trace_dir is not None
        if self.tracing:
            jax.profiler.start_trace(ctx.trace_dir)
        self.t0 = time.perf_counter()

    def unit_done(self) -> bool:
        """Call at the end of each unit; True once the window is over."""
        now = time.perf_counter()
        if self.tracing and now - self.t0 >= self.ctx.trace_seconds:
            self.stop_trace()
        self.t1 = now
        return now - self.t0 >= self.ctx.seconds

    def stop_trace(self) -> None:
        if self.tracing:
            self.jax.profiler.stop_trace()
            self.tracing = False


def reservoir(seed: int):
    """Keep the n-th unit with probability 1/n: one unit drawn from the seed."""
    rng = np.random.default_rng(scenario.lane_seed(seed, 0x5A17))
    n = 0

    def keep() -> bool:
        nonlocal n
        n += 1
        return rng.random() * n < 1.0

    return keep


def events(snap: dict) -> int:
    """Job starts plus job ends so far: each preemption is one start and one
    end; a running job has started, a finished one has started and ended."""
    st = snap["state"][snap["valid"]]
    pre = snap["preempted"][snap["valid"]]
    return int(2 * pre.sum() + (st == RUNNING).sum() + 2 * (st == DONE).sum())
