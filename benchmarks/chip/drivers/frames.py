"""A live dashboard: passes over the first ``span_s`` simulated seconds in
``frame_s`` frames, each an ``advance_sim`` segment followed by a host
snapshot of the job table.  Every pass restarts from the state ``init_sim``
built at set-up, with a key folded from the pass index, so the window
always covers the same stretch of the day however fast the frames run.

Traffic parameters: ``frame_s``, ``span_s``, ``limits``.
"""
from __future__ import annotations

import numpy as np

import scenario
from drivers._window import DRAIN_ROUNDS, Window, events, reservoir
from reference import compare
from reference.gridsim import GridSim


def _reference(lane: dict, cfg: dict, ftype=np.float32, tie_ulps: int = compare.TIE_ULPS):
    return GridSim(lane["jobs"], lane["sites"], data=lane["data"], avail=lane["avail"],
                   quantum=cfg["quantum"], max_retries=cfg["max_retries"], ftype=ftype,
                   tie_ulps=tie_ulps)


def _n_frames(traffic: dict) -> int:
    return int(round(traffic["span_s"] / traffic["frame_s"]))


def drive(ctx) -> dict:
    import jax
    from repro.core import advance_sim, get_policy, init_sim

    cfg, tr = ctx.cfg, ctx.traffic
    (lane,) = ctx.lanes()
    ctx.mark("inputs")
    jobs, sites, kw = scenario.to_program(lane)
    h0 = init_sim(jobs, sites, get_policy(cfg["policy"]), ctx.key, quantum=cfg["quantum"],
                  max_rounds=DRAIN_ROUNDS, max_retries=cfg["max_retries"], **kw)
    jax.block_until_ready(h0.state)
    ctx.mark("init_sim")
    state0 = h0.state
    frame_s = float(tr["frame_s"])
    n_frames = _n_frames(tr)

    def start(i):
        return h0._replace(state=state0._replace(rng=jax.random.fold_in(ctx.key, i)))

    def frame(h, k):
        with jax.profiler.TraceAnnotation("frame"):
            h = advance_sim(h, k * frame_s)
            jax.block_until_ready(h.state.jobs.state)
        with jax.profiler.TraceAnnotation("snapshot"):
            j = h.state.jobs
            snap = jax.device_get(dict(
                state=j.state, site=j.site, t_start=j.t_start, t_finish=j.t_finish,
                retries=j.retries, preempted=j.preempted, xfer_src=j.xfer_src,
                valid=j.valid, round=h.state.round))
        return h, snap

    # warm-up: compiles the segment program (the horizon is an argument of
    # it, so a horizon of 0 s runs one round of the same program) and the key fold
    jax.block_until_ready(advance_sim(start(0), 0.0).state.jobs.state)
    ctx.mark("warm_up")

    keep = reservoir(ctx.seed)
    c = dict(events=0, frames=0, rounds=0, rounds_traced=0, events_traced=0)
    sample: list = []
    win = Window(ctx)
    done, n_pass = False, 0
    while not done:
        n_pass += 1
        kept = keep()
        snaps, prev_ev, prev_round = [], 0, 0
        h = start(n_pass)
        for k in range(1, n_frames + 1):
            traced = win.tracing
            h, snap = frame(h, k)
            ev, r = events(snap), int(snap["round"])
            c["events"] += ev - prev_ev
            c["rounds"] += r - prev_round
            if traced:
                c["events_traced"] += ev - prev_ev
                c["rounds_traced"] += r - prev_round
            prev_ev, prev_round = ev, r
            c["frames"] += 1
            if kept:
                snaps.append(snap)
            if win.unit_done():
                done = True
                break
        if kept:
            sample = snaps
    win.stop_trace()
    c["passes"] = n_pass

    def check() -> dict:
        return compare.numbers(compare.frame_pairs(_reference(lane, cfg), sample, frame_s))

    return dict(t_first=win.t0, window_s=win.t1 - win.t0, counters=c,
                attempted=c["frames"], failed=0, check=check)


def control_pairs(lanes: list, cfg: dict, traffic: dict, ftype, frames: int | None = None) -> list:
    """The reference in ``ftype`` put in the program's place for one pass
    (or its first ``frames`` frames), paired as ``drive``'s check pairs them."""
    (lane,) = lanes
    low = _reference(lane, cfg, ftype, tie_ulps=0)
    frame_s = float(traffic["frame_s"])
    sample = []
    for k in range(1, (frames or _n_frames(traffic)) + 1):
        low.run_until(k * frame_s)
        sample.append(dict(low.snapshot(), round=low.rounds))
    return compare.frame_pairs(_reference(lane, cfg), sample, frame_s)
