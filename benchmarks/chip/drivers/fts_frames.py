"""A live dashboard over a grid whose WAN reads go through FTS-style link
queues: the passes and frames of ``drivers/frames.py``, with the program's
transfer-queue subsystem on (``transfers=``, ``max_active`` from the
configuration's ``transfers`` block) and the plain reference
``reference/fts.py`` in the check.

The timed day is fixed: the configuration's ``day_seed`` deals its jobs onto
their arrival times once (its builder's ``lanes``), and ``--seed`` draws the
order of the job table's rows, the job ids, so the program's sorts, scatters
and id tie-breaks see the rows in a new order in every run.  Dealt anew per
seed, as ``frames`` deals, the events a second spread across seeds by about
2% on the chip (IQR over the median of six): a landing is a round of its
own and costs less than a start round, and a queued transfer brings the
admission sorts in, so a frame's cost follows the day's mix of rounds.

The check compares, at every frame end, what ``frames`` compares and each
job's queue wait ``xfer_wait`` and transfer time ``xfer_time`` (under
``time_gap_rel``) and the queue depth it saw at enqueue ``xfer_qdepth``
(exactly: a row that differs counts in ``rows_differing``), on two passes
run to their last frame on the same compiled program: the timed pass drawn
from the seed (run on after the window where the window closed inside it),
and, untimed, a pass over the day that ``--seed`` deals (``ctx.lanes``).
The dealt pass is compared every ``CHECK_UNIT_S`` simulated seconds, not at
frame ends only: the reference searches the rounding ties of the site score
unit by unit (``compare.frame_pairs``), and a tie that the program breaks
the other way can lead its trajectory to a tie that the reference's own
trajectory never meets; compared a second at a time, the search meets each
tie on the program's side of the ones before it.
Rows and rounds that differ are summed over the two, the widest time gap
and the larger count of ties flipped are kept, each a pass's reading.

Traffic parameters: ``frame_s``, ``span_s``, ``limits``.  ``events`` counts
job starts and ends as ``frames`` does, plus transfer landings: a landing
is an event of the simulation's clock, a round of its own.  Counters beside
``frames``' own: ``transfers`` (WAN reads queued for transfer),
``transfers_waited`` (transfers admitted after waiting for a link slot),
and their ``_traced`` counts; and of the two checked passes, the units
compared ``check_units`` and the rows that met a queue at their ends:
``check_queued`` (``xfer_qdepth`` > 0) and ``check_waited`` (``xfer_wait`` > 0).
"""
from __future__ import annotations

import numpy as np

import harness
import scenario
from drivers._window import DRAIN_ROUNDS, Window, events, reservoir
from drivers.frames import _n_frames
from reference import compare
from reference.fts import FTS_FIELDS, FtsSim

CHECK_UNIT_S = 1.0  # simulated seconds between the dealt pass's compared snapshots


def _reference(lane: dict, cfg: dict, ftype=np.float32, tie_ulps: int = compare.TIE_ULPS):
    return FtsSim(lane["jobs"], lane["sites"], data=lane["data"], avail=lane["avail"],
                  max_active=cfg["transfers"]["max_active"], quantum=cfg["quantum"],
                  max_retries=cfg["max_retries"], ftype=ftype, tie_ulps=tie_ulps)


def day_lane(cfg: dict, seed: int) -> dict:
    """The configuration's day (jobs dealt by ``day_seed``) with the job
    table's rows in an order drawn from ``seed``."""
    (day,) = harness.load("builders", cfg["builder"]).lanes(cfg, cfg["day_seed"], 1)
    n = day["jobs"]["arrival"].shape[0]
    perm = np.random.default_rng(scenario.lane_seed(seed, 0)).permutation(n)
    return dict(day, jobs={k: v[perm] for k, v in day["jobs"].items()})


def transfer_numbers(pairs) -> dict:
    """``xfer_qdepth`` rows that differ and the widest relative gap of
    ``xfer_wait``/``xfer_time`` (as ``compare.numbers`` measures times),
    summed and maximised over the units, with the first differing row."""
    rows, gap, first = 0, 0.0, None
    for u, (p, r) in enumerate(pairs):
        bad = np.asarray(p["xfer_qdepth"]).astype(np.int64) != np.asarray(r["xfer_qdepth"])
        for k in ("xfer_wait", "xfer_time"):
            a = np.asarray(p[k], np.float64)
            b = np.asarray(r[k], np.float64)
            gap = max(gap, float((np.abs(a - b) / np.maximum(np.abs(b), 1.0)).max(initial=0.0)))
        rows += int(bad.sum())
        if first is None and bad.any():
            j = int(np.flatnonzero(bad)[0])
            first = dict(unit=u, job=j, **{f"program_{k}": np.asarray(p[k])[j].item() for k in FTS_FIELDS},
                         **{f"reference_{k}": np.asarray(r[k])[j].item() for k in FTS_FIELDS})
    return dict(rows_differing=rows, time_gap_rel=gap, first_difference=first)


def numbers(pairs) -> dict:
    """``compare.numbers`` with the transfer columns folded in."""
    out = compare.numbers(pairs)
    tr = transfer_numbers(pairs)
    out["rows_differing"] += tr["rows_differing"]
    out["time_gap_rel"] = max(out["time_gap_rel"], tr["time_gap_rel"])
    out["first_difference"] = out["first_difference"] or tr["first_difference"]
    return out


def merge(passes: list) -> dict:
    """The numbers of several checked passes: rows and rounds that differ
    summed, the widest time gap and the most ties flipped in a pass."""
    return dict(rows_differing=sum(n["rows_differing"] for n in passes),
                rounds_differing=sum(n["rounds_differing"] for n in passes),
                time_gap_rel=max(n["time_gap_rel"] for n in passes),
                ties_flipped=max(n["ties_flipped"] for n in passes),
                first_difference=next((n["first_difference"] for n in passes
                                       if n["first_difference"]), None))


def drive(ctx) -> dict:
    import jax
    from repro.core import advance_sim, get_policy, init_sim, make_transfers

    cfg, tr = ctx.cfg, ctx.traffic
    lane = day_lane(cfg, ctx.seed)
    ctx.mark("inputs")

    def handle(lane):
        jobs, sites, kw = scenario.to_program(lane)
        kw["transfers"] = make_transfers(sites.capacity, jobs.capacity,
                                         max_active=cfg["transfers"]["max_active"])
        return init_sim(jobs, sites, get_policy(cfg["policy"]), ctx.key, quantum=cfg["quantum"],
                        max_rounds=DRAIN_ROUNDS, max_retries=cfg["max_retries"], **kw)

    h0 = handle(lane)
    jax.block_until_ready(h0.state)
    ctx.mark("init_sim")
    state0 = h0.state
    frame_s = float(tr["frame_s"])
    n_frames = _n_frames(tr)

    def start(i):
        return h0._replace(state=state0._replace(rng=jax.random.fold_in(ctx.key, i)))

    def frame(h, horizon):
        with jax.profiler.TraceAnnotation("frame"):
            h = advance_sim(h, horizon)
            jax.block_until_ready(h.state.jobs.state)
        with jax.profiler.TraceAnnotation("snapshot"):
            j, ts = h.state.jobs, h.state.ext["transfers"]
            snap = jax.device_get(dict(
                state=j.state, site=j.site, t_start=j.t_start, t_finish=j.t_finish,
                retries=j.retries, preempted=j.preempted, xfer_src=j.xfer_src,
                xfer_wait=j.xfer_wait, xfer_time=j.xfer_time, xfer_qdepth=j.xfer_qdepth,
                valid=j.valid, round=h.state.round, n_enq=ts.n_enq, n_done=ts.n_done,
                n_waited=ts.n_waited))
        return h, snap

    # warm-up: compiles the segment program (the horizon is an argument of
    # it, so a horizon of 0 s runs one round of the same program) and the key fold
    jax.block_until_ready(advance_sim(start(0), 0.0).state.jobs.state)
    ctx.mark("warm_up")

    keep = reservoir(ctx.seed)
    c = dict(events=0, frames=0, rounds=0, transfers=0, transfers_waited=0,
             rounds_traced=0, events_traced=0, transfers_traced=0, transfers_waited_traced=0)
    sample: list = []
    win = Window(ctx)
    done, n_pass = False, 0
    while not done:
        n_pass += 1
        kept = keep()
        snaps = []
        prev = dict(events=0, rounds=0, transfers=0, transfers_waited=0)
        h = start(n_pass)
        for k in range(1, n_frames + 1):
            traced = win.tracing
            h, snap = frame(h, k * frame_s)
            now = dict(events=events(snap) + int(snap["n_done"]), rounds=int(snap["round"]),
                       transfers=int(snap["n_enq"]), transfers_waited=int(snap["n_waited"]))
            for name, v in now.items():
                c[name] += v - prev[name]
                if traced:
                    c[f"{name}_traced"] += v - prev[name]
            prev = now
            c["frames"] += 1
            if kept:
                snaps.append(snap)
            if win.unit_done():
                done = True
                break
        if kept:
            sample = snaps
    win.stop_trace()
    if kept:  # the window closed inside the drawn pass: run it to its end, untimed
        for k in range(k + 1, n_frames + 1):
            h, snap = frame(h, k * frame_s)
            sample.append(snap)
    c["passes"] = n_pass
    (dealt,) = ctx.lanes()
    h = h0._replace(state=handle(dealt).state)  # the same grid and program: no new trace
    dealt_sample = []
    for k in range(1, int(round(n_frames * frame_s / CHECK_UNIT_S)) + 1):
        h, snap = frame(h, k * CHECK_UNIT_S)
        dealt_sample.append(snap)
    ends = (sample[-1], dealt_sample[-1])
    c.update(check_units=len(sample) + len(dealt_sample),
             check_queued=sum(int((u["xfer_qdepth"] > 0).sum()) for u in ends),
             check_waited=sum(int((u["xfer_wait"] > 0).sum()) for u in ends))

    def check() -> dict:
        return merge([numbers(compare.frame_pairs(_reference(u, cfg), snaps, unit))
                      for u, snaps, unit in ((lane, sample, frame_s),
                                             (dealt, dealt_sample, CHECK_UNIT_S))])

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
