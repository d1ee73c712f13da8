"""What-if batches: ``copies`` times the configuration's lanes, stacked into
shape buckets at set-up and run until every lane drains, batch after batch,
each with a key folded from the batch index.  With ``chips`` > 1 the lanes
are sharded over a mesh of that many chips.

Traffic parameters: ``copies`` (default 1), ``check_lanes`` (lanes of one
batch the check compares, drawn from the seed; default all), ``limits``.
"""
from __future__ import annotations

import numpy as np

import scenario
from drivers._window import DRAIN_ROUNDS, Window, reservoir
from reference import compare
from reference.gridsim import DONE, GridSim


def _reference(lane: dict, cfg: dict, ftype=np.float32, tie_ulps: int = compare.TIE_ULPS):
    return GridSim(lane["jobs"], lane["sites"], data=lane["data"], avail=lane["avail"],
                   quantum=cfg["quantum"], max_retries=cfg["max_retries"], ftype=ftype,
                   tie_ulps=tie_ulps)


def drive(ctx) -> dict:
    import jax
    from repro.core import Scenario, availability_subsystem, get_policy, stack_scenarios

    cfg, tr = ctx.cfg, ctx.traffic
    lanes = ctx.lanes(int(tr.get("copies", 1)))
    ctx.mark("inputs")
    subs = (availability_subsystem(),) if lanes[0]["avail"] is not None else ()
    scens = []
    for lane in lanes:
        jobs, sites, kw = scenario.to_program(lane)
        scens.append(Scenario(jobs, sites, {"availability": kw["availability"]} if subs else {}))
    sb = stack_scenarios(scens, subsystems=subs, buckets=cfg["ensemble"]["buckets"])
    ctx.mark("stack")
    policy = get_policy(cfg["policy"])
    run_kw = dict(subsystems=subs, max_rounds=DRAIN_ROUNDS, quantum=cfg["quantum"],
                  max_retries=cfg["max_retries"])
    if ctx.chips > 1:
        from repro.core.distributed import simulate_many_sharded

        mesh = jax.make_mesh((ctx.chips,), ("data",), devices=jax.devices()[: ctx.chips])

        def run(key, horizon):
            return simulate_many_sharded(sb, policy, key, mesh, donate=False, horizon=horizon,
                                         **run_kw)
    else:
        from repro.core import simulate_many

        def run(key, horizon):
            return simulate_many(sb, policy, key, horizon=horizon, **run_kw)

    K = len(lanes)
    index = [np.asarray(ix) for ix in sb.index]
    caps = [s.jobs.capacity for s in sb.buckets]
    n_valid = np.array([lane["jobs"]["arrival"].shape[0] for lane in lanes])

    def batch(i, horizon=np.inf):
        with jax.profiler.TraceAnnotation("batch"):
            res = run(jax.random.fold_in(ctx.key, i), horizon)
            out = jax.device_get(dict(rounds=res.rounds, state=res.jobs.state, valid=res.jobs.valid))
        return res, out

    # warm-up: compiles every bucket's program and the merge.  On one chip the
    # horizon is an argument of those programs, so a horizon of 0 s runs one
    # round of each; the sharded runner keys its programs by the horizon's
    # value, so there the warm-up is a whole batch.
    batch(0, 0.0 if ctx.chips == 1 else np.inf)
    ctx.mark("warm_up")

    keep = reservoir(ctx.seed)
    c = dict(scenarios=0, batches=0, lane_rounds=0, bucket_rounds=0, lockstep_rounds=0,
             bucket_rounds_traced=0, used_rows=0, padded_rows=0)
    sample = None
    failed = 0
    win = Window(ctx)
    while True:
        traced = win.tracing
        res, out = batch(c["batches"] + 1)
        rounds = np.asarray(out["rounds"])
        active = ((out["state"] < DONE) & out["valid"]).any(axis=1)
        drained = (~active) & (rounds < DRAIN_ROUNDS)
        c["scenarios"] += int(drained.sum())
        failed += int(K - drained.sum())
        c["batches"] += 1
        c["lane_rounds"] += int(rounds.sum())
        for ix, cap in zip(index, caps):
            # each chip steps its own block of the bucket's lanes until the
            # block's slowest lane drains
            blocks = np.array_split(ix, ctx.chips)
            steps = sum(int(rounds[b].max()) for b in blocks) / ctx.chips  # per chip
            c["bucket_rounds"] += steps
            c["lockstep_rounds"] += sum(int(rounds[b].max()) * len(b) for b in blocks)
            c["used_rows"] += int(n_valid[ix].sum())
            c["padded_rows"] += cap * len(ix) - int(n_valid[ix].sum())
            if traced:
                c["bucket_rounds_traced"] += steps
        if keep():
            sample = res
        if win.unit_done():
            break
    win.stop_trace()

    def check() -> dict:
        j = jax.device_get(dict(
            state=sample.jobs.state, site=sample.jobs.site, t_start=sample.jobs.t_start,
            t_finish=sample.jobs.t_finish, retries=sample.jobs.retries,
            preempted=sample.jobs.preempted, xfer_src=sample.jobs.xfer_src,
            valid=sample.jobs.valid, round=sample.rounds))
        # every lane, or ``check_lanes`` of them drawn from the seed
        rng = np.random.default_rng(scenario.lane_seed(ctx.seed, 0xC4EC))
        checked = np.sort(rng.choice(K, size=min(K, int(tr.get("check_lanes", K))), replace=False))
        pairs = []
        for i in checked:
            lane, n = lanes[i], n_valid[i]
            prog = {k: (v[i][:n] if np.ndim(v) > 1 else v[i]) for k, v in j.items()}
            pairs.append(compare.lane_pair(lambda lane=lane: _reference(lane, cfg), prog))
        return compare.numbers(pairs)

    return dict(t_first=win.t0, window_s=win.t1 - win.t0, counters=c,
                attempted=K * c["batches"], failed=failed, check=check)


def control_pairs(lanes: list, cfg: dict, traffic: dict, ftype, frames: int | None = None) -> list:
    """The reference in ``ftype`` put in the program's place for every lane
    of one batch, paired as ``drive``'s check pairs them."""
    pairs = []
    for lane in lanes:
        low = _reference(lane, cfg, ftype, tie_ulps=0)
        low.run_until(np.inf)
        pairs.append(compare.lane_pair(lambda lane=lane: _reference(lane, cfg),
                                       dict(low.snapshot(), round=low.rounds)))
    return pairs
