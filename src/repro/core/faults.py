"""Fault injection & recovery — chaos channels for the event engine (DESIGN.md §13).

CGSim's pitch is evaluating grid resilience *policies* before deploying them,
but through PR 9 the only failure mode is a per-site coin flip with instant,
free resubmission: FTS flows never fail, replicas never vanish, overrunning
jobs never get killed, and nothing reacts to a site that fails every job it
touches.  This module adds the retry/failure dynamics real WLCG middleware
exhibits (arxiv 1902.10069, 2403.14903) as a fifth built-in
:class:`~.subsystems.Subsystem` with four channels in one fixed-shape
:class:`FaultState` pytree:

1. **Transfer-failure injection** — each in-flight FTS flow (PR 8) fails at
   its would-complete event with a per-link probability, drawn from the
   subsystem's own RNG stream (``ctx.subkey("faults")``).  Failed flows
   re-enqueue after an exponential-backoff delay (``base * 2^attempt``);
   past ``max_xfer_attempts`` the staging job fails its attempt and takes
   the engine's normal retry path.
2. **Resubmission backoff** — jobs resubmitted after a failed attempt are
   pushed back to PENDING with ``arrival = clock + base * 2^(retries-1)``
   instead of rejoining QUEUED in the same round.  Backoff base 0 (the
   default) keeps the current bitstream: the channel is then statically
   compiled out (it would invalidate the packed start-order fast path,
   which keys on run-constant arrivals — see ``FaultsConfig.mutates_arrival``).
3. **Replica-loss calendar** — timed loss events drop non-pinned replicas
   from the PR 1 catalog mid-run, so later readers re-source from the origin
   over the WAN.  The pinned-origin invariant is preserved by construction
   (origin copies are never dropped) and the catalog stays exact
   (``disk_used`` decremented, ``last_access`` reset to the -inf sentinel).
4. **Adaptive site blacklisting** — a per-site EWMA failure score trips a
   circuit breaker: the site leaves assignment feasibility (and gets zero
   start budget) for a cooldown window, then reopens *half-open* — exactly
   one probe job is admitted; success closes the breaker and resets the
   score, failure re-trips it.

Walltime kills ride along as a fifth behavior: RUNNING jobs whose
``t_start + walltime`` deadline passes are preempted (resources freed,
transfer cancelled, attempt retried or failed), mirroring batch-system
walltime limits.

Every channel contributes its next edge (backoff wake-ups, loss events,
cooldown expiries, kill deadlines) to the engine's event-time min-reduction,
so fault dynamics land on exact event rounds — no polling quantum needed.
``faults=None`` is bit-for-bit inert via static specialization, and a
default-constructed ``make_faults`` state (probability 0, backoff 0, no
events, infinite walltime, blacklisting off) reproduces the faults-off
engine bitstream: all masks are provably False and the subsystem only
draws from its own fold_in stream.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .types import ASSIGNED, FAILED, PENDING, QUEUED, RUNNING

INF = jnp.float32(jnp.inf)

# circuit-breaker states (per site)
BL_CLOSED, BL_TRIPPED, BL_HALF_OPEN = 0, 1, 2


class FaultsConfig(NamedTuple):
    """Static (hashable) compile-time flags for the faults subsystem.

    Both are derived host-side from the concrete initial state by
    :func:`faults_subsystem` so that disabled channels trace no ops:

    - ``job_backoff``: channel 2 mutates ``jobs.arrival``, which invalidates
      the engine's packed start-order fast path (``~srank`` assumes
      run-constant arrivals) — the engine checks ``mutates_arrival`` and
      falls back to the general ranking when set.
    - ``blacklist``: the circuit breaker expands the sparse top-k site-level
      feasibility mask to a full ``[J, S]`` probe gate; compiled out when
      the trip threshold is infinite.
    """

    job_backoff: bool = False
    blacklist: bool = False

    @property
    def mutates_arrival(self) -> bool:
        return self.job_backoff


class FaultState(NamedTuple):
    """The faults subsystem's ``EngineState.ext["faults"]`` slot.

    Link axis ``L = S * S`` (flattened directed links, matching
    :class:`~.transfers.TransferState`); job axis ``J``; site axis ``S``;
    loss-calendar axis ``E`` (fixed, inf-padded).
    """

    # channel 1: transfer-failure injection + exponential backoff re-enqueue
    link_fail_p: jax.Array  # f32[L] per-link failure probability
    xfer_backoff: jax.Array  # f32[] backoff base (s); delay = base * 2^attempt
    max_xfer_attempts: jax.Array  # i32[] failures before the job attempt fails
    attempt: jax.Array  # i32[J] failures of the current stage-in
    retry_at: jax.Array  # f32[J] backoff wake time (inf = no retry pending)
    # channel 2: resubmission backoff (enabled iff base > 0 — static flag)
    job_backoff: jax.Array  # f32[] backoff base (s); delay = base * 2^(retries-1)
    backoff_wait: jax.Array  # f32[J] cumulative scheduled backoff delay per job
    # walltime kills
    walltime: jax.Array  # f32[J] per-job walltime limit (inf = none)
    # channel 3: replica-loss calendar (host-built, sorted by time)
    loss_t: jax.Array  # f32[E] event times (inf = padding)
    loss_d: jax.Array  # i32[E] dataset ids
    loss_s: jax.Array  # i32[E] site ids
    loss_done: jax.Array  # bool[E] already applied
    # channel 4: adaptive site blacklisting (circuit breaker per site)
    bl_threshold: jax.Array  # f32[] EWMA score trip level (inf = disabled)
    bl_alpha: jax.Array  # f32[] EWMA smoothing factor
    bl_cooldown: jax.Array  # f32[] tripped -> half-open delay (s)
    score: jax.Array  # f32[S] EWMA failure fraction
    bl_state: jax.Array  # i32[S] BL_CLOSED / BL_TRIPPED / BL_HALF_OPEN
    bl_until: jax.Array  # f32[S] cooldown expiry (inf unless tripped)
    probe_job: jax.Array  # i32[S] in-flight half-open probe job id (-1 = none)
    seen_failed: jax.Array  # i32[S] sites.n_failed at last scoring pass
    seen_done: jax.Array  # i32[S] sites.n_finished at last scoring pass
    # counters (conservation: transfers.n_enq == n_done + n_cancel + n_xfer_fail)
    n_xfer_fail: jax.Array  # i32 injected transfer failures
    n_xfer_retry: jax.Array  # i32 backoff re-enqueues that fired
    n_xfer_exhaust: jax.Array  # i32 stage-ins that ran out of attempts
    n_kills: jax.Array  # i32 walltime kills
    n_lost_replicas: jax.Array  # i32 replicas dropped by loss events
    n_bl_trips: jax.Array  # i32 circuit-breaker trips (incl. probe re-trips)
    n_probes: jax.Array  # i32 half-open probe jobs admitted
    time_lost: jax.Array  # f32 wall-seconds of failed/killed attempts


def make_faults(
    n_sites,
    job_capacity,
    *,
    link_fail_p=0.0,
    xfer_backoff: float = 60.0,
    max_xfer_attempts: int = 3,
    job_backoff: float = 0.0,
    walltime=None,
    replica_loss=(),
    blacklist_threshold: float | None = None,
    blacklist_alpha: float = 0.25,
    blacklist_cooldown: float = 3600.0,
) -> FaultState:
    """Build a fault-injection state (all channels off by default — the
    default state is bitstream-identical to ``faults=None``).

    ``n_sites`` also accepts a ``SiteState``/``NetworkState``;
    ``job_capacity`` also accepts a ``JobsState``.

    - ``link_fail_p``: scalar, full ``[S, S]`` matrix, or ``{(src, dst): p}``
      mapping of per-link transfer failure probabilities.
    - ``xfer_backoff`` / ``max_xfer_attempts``: transfer retry schedule
      (delay ``base * 2^attempt``; past the cap the job attempt fails).
    - ``job_backoff``: resubmission backoff base in seconds (0 = resubmit in
      the same round, the engine's historical behavior).
    - ``walltime``: scalar seconds or per-job ``f32[J]`` (None = no limit).
    - ``replica_loss``: iterable of ``(t, dataset, site)`` tuples (or dicts
      with those keys) — see :func:`~.workload.replica_loss_calendar`.
    - ``blacklist_threshold``: EWMA failure-score trip level in ``(0, 1]``;
      None disables the circuit breaker entirely (statically compiled out).
    """
    S = getattr(n_sites, "n_sites", None) or getattr(n_sites, "capacity", None) or int(n_sites)
    J = getattr(job_capacity, "capacity", None) or int(job_capacity)
    L = S * S

    if isinstance(link_fail_p, dict):
        mat = np.zeros((S, S), np.float32)
        for (src, dst), p in link_fail_p.items():
            mat[int(src), int(dst)] = float(p)
        p_flat = mat.reshape(L)
    else:
        arr = np.asarray(link_fail_p, np.float32)
        if arr.ndim == 0:
            p_flat = np.full((L,), float(arr), np.float32)
        elif arr.shape == (S, S):
            p_flat = arr.reshape(L)
        else:
            raise ValueError(f"link_fail_p matrix must be [S, S] = [{S}, {S}], got {arr.shape}")
    if np.any((p_flat < 0) | (p_flat > 1)):
        raise ValueError("link_fail_p probabilities must lie in [0, 1]")

    if walltime is None:
        wt = np.full((J,), np.inf, np.float32)
    else:
        arr = np.asarray(walltime, np.float32)
        wt = np.full((J,), float(arr), np.float32) if arr.ndim == 0 else arr
        if wt.shape != (J,):
            raise ValueError(f"walltime must be scalar or shape ({J},), got {arr.shape}")

    events = []
    for ev in replica_loss:
        if isinstance(ev, dict):
            events.append((float(ev["t"]), int(ev["dataset"]), int(ev["site"])))
        else:
            t, d, s = ev
            events.append((float(t), int(d), int(s)))
    events.sort()
    E = max(len(events), 1)
    loss_t = np.full((E,), np.inf, np.float32)
    loss_d = np.full((E,), -1, np.int32)
    loss_s = np.full((E,), -1, np.int32)
    for i, (t, d, s) in enumerate(events):
        if not 0 <= s < S:
            raise ValueError(f"replica_loss site {s} out of range [0, {S})")
        loss_t[i], loss_d[i], loss_s[i] = t, d, s

    thresh = np.inf if blacklist_threshold is None else float(blacklist_threshold)
    return FaultState(
        link_fail_p=jnp.asarray(p_flat),
        xfer_backoff=jnp.float32(xfer_backoff),
        max_xfer_attempts=jnp.int32(max_xfer_attempts),
        attempt=jnp.zeros((J,), jnp.int32),
        retry_at=jnp.full((J,), jnp.inf, jnp.float32),
        job_backoff=jnp.float32(job_backoff),
        backoff_wait=jnp.zeros((J,), jnp.float32),
        walltime=jnp.asarray(wt),
        loss_t=jnp.asarray(loss_t),
        loss_d=jnp.asarray(loss_d),
        loss_s=jnp.asarray(loss_s),
        loss_done=jnp.zeros((E,), bool),
        bl_threshold=jnp.float32(thresh),
        bl_alpha=jnp.float32(blacklist_alpha),
        bl_cooldown=jnp.float32(blacklist_cooldown),
        score=jnp.zeros((S,), jnp.float32),
        bl_state=jnp.zeros((S,), jnp.int32),
        bl_until=jnp.full((S,), jnp.inf, jnp.float32),
        probe_job=jnp.full((S,), -1, jnp.int32),
        seen_failed=jnp.zeros((S,), jnp.int32),
        seen_done=jnp.zeros((S,), jnp.int32),
        n_xfer_fail=jnp.int32(0),
        n_xfer_retry=jnp.int32(0),
        n_xfer_exhaust=jnp.int32(0),
        n_kills=jnp.int32(0),
        n_lost_replicas=jnp.int32(0),
        n_bl_trips=jnp.int32(0),
        n_probes=jnp.int32(0),
        time_lost=jnp.float32(0.0),
    )


# --------------------------------------------------------------------------
# channel 1 helper, called from transfers._tr_on_completions (static branch)
# --------------------------------------------------------------------------


def inject_transfer_failures(ctx, ts, fin, jobs):
    """Fail would-complete flows with per-link probability; schedule backoff
    retries (or, past the attempt cap, fail the staging job's attempt).

    Called by the transfer subsystem *before* releasing ``fin`` rows, so a
    failed flow never prices ``t_finish``, never lands a replica, and never
    counts as done.  Returns ``(fin', xfail, jobs')``: the surviving release
    mask, the injected-failure mask (the caller clears those rows and frees
    their link slots — each counts against ``n_xfer_fail`` in the ledger),
    and jobs with exhausted attempts routed onto the engine's retry path.
    """
    fs: FaultState = ctx.ext["faults"]
    J, L = ctx.J, ctx.S * ctx.S
    u = jax.random.uniform(ctx.subkey("faults"), (J,))
    xfail = fin & (u < fs.link_fail_p[jnp.clip(ts.link, 0, L - 1)])
    nxt = fs.attempt + 1
    exhaust = xfail & (nxt >= fs.max_xfer_attempts)
    retry = xfail & ~exhaust
    delay = fs.xfer_backoff * jnp.exp2(fs.attempt.astype(jnp.float32))
    ctx.ext["faults"] = fs._replace(
        attempt=jnp.where(exhaust, 0, jnp.where(retry, nxt, fs.attempt)),
        retry_at=jnp.where(retry, ctx.clock + delay, jnp.where(exhaust, INF, fs.retry_at)),
        backoff_wait=fs.backoff_wait + jnp.where(retry, delay, 0.0),
        n_xfer_fail=fs.n_xfer_fail + xfail.sum().astype(jnp.int32),
        n_xfer_exhaust=fs.n_xfer_exhaust + exhaust.sum().astype(jnp.int32),
    )
    # out of attempts: leave the staging gate as a failing attempt — next
    # round's completion step retires it through the normal resubmit path
    jobs = jobs._replace(
        will_fail=jobs.will_fail | exhaust,
        t_finish=jnp.where(exhaust, ctx.clock, jobs.t_finish),
    )
    ctx.progressed = ctx.progressed | xfail.any()
    return fin & ~xfail, xfail, jobs


# --------------------------------------------------------------------------
# Subsystem hooks
# --------------------------------------------------------------------------


def _fl_init(sub, state0, jobs, sites):
    if jobs is not None and state0.attempt.shape[-1] != jobs.capacity:
        raise ValueError(
            f"FaultState sized for {state0.attempt.shape[-1]} jobs, got "
            f"capacity {jobs.capacity}; build with make_faults(S, jobs)"
        )
    if sites is not None and state0.score.shape[-1] != sites.capacity:
        raise ValueError(
            f"FaultState sized for {state0.score.shape[-1]} sites, "
            f"got capacity {sites.capacity}"
        )
    return state0


def _fl_validate(sub, state0, jobs, sites):
    if sites is not None:
        S = sites.capacity
        if state0.link_fail_p.shape[-1] != S * S:
            raise ValueError(
                f"FaultState has {state0.link_fail_p.shape[-1]} links, "
                f"expected S*S = {S * S}"
            )
    if jobs is not None and state0.walltime.shape[-1] != jobs.capacity:
        raise ValueError(
            f"FaultState.walltime sized for {state0.walltime.shape[-1]} jobs, "
            f"got capacity {jobs.capacity}"
        )


def _fl_event_times(sub, ctx):
    """Backoff wake-ups, loss-event edges, cooldown expiries, and walltime
    deadlines all join the round clock — fault dynamics are exact events."""
    fs: FaultState = ctx.ext["faults"]
    t = jnp.minimum(fs.retry_at.min(), fs.bl_until.min())
    t = jnp.minimum(t, jnp.where(fs.loss_done, INF, fs.loss_t).min())
    kill = jnp.where(ctx.jobs.state == RUNNING, ctx.jobs.t_start + fs.walltime, INF)
    return jnp.minimum(t, kill.min())


def _fl_on_completions(sub, ctx):
    """Engine step 2b (last in canonical order): walltime kills, resubmission
    backoff, transfer-retry wake-ups, blacklist scoring/transitions, and
    replica-loss events."""
    from .engine import _site_sum

    fs: FaultState = ctx.ext["faults"]
    cfg: FaultsConfig = sub.config or FaultsConfig()
    jobs, sites, S, J = ctx.jobs, ctx.sites, ctx.S, ctx.J
    clock = ctx.clock

    # ---- time lost to failed attempts (engine completions this round) ----
    lost = jnp.where(ctx.failed_now, jnp.maximum(clock - jobs.t_start, 0.0), 0.0).sum()

    # ---- channel 2: resubmission backoff -------------------------------
    # rows the engine just requeued (failed_now & QUEUED — availability
    # preemptions are not in failed_now) go back to PENDING with a pushed
    # arrival; the engine's arrival min-reduction provides the wake event
    if cfg.job_backoff:
        resub = ctx.failed_now & (jobs.state == QUEUED)
        delay = fs.job_backoff * jnp.exp2(
            jnp.maximum(jobs.retries - 1, 0).astype(jnp.float32)
        )
        jobs = jobs._replace(
            state=jnp.where(resub, PENDING, jobs.state),
            arrival=jnp.where(resub, clock + delay, jobs.arrival),
        )
        fs = fs._replace(backoff_wait=fs.backoff_wait + jnp.where(resub, delay, 0.0))

    # ---- walltime kills -------------------------------------------------
    # completions already retired t_finish <= clock, so a job finishing at
    # its deadline still finishes; staging-gate jobs (t_finish = inf) are
    # killable like any other RUNNING job
    killed = (jobs.state == RUNNING) & (jobs.t_start + fs.walltime <= clock)
    kill_resub = killed & (jobs.retries < ctx.max_retries)
    kill_fail = killed & ~kill_resub
    kill_site = jnp.where(killed, jobs.site, S)
    if cfg.job_backoff:
        kdelay = fs.job_backoff * jnp.exp2(jobs.retries.astype(jnp.float32))
        new_state = jnp.where(kill_resub, PENDING, jnp.where(kill_fail, FAILED, jobs.state))
        new_arrival = jnp.where(kill_resub, clock + kdelay, jobs.arrival)
        fs = fs._replace(backoff_wait=fs.backoff_wait + jnp.where(kill_resub, kdelay, 0.0))
    else:
        new_state = jnp.where(kill_resub, QUEUED, jnp.where(kill_fail, FAILED, jobs.state))
        new_arrival = jobs.arrival
    jobs = jobs._replace(
        state=new_state,
        arrival=new_arrival,
        retries=jobs.retries + kill_resub.astype(jnp.int32),
        site=jnp.where(kill_resub, -1, jobs.site),
        t_finish=jnp.where(kill_resub, INF, jnp.where(kill_fail, clock, jobs.t_finish)),
        preempted=jobs.preempted + killed.astype(jnp.int32),
    )
    sites = sites._replace(
        free_cores=sites.free_cores + _site_sum(jnp.where(killed, jobs.cores, 0), kill_site, S),
        free_memory=sites.free_memory
        + _site_sum(jnp.where(killed, jobs.memory, 0.0), kill_site, S),
    )
    lost = lost + jnp.where(killed, jnp.maximum(clock - jobs.t_start, 0.0), 0.0).sum()
    fs = fs._replace(
        n_kills=fs.n_kills + killed.sum().astype(jnp.int32),
        time_lost=fs.time_lost + lost,
    )
    ctx.progressed = ctx.progressed | killed.any()

    # ---- channel 1: transfer retries & kill-side cancels ----------------
    if "transfers" in ctx.ext:
        from .transfers import T_ACTIVE, T_IDLE, _admit, _enqueue, _link_count, _reprice

        ts = ctx.ext["transfers"]
        dext = ctx.ext.get("data")
        L = S * S
        # a killed staging job abandons its flow now (the transfer
        # subsystem's own cancel sweep runs before this hook, so without
        # this the slot would stay occupied until the next event round)
        tr = killed & (ts.stat > T_IDLE)
        ts = ts._replace(
            stat=jnp.where(tr, T_IDLE, ts.stat),
            rem=jnp.where(tr, 0.0, ts.rem),
            t_done=jnp.where(tr, INF, ts.t_done),
            active=ts.active
            - _link_count(tr & (ts.stat == T_ACTIVE), jnp.clip(ts.link, 0, L - 1), L),
            n_cancel=ts.n_cancel + tr.sum().astype(jnp.int32),
            bytes_cancel=ts.bytes_cancel + jnp.where(tr, jobs.xfer_bytes, 0.0).sum(),
        )
        # a pending backoff retry whose job left the staging gate (killed,
        # preempted, cancelled, or exhausted) is dropped — its failure is
        # already on the ledger, so conservation holds without a re-enqueue
        orphan = jnp.isfinite(fs.retry_at) & (jobs.state != RUNNING)
        due = (fs.retry_at <= clock) & (jobs.state == RUNNING)
        # backoff expired: the full transfer restarts as a fresh ledger
        # attempt on the same link (resid/cache/link survive in the
        # transfer rows; rem resets to the full size)
        ts = _enqueue(ts, due, ts.link, jobs.xfer_bytes, ts.resid, ts.cache, clock)
        fs = fs._replace(
            retry_at=jnp.where(due | orphan, INF, fs.retry_at),
            attempt=jnp.where(orphan, 0, fs.attempt),
            n_xfer_retry=fs.n_xfer_retry + due.sum().astype(jnp.int32),
        )
        if dext is not None:
            ts, _ = _admit(ts, clock)
            ts = _reprice(ts, dext.network.bw.reshape(L), clock)
        ctx.ext["transfers"] = ts
        ctx.progressed = ctx.progressed | due.any() | tr.any()

    # ---- channel 4: blacklist scoring + circuit transitions -------------
    if cfg.blacklist:
        idx = jnp.arange(J, dtype=jnp.int32)
        kills_per_site = _site_sum(killed.astype(jnp.int32), kill_site, S)
        d_fail = (sites.n_failed - fs.seen_failed) + kills_per_site
        d_done = sites.n_finished - fs.seen_done
        n_ev = d_fail + d_done
        frac = d_fail.astype(jnp.float32) / jnp.maximum(n_ev, 1).astype(jnp.float32)
        score = jnp.where(
            n_ev > 0, fs.score + fs.bl_alpha * (frac - fs.score), fs.score
        )
        closed = fs.bl_state == BL_CLOSED
        tripped = fs.bl_state == BL_TRIPPED
        half = fs.bl_state == BL_HALF_OPEN
        trip = closed & (score >= fs.bl_threshold)
        expire = tripped & (fs.bl_until <= clock)
        # half-open probe resolution (states are disjoint, so the masks are)
        pj = jnp.clip(fs.probe_job, 0, J - 1)
        has = half & (fs.probe_job >= 0)
        p_succ = has & ctx.done_now[pj]
        p_fail = has & (ctx.failed_now[pj] | killed[pj])
        p_gone = has & ~p_succ & ~p_fail & (jobs.site[pj] != jnp.arange(S))
        retrip = trip | p_fail
        fs = fs._replace(
            score=jnp.where(p_succ, 0.0, score),
            bl_state=jnp.where(
                retrip,
                BL_TRIPPED,
                jnp.where(expire, BL_HALF_OPEN, jnp.where(p_succ, BL_CLOSED, fs.bl_state)),
            ),
            bl_until=jnp.where(retrip, clock + fs.bl_cooldown, jnp.where(expire | p_succ, INF, fs.bl_until)),
            probe_job=jnp.where(expire | p_succ | p_fail | p_gone, -1, fs.probe_job),
            seen_failed=sites.n_failed,
            seen_done=sites.n_finished,
            n_bl_trips=fs.n_bl_trips + retrip.sum().astype(jnp.int32),
        )
        # jobs queued at a newly tripped site bounce back to the server (no
        # attempt lost, no retry) so the half-open window admits exactly the
        # probe, not a backlog — mirrors the availability drain bounce
        bounce = (jobs.state == ASSIGNED) & trip[jnp.clip(jobs.site, 0, S - 1)]
        jobs = jobs._replace(
            state=jnp.where(bounce, QUEUED, jobs.state),
            site=jnp.where(bounce, -1, jobs.site),
        )
        ctx.progressed = (
            ctx.progressed | retrip.any() | expire.any() | p_succ.any() | bounce.any()
        )

    # ---- channel 3: replica-loss calendar -------------------------------
    due_loss = ~fs.loss_done & (fs.loss_t <= clock)
    dext = ctx.ext.get("data")
    if dext is not None:
        rep = dext.replicas
        D = rep.size.shape[-1]
        dd = jnp.where(due_loss, jnp.clip(fs.loss_d, 0, D - 1), D)
        ss = jnp.clip(fs.loss_s, 0, S - 1)
        hit = jnp.zeros((D, S), bool).at[dd, ss].set(True, mode="drop")
        org = jnp.clip(rep.origin, 0, S - 1)
        is_origin = (jnp.arange(S)[None, :] == org[:, None]) & (rep.origin >= 0)[:, None]
        dropped = hit & rep.present & ~is_origin  # pinned origins never drop
        ctx.ext["data"] = dext._replace(
            replicas=rep._replace(
                present=rep.present & ~dropped,
                disk_used=rep.disk_used - (dropped * rep.size[:, None]).sum(-2),
                last_access=jnp.where(dropped, -INF, rep.last_access),
            )
        )
        fs = fs._replace(
            n_lost_replicas=fs.n_lost_replicas + dropped.sum().astype(jnp.int32)
        )
        ctx.progressed = ctx.progressed | due_loss.any()
    fs = fs._replace(loss_done=fs.loss_done | due_loss)

    ctx.jobs = jobs
    ctx.sites = sites
    ctx.ext["faults"] = fs


def _fl_pre_assign(sub, ctx):
    """Remove tripped sites from feasibility (and zero their start budget);
    gate half-open sites down to a single probe candidate."""
    cfg: FaultsConfig = sub.config or FaultsConfig()
    if not cfg.blacklist:
        return
    fs: FaultState = ctx.ext["faults"]
    J = ctx.J
    tripped = fs.bl_state == BL_TRIPPED
    probe_ok = (fs.bl_state == BL_HALF_OPEN) & (fs.probe_job < 0)
    # probe candidate: the lowest queued job id — matches the engine's
    # start-order id tiebreak, so the probe is deterministic
    idx = jnp.arange(J, dtype=jnp.int32)
    queued = ctx.jobs.state == QUEUED
    cand = jnp.where(queued, idx, J).min()
    # note: the [J, S] probe gate expands a sparse top-k [1, S] site mask to
    # per-job feasibility — the assignment gather dispatches on the leading
    # dim, so this is correct (if heavier) under topk
    gate = (fs.bl_state == BL_CLOSED)[None, :] | (
        probe_ok[None, :] & (idx[:, None] == cand)
    )
    ctx.feasible = ctx.feasible & gate
    ctx.start_cores = jnp.where(tripped, 0, ctx.start_cores)


def _fl_on_start(sub, ctx):
    """Register half-open probes; reset transfer-attempt counters for jobs
    entering a fresh stage-in."""
    fs: FaultState = ctx.ext["faults"]
    cfg: FaultsConfig = sub.config or FaultsConfig()
    if cfg.blacklist:
        half_free = (fs.bl_state == BL_HALF_OPEN) & (fs.probe_job < 0)
        ps = ctx.started & half_free[ctx.site_c]
        tgt = jnp.where(ps, ctx.site_c, ctx.S)
        fs = fs._replace(
            probe_job=fs.probe_job.at[tgt].set(
                jnp.arange(ctx.J, dtype=jnp.int32), mode="drop"
            ),
            n_probes=fs.n_probes + ps.sum().astype(jnp.int32),
        )
    sc = ctx.scratch.get("transfers")
    if sc is not None:
        xfer = sc["xfer"]
        fs = fs._replace(
            attempt=jnp.where(xfer, 0, fs.attempt),
            retry_at=jnp.where(xfer, INF, fs.retry_at),
        )
    ctx.ext["faults"] = fs


def _fl_log_spec(sub, fs: FaultState, jobs, sites):
    S = fs.score.shape[-1]
    return {
        "site_fault_score": jnp.zeros((S,), jnp.float32),
        "site_blacklist": jnp.zeros((S,), jnp.int32),
    }


def _fl_log_columns(sub, ctx, write):
    fs: FaultState = ctx.ext["faults"]
    return {"site_fault_score": fs.score, "site_blacklist": fs.bl_state}


def _fl_pad_jobs(sub, fs: FaultState, old_cap: int, new_cap: int):
    n = new_cap - old_cap
    fills = {"attempt": 0, "retry_at": jnp.inf, "backoff_wait": 0.0, "walltime": jnp.inf}

    def pad(name, x):
        widths = [(0, 0)] * (x.ndim - 1) + [(0, n)]
        return jnp.pad(x, widths, constant_values=fills[name])

    return fs._replace(**{k: pad(k, getattr(fs, k)) for k in fills})


def faults_subsystem(state0: FaultState | None = None, *, job_backoff=None, blacklist=None):
    """The fault-injection engine plugin.  Initial state is a
    :class:`FaultState` from :func:`make_faults`.

    The static channel flags (``job_backoff``, ``blacklist`` — see
    :class:`FaultsConfig`) are derived host-side from ``state0`` when not
    given explicitly; pass them explicitly when building the subsystem for
    traced/stacked states (e.g. the explicit ``subsystems=`` ensemble path
    with per-lane fault configs).
    """
    from .subsystems import Subsystem

    if state0 is not None:
        if job_backoff is None:
            job_backoff = bool((np.asarray(jax.device_get(state0.job_backoff)) > 0).any())
        if blacklist is None:
            blacklist = bool(
                np.isfinite(np.asarray(jax.device_get(state0.bl_threshold))).any()
            )
    cfg = FaultsConfig(job_backoff=bool(job_backoff), blacklist=bool(blacklist))
    return Subsystem(
        name="faults",
        config=cfg,
        init=_fl_init,
        validate=_fl_validate,
        event_times=_fl_event_times,
        on_completions=_fl_on_completions,
        pre_assign=_fl_pre_assign,
        on_start=_fl_on_start,
        log_spec=_fl_log_spec,
        log_columns=_fl_log_columns,
        pad_jobs=_fl_pad_jobs,
    )
