"""FTS-style transfer queues — queued, rate-limited WAN flows (DESIGN.md §11).

PR 1's data subsystem prices every WAN stage-in instantaneously: the round
that starts a dataset job folds ``shared_transfer_times`` into its service
time, with bandwidth split among the transfers that happen to start in the
same round.  Real grids funnel third-party copies through FTS channels with
per-link *active-transfer limits*; queue-wait and link contention — not raw
bandwidth — dominate data-access latency at scale (arxiv 2403.14903,
1902.10069).

This module models that as a pure additive :class:`~.subsystems.Subsystem`:

- Each directed link ``src -> dst`` (flattened id ``src * S + dst``) has an
  ``active`` counter and a ``cap`` (``max_active``).  The queue itself is
  per-job state: a queued row keeps its link and its enqueue ticket, and a
  link's FIFO is its queued rows in ticket order.
- When a dataset job starts on a WAN read, the data subsystem *defers* the
  transfer here instead of pricing it: the job enters a **staging gate** —
  it is RUNNING with ``t_finish = inf`` so it is excluded from the engine's
  finish-time min-reduction, exactly like gated workflow children.  Its wake
  event is the transfer completion, contributed through ``event_times``.
- Link bandwidth splits equal-share among the *active* transfers on that
  link only; everything past ``cap`` waits in FIFO order.  Because the
  active set is constant between rounds, each flow's completion time is a
  closed form and byte progress integrates exactly.
- On completion the remaining compute (+ stage-out + WAN latency) is priced
  into ``t_finish``, cache-on-read replicas materialize at the destination,
  and the freed slot admits the next queued transfer.

Every array is J-wide or L-wide (``L = S * S``): at S=300 and J=100k the
state is a few MB.  Fixed shapes and masked algebra throughout: the
subsystem jit/vmaps under ``simulate_many`` / ``simulate_many_sharded``, and
``transfers=None`` is a bit-for-bit no-op (static specialization removes
every trace).  A preempted staging job cancels its transfer, which leaves
nothing behind in the queue.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .network import link_caps
from .types import RUNNING

INF = jnp.float32(jnp.inf)

# per-transfer status (one slot per job row: a job has at most one in-flight
# transfer — its current stage-in attempt)
T_IDLE, T_QUEUED, T_ACTIVE = 0, 1, 2


class TransferState(NamedTuple):
    """The transfer subsystem's ``EngineState.ext["transfers"]`` slot.

    Link axis ``L = S * S`` over flattened directed links; transfer axis =
    the job capacity ``J``.
    """

    # per-link
    active: jax.Array   # i32[L] transfers currently moving bytes
    cap: jax.Array      # i32[L] max_active per link (FTS channel limit)
    # per-transfer rows (indexed by job id)
    stat: jax.Array     # i32[J] T_IDLE / T_QUEUED / T_ACTIVE
    link: jax.Array     # i32[J] flattened link id (-1 = none)
    rem: jax.Array      # f32[J] remaining bytes
    t_done: jax.Array   # f32[J] completion time under the current share (inf
    #                     unless active) — the subsystem's event_times source
    resid: jax.Array    # f32[J] post-staging service remainder (compute +
    #                     stage-out + WAN latency), priced into t_finish at release
    enq_t: jax.Array    # f32[J] enqueue clock
    act_t: jax.Array    # f32[J] activation clock
    ticket: jax.Array   # i32[J] enqueue ticket: the link's FIFO order (-1 = none)
    cache: jax.Array    # bool[J] materialize a replica at the dst on landing
    # conservation counters (every enqueue terminates as done or cancelled)
    n_enq: jax.Array       # i32 total transfers enqueued (also ticket counter)
    n_done: jax.Array      # i32 transfers landed
    n_cancel: jax.Array    # i32 transfers cancelled (staging job preempted)
    n_waited: jax.Array    # i32 transfers admitted after waiting for a slot (act_t > enq_t)
    bytes_enq: jax.Array     # f32 bytes enqueued
    bytes_done: jax.Array    # f32 bytes of completed transfers (full size)
    bytes_cancel: jax.Array  # f32 bytes of cancelled transfers (full size)


def make_transfers(
    n_sites: int,
    job_capacity: int,
    *,
    max_active: int = 4,
    caps=None,
) -> TransferState:
    """Build an empty transfer-queue state.

    ``n_sites`` also accepts a ``NetworkState``/``SiteState``; ``job_capacity``
    also accepts a ``JobsState``.  ``max_active`` is the default per-link
    concurrency cap, refined by ``caps`` (a ``{(src, dst): cap}`` mapping or a
    full ``[S, S]`` matrix — see :func:`~.network.link_caps`).
    """
    S = getattr(n_sites, "n_sites", None) or getattr(n_sites, "capacity", None) or int(n_sites)
    J = getattr(job_capacity, "capacity", None) or int(job_capacity)
    L = S * S
    return TransferState(
        active=jnp.zeros((L,), jnp.int32),
        cap=link_caps(S, max_active, caps),
        stat=jnp.zeros((J,), jnp.int32),
        link=jnp.full((J,), -1, jnp.int32),
        rem=jnp.zeros((J,), jnp.float32),
        t_done=jnp.full((J,), jnp.inf, jnp.float32),
        resid=jnp.zeros((J,), jnp.float32),
        enq_t=jnp.zeros((J,), jnp.float32),
        act_t=jnp.zeros((J,), jnp.float32),
        ticket=jnp.full((J,), -1, jnp.int32),
        cache=jnp.zeros((J,), bool),
        n_enq=jnp.int32(0),
        n_done=jnp.int32(0),
        n_cancel=jnp.int32(0),
        n_waited=jnp.int32(0),
        bytes_enq=jnp.float32(0.0),
        bytes_done=jnp.float32(0.0),
        bytes_cancel=jnp.float32(0.0),
    )


# --------------------------------------------------------------------------
# queue mechanics (fixed-shape [J] / [L] masked algebra)
# --------------------------------------------------------------------------


def _link_count(mask, link, L):
    """Per-link count of True rows (mask[J], link[J]) -> i32[L]."""
    from .engine import _segment_sum_small

    seg = jnp.where(mask, link, L)
    return _segment_sum_small(mask.astype(jnp.int32), seg, L + 1)[:L]


def _enqueue(ts: TransferState, want, link, nbytes, resid, cache, clock):
    """Queue the ``want`` rows on their links.

    Each gets the next enqueue ticket; same-round enqueuers take them in
    job-id order — they start at the same instant, and the id tiebreak
    matches the engine's start-order sort.
    """
    L = ts.cap.shape[-1]
    lc = jnp.clip(link, 0, L - 1)
    w = want.astype(jnp.int32)
    tkt = ts.n_enq + jnp.cumsum(w) - w
    return ts._replace(
        stat=jnp.where(want, T_QUEUED, ts.stat),
        link=jnp.where(want, lc, ts.link),
        rem=jnp.where(want, nbytes, ts.rem),
        resid=jnp.where(want, resid, ts.resid),
        enq_t=jnp.where(want, clock, ts.enq_t),
        act_t=jnp.where(want, clock, ts.act_t),  # re-stamped on admission
        ticket=jnp.where(want, tkt, ts.ticket),
        cache=jnp.where(want, cache, ts.cache),
        n_enq=ts.n_enq + w.sum(),
        bytes_enq=ts.bytes_enq + jnp.where(want, nbytes, 0.0).sum(),
    )


def _queue_rank(ts: TransferState):
    """``i32[J]``: each queued row's place in its link's FIFO (0 = head), the
    number of queued rows on its link with an earlier ticket.

    A stable sort by ticket, then by link (rows not queued last), puts each
    link's queue in one run; a row's place is its distance from the run's
    first position."""
    from .engine import _lexsort_i32

    L = ts.cap.shape[-1]
    J = ts.stat.shape[-1]
    seg = jnp.where(ts.stat == T_QUEUED, ts.link, L)
    perm = _lexsort_i32((ts.ticket, seg))
    seg_s = seg[perm]
    pos = jnp.arange(J, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), seg_s[1:] != seg_s[:-1]])
    run_start = jax.lax.cummax(jnp.where(first, pos, 0))
    return jnp.zeros((J,), jnp.int32).at[perm].set(pos - run_start)


def _admit(ts: TransferState, clock):
    """Move the head of each link's queue into its free ``cap - active``
    slots.  Returns ``(ts, rank)``, ``rank`` being ``_queue_rank`` before
    admission (0 on rounds with nothing queued, when the sort is skipped)."""
    from .engine import _ensemble_any

    L = ts.cap.shape[-1]
    J = ts.stat.shape[-1]
    queued = ts.stat == T_QUEUED

    def admit(ts):
        rank = _queue_rank(ts)
        lc = jnp.clip(ts.link, 0, L - 1)
        go = queued & (rank < jnp.maximum(ts.cap - ts.active, 0)[lc])
        return ts._replace(
            active=ts.active + _link_count(go, lc, L),
            stat=jnp.where(go, T_ACTIVE, ts.stat),
            act_t=jnp.where(go, clock, ts.act_t),
            n_waited=ts.n_waited + (go & (clock > ts.enq_t)).sum().astype(jnp.int32),
        ), rank

    return jax.lax.cond(
        _ensemble_any(queued.any()), admit, lambda ts: (ts, jnp.zeros((J,), jnp.int32)), ts
    )


def _reprice(ts: TransferState, bw_flat, clock):
    """Materialize each active flow's completion time under the current
    equal-share split.  The active sets only change at rounds, so this is
    exact — and it is the invariant ``event_times`` reads."""
    L = bw_flat.shape[-1]
    lc = jnp.clip(ts.link, 0, L - 1)
    act = ts.stat == T_ACTIVE
    rate = bw_flat[lc] / jnp.maximum(ts.active[lc], 1).astype(jnp.float32)
    t_done = clock + ts.rem / jnp.maximum(rate, 1e-9)
    return ts._replace(t_done=jnp.where(act, t_done, INF))


# --------------------------------------------------------------------------
# Subsystem hooks
# --------------------------------------------------------------------------


def _tr_init(sub, state0, jobs, sites):
    if jobs is not None and state0.stat.shape[-1] != jobs.capacity:
        raise ValueError(
            f"TransferState sized for {state0.stat.shape[-1]} jobs, "
            f"got capacity {jobs.capacity}; build with make_transfers(S, jobs)"
        )
    if sites is not None and state0.cap.shape[-1] != sites.capacity**2:
        raise ValueError(
            f"TransferState has {state0.cap.shape[-1]} links, "
            f"expected S*S = {sites.capacity**2}"
        )
    return state0


def _tr_event_times(sub, ctx):
    """Transfer completions join the round clock: the staging gate's wake."""
    return ctx.ext["transfers"].t_done.min()


def _tr_on_completions(sub, ctx):
    """Engine step 2b: integrate byte progress over the elapsed interval,
    release jobs whose transfer landed (pricing the post-staging remainder
    into ``t_finish``), cancel transfers whose staging job was preempted,
    then admit queued flows into the freed slots."""
    from .datapolicies import land_deferred

    ts: TransferState = ctx.ext["transfers"]
    dext = ctx.ext.get("data")
    if dext is None:
        return
    jobs, S, J = ctx.jobs, ctx.S, ctx.J
    L = S * S
    bw_flat = dext.network.bw.reshape(L)
    lc = jnp.clip(ts.link, 0, L - 1)
    act = ts.stat == T_ACTIVE

    # byte progress: the active set (and so each flow's share) was constant
    # over [clock_prev, clock]
    dt = jnp.maximum(ctx.clock - ctx.clock_prev, 0.0)
    rate = bw_flat[lc] / jnp.maximum(ts.active[lc], 1).astype(jnp.float32)
    rem = jnp.where(act, jnp.maximum(ts.rem - rate * dt, 0.0), ts.rem)

    # a preempted staging job (availability outage moved it out of RUNNING
    # in this same hook phase — availability runs first) abandons its
    # transfer, queued or active
    staging = jobs.state == RUNNING
    fin = act & (ts.t_done <= ctx.clock) & staging
    cancel = (ts.stat > T_IDLE) & ~staging

    # fault injection (static specialization, like the data subsystem's
    # defer branch): would-complete flows may fail with per-link probability
    # before release — failed rows clear like cancels but land on the fault
    # ledger (n_enq == n_done + n_cancel + faults.n_xfer_fail)
    xfail = jnp.zeros((J,), bool)
    if "faults" in ctx.ext:
        from .faults import inject_transfer_failures

        fin, xfail, jobs = inject_transfer_failures(ctx, ts, fin, jobs)

    # release: price the post-staging remainder into t_finish so the job
    # rejoins the round clock.  The engine's partial-failure fraction was
    # consumed by the staging gate's inf, so failing attempts re-draw it
    # from the subsystem's own RNG stream.
    frac = jax.random.uniform(ctx.subkey("transfers"), (J,), minval=0.05, maxval=1.0)
    t_rest = jnp.where(jobs.will_fail, ts.resid * frac, ts.resid)
    ctx.jobs = jobs._replace(
        t_finish=jnp.where(fin, ctx.clock + t_rest, jobs.t_finish),
        xfer_time=jnp.where(fin, ctx.clock - ts.act_t, jobs.xfer_time),
        xfer_wait=jnp.where(fin, ts.act_t - ts.enq_t, jobs.xfer_wait),
    )
    # deferred landing: replica materialization + WAN counters at the dst
    ctx.ext["data"] = land_deferred(dext, ctx.jobs, fin, ts.cache, ctx.clock, S)

    clear = fin | cancel | xfail
    ts = ts._replace(
        stat=jnp.where(clear, T_IDLE, ts.stat),
        rem=jnp.where(clear, 0.0, rem),
        t_done=jnp.where(clear, INF, ts.t_done),
        active=ts.active - _link_count(fin | xfail | (cancel & act), lc, L),
        n_done=ts.n_done + fin.sum().astype(jnp.int32),
        n_cancel=ts.n_cancel + cancel.sum().astype(jnp.int32),
        bytes_done=ts.bytes_done + jnp.where(fin, jobs.xfer_bytes, 0.0).sum(),
        bytes_cancel=ts.bytes_cancel + jnp.where(cancel, jobs.xfer_bytes, 0.0).sum(),
    )
    ts, _ = _admit(ts, ctx.clock)
    ctx.ext["transfers"] = _reprice(ts, bw_flat, ctx.clock)
    ctx.progressed = ctx.progressed | fin.any() | cancel.any()


def _tr_on_start(sub, ctx):
    """Engine step 5b, after the data subsystem: divert this round's WAN
    reads (staged in ``ctx.scratch['transfers']``) into the link queues and
    hold the jobs in the staging gate (``t_serv = inf``)."""
    ts: TransferState = ctx.ext["transfers"]
    dext = ctx.ext.get("data")
    if dext is None:
        return
    L = ctx.S * ctx.S
    sc = ctx.scratch.get("transfers")
    if sc is not None:
        xfer = sc["xfer"]
        # staging gate: inf service time keeps t_finish = inf, excluding the
        # job from the clock min-reduction until its transfer lands
        ctx.t_serv = jnp.where(xfer, INF, ctx.t_serv)
        ts = _enqueue(ts, xfer, sc["link"], sc["bytes"], sc["resid"], sc["cache"], ctx.clock)
    # newly enqueued flows activate now if their link has free slots —
    # required for liveness: an uncontended transfer must create its own
    # wake event this same round
    ts, rank = _admit(ts, ctx.clock)
    if sc is not None:
        # a new row's place in its link's queue is the depth it saw at enqueue
        ctx.jobs = ctx.jobs._replace(
            xfer_qdepth=jnp.where(xfer, rank, ctx.jobs.xfer_qdepth),
            xfer_wait=jnp.where(xfer, 0.0, ctx.jobs.xfer_wait),
        )
    ctx.ext["transfers"] = _reprice(ts, dext.network.bw.reshape(L), ctx.clock)


def _tr_log_spec(sub, ts: TransferState, jobs, sites):
    L = ts.cap.shape[-1]
    zeros = jnp.zeros((L,), jnp.int32)
    return {"link_active": zeros, "link_queued": zeros}


def _tr_log_columns(sub, ctx, write):
    ts: TransferState = ctx.ext["transfers"]
    L = ts.cap.shape[-1]
    queued = _link_count(ts.stat == T_QUEUED, jnp.clip(ts.link, 0, L - 1), L)
    return {"link_active": ts.active, "link_queued": queued}


def _tr_pad_jobs(sub, ts: TransferState, old_cap: int, new_cap: int):
    n = new_cap - old_cap
    fills = {
        "stat": T_IDLE, "link": -1, "rem": 0.0, "t_done": jnp.inf, "resid": 0.0,
        "enq_t": 0.0, "act_t": 0.0, "ticket": -1, "cache": False,
    }

    def pad(name, x):
        if name not in fills:
            return x
        widths = [(0, 0)] * (x.ndim - 1) + [(0, n)]
        return jnp.pad(x, widths, constant_values=fills[name])

    return ts._replace(**{k: pad(k, getattr(ts, k)) for k in fills})


def _tr_finalize(sub, ts: TransferState, jobs, sites, clock):
    return ts, {
        "xfer_enqueued": ts.n_enq,
        "xfer_landed": ts.n_done,
        "xfer_waited": ts.n_waited,
    }


def transfers_subsystem() -> "Subsystem":
    """The transfer-queue engine plugin.  Initial state is a
    :class:`TransferState` from :func:`make_transfers`; requires the data
    subsystem (it owns the network matrices and the replica catalog)."""
    from .subsystems import Subsystem

    return Subsystem(
        name="transfers",
        config=None,
        init=_tr_init,
        event_times=_tr_event_times,
        on_completions=_tr_on_completions,
        on_start=_tr_on_start,
        log_spec=_tr_log_spec,
        log_columns=_tr_log_columns,
        pad_jobs=_tr_pad_jobs,
        finalize=_tr_finalize,
    )
