"""Vectorized discrete-event engine.

SimGrid runs one event at a time through coroutine actors.  On an accelerator
we instead run *event rounds*: a ``lax.while_loop`` whose body advances the
clock to the next event time (an O(J) min-reduction) and applies every
transition that fires at that instant as masked dense updates:

  round(t*):
    1. completions   — running jobs with t_finish <= t*  → DONE/FAILED/resubmit
    2. subsystems    — post-completion transitions (outage preemption,
                       DAG cascade-cancel, ...) via ``on_completions`` hooks
    3. arrivals      — pending jobs with arrival  <= t*  → QUEUED at the server
    4. assignment    — the policy plugin scores QUEUED jobs against sites;
                       feasible best-site rows become ASSIGNED (site queue)
    5. starts        — per-site FIFO-with-capacity: sort ASSIGNED rows by
                       (site, -priority, arrival), start the per-site prefix
                       whose cumulative core/memory demand fits free resources
    6. bookkeeping   — service times, failure sampling, counters, event log

The round body is an ordered phase pipeline over a *static* tuple of
``Subsystem`` hook bundles (DESIGN.md §7): each subsystem contributes clock
event sources, arrival gates, completion filters, post-completion
transitions, feasibility/speed modifiers, service-time adjustments, and event
log columns, and owns one slot of the generic ``EngineState.ext`` mapping.
Specialization happens at trace time — a run without a subsystem compiles to
the exact program the hand-written engine produced, with no ``lax.cond``
overhead (the golden-trace matrix pins all 8 on/off combinations).

FIFO-with-capacity ≡ sort + segmented prefix-sum + mask is the central
de-actorification trick (DESIGN.md §2).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .subsystems import RoundCtx, resolve_subsystems
from .telemetry import span
from .types import (
    ASSIGNED,
    DONE,
    FAILED,
    N_STATES,
    PENDING,
    QUEUED,
    RESULT_COUNTERS,
    RUNNING,
    EngineState,
    EventLog,
    JobsState,
    SimResult,
    SiteState,
    make_log,
)

INF = jnp.float32(jnp.inf)

# The round loop's phases, each a ``jax.named_scope`` in ``_round_fns``, so
# every device op of the loop carries one of them in its HLO ``op_name``
# (with the name of the subsystem whose hook emitted it, where one did):
# ``cond`` and the next-event min-reduction; completions, resubmission and
# arrivals; candidate refresh, feasibility and the policy's assignment;
# start order, admission and service times; halt detection and the event log.
PHASES = ("clock", "completions", "score", "start", "bookkeeping")


def compute_time(jobs: JobsState, sites: SiteState, site: jax.Array) -> jax.Array:
    """Amdahl-style compute term: ``work / (speed * c / (1 + gamma (c-1)))``
    so ``par_gamma`` can be calibrated per site."""
    c = jobs.cores.astype(jnp.float32)
    gamma = sites.par_gamma[site]
    speedup = c / (1.0 + gamma * jnp.maximum(c - 1.0, 0.0))
    return jobs.work / (sites.speed[site] * jnp.maximum(speedup, 1e-9))


def stage_in_time(
    jobs: JobsState, sites: SiteState, site: jax.Array, share_in: jax.Array
) -> jax.Array:
    """Flat-link stage-in: site latency + ``bytes_in`` over the ingress link
    shared equally among the ``share_in`` jobs staging concurrently."""
    bw_in = sites.bw_in[site] / jnp.maximum(share_in, 1.0)
    return sites.latency[site] + jobs.bytes_in / bw_in


def service_time(
    jobs: JobsState, sites: SiteState, site: jax.Array, share_in: jax.Array, share_out: jax.Array
) -> jax.Array:
    """Deterministic-at-start service time model (DESIGN.md §2 network note).

    t = latency + stage_in + compute + stage_out, where stage bandwidth is the
    site link shared among the ``share`` jobs staging concurrently.  This is
    the flat-link model; jobs with a catalogued dataset replace the latency +
    stage-in terms with a replica-aware WAN transfer (DESIGN.md §3).
    """
    bw_out = sites.bw_out[site] / jnp.maximum(share_out, 1.0)
    return (
        stage_in_time(jobs, sites, site, share_in)
        + compute_time(jobs, sites, site)
        + jobs.bytes_out / bw_out
    )


@functools.lru_cache(maxsize=None)
def _int_segment_sum(num_segments: int):
    """Integer ``segment_sum`` that picks its lowering by batching context.

    Solo runs use the native scatter-add — O(J) work and O(J) memory, which
    matters at WLCG scale where a one-hot ``[J, S+1]`` intermediate is ~100MB+
    per call.  Under ``vmap`` (ensembles) the ``def_vmap`` rule switches to a
    one-hot contraction: on CPU a *batched* scatter is the single most
    expensive op in an ensemble round (~6x a one-hot matmul at K=16, J=320 —
    DESIGN.md §8).  Integer sums are exact in any reduction order, so the two
    lowerings are bit-for-bit identical in every context.
    """

    @jax.custom_batching.custom_vmap
    def seg_sum(values: jax.Array, seg: jax.Array) -> jax.Array:
        return jax.ops.segment_sum(values, seg, num_segments=num_segments)

    @seg_sum.def_vmap
    def _seg_sum_batched(axis_size, in_batched, values, seg):
        vb, sb = in_batched
        if not vb:
            values = jnp.broadcast_to(values, (axis_size,) + values.shape)
        if not sb:
            seg = jnp.broadcast_to(seg, (axis_size,) + seg.shape)
        onehot = (seg[..., None] == jnp.arange(num_segments, dtype=seg.dtype)).astype(
            values.dtype
        )
        return jnp.einsum("...j,...js->...s", values, onehot), True

    return seg_sum


def _segment_sum_small(values: jax.Array, seg: jax.Array, num_segments: int) -> jax.Array:
    """``segment_sum`` specialized for the engine's few-segment reductions.

    Integer (and bool) values dispatch through ``_int_segment_sum`` — a
    scatter-add solo and a one-hot contraction under ``vmap`` (both exact for
    ints, so bit-for-bit identical).  Float values keep ``segment_sum``'s
    sequential accumulation order — reordering float adds would shift low
    bits and break the golden traces.
    """
    if jnp.issubdtype(values.dtype, jnp.integer) or values.dtype == jnp.bool_:
        # bool saturates under einsum (logical OR), so count in int32
        values = values.astype(jnp.int32) if values.dtype == jnp.bool_ else values
        return _int_segment_sum(num_segments)(values, seg)
    return jax.ops.segment_sum(values, seg, num_segments=num_segments)


def _site_sum(values: jax.Array, site: jax.Array, num_sites: int) -> jax.Array:
    """Scatter per-job values onto their site: ``segment_sum`` with one extra
    padding segment (site == ``num_sites``) for non-participating rows.

    The ubiquitous engine scatter — completions, preemption, starts, and log
    pressure columns all reduce job rows to per-site totals this way.
    """
    return _segment_sum_small(values, site, num_sites + 1)[:num_sites]


@functools.lru_cache(maxsize=None)
def _int_segment_sum_stacked(num_segments: int):
    """``_int_segment_sum`` for feature-stacked int values ``[J, F] -> [seg, F]``.

    One scatter pass over J for F columns sharing segment ids, instead of F
    separate passes — the completion and start phases each fold their integer
    per-site reductions through this (integer adds are order-exact, so the
    stacking is bit-for-bit identical to the separate calls it replaces).
    """

    @jax.custom_batching.custom_vmap
    def seg_sum(values: jax.Array, seg: jax.Array) -> jax.Array:
        return jax.ops.segment_sum(values, seg, num_segments=num_segments)

    @seg_sum.def_vmap
    def _seg_sum_batched(axis_size, in_batched, values, seg):
        vb, sb = in_batched
        if not vb:
            values = jnp.broadcast_to(values, (axis_size,) + values.shape)
        if not sb:
            seg = jnp.broadcast_to(seg, (axis_size,) + seg.shape)
        onehot = (seg[..., None] == jnp.arange(num_segments, dtype=seg.dtype)).astype(
            values.dtype
        )
        return jnp.einsum("...jf,...js->...sf", values, onehot), True

    return seg_sum


def _site_sum_stacked(values: jax.Array, site: jax.Array, num_sites: int) -> jax.Array:
    """``_site_sum`` over int features stacked in the trailing axis ``[J, F]``."""
    return _int_segment_sum_stacked(num_sites + 1)(values, site)[:num_sites]


# Below this job capacity a *solo* run computes the start order by pairwise
# ranking instead of ``jnp.lexsort`` (the O(J^2) comparison matrix wins for
# small J on CPU).  Ensembles never hit either per-lane path: ``_start_order``
# carries a ``custom_vmap`` rule that flattens the whole batch into ONE
# lane-major lexsort — under vmap a 16-way ensemble used to pay ~18x one sort
# per round through batched ``lax.sort`` (the DESIGN.md §7 note), now it pays
# a single O(KJ log KJ) sort.  All paths produce the *same* permutation — the
# job-index tiebreak makes the order strict, so the rank is unique — and the
# downstream cumulative sums fold in the identical sequence, keeping results
# bit-for-bit equal.
_PAIRWISE_ORDER_MAX_J = 512


@jax.custom_batching.custom_vmap
def _start_order(
    sort_site: jax.Array, priority: jax.Array, rank_val: jax.Array, arrival: jax.Array
) -> jax.Array:
    """Start-order permutation by (site, -priority, -rank, arrival, index)."""
    J = sort_site.shape[-1]
    idx = jnp.arange(J)
    if J > _PAIRWISE_ORDER_MAX_J:
        return jnp.lexsort((idx, arrival, -rank_val, -priority, sort_site))

    def asc(k):  # strictly-before / tie masks on one [J, J] key level
        return k[:, None] < k[None, :], k[:, None] == k[None, :]

    def desc(k):
        return k[:, None] > k[None, :], k[:, None] == k[None, :]

    s_lt, s_eq = asc(sort_site)
    p_lt, p_eq = desc(priority)
    r_lt, r_eq = desc(rank_val)
    a_lt, a_eq = asc(arrival)
    before = s_lt | (
        s_eq & (p_lt | (p_eq & (r_lt | (r_eq & (a_lt | (a_eq & (idx[:, None] < idx[None, :])))))))
    )
    rank = jnp.sum(before, axis=0, dtype=jnp.int32)   # unique in [0, J)
    return jnp.zeros((J,), jnp.int32).at[rank].set(idx)


@_start_order.def_vmap
def _start_order_batched(axis_size, in_batched, sort_site, priority, rank_val, arrival):
    """Batched start order as ONE lane-major flattened lexsort (DESIGN.md §8).

    The lane id is the most-significant sort key, so rows of the flat
    permutation group by lane and each lane's block is exactly the
    permutation its solo run computes (the key tuple is a strict total order
    thanks to the index tiebreak, so *any* correct sort yields the identical
    permutation — bit-for-bit lane equivalence is preserved).
    """
    K = axis_size
    site_b, prio_b, rank_b, arr_b = (
        x if b else jnp.broadcast_to(x, (K,) + x.shape)
        for x, b in zip((sort_site, priority, rank_val, arrival), in_batched)
    )
    J = site_b.shape[-1]
    lane = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[:, None], (K, J)).reshape(-1)
    idx = jnp.broadcast_to(jnp.arange(J, dtype=jnp.int32)[None, :], (K, J)).reshape(-1)
    perm = jnp.lexsort(
        (idx, arr_b.reshape(-1), -rank_b.reshape(-1), -prio_b.reshape(-1),
         site_b.reshape(-1), lane)
    )
    order = perm.reshape(K, J).astype(jnp.int32) - (jnp.arange(K, dtype=jnp.int32) * J)[:, None]
    return order, True


@jax.custom_batching.custom_vmap
def _start_order_packed(packed: jax.Array) -> jax.Array:
    """Start-order permutation from a single strict-total-order i32 key.

    The packed key is ``sort_site * J + srank`` where ``srank`` is the
    (init-time) rank of each job under ``(-priority, arrival, index)`` — a
    bijection onto ``[0, J)``, so the packed keys are all distinct and *any*
    sort yields the identical permutation ``_start_order`` computes with its
    5-level lexsort.  One single-key argsort per round instead of a 5-key
    lexsort is the difference between the sort dominating and vanishing from
    the per-round profile at J=100k (DESIGN.md §12).  Only valid while
    priority/arrival are run-constant (nothing in the engine or the stock
    subsystems mutates them) and the policy has no dynamic ``rank`` fn.
    ``stable=False`` is safe for the same reason any sort is: distinct keys
    admit exactly one sorted permutation.
    """
    return jnp.argsort(packed, stable=False).astype(jnp.int32)


@_start_order_packed.def_vmap
def _start_order_packed_batched(axis_size, in_batched, packed):
    """Batched packed order: ONE lane-major flattened 2-key lexsort, same
    construction as ``_start_order_batched`` (lane id most significant)."""
    K = axis_size
    p = packed if in_batched[0] else jnp.broadcast_to(packed, (K,) + packed.shape)
    J = p.shape[-1]
    lane = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[:, None], (K, J)).reshape(-1)
    perm = _lexsort_i32((p.reshape(-1), lane))
    order = perm.reshape(K, J).astype(jnp.int32) - (jnp.arange(K, dtype=jnp.int32) * J)[:, None]
    return order, True


def _f32_sort_key(x: jax.Array) -> jax.Array:
    """Order-preserving ``i32`` image of an ``f32`` array under ``lax.sort``'s
    float order (-0 equals +0, NaN sorts last)."""
    x = jnp.where(x == 0, jnp.float32(0), x.astype(jnp.float32))
    x = jnp.where(jnp.isnan(x), jnp.float32(jnp.nan), x)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _lexsort_i32(keys) -> jax.Array:
    """``jnp.lexsort`` over ``i32`` keys (last key most significant, ties by
    index) as one stable single-key argsort per key: the same permutation.
    The TPU compiler takes minutes over a multi-key or float sort at
    J=100k and seconds over these."""
    perm = jnp.argsort(keys[0], stable=True)
    for key in keys[1:]:
        perm = perm[jnp.argsort(key[perm], stable=True)]
    return perm


def _static_start_rank(jobs) -> jax.Array:
    """``i32[J]``: rank of each job under ``(-priority, arrival, index)`` —
    the run-constant suffix of the start-order key (see ``_start_order_packed``)."""
    J = jobs.capacity
    perm = _lexsort_i32((_f32_sort_key(jobs.arrival), _f32_sort_key(-jobs.priority)))
    return jnp.zeros((J,), jnp.int32).at[perm].set(jnp.arange(J, dtype=jnp.int32))


def _packed_order_ok(policy, J: int, S: int) -> bool:
    """Static predicate: can this run use the packed single-key start order?
    Needs a rank-less policy (dynamic ranks change the key mid-run) and the
    packed key ``site * J + srank`` to fit int32 (site spans [0, S])."""
    return getattr(policy, "rank", None) is None and (S + 1) * J <= 2**31 - 1


@jax.custom_batching.custom_vmap
def _ensemble_any(pred: jax.Array) -> jax.Array:
    """Identity on a scalar bool — except under ``vmap``, where it reduces to
    a single *unbatched* ``any`` over the whole batch.

    This is what keeps the phase-skip guard a real scalar ``lax.cond`` inside
    a vmapped ensemble: the round body branches on "does ANY lane have
    dispatchable work", and lanes without work execute the taken branch as an
    exact no-op (DESIGN.md §8).  A lane is therefore always bit-for-bit equal
    to its solo run, while a fully drained batch (or mesh shard) skips the
    assignment/start phases outright.
    """
    return pred


@_ensemble_any.def_vmap
def _ensemble_any_batched(axis_size, in_batched, pred):
    return jnp.any(pred, axis=0) if in_batched[0] else pred, False


def _first_rows(mask: jax.Array, k: int) -> jax.Array:
    """``i32[k]``: the ascending indices of the first ``k`` true rows of
    ``mask``, padded with ``J`` (one past the last row).

    Row compaction for hooks that only need the few rows a round touches.
    Built from a prefix count and a ``[k, J]`` compare-and-count, with no
    gather or scatter over J: the ``r``-th true row sits at the number of
    rows whose running count is still ``<= r``, which is ``J`` when fewer
    than ``r + 1`` rows are true."""
    count = jnp.cumsum(mask.astype(jnp.int32))
    r = jnp.arange(k, dtype=jnp.int32)
    return jnp.sum(count[None, :] <= r[:, None], axis=-1, dtype=jnp.int32)


def _segment_exclusive_base(values: jax.Array, seg_ids: jax.Array, num_segments: int):
    """For values sorted by seg_ids: per-element cumulative sum *within* its segment."""
    total_cum = jnp.cumsum(values)
    seg_totals = _segment_sum_small(values, seg_ids, num_segments)
    seg_base = jnp.concatenate([jnp.zeros((1,), values.dtype), jnp.cumsum(seg_totals)[:-1]])
    return total_cum - seg_base[seg_ids]


def default_assign(scores: jax.Array, queued: jax.Array, feasible: jax.Array, sites=None):
    """Reference assignment: best feasible site per queued job (site-queue mode).

    Returns (site[J] int32 with -1 for unassigned, assigned_mask[J]).
    Capacity-constrained assignment is provided by ``repro.kernels.assign``.
    """
    neg = jnp.float32(-jnp.inf)
    masked = jnp.where(feasible, scores, neg)
    best = jnp.argmax(masked, axis=-1).astype(jnp.int32)
    best_val = jnp.max(masked, axis=-1)
    ok = queued & jnp.isfinite(best_val)
    return jnp.where(ok, best, -1), ok


def default_assign_cand(scores_k, queued, feas_k, cand, sites=None):
    """Candidate-set analogue of ``default_assign`` (DESIGN.md §12).

    ``scores_k``/``feas_k`` are ``[J, K]`` over the candidate index ``cand``
    (clamped site ids, ascending per row).  Because candidates are sorted
    ascending, the slot argmax picks the lowest site id among score ties —
    the same tie-break ``jnp.argmax`` applies over the dense ``[J, S]`` row,
    so ``topk=S`` matches the dense path bit-for-bit.
    """
    neg = jnp.float32(-jnp.inf)
    masked = jnp.where(feas_k, scores_k, neg)
    best_c = jnp.argmax(masked, axis=-1)
    best_val = jnp.max(masked, axis=-1)
    site = jnp.take_along_axis(cand, best_c[:, None], axis=-1)[..., 0].astype(jnp.int32)
    ok = queued & jnp.isfinite(best_val)
    return jnp.where(ok, site, -1), ok


def _init_state(
    jobs0: JobsState,
    sites0: SiteState,
    policy,
    rng: jax.Array,
    ext0: dict,
    subsystems: tuple,
    log_rows: int,
    topk: int | None = None,
) -> EngineState:
    """Build the round-loop carry: run policy/subsystem init hooks, allocate
    the frame ring buffer, seat the extension states."""
    policy_state0 = policy.init(jobs0, sites0)
    ext0 = dict(ext0)
    for sub in subsystems:
        if sub.init is not None:
            ext0[sub.name] = sub.init(sub, ext0[sub.name], jobs0, sites0)
    if topk is not None:
        # sparse-mode candidate index (DESIGN.md §12): engine-internal carry
        # keys start with "~" and are dropped from SimResult.ext in _finalize
        from .sparse import CAND_SALT, build_candidates

        ext0["~cand"] = build_candidates(
            jobs0, sites0, policy, policy_state0, jnp.float32(0.0),
            jax.random.fold_in(rng, CAND_SALT), ext0, topk,
        )
    # the packed key assumes run-constant arrivals — a subsystem that pushes
    # arrivals (faults resubmission backoff) disables the fast path statically
    mutates_arrival = any(
        getattr(sub.config, "mutates_arrival", False) for sub in subsystems
    )
    if not mutates_arrival and _packed_order_ok(policy, jobs0.capacity, sites0.capacity):
        # run-constant start-order key suffix (see _start_order_packed)
        ext0["~srank"] = _static_start_rank(jobs0)
    log_extra0 = {}
    for sub in subsystems:
        if sub.log_spec is not None:
            log_extra0.update(sub.log_spec(sub, ext0[sub.name], jobs0, sites0))
    log0 = make_log(log_rows, sites0.capacity, extra=log_extra0)
    return EngineState(
        clock=jnp.float32(0.0),
        round=jnp.int32(0),
        jobs=jobs0,
        sites=sites0,
        rng=rng,
        policy_state=policy_state0,
        log=log0,
        halted=jnp.array(False),
        ext=ext0,
    )


def _round_fns(
    policy,
    subsystems: tuple,
    *,
    max_rounds: int,
    log_rows: int,
    max_retries: int,
    monitor_every: int,
    quantum: float,
    phase_skip: bool,
    topk: int | None = None,
    topk_refresh: int = 0,
):
    """Build the engine while-loop's ``(cond, body)`` pair for one static
    configuration.  ``cond`` takes the horizon as a second (traced) argument
    so segmented drivers (``advance_sim``/``monitor.watch``) re-enter the
    *same* compiled loop with a different stopping time per segment — the
    round sequence of a run is identical whether it executes in one
    ``while_loop`` or paused-and-resumed across many."""

    def cond(st: EngineState, horizon):
        with jax.named_scope("clock"):
            active = (
                (st.jobs.state == PENDING)
                | (st.jobs.state == QUEUED)
                | (st.jobs.state == ASSIGNED)
                | (st.jobs.state == RUNNING)
            )
            return (
                (~st.halted)
                & jnp.any(active & st.jobs.valid)
                & (st.round < max_rounds)
                & (st.clock <= horizon)
            )

    def body(st: EngineState) -> EngineState:
        S = st.sites.capacity
        J = st.jobs.capacity
        jobs, sites = st.jobs, st.sites
        with jax.named_scope("clock"):
            rng, k_fail, k_frac, k_policy = jax.random.split(st.rng, 4)
            ctx = RoundCtx(
                jobs=jobs, sites=sites, ext=dict(st.ext),
                clock_prev=st.clock, max_retries=max_retries,
                # per-subsystem RNG streams fold off the round's carry key (see
                # RoundCtx.subkey); the split above is untouched, so subsystem
                # draws never shift the engine's own bitstream
                rng=st.rng,
            )

            # ---- 1. advance the clock to the next event --------------------
            arrivable = (jobs.state == PENDING) & jobs.valid
            for sub in subsystems:
                if sub.arrival_gate is not None:
                    # gated jobs are not an event source: their wake-up event is
                    # whatever un-gates them (e.g. a DAG parent's completion)
                    with jax.named_scope(sub.name):
                        arrivable = arrivable & sub.arrival_gate(sub, ctx)
            arr_t = jnp.where(arrivable, jobs.arrival, INF)
            fin_t = jnp.where(jobs.state == RUNNING, jobs.t_finish, INF)
            t_next = jnp.minimum(arr_t.min(), fin_t.min())
            for sub in subsystems:
                if sub.event_times is not None:
                    # subsystem event sources (e.g. outage window edges) join the
                    # min-reduction so rounds land exactly on their boundaries
                    with jax.named_scope(sub.name):
                        t_next = jnp.minimum(t_next, sub.event_times(sub, ctx))
            if quantum > 0.0:
                t_next = t_next + quantum
            clock = jnp.where(jnp.isfinite(t_next), jnp.maximum(st.clock, t_next), st.clock)
            ctx.clock = clock

        with jax.named_scope("completions"):
            # ---- 2. completions ---------------------------------------------
            comp = (jobs.state == RUNNING) & (jobs.t_finish <= clock)
            for sub in subsystems:
                if sub.completion_filter is not None:
                    with jax.named_scope(sub.name):
                        comp = sub.completion_filter(sub, ctx, comp)
            comp_site = jnp.where(comp, jobs.site, S)  # padded segment for non-events
            freed_mem = _site_sum(jnp.where(comp, jobs.memory, 0.0), comp_site, S)
            failed_now = comp & jobs.will_fail
            resubmit = failed_now & (jobs.retries < max_retries)
            perm_fail = failed_now & ~resubmit
            done_now = comp & ~jobs.will_fail
            # one stacked scatter for the three int per-site completion reductions
            comp_sums = _site_sum_stacked(
                jnp.stack(
                    [
                        jnp.where(comp, jobs.cores, 0),
                        done_now.astype(jnp.int32),
                        failed_now.astype(jnp.int32),
                    ],
                    axis=-1,
                ),
                comp_site,
                S,
            )
            freed_cores = comp_sums[..., 0]

            new_state = jobs.state
            new_state = jnp.where(done_now, DONE, new_state)
            new_state = jnp.where(perm_fail, FAILED, new_state)
            new_state = jnp.where(resubmit, QUEUED, new_state)  # PanDA-style resubmission
            jobs = jobs._replace(
                state=new_state,
                retries=jobs.retries + resubmit.astype(jnp.int32),
                site=jnp.where(resubmit, -1, jobs.site),
                t_finish=jnp.where(resubmit, INF, jobs.t_finish),
            )
            sites = sites._replace(
                free_cores=sites.free_cores + freed_cores,
                free_memory=sites.free_memory + freed_mem,
                n_finished=sites.n_finished + comp_sums[..., 1],
                n_failed=sites.n_failed + comp_sums[..., 2],
            )
            ctx.jobs, ctx.sites = jobs, sites
            ctx.comp, ctx.done_now, ctx.failed_now = comp, done_now, failed_now

            # ---- 2b. subsystem post-completion transitions -------------------
            # (availability preemption/brown-out, workflow cascade-cancel, ...)
            for sub in subsystems:
                if sub.on_completions is not None:
                    with jax.named_scope(sub.name):
                        sub.on_completions(sub, ctx)
            jobs, sites = ctx.jobs, ctx.sites

            # ---- 3. arrivals -------------------------------------------------
            arrived = (jobs.state == PENDING) & (jobs.arrival <= clock) & jobs.valid
            for sub in subsystems:
                if sub.arrival_gate is not None:
                    # re-gate against post-completion states so a job un-gated
                    # *this round* arrives (and can start) this round
                    with jax.named_scope(sub.name):
                        arrived = arrived & sub.arrival_gate(sub, ctx)
            jobs = jobs._replace(state=jnp.where(arrived, QUEUED, jobs.state))
            ctx.jobs, ctx.arrived = jobs, arrived

        with jax.named_scope("score"):
            # ---- 4+5. assignment & starts ------------------------------------
            queued = jobs.state == QUEUED
            if topk is not None and topk_refresh > 0:
                # periodic candidate rebuild (DESIGN.md §12): O(J*S) behind a
                # scalar cond so non-refresh rounds never touch dense shapes.
                # ``_ensemble_any`` keeps the cond scalar under vmap — lanes of
                # an ensemble therefore refresh on shared rounds (exact only at
                # k >= S, where rebuilds are idempotent).
                from .sparse import CAND_SALT, build_candidates

                do_refresh = _ensemble_any(jnp.mod(st.round, topk_refresh) == 0)
                ctx.ext["~cand"] = jax.lax.cond(
                    do_refresh,
                    lambda ops: build_candidates(
                        ops[0], ops[1], policy, st.policy_state, clock,
                        jax.random.fold_in(st.rng, CAND_SALT), ctx.ext, topk,
                    ),
                    lambda ops: ctx.ext["~cand"],
                    (jobs, sites),
                )
            if topk is None:
                # static feasibility: job can ever fit the site
                ctx.feasible = (
                    sites.active[None, :]
                    & (jobs.cores[:, None] <= sites.cores[None, :])
                    & (jobs.memory[:, None] <= sites.memory[None, :])
                )
            else:
                # sparse mode: the static core/memory fit lives in the candidate
                # index; per-round feasibility starts as a per-site [1, S] mask
                # that pre_assign hooks compose with [None, :]-broadcast masks
                # (availability does).  A hook may still write a full [J, S] —
                # the gather below dispatches on the leading dim.
                ctx.feasible = sites.active[None, :]
            ctx.start_cores = sites.free_cores
            ctx.sites_serv = sites
            for sub in subsystems:
                if sub.pre_assign is not None:
                    with jax.named_scope(sub.name):
                        sub.pre_assign(sub, ctx)
            pstate = st.policy_state
            rank_fn = getattr(policy, "rank", None)
            feasible, start_cores = ctx.feasible, ctx.start_cores

        def _assign_and_start(ops):
            """Phases 4 (policy assignment, the plugin hot spot) and 5
            (per-site FIFO-with-capacity starts), exactly as the unguarded
            engine ran them.  With no QUEUED or ASSIGNED rows every update in
            here is a masked no-op, which is what makes the phase-skip guard
            below bit-for-bit safe."""
            jobs, sites = ops
            with jax.named_scope("score"):
                if topk is None:
                    scores = policy.score(jobs, sites, pstate, clock, k_policy)  # [J, S]
                    site_pick, assigned_now = policy.assign(scores, queued, feasible, sites)
                else:
                    cand = ctx.ext["~cand"]                     # i32[J, K]
                    cand_c = jnp.minimum(cand, S - 1)
                    # re-check everything the dense mask carries, gathered at the
                    # candidates: validity, per-round dynamic feasibility, and the
                    # static core/memory fit (exact at k=S, where ``cand``
                    # enumerates every statically feasible site)
                    f_at = (
                        feasible[0][cand_c]
                        if feasible.shape[0] == 1
                        else jnp.take_along_axis(feasible, cand_c, axis=-1)
                    )
                    feas_k = (
                        (cand < S)
                        & f_at
                        & (jobs.cores[:, None] <= sites.cores[cand_c])
                        & (jobs.memory[:, None] <= sites.memory[cand_c])
                    )
                    score_c = getattr(policy, "score_cand", None)
                    if score_c is not None:
                        scores_k = score_c(jobs, sites, pstate, clock, k_policy, cand_c)
                    else:
                        # exact fallback: dense score + gather (no memory win)
                        scores_k = jnp.take_along_axis(
                            policy.score(jobs, sites, pstate, clock, k_policy), cand_c, axis=-1
                        )
                    assign_c = getattr(policy, "assign_cand", None) or default_assign_cand
                    site_pick, assigned_now = assign_c(scores_k, queued, feas_k, cand_c, sites)
                assigned_now = assigned_now & queued
                jobs = jobs._replace(
                    state=jnp.where(assigned_now, ASSIGNED, jobs.state),
                    site=jnp.where(assigned_now, site_pick, jobs.site),
                    t_assign=jnp.where(assigned_now, clock, jobs.t_assign),
                )
                asg_site = jnp.where(assigned_now, site_pick, S)
                sites = sites._replace(
                    n_assigned=sites.n_assigned
                    + _site_sum(assigned_now.astype(jnp.int32), asg_site, S)
                )

            with jax.named_scope("start"):
                cand = jobs.state == ASSIGNED
                sort_site = jnp.where(cand, jobs.site, S).astype(jnp.int32)
                if "~srank" in st.ext:
                    # packed fast path: one single-key sort, provably the same
                    # permutation as the 5-key lexsort (see _start_order_packed)
                    order = _start_order_packed(sort_site * J + ctx.ext["~srank"])
                else:
                    # policy rank is a secondary start-order key: priority still
                    # dominates, rank breaks ties before arrival time (a rank-less
                    # policy contributes a constant key, which the stable lexsort
                    # ignores)
                    rank_val = (
                        jnp.zeros((J,), jnp.float32) if rank_fn is None
                        else rank_fn(jobs, sites, pstate, clock)
                    )
                    order = _start_order(sort_site, jobs.priority, rank_val, jobs.arrival)
                site_s = sort_site[order]
                cand_s = cand[order]
                cores_s = jnp.where(cand_s, jobs.cores[order], 0).astype(jnp.int32)
                mem_s = jnp.where(cand_s, jobs.memory[order], 0.0)
                cum_cores = _segment_exclusive_base(cores_s, site_s, S + 1)
                cum_mem = _segment_exclusive_base(mem_s, site_s, S + 1)
                fits = (
                    cand_s
                    & (cum_cores <= start_cores[jnp.minimum(site_s, S - 1)])
                    & (cum_mem <= sites.free_memory[jnp.minimum(site_s, S - 1)] + 1e-6)
                    & (site_s < S)
                )
                started = jnp.zeros((J,), bool).at[order].set(fits)
                return jobs, sites, started

        if phase_skip:
            # phase-skip guard (DESIGN.md §8): completion-only rounds — the
            # rounds that dominate a draining ensemble lane — skip the score
            # matrix, the start-order sort, and the segmented prefix sums
            # entirely.  ``_ensemble_any`` reduces the predicate over the
            # whole vmap batch, so the cond stays scalar (a real branch, not
            # a select) inside ensembles and mesh shards alike.
            with jax.named_scope("score"):
                has_work = _ensemble_any(jnp.any(queued | (jobs.state == ASSIGNED)))
                jobs, sites, started = jax.lax.cond(
                    has_work,
                    _assign_and_start,
                    lambda ops: (ops[0], ops[1], jnp.zeros((J,), bool)),
                    (jobs, sites),
                )
        else:
            jobs, sites, started = _assign_and_start((jobs, sites))
        ctx.jobs, ctx.sites = jobs, sites

        with jax.named_scope("start"):
            start_site = jnp.where(started, jobs.site, S)
            start_sums = _site_sum_stacked(
                jnp.stack(
                    [jnp.where(started, jobs.cores, 0), started.astype(jnp.int32)], axis=-1
                ),
                start_site,
                S,
            )
            used_cores = start_sums[..., 0]
            used_mem = _site_sum(jnp.where(started, jobs.memory, 0.0), start_site, S)
            n_start_per_site = start_sums[..., 1]
            site_c = jnp.minimum(jobs.site, S - 1)
            share = n_start_per_site[site_c].astype(jnp.float32)

            # ---- 5b. service times + subsystem adjustments -------------------
            ctx.started, ctx.site_c = started, site_c
            ctx.start_site, ctx.start_count = start_site, n_start_per_site
            ctx.t_serv = service_time(jobs, ctx.sites_serv, site_c, share, share)
            for sub in subsystems:
                if sub.on_start is not None:
                    # e.g. workflow output materialization, then replica-aware
                    # stage-in repricing (DESIGN.md §3/§6) — tuple order matters
                    with jax.named_scope(sub.name):
                        sub.on_start(sub, ctx)
            jobs = ctx.jobs
            t_serv = ctx.t_serv

            u_fail = jax.random.uniform(k_fail, (J,))
            # clip (not minimum): unassigned rows carry site == -1, and minimum
            # would map them to the *last* site's fail rate — masked by `started`
            # today, but an OOB/NaN-hygiene hazard under refactors
            will_fail = started & (u_fail < sites.fail_rate[jnp.clip(jobs.site, 0, S - 1)])
            # a failing attempt dies partway through its service time
            frac = jax.random.uniform(k_frac, (J,), minval=0.05, maxval=1.0)
            t_fin = clock + jnp.where(will_fail, t_serv * frac, t_serv)

            jobs = jobs._replace(
                state=jnp.where(started, RUNNING, jobs.state),
                t_start=jnp.where(started, clock, jobs.t_start),
                t_finish=jnp.where(started, t_fin, jobs.t_finish),
                will_fail=jnp.where(started, will_fail, jobs.will_fail),
            )
            sites = sites._replace(
                free_cores=sites.free_cores - used_cores,
                free_memory=sites.free_memory - used_mem,
            )
            ctx.jobs, ctx.sites = jobs, sites

            pstate = policy.on_step(pstate, jobs, sites, comp, started, clock)

        with jax.named_scope("bookkeeping"):
            # ---- 6. halt detection & event log -------------------------------
            n_started = started.sum()
            n_completed = comp.sum()
            # subsystem transitions (preemption, cascade rounds) count as progress
            # so halt detection gives the dispatcher a round to react to them
            progressed = (n_started > 0) | (n_completed > 0) | jnp.any(arrived) | ctx.progressed
            halted = (~jnp.isfinite(t_next)) & ~progressed

            log = st.log
            if log_rows > 0:
                slot = jnp.mod(log.cursor, log_rows)
                write = jnp.mod(st.round, monitor_every) == 0

                def _log_write(operand):
                    log, ext = operand
                    # branch-local ext: subsystem log hooks may update engine
                    # state (e.g. the data subsystem's between-writes WAN
                    # accumulator), so ext rides the cond carry
                    ctx.ext = dict(ext)
                    counts = jax.vmap(
                        lambda s: jnp.sum((jobs.state == s) & jobs.valid).astype(jnp.int32)
                    )(jnp.arange(N_STATES))
                    q_site = jnp.where(jobs.state == ASSIGNED, jobs.site, S)
                    r_site = jnp.where(jobs.state == RUNNING, jobs.site, S)
                    site_queued = _site_sum(jnp.ones((J,), jnp.int32), q_site, S)
                    site_running = _site_sum(jnp.ones((J,), jnp.int32), r_site, S)

                    def wr(buf, val):
                        return jnp.where(write, buf.at[slot].set(val), buf)

                    extra = dict(log.extra)
                    for sub in subsystems:
                        if sub.log_columns is not None:
                            with jax.named_scope(sub.name):
                                for k, v in sub.log_columns(sub, ctx, write).items():
                                    extra[k] = wr(extra[k], v)
                    return EventLog(
                        time=wr(log.time, clock),
                        round_idx=wr(log.round_idx, st.round),
                        counts=wr(log.counts, counts),
                        n_started=wr(log.n_started, n_started.astype(jnp.int32)),
                        n_completed=wr(log.n_completed, n_completed.astype(jnp.int32)),
                        site_free=wr(log.site_free, sites.free_cores),
                        site_queued=wr(log.site_queued, site_queued),
                        site_running=wr(log.site_running, site_running),
                        extra=extra,
                        cursor=log.cursor + write.astype(jnp.int32),
                    ), ctx.ext

                # the log reductions (two segment sums + a per-state count sweep)
                # are real per-round work at WLCG scale; behind a scalar cond,
                # rounds between monitor samples skip them entirely (``wr`` still
                # selects per lane, so a mixed-write ensemble batch stays exact)
                log, ctx.ext = jax.lax.cond(
                    _ensemble_any(write), _log_write, lambda op: op, (log, dict(ctx.ext))
                )

            return EngineState(
                clock=clock,
                round=st.round + 1,
                jobs=jobs,
                sites=sites,
                rng=rng,
                policy_state=pstate,
                log=log,
                halted=halted,
                ext=ctx.ext,
            )

    return cond, body


def _finalize(st: EngineState, policy, subsystems: tuple) -> SimResult:
    """End-of-run hooks (policy ``on_end``, subsystem ``finalize``) plus
    SimResult assembly — shared by the one-shot jit and the segmented API."""
    pstate = policy.on_end(st.policy_state, st.jobs, st.sites, st.clock)
    # "~"-prefixed keys are engine-internal carry (e.g. the sparse candidate
    # index): dropped here so sparse results keep the dense pytree structure
    ext = {k: v for k, v in st.ext.items() if not k.startswith("~")}
    result_fields = {}
    for sub in subsystems:
        if sub.finalize is not None:
            ext[sub.name], fields = sub.finalize(sub, ext[sub.name], st.jobs, st.sites, st.clock)
            result_fields.update(fields)
    return SimResult(
        makespan=st.clock,
        rounds=st.round,
        jobs=st.jobs,
        sites=st.sites,
        log=st.log,
        policy_state=pstate,
        ext=ext,
        **result_fields,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "policy",
        "subsystems",
        "max_rounds",
        "log_rows",
        "max_retries",
        "monitor_every",
        "quantum",
        "phase_skip",
        "topk",
        "topk_refresh",
    ),
)
def _simulate(
    jobs0: JobsState,
    sites0: SiteState,
    policy,
    rng: jax.Array,
    ext0: dict,
    *,
    subsystems: tuple = (),
    max_rounds: int = 100_000,
    horizon: float = float("inf"),
    log_rows: int = 0,
    max_retries: int = 3,
    monitor_every: int = 1,
    quantum: float = 0.0,
    phase_skip: bool = True,
    topk: int | None = None,
    topk_refresh: int = 0,
) -> SimResult:
    """The jitted phase pipeline; ``subsystems`` is a static Subsystem tuple,
    ``ext0`` the matching name -> state pytree mapping (see subsystems.py)."""
    if topk is not None:
        topk = min(int(topk), sites0.capacity)  # k >= S is exactly dense
    st0 = _init_state(jobs0, sites0, policy, rng, ext0, subsystems, log_rows, topk)
    cond, body = _round_fns(
        policy,
        subsystems,
        max_rounds=max_rounds,
        log_rows=log_rows,
        max_retries=max_retries,
        monitor_every=monitor_every,
        quantum=quantum,
        phase_skip=phase_skip,
        topk=topk,
        topk_refresh=topk_refresh,
    )
    st = jax.lax.while_loop(lambda s: cond(s, horizon), body, st0)
    return _finalize(st, policy, subsystems)


def simulate(
    jobs0: JobsState,
    sites0: SiteState,
    policy,
    rng: jax.Array,
    *,
    data_policy=None,
    network=None,
    replicas=None,
    availability=None,
    workflow=None,
    transfers=None,
    faults=None,
    subsystems=(),
    max_rounds: int = 100_000,
    horizon: float = float("inf"),
    log_rows: int = 0,
    max_retries: int = 3,
    monitor_every: int = 1,
    quantum: float = 0.0,
    phase_skip: bool = True,
    topk: int | None = None,
    topk_refresh: int = 0,
    recorder=None,
) -> SimResult:
    """Run the grid simulation to completion (or ``max_rounds``/``horizon``).

    The jit call is a ``dispatch`` span (``telemetry.span``) whose
    ``compiled`` argument says whether it traced and compiled.  A
    ``recorder`` (a ``telemetry.TraceRecorder``) also gets an ``execute``
    span (``block_until_ready``), the ``compiles`` count, the
    ``rounds_executed`` counter and, with a data policy, the
    ``data_wide_rounds`` counter (rounds whose stage-in ran on all J rows);
    with transfer queues, ``xfer_enqueued``, ``xfer_landed`` and
    ``xfer_waited`` (transfers admitted after waiting for a link slot).
    ``None`` (the default) adds no host sync — results are bit-for-bit
    identical either way.

    ``phase_skip`` (default on) guards the assignment + start phases behind a
    scalar ``lax.cond`` on "any QUEUED/ASSIGNED rows": completion-only rounds
    skip the score matrix, start-order sort, and segmented prefix sums
    entirely, with bit-for-bit identical results (DESIGN.md §8).  ``False``
    forces the unguarded pipeline (the equivalence is property-tested).

    ``topk`` switches assignment to the sparse candidate-set path
    (DESIGN.md §12): scores are evaluated over a static ``i32[J, topk]``
    candidate-site index instead of the dense ``[J, S]`` matrix — the
    WLCG-scale lever (S=300, J=100k).  ``topk >= S`` is bit-for-bit equal to
    the dense path; smaller k is a documented approximation.  The index is
    built once at init from static feasibility, data locality, and the
    policy pre-rank; ``topk_refresh=N`` rebuilds it every N rounds (0 =
    never) so load/locality-sensitive pre-ranks stay current.

    ``quantum`` > 0 batches all events inside [t*, t* + quantum] into one
    round (SimGrid-style time-precision knob): timestamps quantize to the
    window but each round retires many events — the lever that turns
    O(events) rounds into O(horizon/quantum) for dense workloads (paper
    Fig. 4 scaling regime).

    Engine extensions are ``Subsystem`` hook bundles (DESIGN.md §7) composed
    into the round loop at trace time.  The built-in trio keeps its keyword
    API — each maps onto a subsystem in canonical order:

    - ``data_policy=`` (with ``network=`` and ``replicas=``) switches stage-in
      for dataset-carrying jobs to the replica-aware WAN model: each starting
      job reads its dataset from the policy-selected replica over the shared
      link matrix (zero-cost local cache hits), and the policy may
      cache-on-read into the site's storage element (DESIGN.md §3).  Jobs with
      ``dataset == -1`` — and every run without a data policy — keep the flat
      per-site link model.

    - ``availability=`` (an ``AvailabilityState`` downtime calendar) turns on
      availability dynamics (DESIGN.md §5): window edges become event rounds,
      full outages block assignment/starts and either preempt running jobs
      (back to QUEUED with a retry) or drain them, and brown-out windows scale
      a site's effective speed and usable cores by the window factor.

    - ``workflow=`` (a ``WorkflowState`` DAG, DESIGN.md §6) gates the
      dispatcher on dependencies: a job stays PENDING until every parent is
      DONE, a terminally failed parent cascade-cancels its descendants, and —
      when the data subsystem is on — each completing parent materializes its
      ``jobs.out_dataset`` into the replica catalog at the site it ran on.

    - ``faults=`` (a ``FaultState`` from ``make_faults``, DESIGN.md §13) adds
      fault injection and recovery: per-link transfer failures with
      exponential-backoff re-enqueue, resubmission backoff, walltime kills, a
      replica-loss calendar, and adaptive site blacklisting with a half-open
      circuit breaker.  The default-constructed state is inert.

    ``subsystems=((Subsystem, state0), ...)`` appends custom subsystems after
    the built-ins (see ``examples/custom_subsystem.py``).  Every ``None``/
    absent subsystem costs nothing: specialization is static, so such runs
    stay bit-for-bit identical to an engine compiled without the subsystem.
    """
    subs, ext0 = resolve_subsystems(
        data_policy=data_policy,
        network=network,
        replicas=replicas,
        availability=availability,
        workflow=workflow,
        transfers=transfers,
        faults=faults,
        subsystems=subsystems,
        jobs=jobs0,
        sites=sites0,
    )
    kw = dict(
        subsystems=subs,
        max_rounds=max_rounds,
        horizon=horizon,
        log_rows=log_rows,
        max_retries=max_retries,
        monitor_every=monitor_every,
        quantum=quantum,
        phase_skip=phase_skip,
        topk=topk,
        topk_refresh=topk_refresh,
    )
    before = _simulate._cache_size()
    with span("dispatch", recorder) as sp:
        res = _simulate(jobs0, sites0, policy, rng, ext0, **kw)
        sp.set(compiled=int(_simulate._cache_size() > before))
    if recorder is not None:
        with span("execute", recorder):
            jax.block_until_ready(res)
        recorder.gauge("rounds_executed", int(res.rounds))
        for name in RESULT_COUNTERS:
            if getattr(res, name) is not None:
                recorder.gauge(name, int(getattr(res, name)))
        recorder.note("subsystems", [s.name for s in subs])
    return res


# --------------------------------------------------------------------------
# segmented execution: pause/resume the round loop between frames
# --------------------------------------------------------------------------


class SimHandle(NamedTuple):
    """A paused simulation: the while-loop carry plus everything needed to
    resume it.  Produced by ``init_sim``, advanced by ``advance_sim``,
    finished by ``finish_sim`` — the substrate of ``monitor.watch`` and of
    any streaming driver that wants frames *between* jit re-entries rather
    than inside the hot loop."""

    state: EngineState
    policy: object
    subsystems: tuple
    statics: tuple  # (max_rounds, log_rows, max_retries, monitor_every, quantum,
    #                  phase_skip, topk, topk_refresh)

    @property
    def max_rounds(self) -> int:
        return self.statics[0]


def init_sim(
    jobs0: JobsState,
    sites0: SiteState,
    policy,
    rng: jax.Array,
    *,
    data_policy=None,
    network=None,
    replicas=None,
    availability=None,
    workflow=None,
    transfers=None,
    faults=None,
    subsystems=(),
    max_rounds: int = 100_000,
    log_rows: int = 0,
    max_retries: int = 3,
    monitor_every: int = 1,
    quantum: float = 0.0,
    phase_skip: bool = True,
    topk: int | None = None,
    topk_refresh: int = 0,
) -> SimHandle:
    """Initialize a resumable simulation (same kwargs as ``simulate`` minus
    ``horizon``, which ``advance_sim`` takes per segment)."""
    from .subsystems import resolve_subsystems as _resolve

    subs, ext0 = _resolve(
        data_policy=data_policy,
        network=network,
        replicas=replicas,
        availability=availability,
        workflow=workflow,
        transfers=transfers,
        faults=faults,
        subsystems=subsystems,
        jobs=jobs0,
        sites=sites0,
    )
    if topk is not None:
        topk = min(int(topk), sites0.capacity)
    with span("init_sim"):
        st0 = _init_state(jobs0, sites0, policy, rng, ext0, subs, log_rows, topk)
    statics = (max_rounds, log_rows, max_retries, monitor_every, quantum, phase_skip,
               topk, topk_refresh)
    return SimHandle(state=st0, policy=policy, subsystems=subs, statics=statics)


@functools.lru_cache(maxsize=None)
def _segment_fn(policy, subsystems: tuple, statics: tuple):
    """The cached jitted segment runner: the exact engine while loop with the
    horizon as a *dynamic* argument, so every segment of every run with the
    same static configuration shares one compile."""
    (max_rounds, log_rows, max_retries, monitor_every, quantum, phase_skip,
     topk, topk_refresh) = statics
    cond, body = _round_fns(
        policy,
        subsystems,
        max_rounds=max_rounds,
        log_rows=log_rows,
        max_retries=max_retries,
        monitor_every=monitor_every,
        quantum=quantum,
        phase_skip=phase_skip,
        topk=topk,
        topk_refresh=topk_refresh,
    )

    def run(st: EngineState, horizon):
        return jax.lax.while_loop(lambda s: cond(s, horizon), body, st)

    return jax.jit(run)


def advance_sim(handle: SimHandle, horizon: float = float("inf"), *,
                recorder=None) -> SimHandle:
    """Run rounds until the clock passes ``horizon`` (or the run drains).

    Because ``cond`` checks the clock *before* each round, resuming with a
    larger horizon continues the identical round sequence a single
    ``simulate`` call would have executed — segmentation changes where the
    loop pauses, never what it computes (property-tested bit-for-bit).

    The call, from entry to return, is an ``advance_sim`` span
    (``telemetry.span``; timed into ``recorder`` when given) whose
    ``compiled`` argument says whether this call traced or compiled the
    segment program."""
    with span("advance_sim", recorder) as sp:
        run = _segment_fn(handle.policy, tuple(handle.subsystems), handle.statics)
        before = run._cache_size()  # 0 for a segment program made just now
        state = run(handle.state, jnp.float32(horizon))
        sp.set(compiled=int(run._cache_size() > before))
    return handle._replace(state=state)


def sim_active(handle: SimHandle) -> bool:
    """Host-side: would the round loop still run, given an open horizon?"""
    st = handle.state
    if bool(st.halted) or int(st.round) >= handle.max_rounds:
        return False
    state = np.asarray(st.jobs.state)
    valid = np.asarray(st.jobs.valid)
    active = (
        (state == PENDING) | (state == QUEUED) | (state == ASSIGNED) | (state == RUNNING)
    )
    return bool((active & valid).any())


def finish_sim(handle: SimHandle) -> SimResult:
    """Run end-of-run hooks on a (drained or abandoned) handle."""
    with span("finish_sim"):
        return _finalize(handle.state, handle.policy, tuple(handle.subsystems))


# --------------------------------------------------------------------------
# scenario ensembles: one compile, many simulations
# --------------------------------------------------------------------------


class Scenario(NamedTuple):
    """One point of a scenario ensemble: a workload + platform + per-scenario
    subsystem states (calendars, catalogs, DAGs) keyed by subsystem name.

    Feed a list of these (identical shapes/treedefs) to ``simulate_many`` —
    or pre-stack them with ``stack_scenarios`` — to batch the whole ensemble
    through one vmapped compile.
    """

    jobs: JobsState
    sites: SiteState
    ext: dict | None = None


class ScenarioBuckets(NamedTuple):
    """A ragged ensemble grouped into a few padded shape buckets.

    ``buckets[b]`` is a stacked ``Scenario`` whose jobs are padded only to
    that bucket's largest capacity — instead of every scenario paying dense
    rows up to the *global* max J (the padding tax of one-bucket stacking).
    ``index[b]`` holds each lane's position in the original scenario list, so
    results reassemble in caller order (and lane ``i`` draws the same RNG key
    it would in a single-bucket stack).
    """

    buckets: tuple  # tuple[Scenario], each stacked with leading K_b
    index: tuple    # tuple[tuple[int, ...]] original scenario positions

    @property
    def n_scenarios(self) -> int:
        return sum(len(ix) for ix in self.index)

    def padding_stats(self) -> dict:
        """Measure the padding tax this bucketing actually pays.

        Returns per-bucket rows (capacity, lanes, used vs padded job rows,
        waste fraction) plus a summary comparing against the one-bucket
        alternative (every lane dense to the global max capacity) — the
        saved-row count that justifies the extra compiles."""
        rows = []
        total_rows = total_used = 0
        for b, (scn, ix) in enumerate(zip(self.buckets, self.index)):
            cap = scn.jobs.capacity
            lanes = len(ix)
            used = int(np.asarray(scn.jobs.valid).sum())
            dense = lanes * cap
            rows.append(
                dict(
                    bucket=b,
                    capacity=cap,
                    lanes=lanes,
                    used_rows=used,
                    padded_rows=dense - used,
                    waste_frac=float((dense - used) / dense) if dense else 0.0,
                )
            )
            total_rows += dense
            total_used += used
        cap_max = max(r["capacity"] for r in rows)
        flat_rows = self.n_scenarios * cap_max
        return dict(
            buckets=rows,
            summary=dict(
                n_buckets=len(rows),
                n_scenarios=self.n_scenarios,
                total_rows=total_rows,
                used_rows=total_used,
                waste_frac=(
                    float((total_rows - total_used) / total_rows) if total_rows else 0.0
                ),
                flat_rows=flat_rows,
                flat_waste_frac=(
                    float((flat_rows - total_used) / flat_rows) if flat_rows else 0.0
                ),
                saved_rows=flat_rows - total_rows,
            ),
        )


def stack_scenarios(scenarios, *, subsystems: tuple = (), buckets: int = 1):
    """Stack a list of Scenarios into one leading-K pytree.

    Ragged workloads (different job counts per scenario) are canonicalized by
    padding every ``jobs`` to the largest capacity with inert rows — the
    static-shape normalization that lets the whole ensemble share a single
    compile where a ``simulate`` loop would retrace per size.  Job-shaped
    subsystem state (e.g. a workflow parent matrix) pads alongside through
    each subsystem's ``pad_jobs`` hook when ``subsystems`` is given
    (``simulate_many`` passes its own).  Sites and non-job-shaped subsystem
    state must already share shapes (pad calendars/catalogs with their
    builders' ``max_windows=``/``capacity=`` knobs).

    ``buckets > 1`` returns a ``ScenarioBuckets`` instead: scenarios are
    ordered by job capacity and split into up to ``buckets`` similar-size
    groups, each padded only to its own max — a few compiles instead of one,
    but far fewer wasted dense rows on very ragged ensembles (DESIGN.md §8).
    ``simulate_many`` and ``simulate_many_sharded`` dispatch per bucket and
    return results in the original scenario order.
    """
    from .subsystems import pad_ext_jobs
    from .types import pad_jobs_capacity

    scenarios = list(scenarios)
    if not scenarios:
        raise ValueError("need at least one scenario")
    if buckets > 1:
        order = sorted(range(len(scenarios)), key=lambda i: scenarios[i].jobs.capacity)
        groups = [g for g in np.array_split(order, min(buckets, len(scenarios))) if len(g)]
        return ScenarioBuckets(
            buckets=tuple(
                stack_scenarios([scenarios[i] for i in g], subsystems=subsystems)
                for g in groups
            ),
            index=tuple(tuple(int(i) for i in g) for g in groups),
        )
    cap = max(s.jobs.capacity for s in scenarios)
    norm = [
        Scenario(
            pad_jobs_capacity(s.jobs, cap),
            s.sites,
            pad_ext_jobs(subsystems, s.ext or {}, s.jobs.capacity, cap),
        )
        for s in scenarios
    ]
    return jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *norm)


def _check_ensemble(scenarios: Scenario, subsystems: tuple) -> dict:
    """Validate a stacked ensemble against its subsystem tuple; returns ext."""
    ext = scenarios.ext or {}
    known = {sub.name for sub in subsystems}
    if set(ext) != known:
        raise ValueError(
            f"scenario ext keys {sorted(ext)} must match the attached "
            f"subsystems {sorted(known)} one-to-one"
        )
    for sub in subsystems:
        if sub.validate is not None:
            # shape checks use negative axes, so the leading K is transparent
            sub.validate(sub, ext[sub.name], scenarios.jobs, scenarios.sites)
    return ext


def _simulate_many_stacked(
    scenarios: Scenario, policy, keys: jax.Array, *, subsystems: tuple = (), **kw
) -> SimResult:
    """The vmapped ensemble core: one compile, per-lane RNG keys supplied."""
    ext = _check_ensemble(scenarios, subsystems)

    def one(jobs, sites, ext_k, key):
        return _simulate(jobs, sites, policy, key, ext_k, subsystems=subsystems, **kw)

    return jax.vmap(one)(scenarios.jobs, scenarios.sites, ext, keys)


def _pad_result_jobs(jobs: JobsState, capacity: int) -> JobsState:
    """Pad the trailing job axis of a leading-K ``JobsState`` with inert rows
    (the ``types.JOB_PAD_FILLS`` fixed point) — how bucketed results rejoin a
    common shape."""
    from .types import JOB_PAD_FILLS

    J = jobs.capacity
    if capacity == J:
        return jobs
    n = capacity - J

    def pad(name, x):
        fill = JOB_PAD_FILLS.get(name, 0)
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n)], constant_values=fill)

    return JobsState(**{k: pad(k, v) for k, v in jobs._asdict().items()})


# legacy SimResult accessors that alias a subsystem's ext slot; after a
# bucketed merge re-pads ext, the aliases must point at the padded state
_EXT_ALIASES = {"workflow": ("wf",), "availability": ("avail",)}


def _pad_result_to(res: SimResult, subsystems: tuple, capacity: int) -> SimResult:
    """Grow one bucket's SimResult to the ensemble-wide job capacity."""
    J_b = res.jobs.capacity
    repl = {"jobs": _pad_result_jobs(res.jobs, capacity)}
    if J_b != capacity and res.ext:
        ext = dict(res.ext)
        for sub in subsystems:
            if sub.pad_jobs is not None and sub.name in ext:
                padded = jax.vmap(lambda s: sub.pad_jobs(sub, s, J_b, capacity))(
                    ext[sub.name]
                )
                ext[sub.name] = padded
                for field in _EXT_ALIASES.get(sub.name, ()):
                    if getattr(res, field) is not None:
                        repl[field] = padded
        repl["ext"] = ext
    return res._replace(**repl)


@functools.lru_cache(maxsize=None)
def _bucket_merger(subsystems: tuple, cap: int, inv: tuple):
    """Jitted bucket-result reassembly (pad to the common capacity, concat,
    un-permute): one program instead of hundreds of eager per-leaf dispatches
    — the merge is on the hot path of every bucketed ensemble call."""
    inv_a = jnp.asarray(inv)

    def merge(*results):
        padded = [_pad_result_to(r, subsystems, cap) for r in results]
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0)[inv_a], *padded)

    return jax.jit(merge)


def _run_buckets(sb: ScenarioBuckets, rng: jax.Array, runner, subsystems):
    """Dispatch a bucketed ensemble through ``runner(stacked, keys)`` per
    bucket, then reassemble one SimResult in the original scenario order.

    Lane ``i`` draws ``split(rng, K)[i]`` exactly as it would in a
    single-bucket stack, so bucketing is invisible to the results (the merge
    re-pads each bucket's jobs/ext to the global max capacity with inert
    rows — the same rows single-bucket stacking would have carried through
    the whole run).
    """
    keys = jax.random.split(rng, sb.n_scenarios)
    cap = max(s.jobs.capacity for s in sb.buckets)
    results = [
        runner(scen, keys[np.asarray(ix)]) for scen, ix in zip(sb.buckets, sb.index)
    ]
    inv = np.argsort(np.concatenate([np.asarray(ix) for ix in sb.index]))
    merge = _bucket_merger(tuple(subsystems), cap, tuple(int(i) for i in inv))
    return merge(*results)


def simulate_many(
    scenarios,
    policy,
    rng: jax.Array,
    *,
    subsystems: tuple = (),
    **kw,
) -> SimResult:
    """Batched ensemble execution: K scenarios, one compile, one device program.

    ``scenarios`` is a list of ``Scenario``s (stacked here), an already
    stacked ``Scenario`` whose leaves carry a leading K axis, or a
    ``ScenarioBuckets`` from ``stack_scenarios(..., buckets=n)`` (dispatched
    per bucket, one compile per distinct shape) — stacked workloads,
    platforms (speeds), and subsystem states (outage calendars, replica
    catalogs, workflow DAGs) all vary per scenario.  ``subsystems`` is a
    tuple of the static ``Subsystem`` bundles matching the keys of
    ``Scenario.ext`` (empty for plain runs).  Each scenario gets its own RNG
    stream; the returned ``SimResult`` has a leading K axis on every leaf, in
    the original scenario order.

    This is the surrogate-dataset / design-space lever (ROADMAP): the paper
    runs scenarios one process at a time, a vmapped ensemble retires them in
    lockstep rounds at device throughput (``benchmarks/bench_engine_rounds``).
    To spread the ensemble over a device mesh — and break the global
    lock-step — see ``distributed.simulate_many_sharded``.
    """
    if isinstance(scenarios, ScenarioBuckets):
        runner = lambda scen, keys: _simulate_many_stacked(  # noqa: E731
            scen, policy, keys, subsystems=subsystems, **kw
        )
        return _run_buckets(scenarios, rng, runner, subsystems)
    if not isinstance(scenarios, Scenario):
        scenarios = stack_scenarios(scenarios, subsystems=subsystems)
    K = scenarios.jobs.arrival.shape[0]
    return _simulate_many_stacked(
        scenarios, policy, jax.random.split(rng, K), subsystems=subsystems, **kw
    )


def simulate_ensemble(
    jobs0: JobsState,
    sites0: SiteState,
    policy,
    rng: jax.Array,
    *,
    speed_candidates: jax.Array,  # f32[K, S] per-site speeds to evaluate
    **kw,
) -> SimResult:
    """vmap the full simulation over K per-site speed vectors (calibration inner loop)."""

    def one(speed, key):
        sites = sites0._replace(speed=speed)
        return simulate(jobs0, sites, policy, key, **kw)

    keys = jax.random.split(rng, speed_candidates.shape[0])
    return jax.vmap(one)(speed_candidates, keys)


def walltimes(result: SimResult) -> jax.Array:
    """Per-job walltime (t_finish - t_start); inf for jobs that never ran."""
    return result.jobs.t_finish - result.jobs.t_start


def queue_times(result: SimResult) -> jax.Array:
    return result.jobs.t_start - result.jobs.arrival


AssignFn = Callable[[jax.Array, jax.Array, jax.Array, SiteState], tuple]
