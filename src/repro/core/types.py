"""Core state types for the vectorized grid simulator.

CGSim models a computing grid as sites (SimGrid netzones) of hosts plus a
central main server that dispatches jobs.  Here the whole simulation state is
a fixed-capacity struct-of-arrays pytree so every simulator advance is dense,
masked algebra (see DESIGN.md §2).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

INF = jnp.float32(jnp.inf)

# --- job lifecycle states (CGSim: pending/assigned/running/finished/failed) ---
PENDING = 0   # not yet arrived at the main server
QUEUED = 1    # at the main server, awaiting a site assignment ("pending list")
ASSIGNED = 2  # placed in a site queue, awaiting free cores
RUNNING = 3   # executing on site cores
DONE = 4
FAILED = 5    # terminally failed (retries exhausted)
CANCELLED = 6  # cascade-cancelled: an ancestor in its workflow DAG failed
N_STATES = 7

STATE_NAMES = ("pending", "queued", "assigned", "running", "finished", "failed", "cancelled")


class JobsState(NamedTuple):
    """Struct-of-arrays over a fixed job capacity J (padded with inactive rows)."""

    job_id: jax.Array     # i32[J] external id (e.g. PanDA job id)
    arrival: jax.Array    # f32[J] seconds
    work: jax.Array       # f32[J] compute demand (HS23-normalised core-seconds)
    cores: jax.Array      # i32[J] cores required (1 or 8 for ATLAS single/multicore)
    memory: jax.Array     # f32[J] GB resident
    bytes_in: jax.Array   # f32[J] stage-in volume
    bytes_out: jax.Array  # f32[J] stage-out volume
    priority: jax.Array   # f32[J] higher starts first within a site queue
    state: jax.Array      # i32[J] lifecycle state
    site: jax.Array       # i32[J] assigned site, -1 if none
    t_assign: jax.Array   # f32[J] time assigned to a site (inf until set)
    t_start: jax.Array    # f32[J] time execution started
    t_finish: jax.Array   # f32[J] time execution finished/failed
    retries: jax.Array    # i32[J] resubmission count
    will_fail: jax.Array  # bool[J] sampled at start: this attempt fails
    valid: jax.Array      # bool[J] row is a real job (padding rows are False)
    dataset: jax.Array    # i32[J] input dataset id, -1 = no catalogued dataset
    xfer_src: jax.Array   # i32[J] replica site the last stage-in read from (-1 none)
    xfer_bytes: jax.Array  # f32[J] WAN bytes moved by the last stage-in (0 = cache hit)
    xfer_time: jax.Array  # f32[J] stage-in duration of the last attempt
    xfer_wait: jax.Array  # f32[J] transfer queue-wait of the last attempt (0 = never queued)
    xfer_qdepth: jax.Array  # i32[J] queued transfers ahead on the link at enqueue (-1 = never enqueued)
    preempted: jax.Array  # i32[J] attempts cut short by site outages (DESIGN.md §5)
    wf_id: jax.Array      # i32[J] workflow the job belongs to, -1 = standalone
    n_parents: jax.Array  # i32[J] number of DAG parents (0 = root / standalone)
    dag_depth: jax.Array  # i32[J] longest root->job path length (0 for roots)
    wf_crit: jax.Array    # f32[J] critical-path weight: own work + heaviest descendant chain
    out_dataset: jax.Array  # i32[J] dataset this job materializes on completion, -1 = none

    @property
    def capacity(self) -> int:
        return self.arrival.shape[-1]


class SiteState(NamedTuple):
    """Struct-of-arrays over a fixed site capacity S."""

    cores: jax.Array        # i32[S] total cores
    speed: jax.Array        # f32[S] per-core work units / second  (CALIBRATION TARGET)
    memory: jax.Array       # f32[S] GB
    bw_in: jax.Array        # f32[S] ingress bandwidth bytes/s (shared by staging jobs)
    bw_out: jax.Array       # f32[S] egress bandwidth bytes/s
    latency: jax.Array      # f32[S] per-transfer latency seconds
    par_gamma: jax.Array    # f32[S] Amdahl contention: speedup = c / (1 + gamma*(c-1))
    fail_rate: jax.Array    # f32[S] per-attempt failure probability
    active: jax.Array       # bool[S] site exists / is up (elasticity + padding)
    free_cores: jax.Array   # i32[S]
    free_memory: jax.Array  # f32[S]
    n_assigned: jax.Array   # i32[S] cumulative jobs assigned
    n_finished: jax.Array   # i32[S] cumulative finished
    n_failed: jax.Array     # i32[S] cumulative failed attempts

    @property
    def capacity(self) -> int:
        return self.cores.shape[-1]


class EventLog(NamedTuple):
    """Fixed-shape ring buffer of per-round snapshots (CGSim Table 1 / dashboard feed).

    ``site_free``/``site_running``/``site_queued`` are per-site columns so the
    monitor can render node pressure; ``counts`` are global per-state tallies.
    ``extra`` holds subsystem-declared columns (DESIGN.md §7) keyed by name —
    e.g. ``site_disk``/``site_net_in`` from the data subsystem, ``site_avail``
    from availability — so new subsystems export dashboard feeds without
    touching this type.
    """

    time: jax.Array          # f32[R]
    round_idx: jax.Array     # i32[R]
    counts: jax.Array        # i32[R, N_STATES]
    n_started: jax.Array     # i32[R] jobs started this round
    n_completed: jax.Array   # i32[R]
    site_free: jax.Array     # i32[R, S]
    site_queued: jax.Array   # i32[R, S] jobs sitting in each site queue
    site_running: jax.Array  # i32[R, S]
    extra: dict              # {name: [R, ...]} subsystem-declared columns
    cursor: jax.Array        # i32[] next write slot (wraps)

    @property
    def rows(self) -> int:
        return self.time.shape[-1]


class EngineState(NamedTuple):
    """The while-loop carry: core engine state plus the generic subsystem
    extension mapping ``ext`` (a dict pytree, one slot per Subsystem name —
    DESIGN.md §7).  Subsystem-specific fields never appear here."""

    clock: jax.Array        # f32[]
    round: jax.Array        # i32[]
    jobs: JobsState
    sites: SiteState
    rng: jax.Array          # PRNGKey
    policy_state: object    # policy-defined pytree
    log: EventLog
    halted: jax.Array       # bool[] no further progress possible
    ext: dict               # {subsystem name: subsystem-defined state pytree};
                            # "~"-prefixed keys are engine-internal carries
                            # (e.g. "~cand", "~srank") stripped at finalize


class SimResult(NamedTuple):
    makespan: jax.Array     # f32[] clock at termination
    rounds: jax.Array       # i32[]
    jobs: JobsState
    sites: SiteState
    log: EventLog
    policy_state: object
    replicas: object = None     # final ReplicaState (None without a DataPolicy)
    data_state: object = ()
    data_wide_rounds: object = None  # i32[] rounds whose stage-in ran on all J rows
    xfer_enqueued: object = None  # i32[] WAN reads queued for transfer (None without transfers)
    xfer_landed: object = None    # i32[] transfers landed
    xfer_waited: object = None    # i32[] transfers admitted after waiting for a link slot
    avail: object = None        # final AvailabilityState (None without availability)
    wf: object = None           # final WorkflowState (None without a workflow DAG)
    ext: object = None          # {name: final state} for every attached subsystem


# SimResult's subsystem counters (i32[], None where the subsystem is absent):
# ``simulate`` and ``monitor.watch`` give each to a recorder as a gauge.
RESULT_COUNTERS = ("data_wide_rounds", "xfer_enqueued", "xfer_landed", "xfer_waited")


def make_jobs(
    *,
    job_id,
    arrival,
    work,
    cores,
    memory,
    bytes_in,
    bytes_out,
    priority=None,
    dataset=None,
    wf_id=None,
    n_parents=None,
    dag_depth=None,
    wf_crit=None,
    out_dataset=None,
    capacity: int | None = None,
) -> JobsState:
    """Build a JobsState from per-job vectors, padding to ``capacity`` rows."""
    arrival = jnp.asarray(arrival, jnp.float32)
    n = arrival.shape[0]
    cap = capacity or n
    if cap < n:
        raise ValueError(f"capacity {cap} < number of jobs {n}")

    def pad_f(x, fill=0.0):
        x = jnp.asarray(x, jnp.float32)
        return jnp.pad(x, (0, cap - n), constant_values=fill)

    def pad_i(x, fill=0):
        x = jnp.asarray(x, jnp.int32)
        return jnp.pad(x, (0, cap - n), constant_values=fill)

    if priority is None:
        priority = jnp.zeros((n,), jnp.float32)
    if dataset is None:
        dataset = jnp.full((n,), -1, jnp.int32)
    if wf_id is None:
        wf_id = jnp.full((n,), -1, jnp.int32)
    if n_parents is None:
        n_parents = jnp.zeros((n,), jnp.int32)
    if dag_depth is None:
        dag_depth = jnp.zeros((n,), jnp.int32)
    if wf_crit is None:
        wf_crit = jnp.zeros((n,), jnp.float32)
    if out_dataset is None:
        out_dataset = jnp.full((n,), -1, jnp.int32)
    valid = jnp.arange(cap) < n
    return JobsState(
        job_id=pad_i(job_id, -1),
        arrival=pad_f(arrival, jnp.inf),
        work=pad_f(work),
        cores=pad_i(cores, 1),
        memory=pad_f(memory),
        bytes_in=pad_f(bytes_in),
        bytes_out=pad_f(bytes_out),
        priority=pad_f(priority),
        state=jnp.where(valid, PENDING, DONE).astype(jnp.int32),
        site=jnp.full((cap,), -1, jnp.int32),
        t_assign=jnp.full((cap,), jnp.inf, jnp.float32),
        t_start=jnp.full((cap,), jnp.inf, jnp.float32),
        t_finish=jnp.full((cap,), jnp.inf, jnp.float32),
        retries=jnp.zeros((cap,), jnp.int32),
        will_fail=jnp.zeros((cap,), bool),
        valid=valid,
        dataset=pad_i(dataset, -1),
        xfer_src=jnp.full((cap,), -1, jnp.int32),
        xfer_bytes=jnp.zeros((cap,), jnp.float32),
        xfer_time=jnp.zeros((cap,), jnp.float32),
        xfer_wait=jnp.zeros((cap,), jnp.float32),
        xfer_qdepth=jnp.full((cap,), -1, jnp.int32),
        preempted=jnp.zeros((cap,), jnp.int32),
        wf_id=pad_i(wf_id, -1),
        n_parents=pad_i(n_parents),
        dag_depth=pad_i(dag_depth),
        wf_crit=pad_f(wf_crit),
        out_dataset=pad_i(out_dataset, -1),
    )


# Per-column fill values for inert job padding rows (DONE/invalid, never
# arriving).  A padding row built from these is a fixed point of the engine:
# it passes through every round untouched, which is what makes padded and
# unpadded runs bit-for-bit comparable (and lets bucketed ensemble results be
# re-padded to a common capacity after the fact).
JOB_PAD_FILLS = dict(
    job_id=-1, arrival=float("inf"), state=DONE, site=-1, t_assign=float("inf"),
    t_start=float("inf"), t_finish=float("inf"), valid=False, dataset=-1,
    xfer_src=-1, xfer_qdepth=-1, wf_id=-1, out_dataset=-1, cores=1,
)


def pad_jobs_capacity(jobs: JobsState, capacity: int) -> JobsState:
    """Grow a JobsState to ``capacity`` rows of inert padding (DONE/invalid,
    never arriving) — the shape canonicalization used by ragged scenario
    ensembles (``stack_scenarios``) and mesh sharding (``shard_jobs``)."""
    J = jobs.capacity
    if capacity == J:
        return jobs
    if capacity < J:
        raise ValueError(f"capacity {capacity} < current job capacity {J}")
    n = capacity - J

    def pad(name, x):
        fill = JOB_PAD_FILLS.get(name, 0)
        return jnp.pad(x, [(0, n)] + [(0, 0)] * (x.ndim - 1), constant_values=fill)

    return JobsState(**{k: pad(k, v) for k, v in jobs._asdict().items()})


def make_sites(
    *,
    cores,
    speed,
    memory,
    bw_in,
    bw_out,
    latency=None,
    par_gamma=None,
    fail_rate=None,
    capacity: int | None = None,
) -> SiteState:
    cores = jnp.asarray(cores, jnp.int32)
    n = cores.shape[0]
    cap = capacity or n

    def pad_f(x, fill=0.0):
        x = jnp.broadcast_to(jnp.asarray(x, jnp.float32), (n,))
        return jnp.pad(x, (0, cap - n), constant_values=fill)

    def pad_i(x, fill=0):
        x = jnp.broadcast_to(jnp.asarray(x, jnp.int32), (n,))
        return jnp.pad(x, (0, cap - n), constant_values=fill)

    if latency is None:
        latency = jnp.zeros((n,), jnp.float32)
    if par_gamma is None:
        par_gamma = jnp.zeros((n,), jnp.float32)
    if fail_rate is None:
        fail_rate = jnp.zeros((n,), jnp.float32)
    active = jnp.arange(cap) < n
    cores_p = pad_i(cores)
    mem_p = pad_f(memory)
    return SiteState(
        cores=cores_p,
        speed=pad_f(speed, 1.0),
        memory=mem_p,
        bw_in=pad_f(bw_in, 1.0),
        bw_out=pad_f(bw_out, 1.0),
        latency=pad_f(latency),
        par_gamma=pad_f(par_gamma),
        fail_rate=pad_f(fail_rate),
        active=active,
        free_cores=cores_p,
        free_memory=mem_p,
        n_assigned=jnp.zeros((cap,), jnp.int32),
        n_finished=jnp.zeros((cap,), jnp.int32),
        n_failed=jnp.zeros((cap,), jnp.int32),
    )


def make_log(rows: int, n_sites: int, extra: dict | None = None) -> EventLog:
    """Allocate the ring buffer.  ``extra`` maps subsystem column names to
    their time-zero row values; unwritten rows keep that initial value."""
    r = max(rows, 1)
    return EventLog(
        time=jnp.full((r,), jnp.nan, jnp.float32),
        round_idx=jnp.full((r,), -1, jnp.int32),
        counts=jnp.zeros((r, N_STATES), jnp.int32),
        n_started=jnp.zeros((r,), jnp.int32),
        n_completed=jnp.zeros((r,), jnp.int32),
        site_free=jnp.zeros((r, n_sites), jnp.int32),
        site_queued=jnp.zeros((r, n_sites), jnp.int32),
        site_running=jnp.zeros((r, n_sites), jnp.int32),
        extra={
            k: jnp.broadcast_to(jnp.asarray(v)[None], (r,) + jnp.asarray(v).shape)
            for k, v in (extra or {}).items()
        },
        cursor=jnp.zeros((), jnp.int32),
    )
