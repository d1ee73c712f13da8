"""Composable engine subsystems — the round loop as an ordered phase pipeline.

Three PRs of growth (data movement, availability, workflow DAGs) each wove
``if <flag>:`` blocks through ``engine.simulate`` plus lockstep edits to
``EngineState``, ``distributed``, ``events`` and ``monitor`` — exactly the
"hardwired algorithms" failure mode CGSim exists to fix.  This module turns
each capability into a ``Subsystem``: a static bundle of hook functions the
engine calls at fixed points of every event round, with all of the
subsystem's dynamic state living in one slot of the generic
``EngineState.ext`` mapping (a dict pytree keyed by subsystem name).

Static specialization (DESIGN.md §7): the subsystem tuple is a *static* jit
argument, so a run without a subsystem traces no trace of it — no ``lax.cond``
at runtime, no extra ops or RNG draws, bit-for-bit identical to an engine
that never knew the subsystem existed (the golden-trace matrix test pins all
8 on/off combinations of the built-in trio).

Hook protocol — every hook is optional (``None`` = not interested), takes the
subsystem itself first (so hooks can be module-level functions and the
``Subsystem`` stays hashable for jit caching), and reads/writes the mutable
trace-time ``RoundCtx``:

  phase (engine round)       | hook
  ---------------------------+------------------------------------------------
  0. pre-run (host)          | validate(sub, state0, jobs, sites)   may raise
  0. pre-run (traced)        | init(sub, state0, jobs, sites) -> ext
  1. clock min-reduction     | event_times(sub, ctx) -> f32[] next event time
     arrivability            | arrival_gate(sub, ctx) -> bool[J]  (also step 3)
  2. completions             | completion_filter(sub, ctx, comp) -> bool[J]
  2b/2c. post-completion     | on_completions(sub, ctx)      state transitions
  4. assignment              | pre_assign(sub, ctx)   feasibility/speed mods
  5b. starts                 | on_start(sub, ctx)     service-time adjustments
  6. event log               | log_columns(sub, ctx, write) -> {name: [S] col}
     (declaration)           | log_spec(sub, ext, jobs, sites) -> {name: [S]}
  end of run                 | finalize(sub, ext, jobs, sites, clock)
                             |   -> (ext, {SimResult field: value})
  capacity padding (host)    | pad_jobs(sub, state0, old_J, new_J) -> state0

Hooks fire in subsystem-tuple order within each phase; the canonical order
for the built-ins is (availability, workflow, data, transfers, faults), which
reproduces the hand-written engine exactly: outage preemption before
cascade-cancel, output materialization before replica-source selection,
stage-in pricing before transfer-queue diversion, and fault recovery last so
it observes every other subsystem's transitions (DESIGN.md §11, §13).
"""
from __future__ import annotations

import zlib
from typing import Any, Callable, NamedTuple

INF = float("inf")

# fold_in salt separating the subsystem key tree from the engine's own
# split(key, 4) stream (see RoundCtx.subkey)
_SUBKEY_SALT = 0x5B5D5


class Subsystem(NamedTuple):
    """Static hook bundle for one engine extension (see module docstring).

    ``config`` carries compile-time constants (e.g. the ``DataPolicy``); all
    run-time state lives in ``EngineState.ext[name]``.  Keep hooks
    module-level functions so two identically-configured subsystems compare
    equal and hit the same jit cache entry.
    """

    name: str
    config: Any = None
    init: Callable | None = None
    validate: Callable | None = None
    event_times: Callable | None = None
    arrival_gate: Callable | None = None
    completion_filter: Callable | None = None
    on_completions: Callable | None = None
    pre_assign: Callable | None = None
    on_start: Callable | None = None
    log_spec: Callable | None = None
    log_columns: Callable | None = None
    finalize: Callable | None = None
    pad_jobs: Callable | None = None


def make_subsystem(name: str, **hooks) -> Subsystem:
    """Convenience constructor: ``make_subsystem("scratch", on_start=f, ...)``."""
    return Subsystem(name=name, **hooks)


class RoundCtx:
    """Mutable trace-time context threaded through one engine round.

    This is *staging state*, not carried state: the engine rebuilds it every
    round from the ``EngineState`` pytree, hooks mutate it in place while the
    round body is traced, and the engine collects the mutated fields back
    into the next ``EngineState``.  Fields a hook may read/write:

      jobs, sites        current JobsState / SiteState (replace to transition)
      ext                dict name -> subsystem state (replace your slot)
      clock_prev, clock  round entry time / this round's event time
      comp, done_now, failed_now   completion masks (set by the engine, step 2)
      arrived            this round's arrival mask (engine, step 3)
      feasible           bool[J, S] assignment feasibility (AND your mask in);
                         sparse top-k mode (``simulate(topk=)``) carries a
                         broadcastable bool[1, S] site-level mask instead —
                         per-job feasibility lives in the candidate index
                         (DESIGN.md §12)
      start_cores        i32[S] cores the start phase may claim this round
      sites_serv         SiteState used for service-time pricing (speed mods)
      started, site_c, start_site   start-phase masks (engine, step 5)
      start_count        i32[S] jobs starting at each site (they share its
                         stage links: a row's share is ``start_count[site_c]``)
      t_serv             f32[J] service time of starting jobs (override/adjust;
                         only the ``started`` rows are read)
      progressed         OR in a bool[] if your transitions made progress
      scratch            per-round dict for passing values between your hooks
      max_retries, S, J  static knobs

    Stochastic subsystems draw randomness through ``subkey(name)`` — a
    per-round, per-subsystem PRNG stream folded off the engine's carry key
    *without consuming it*, so adding draws never perturbs the engine's own
    bitstream (failure sampling, policy keys) and existing runs stay
    bit-for-bit reproducible (ROADMAP: subsystem-level RNG streams).
    """

    def __init__(self, *, jobs, sites, ext, clock_prev, max_retries, rng=None):
        self.jobs = jobs
        self.sites = sites
        self.ext = ext
        self.clock_prev = clock_prev
        self.clock = clock_prev
        self.max_retries = max_retries
        self.rng = rng
        self.S = sites.capacity
        self.J = jobs.capacity
        self.comp = None
        self.done_now = None
        self.failed_now = None
        self.arrived = None
        self.feasible = None
        self.start_cores = None
        self.sites_serv = None
        self.started = None
        self.site_c = None
        self.start_site = None
        self.start_count = None
        self.t_serv = None
        self.progressed = False
        self.scratch = {}

    def subkey(self, name: str, salt: int = 0):
        """This round's PRNG key for subsystem ``name`` (salt for extra
        streams).  Derived by ``fold_in`` from the round's carry key — the
        engine splits that key separately, so drawing here adds no ops to and
        removes no draws from the engine's own stream: a subsystem that
        starts (or stops) consuming randomness leaves every other consumer's
        bitstream untouched.  Deterministic across runs: the stream depends
        only on (run key, round, subsystem name, salt)."""
        import jax

        if self.rng is None:
            raise ValueError("RoundCtx.subkey needs the engine round key (rng=)")
        key = jax.random.fold_in(self.rng, _SUBKEY_SALT)
        key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        return jax.random.fold_in(key, salt) if salt else key


SubsystemPair = tuple  # (Subsystem, initial state pytree)


def resolve_subsystems(
    *,
    data_policy=None,
    network=None,
    replicas=None,
    availability=None,
    workflow=None,
    transfers=None,
    faults=None,
    subsystems=(),
    jobs=None,
    sites=None,
    validate=True,
):
    """Normalize the engine's keyword API into ``(static tuple, ext0 dict)``.

    The legacy kwargs (``availability=``, ``workflow=``, ``data_policy=`` +
    ``network=``/``replicas=``, ``transfers=``, ``faults=``) map onto the
    built-in subsystems in canonical order — availability, workflow, data,
    transfers, faults — followed by any explicit
    ``subsystems=((Subsystem, state0), ...)`` pairs in caller order.
    Host-side ``validate`` hooks run here, before anything is traced.
    """
    pairs: list[tuple[Subsystem, Any]] = []
    if availability is not None:
        from .availability import availability_subsystem

        pairs.append((availability_subsystem(), availability))
    if workflow is not None:
        from .workflows import workflow_subsystem

        pairs.append((workflow_subsystem(), workflow))
    if data_policy is not None:
        if network is None or replicas is None:
            raise ValueError("data_policy requires both network= and replicas=")
        from .datapolicies import data_subsystem

        pairs.append((data_subsystem(data_policy), (network, replicas)))
    if transfers is not None:
        if data_policy is None:
            raise ValueError(
                "transfers= requires the data subsystem (data_policy= with "
                "network=/replicas=) — it owns the WAN matrices and catalog"
            )
        from .transfers import transfers_subsystem

        pairs.append((transfers_subsystem(), transfers))
    if faults is not None:
        from .faults import faults_subsystem

        # the static channel flags are derived host-side from the concrete
        # state here, before anything is traced (FaultsConfig docstring)
        pairs.append((faults_subsystem(faults), faults))
    for entry in subsystems:
        if isinstance(entry, Subsystem):
            raise TypeError(
                f"subsystems entries are (Subsystem, state0) pairs; got bare "
                f"Subsystem {entry.name!r} — pass ({entry.name}, state0)"
            )
        sub, state0 = entry
        pairs.append((sub, state0))

    names = [sub.name for sub, _ in pairs]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate subsystem names: {sorted(names)}")
    if validate:
        for sub, state0 in pairs:
            if sub.validate is not None:
                sub.validate(sub, state0, jobs, sites)
    return tuple(sub for sub, _ in pairs), {sub.name: state0 for sub, state0 in pairs}


def pad_ext_jobs(subsystems, ext: dict, old_capacity: int, new_capacity: int) -> dict:
    """Grow job-capacity-shaped subsystem state (host-side, for distributed
    padding) via each subsystem's ``pad_jobs`` hook — no per-subsystem code in
    the caller."""
    if new_capacity == old_capacity:
        return ext
    out = dict(ext)
    for sub in subsystems:
        if sub.pad_jobs is not None and sub.name in out:
            out[sub.name] = sub.pad_jobs(sub, out[sub.name], old_capacity, new_capacity)
    return out
