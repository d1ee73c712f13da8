"""Distributed simulation — beyond the paper's single-process scaling.

CGSim runs on one laptop core; its multi-site scaling is wall-time-linear in
sites.  Because our engine state is dense arrays, the *simulator itself*
shards: jobs over the ``data`` mesh axis (and calibration replicas over the
whole mesh).  We deliberately use pjit/SPMD rather than hand-rolled actors:
the engine body's min-reductions become ``all-reduce(min)``, the per-site
``segment_sum`` updates become scatter+``psum``, inserted by XLA.  The
collective schedule is inspected by the dry-run (EXPERIMENTS.md §Dry-run).

Sharding map:
  jobs.* [J]      -> P(axis)       one shard of jobs per device
  sites.* [S]     -> replicated    every device sees the whole grid
  scalars, rng    -> replicated

Ensemble (calibration) map:
  candidates [K,S] -> P(axis, None)  independent sims per device (no comms)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .engine import (
    Scenario,
    ScenarioBuckets,
    _run_buckets,
    _simulate,
    simulate,
    simulate_many,
    stack_scenarios,
)
from .telemetry import span
from .types import JobsState, SimResult, SiteState


def auto_mesh(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis ``Auto``.

    ``jax.make_mesh`` builds ``Explicit`` axes, under which every op must
    agree on its operands' shardings: the engine's start-order sort over a
    sharded job column and a replicated tiebreak, or a gather of replicated
    site rows by sharded job indices, then fails to trace.  Under ``Auto``
    axes XLA's partitioner chooses those shardings, so the engine runs with
    no mesh-specific code.  Every entry point below takes its mesh through
    here."""
    return Mesh(mesh.devices, mesh.axis_names, axis_types=(AxisType.Auto,) * mesh.devices.ndim)


def job_shardings(mesh: Mesh, axis: str, jobs: JobsState, sites: SiteState):
    """NamedShardings for (jobs, sites, rng) under job-parallel simulation."""
    jsh = jax.tree.map(lambda _: NamedSharding(mesh, P(axis)), jobs)
    ssh = jax.tree.map(lambda _: NamedSharding(mesh, P()), sites)
    return jsh, ssh, NamedSharding(mesh, P())


def shard_jobs(jobs: JobsState, sites: SiteState, mesh: Mesh, axis: str = "data"):
    """Place a workload on the mesh for job-parallel simulation.

    Pads the job capacity to a multiple of the axis size (padding rows are
    DONE/invalid so they never participate)."""
    n_dev = mesh.shape[axis]
    J = jobs.capacity
    pad = (-J) % n_dev
    if pad:
        from .types import pad_jobs_capacity

        jobs = pad_jobs_capacity(jobs, J + pad)
    jsh, ssh, _ = job_shardings(mesh, axis, jobs, sites)
    return jax.device_put(jobs, jsh), jax.device_put(sites, ssh)


def _prepare_subsystems(kw: dict, jobs, sites, mesh: Mesh, old_capacity: int) -> dict:
    """Normalize the subsystem kwargs into explicit ``(Subsystem, state)``
    pairs with state padded to the (possibly grown) job capacity and fully
    replicated on the mesh, mirroring ``sites``.  Subsystem state is
    read-only or all-reduced inside the round loop, so replication costs one
    copy — and the engine never sees a mesh-specific code path.

    Entirely generic: capacity padding goes through each subsystem's
    ``pad_jobs`` hook and replication is one ``tree.map`` over the whole ext
    mapping, so new subsystems distribute with zero code here."""
    from .subsystems import pad_ext_jobs, resolve_subsystems

    kw = dict(kw)
    subs, ext = resolve_subsystems(
        data_policy=kw.pop("data_policy", None),
        network=kw.pop("network", None),
        replicas=kw.pop("replicas", None),
        availability=kw.pop("availability", None),
        workflow=kw.pop("workflow", None),
        transfers=kw.pop("transfers", None),
        faults=kw.pop("faults", None),
        subsystems=kw.pop("subsystems", ()),
        jobs=jobs,
        sites=sites,
        validate=False,  # validated by simulate() against the padded shapes
    )
    ext = pad_ext_jobs(subs, ext, old_capacity, jobs.capacity)
    rep = NamedSharding(mesh, P())
    ext = jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), rep), ext)
    kw["subsystems"] = tuple((sub, ext[sub.name]) for sub in subs)
    return kw


def simulate_distributed(
    jobs: JobsState,
    sites: SiteState,
    policy,
    rng: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "data",
    **kw,
) -> SimResult:
    """Job-parallel simulation: identical semantics to ``engine.simulate``
    (same event rounds, same FIFO), with XLA SPMD distributing each round."""
    mesh = auto_mesh(mesh)
    jobs_d, sites_d = shard_jobs(jobs, sites, mesh, axis)
    kw = _prepare_subsystems(kw, jobs_d, sites_d, mesh, jobs.capacity)
    with jax.set_mesh(mesh):
        return simulate(jobs_d, sites_d, policy, rng, **kw)


def lower_distributed(
    jobs: JobsState,
    sites: SiteState,
    policy,
    mesh: Mesh,
    *,
    axis: str = "data",
    **kw,
):
    """Lower+compile the engine for a mesh from ShapeDtypeStructs only —
    the simulator's own multi-pod dry-run (no allocation)."""
    mesh = auto_mesh(mesh)
    jsh, ssh, rsh = job_shardings(mesh, axis, jobs, sites)
    jobs_s = jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), jobs, jsh)
    sites_s = jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), sites, ssh)
    rng_s = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rsh)

    def fn(j, s, r):
        return simulate(j, s, policy, r, **kw)

    with jax.set_mesh(mesh):
        lowered = jax.jit(fn).lower(jobs_s, sites_s, rng_s)
        return lowered, lowered.compile()


def simulate_ensemble_distributed(
    jobs: JobsState,
    sites: SiteState,
    policy,
    rng: jax.Array,
    speed_candidates: jax.Array,  # [K, S]
    mesh: Mesh,
    *,
    axis: str = "data",
    **kw,
) -> SimResult:
    """K independent sims (calibration ensemble), candidates sharded over the
    mesh axis — embarrassingly parallel, zero collectives in steady state."""
    mesh = auto_mesh(mesh)
    K = speed_candidates.shape[0]
    n_dev = mesh.shape[axis]
    if K % n_dev:
        raise ValueError(f"candidates {K} must divide over {n_dev} devices")
    cand = jax.device_put(speed_candidates, NamedSharding(mesh, P(axis, None)))
    keys = jax.device_put(jax.random.split(rng, K), NamedSharding(mesh, P(axis, None)))
    kw = _prepare_subsystems(kw, jobs, sites, mesh, jobs.capacity)

    def one(speed, key):
        return simulate(jobs, sites._replace(speed=speed), policy, key, **kw)

    with jax.set_mesh(mesh):
        return jax.vmap(one)(cand, keys)


# --------------------------------------------------------------------------
# sharded scenario ensembles: lock-step-free simulate_many (DESIGN.md §8)
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _sharded_ensemble_fn(policy, subsystems, mesh, axis, donate, lane_mode, kw_items):
    """Build (and cache) the jitted shard_map program for one ensemble
    configuration.  Caching on the static configuration keeps repeat calls on
    the jit fast path instead of retracing a fresh closure every time."""
    kw = dict(kw_items)

    def block(jobs, sites, ext, keys):
        # one device's lane block, free of *global* lock-step either way:
        #
        # - "scan": lanes run one after another, each in its own solo
        #   while_loop — zero lock-step even inside the block, and the
        #   phase-skip guard fires per lane.  The right mode when lanes
        #   don't vectorize (CPU hosts: a batched round costs ~K solo
        #   rounds, so retiring lanes independently strictly wins).
        # - "vmap": lanes batch SIMD-style; the block's while_loop halts
        #   when the *local* lanes drain and the phase-skip batch-any
        #   reduces over the block alone.  The right mode on accelerators,
        #   where a batched round is far cheaper than K solo rounds.
        def one(j, s, e, k):
            return _simulate(j, s, policy, k, e, subsystems=subsystems, **kw)

        if lane_mode == "scan":
            def step(carry, x):
                return carry, one(*x)

            _, res = jax.lax.scan(step, None, (jobs, sites, ext, keys))
            return res
        return jax.vmap(one)(jobs, sites, ext, keys)

    fn = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    # the stacked lane buffers are device_put copies owned by the caller
    # below, so they are donated into the program: XLA aliases them straight
    # into the while-loop carry instead of defensively copying K-lane state
    return jax.jit(fn, donate_argnums=(0, 1, 2, 3) if donate else ())


def _sharded_stacked(
    scenarios: Scenario,
    keys: jax.Array,
    policy,
    mesh: Mesh,
    axis: str,
    subsystems: tuple,
    donate: bool | None,
    lane_mode: str,
    kw: dict,
) -> SimResult:
    from .engine import _check_ensemble

    if lane_mode == "auto":
        # scan lanes where batching doesn't pay (CPU), vectorize where it
        # does (accelerators) — both are bit-for-bit identical per lane
        lane_mode = "scan" if jax.default_backend() == "cpu" else "vmap"
    if lane_mode not in ("scan", "vmap"):
        raise ValueError(f"lane_mode must be auto|scan|vmap, got {lane_mode!r}")
    ext = _check_ensemble(scenarios, subsystems)
    scenarios = Scenario(scenarios.jobs, scenarios.sites, ext)
    K = scenarios.jobs.arrival.shape[0]
    n_dev = mesh.shape[axis]
    pad = (-K) % n_dev
    if pad:
        # round the lane count up to the mesh axis: repeat the last scenario
        # into throwaway lanes (their results are sliced off below)
        pad_ix = jnp.concatenate(
            [jnp.arange(K), jnp.full((pad,), K - 1, jnp.int32)]
        )
        scenarios = jax.tree.map(lambda x: x[pad_ix], scenarios)
        keys = keys[pad_ix]
    if donate is None:
        # on a 1-device mesh the device_put below can alias the caller's
        # arrays instead of resharding, so donation is only safe (and only
        # useful) when the lanes actually spread over the mesh
        donate = mesh.devices.size > 1
    sh = NamedSharding(mesh, P(axis))
    if donate:
        # inputs already laid out on the mesh pass through device_put
        # untouched — donating would hand the *caller's* buffers to XLA and
        # invalidate them for the next call, so fall back to non-donating
        leaves = jax.tree.leaves((scenarios, keys))
        if any(isinstance(x, jax.Array) and x.sharding.is_equivalent_to(sh, x.ndim)
               for x in leaves):
            donate = False
    args = jax.tree.map(
        lambda x: jax.device_put(jnp.asarray(x), sh),
        (scenarios.jobs, scenarios.sites, scenarios.ext, keys),
    )
    fn = _sharded_ensemble_fn(
        policy, tuple(subsystems), mesh, axis, donate, lane_mode,
        tuple(sorted(kw.items())),
    )
    with jax.set_mesh(mesh):
        res = fn(*args)
    if pad:
        res = jax.tree.map(lambda x: x[:K], res)
    return res


def simulate_many_sharded(
    scenarios,
    policy,
    rng: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "data",
    subsystems: tuple = (),
    donate: bool | None = None,
    lane_mode: str = "auto",
    recorder=None,
    **kw,
) -> SimResult:
    """Lock-step-free ensemble execution: the stacked scenario axis K is
    partitioned over ``mesh[axis]`` with ``shard_map``, and every device runs
    its *own* ``lax.while_loop`` over its lane block.

    This attacks the ensemble lock-step tax at the shard level (DESIGN.md
    §8): under plain ``simulate_many`` all K lanes spin until the slowest
    scenario terminates, paying full round work per lane per round; here a
    shard whose scenarios drain early simply stops.  There are no cross-
    device collectives — each lane's state is fully local to its device — so
    scaling is near-linear in devices (``benchmarks/bench_engine_rounds
    --devices``).  Lane results are bit-for-bit identical to plain
    ``simulate_many`` and to solo ``simulate`` runs: sharding only changes
    *which* device retires a lane's rounds, never the rounds themselves.

    ``scenarios`` is a list of ``Scenario``s, a stacked ``Scenario``, or a
    ``ScenarioBuckets`` (each bucket is sharded separately and results merge
    in original order).  Lane counts that do not divide the mesh axis are
    padded with throwaway repeats of the last lane.  ``donate`` controls
    donating the on-mesh lane buffers into the program (default: on for
    multi-device meshes).  ``lane_mode`` picks how a device walks its lane
    block: ``"scan"`` (sequential solo loops — zero lock-step, the CPU
    default) or ``"vmap"`` (SIMD batching — the accelerator default);
    ``"auto"`` resolves by backend.

    Stacking and the run are ``ensemble_stack`` and ``ensemble_run`` spans
    (``telemetry.span``).  Pass a ``telemetry.TraceRecorder`` as ``recorder``
    to time them (the run span then waits for the result) and, for bucketed
    input, to note the padding-waste breakdown from
    ``ScenarioBuckets.padding_stats``.
    """
    mesh = auto_mesh(mesh)
    runner = lambda scen, keys: _sharded_stacked(  # noqa: E731
        scen, keys, policy, mesh, axis, subsystems, donate, lane_mode, kw
    )
    if not isinstance(scenarios, (Scenario, ScenarioBuckets)):
        with span("ensemble_stack", recorder):
            scenarios = stack_scenarios(scenarios, subsystems=subsystems)
    if recorder is not None and isinstance(scenarios, ScenarioBuckets):
        recorder.note("bucket_padding", scenarios.padding_stats())
    with span("ensemble_run", recorder):
        if isinstance(scenarios, ScenarioBuckets):
            res = _run_buckets(scenarios, rng, runner, subsystems)
        else:
            K = scenarios.jobs.arrival.shape[0]
            res = runner(scenarios, jax.random.split(rng, K))
        if recorder is not None:
            jax.block_until_ready(res)
    return res


def simulate_population(
    scenarios,
    policy,
    rng: jax.Array,
    *,
    mesh: Mesh | None = None,
    axis: str = "data",
    subsystems: tuple = (),
    **kw,
) -> SimResult:
    """One entry point for candidate-population ensembles (calibration lanes).

    A calibration step evaluates a whole candidate population as ensemble
    lanes; whether those lanes run on one device (``simulate_many``) or
    spread over a mesh (``simulate_many_sharded``) is a deployment detail the
    optimizer should not care about.  ``mesh=None`` takes the single-device
    vmapped path; a mesh takes the lock-step-free sharded path (lane counts
    that do not divide the mesh are padded with repeats, results unpadded).
    Lane ``i`` draws ``split(rng, K)[i]`` on both paths, so results are
    bit-for-bit identical across deployments and to solo ``simulate`` runs.
    """
    if mesh is None:
        return simulate_many(scenarios, policy, rng, subsystems=subsystems, **kw)
    return simulate_many_sharded(
        scenarios, policy, rng, mesh, axis=axis, subsystems=subsystems, **kw
    )
