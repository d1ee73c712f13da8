"""The round loop's phase scopes (``engine.PHASES``): every equation of the
loop's ``body`` and ``cond`` sits in exactly one phase, and a subsystem
hook's equations also carry the subsystem's name.

Equations of nested jaxprs (``cond`` branches, custom-vmap calls) are traced
with a fresh name stack; XLA's ``op_name`` joins it to the enclosing
equation's, so the innermost phase on the joined path is an op's phase."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    atlas_like_platform,
    get_data_policy,
    get_policy,
    make_replicas,
    synthetic_panda_jobs,
    uniform_network,
    zipf_dataset_sizes,
)
from repro.core.engine import PHASES, _init_state, _round_fns
from repro.core.subsystems import Subsystem, resolve_subsystems

# each hook of the probe subsystem emits one primitive the engine never
# uses, so its equations can be found; the phase each must land in
PROBE_PHASES = {
    "sin": {"clock"},                    # event_times
    "cos": {"clock", "completions"},     # arrival_gate: the clock, then arrivals
    "tan": {"completions"},              # completion_filter
    "sinh": {"completions"},             # on_completions
    "cosh": {"score"},                   # pre_assign
    "atan": {"start"},                   # on_start
    "asinh": {"bookkeeping"},            # log_columns
}


def _probe_subsystem() -> Subsystem:
    def event_times(sub, ctx):
        return jnp.sin(ctx.clock_prev)

    def arrival_gate(sub, ctx):
        return jnp.cos(ctx.jobs.arrival) < 2.0

    def completion_filter(sub, ctx, comp):
        return comp & (jnp.tan(ctx.jobs.t_finish) < jnp.inf)

    def on_completions(sub, ctx):
        ctx.progressed = ctx.progressed | (jnp.sinh(ctx.clock) > jnp.inf)

    def pre_assign(sub, ctx):
        ctx.start_cores = ctx.start_cores + (jnp.cosh(ctx.clock) < 0).astype(jnp.int32)

    def on_start(sub, ctx):
        ctx.t_serv = ctx.t_serv + 0.0 * jnp.atan(ctx.t_serv)

    def log_spec(sub, st, jobs, sites):
        return {"probe": jnp.zeros((sites.capacity,), jnp.float32)}

    def log_columns(sub, ctx, write):
        return {"probe": jnp.asinh(ctx.sites.free_cores.astype(jnp.float32))}

    return Subsystem(
        name="probe", event_times=event_times, arrival_gate=arrival_gate,
        completion_filter=completion_filter, on_completions=on_completions,
        pre_assign=pre_assign, on_start=on_start, log_spec=log_spec,
        log_columns=log_columns,
    )


def _loop(probe: bool, **kw):
    n_ds = 6
    jobs = synthetic_panda_jobs(40, seed=11, duration=900.0, n_datasets=n_ds)
    sites = atlas_like_platform(4, seed=12, fail_rate=0.05)
    replicas = make_replicas(
        zipf_dataset_sizes(n_ds, seed=3, mean_bytes=2e9),
        disk_capacity=np.array([1e13, 6e9, 6e9, 6e9]),
        origin=np.zeros(n_ds, np.int32),
    )
    subs, ext0 = resolve_subsystems(
        data_policy=get_data_policy("cache_on_read"),
        network=uniform_network(4, bw=5e8, latency=0.05), replicas=replicas,
        subsystems=((_probe_subsystem(), jnp.zeros(())),) if probe else (),
        jobs=jobs, sites=sites,
    )
    pol = get_policy("panda_dispatch")
    topk = kw.get("topk")
    st0 = _init_state(jobs, sites, pol, jax.random.PRNGKey(0), ext0, subs, 8, topk)
    cond, body = _round_fns(pol, subs, max_rounds=1000, log_rows=8, max_retries=3,
                            monitor_every=2, quantum=0.0, **kw)
    return (jax.make_jaxpr(body)(st0).jaxpr,
            jax.make_jaxpr(cond)(st0, jnp.float32(1e9)).jaxpr)


def _walk(jaxpr, prefix=(), depth=0):
    """``(equation, joined name-stack path, own path, depth)`` over every
    equation, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        own = tuple(s for s in str(eqn.source_info.name_stack).split("/") if s)
        path = prefix + own
        yield eqn, path, own, depth
        for sub in jax.core.jaxprs_in_params(eqn.params):
            # XLA names a nested op ``<eqn path>/<primitive>/<branch>/<own path>``;
            # the primitive and branch names are never phase names
            yield from _walk(sub, path, depth + 1)


def _phases(path):
    return [s for s in path if s in PHASES]


CONFIGS = {
    "dense": dict(phase_skip=True),
    "dense_no_phase_skip": dict(phase_skip=False),
    "topk": dict(phase_skip=True, topk=2, topk_refresh=3),
}


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_every_equation_in_one_phase(config):
    body, cond = _loop(probe=True, **CONFIGS[config])
    seen = set()
    for jaxpr, allowed in ((body, set(PHASES)), (cond, {"clock"})):
        for eqn, path, own, depth in _walk(jaxpr):
            if depth == 0:
                assert len(_phases(own)) == 1, (eqn.primitive, own)
            else:
                # a nested equation adds at most one phase of its own
                assert len(_phases(own)) <= 1, (eqn.primitive, own)
            phases = _phases(path)
            assert phases and phases[-1] in allowed, (eqn.primitive, path)
            seen.add(phases[-1])
            if "data" in path:
                # the data subsystem prices stage-in at start and writes
                # its log columns in bookkeeping
                assert phases[-1] in {"start", "bookkeeping"}, path
    assert seen == set(PHASES)


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_hook_equations_carry_their_subsystem(config):
    body, cond = _loop(probe=True, **CONFIGS[config])
    found = {p: set() for p in PROBE_PHASES}
    for eqn, path, _, _ in _walk(body):
        name = eqn.primitive.name
        if name in PROBE_PHASES:
            assert "probe" in path, (name, path)
            found[name].add(_phases(path)[-1])
    assert found == PROBE_PHASES
    # the engine itself emits none of the probe's primitives
    plain, _ = _loop(probe=False, **CONFIGS[config])
    assert not {e.primitive.name for e, *_ in _walk(plain)} & set(PROBE_PHASES)
    assert any("data" in path for _, path, _, _ in _walk(plain))
