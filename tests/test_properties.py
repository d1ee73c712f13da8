"""Hypothesis property tests on simulator invariants.

The conservation-law harness at the bottom is the engine-invariant contract
(ISSUE 2): for random workloads and scenarios — with and without availability
calendars and data policies — every valid job terminates, site resources
return to their initial values, storage stays within capacity, and the
per-site counters exactly account for every attempt.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need the 'dev' extra")
from hypothesis import given, settings, strategies as st

from repro.core import (
    CANCELLED,
    DONE,
    FAILED,
    catalog_invariants,
    get_data_policy,
    get_policy,
    make_availability,
    make_jobs,
    make_replicas,
    make_sites,
    make_transfers,
    make_workflow,
    simulate,
    uniform_network,
    zipf_dataset_sizes,
)
from repro.core.events import transition_rows
from repro.core.monitor import link_occupancy_timeline, transfer_queue_timeline

POLICIES = ["random", "round_robin", "least_loaded", "shortest_wait", "panda_dispatch"]


def build(n_jobs, n_sites, seed, multicore_frac, policy):
    rng = np.random.default_rng(seed)
    cores = np.where(rng.random(n_jobs) < multicore_frac, 8, 1)
    jobs = make_jobs(
        job_id=np.arange(n_jobs),
        arrival=np.sort(rng.uniform(0, 100.0, n_jobs)),
        work=rng.lognormal(np.log(500.0), 1.0, n_jobs),
        cores=cores,
        memory=np.where(cores > 1, 16.0, 2.0),
        bytes_in=rng.lognormal(np.log(1e8), 1.0, n_jobs),
        bytes_out=rng.lognormal(np.log(1e7), 1.0, n_jobs),
    )
    sites = make_sites(
        cores=rng.integers(8, 64, n_sites),
        speed=rng.uniform(1.0, 30.0, n_sites),
        memory=rng.uniform(64.0, 512.0, n_sites),
        bw_in=rng.uniform(1e8, 1e10, n_sites),
        bw_out=rng.uniform(1e8, 1e10, n_sites),
    )
    return simulate(jobs, sites, get_policy(policy), jax.random.PRNGKey(seed))


@settings(max_examples=12, deadline=None)
@given(
    n_jobs=st.integers(5, 80),
    n_sites=st.integers(1, 8),
    seed=st.integers(0, 2**16),
    multicore_frac=st.floats(0.0, 1.0),
    policy=st.sampled_from(POLICIES),
)
def test_conservation_and_timestamps(n_jobs, n_sites, seed, multicore_frac, policy):
    res = build(n_jobs, n_sites, seed, multicore_frac, policy)
    jobs = res.jobs
    valid = np.asarray(jobs.valid)
    state = np.asarray(jobs.state)[valid]
    # conservation: every valid job terminates (sites are always feasible here)
    assert np.isin(state, [DONE, FAILED]).all()
    # timestamp ordering: arrival <= assign <= start <= finish
    a = np.asarray(jobs.arrival)[valid]
    g = np.asarray(jobs.t_assign)[valid]
    s = np.asarray(jobs.t_start)[valid]
    f = np.asarray(jobs.t_finish)[valid]
    assert (a <= g + 1e-5).all()
    assert (g <= s + 1e-5).all()
    assert (s < f).all()


@settings(max_examples=8, deadline=None)
@given(
    n_jobs=st.integers(10, 60),
    n_sites=st.integers(1, 6),
    seed=st.integers(0, 2**16),
    policy=st.sampled_from(POLICIES),
)
def test_capacity_never_exceeded(n_jobs, n_sites, seed, policy):
    res = build(n_jobs, n_sites, seed, 0.5, policy)
    # replaying the transition stream keeps available cores non-negative
    rows = transition_rows(res)
    assert min((r["avail_cores"] for r in rows), default=0) >= 0


@settings(max_examples=8, deadline=None)
@given(n_jobs=st.integers(5, 40), seed=st.integers(0, 2**16))
def test_single_core_fifo_order(n_jobs, seed):
    """Equal-priority single-core jobs on one site start in arrival order."""
    rng = np.random.default_rng(seed)
    jobs = make_jobs(
        job_id=np.arange(n_jobs),
        arrival=np.sort(rng.uniform(0, 10.0, n_jobs)),
        work=rng.uniform(10.0, 100.0, n_jobs),
        cores=np.ones(n_jobs),
        memory=np.ones(n_jobs),
        bytes_in=np.zeros(n_jobs),
        bytes_out=np.zeros(n_jobs),
    )
    sites = make_sites(cores=[2], speed=[10.0], memory=[1e6], bw_in=[1e12], bw_out=[1e12])
    res = simulate(jobs, sites, get_policy("fastest_site"), jax.random.PRNGKey(0))
    starts = np.asarray(res.jobs.t_start)[:n_jobs]
    # arrival order == start order (ties broken by id which follows arrival)
    assert (np.diff(starts) >= -1e-5).all()


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**16), frac=st.floats(0.05, 0.95))
def test_determinism_same_key(seed, frac):
    r1 = build(30, 3, seed, frac, "panda_dispatch")
    r2 = build(30, 3, seed, frac, "panda_dispatch")
    np.testing.assert_array_equal(np.asarray(r1.jobs.t_start), np.asarray(r2.jobs.t_start))
    assert float(r1.makespan) == float(r2.makespan)


# --------------------------------------------------------------------------
# engine-invariant conservation laws (ISSUE 2 harness)
# --------------------------------------------------------------------------

N_SITES = 4  # fixed shape: hypothesis varies values, not compile shapes


def build_scenario(n_jobs, seed, policy, *, fail_rate, with_avail, with_data,
                   with_transfers=False, max_active=2, **sim_kw):
    """Random-but-terminating scenario: sites always feasible, every outage
    window finite, so each valid job must end DONE or FAILED."""
    rng = np.random.default_rng(seed)
    cores = np.where(rng.random(n_jobs) < 0.4, 8, 1)
    jobs = make_jobs(
        job_id=np.arange(n_jobs),
        arrival=np.sort(rng.uniform(0, 100.0, n_jobs)),
        work=rng.lognormal(np.log(400.0), 1.0, n_jobs),
        cores=cores,
        memory=np.where(cores > 1, 16.0, 2.0),
        bytes_in=rng.lognormal(np.log(1e8), 1.0, n_jobs),
        bytes_out=rng.lognormal(np.log(1e7), 1.0, n_jobs),
        dataset=rng.integers(0, 8, n_jobs) if with_data else None,
        capacity=n_jobs + 3,  # padding rows must stay inert
    )
    sites = make_sites(
        cores=rng.integers(8, 48, N_SITES),
        speed=rng.uniform(2.0, 20.0, N_SITES),
        memory=rng.uniform(64.0, 256.0, N_SITES),
        bw_in=rng.uniform(1e8, 1e10, N_SITES),
        bw_out=rng.uniform(1e8, 1e10, N_SITES),
        fail_rate=np.full(N_SITES, fail_rate),
    )
    kw = {}
    if with_avail:
        windows = []
        for s in range(N_SITES - 1):  # keep one site clean so work always drains
            for _ in range(int(rng.integers(0, 3))):
                t0 = float(rng.uniform(0.0, 400.0))
                windows.append(
                    dict(
                        site=s,
                        start=t0,
                        end=t0 + float(rng.uniform(20.0, 300.0)),
                        factor=float(rng.choice([0.0, 0.0, 0.5])),
                        preempt=bool(rng.random() < 0.7),
                    )
                )
        kw["availability"] = make_availability(N_SITES, windows)
    if with_data:
        kw["data_policy"] = get_data_policy("cache_on_read")
        kw["network"] = uniform_network(N_SITES, bw=1e9, latency=0.01)
        # site 0 is the data lake holding every origin; the rest run tight
        # caches (~2 datasets) so insertion/eviction churns under load
        kw["replicas"] = make_replicas(
            zipf_dataset_sizes(8, seed=seed % 1000, mean_bytes=1e9),
            disk_capacity=np.array([1e12] + [2.5e9] * (N_SITES - 1)),
            origin=np.zeros(8, np.int32),
        )
    if with_transfers:
        kw["transfers"] = make_transfers(N_SITES, n_jobs + 3, max_active=max_active)
    res = simulate(jobs, sites, get_policy(policy), jax.random.PRNGKey(seed), **kw, **sim_kw)
    return res, jobs, sites, kw


def assert_conservation_laws(res, jobs0, sites0):
    valid = np.asarray(res.jobs.valid)
    state = np.asarray(res.jobs.state)[valid]
    # 1. termination: every valid job ends DONE or FAILED
    assert np.isin(state, [DONE, FAILED]).all()
    # padding rows never move
    assert (np.asarray(res.jobs.state)[~valid] == DONE).all()
    assert not np.isfinite(np.asarray(res.jobs.t_start)[~valid]).any()
    # 2. resources return to initial values
    np.testing.assert_array_equal(
        np.asarray(res.sites.free_cores), np.asarray(sites0.cores)
    )
    np.testing.assert_allclose(
        np.asarray(res.sites.free_memory), np.asarray(sites0.memory), rtol=1e-4, atol=1e-2
    )
    # 3. per-site counters account for every attempt exactly:
    #    finishes == DONE jobs; every unsuccessful attempt is a machine
    #    failure or a preemption; each one is a resubmission or terminal
    n_done = int((state == DONE).sum())
    n_term_failed = int((state == FAILED).sum())
    retries = int(np.asarray(res.jobs.retries)[valid].sum())
    n_pre = int(np.asarray(res.avail.n_preempted).sum()) if res.avail is not None else 0
    assert int(np.asarray(res.sites.n_finished).sum()) == n_done
    assert int(np.asarray(res.sites.n_failed).sum()) + n_pre == retries + n_term_failed
    if res.avail is not None:
        assert n_pre == int(np.asarray(res.jobs.preempted)[valid].sum())
    # 4. storage never exceeds capacity
    if res.replicas is not None:
        inv = catalog_invariants(res.replicas)
        assert inv["capacity_ok"] and inv["accounting_ok"] and inv["origins_ok"]
    # 5. timestamps stay ordered for every terminal job
    a = np.asarray(res.jobs.arrival)[valid]
    s = np.asarray(res.jobs.t_start)[valid]
    f = np.asarray(res.jobs.t_finish)[valid]
    assert (a <= s + 1e-5).all() and (s < f).all()


@settings(max_examples=8, deadline=None)
@given(
    n_jobs=st.integers(10, 60),
    seed=st.integers(0, 2**16),
    fail_rate=st.sampled_from([0.0, 0.3]),
    policy=st.sampled_from(POLICIES),
)
def test_conservation_laws_plain(n_jobs, seed, fail_rate, policy):
    res, jobs0, sites0, _ = build_scenario(
        n_jobs, seed, policy, fail_rate=fail_rate, with_avail=False, with_data=False
    )
    assert_conservation_laws(res, jobs0, sites0)


@settings(max_examples=8, deadline=None)
@given(
    n_jobs=st.integers(10, 60),
    seed=st.integers(0, 2**16),
    fail_rate=st.sampled_from([0.0, 0.2]),
    policy=st.sampled_from(["round_robin", "least_loaded", "panda_dispatch"]),
)
def test_conservation_laws_with_availability(n_jobs, seed, fail_rate, policy):
    res, jobs0, sites0, _ = build_scenario(
        n_jobs, seed, policy, fail_rate=fail_rate, with_avail=True, with_data=False
    )
    assert_conservation_laws(res, jobs0, sites0)


@settings(max_examples=6, deadline=None)
@given(
    n_jobs=st.integers(10, 48),
    seed=st.integers(0, 2**16),
    with_avail=st.booleans(),
)
def test_conservation_laws_with_data_policy(n_jobs, seed, with_avail):
    res, jobs0, sites0, _ = build_scenario(
        n_jobs, seed, "round_robin", fail_rate=0.1, with_avail=with_avail, with_data=True
    )
    assert_conservation_laws(res, jobs0, sites0)


_XFER_LOG_ROWS = 4096  # plenty: rounds ~ O(jobs * retries), far below this


@settings(max_examples=6, deadline=None)
@given(
    n_jobs=st.integers(10, 48),
    seed=st.integers(0, 2**16),
    cap=st.integers(1, 4),
    fail_rate=st.sampled_from([0.0, 0.2]),
    with_avail=st.booleans(),
)
def test_transfer_conservation_laws(n_jobs, seed, cap, fail_rate, with_avail):
    """Transfer-queue invariants (ISSUE 8): every enqueue is accounted as a
    completion or a cancellation (in flows and in bytes), no more transfers
    waited for a slot than were enqueued, queues fully drain by termination,
    and per-link occupancy never exceeds the cap at any logged round."""
    res, jobs0, sites0, kw = build_scenario(
        n_jobs, seed, "least_loaded", fail_rate=fail_rate,
        with_avail=with_avail, with_data=True, with_transfers=True,
        max_active=cap, log_rows=_XFER_LOG_ROWS,
    )
    assert_conservation_laws(res, jobs0, sites0)

    ts = res.ext["transfers"]
    n_enq = int(ts.n_enq)
    n_done = int(ts.n_done)
    n_cancel = int(ts.n_cancel)
    # flow accounting: enqueues == completions + cancellations
    assert n_enq == n_done + n_cancel
    assert 0 <= int(ts.n_waited) <= n_enq
    np.testing.assert_allclose(
        float(ts.bytes_enq), float(ts.bytes_done) + float(ts.bytes_cancel), rtol=1e-4
    )
    # without failures or outages nothing ever interrupts a staging job
    if fail_rate == 0.0 and not with_avail:
        assert n_cancel == 0
    # queues drain: no transfer left queued or active, all slots released
    assert (np.asarray(ts.stat) == 0).all()
    assert (np.asarray(ts.active) == 0).all()
    assert (transfer_queue_timeline(res)[-1] == 0).all()  # the last round's queues
    # per-link occupancy respects the cap at every logged round; the log
    # ring did not wrap, so this covers the whole run
    assert int(np.asarray(res.log.cursor)) <= _XFER_LOG_ROWS
    occ = link_occupancy_timeline(res)
    caps = np.asarray(ts.cap, dtype=np.float64).reshape(N_SITES, N_SITES)
    assert (occ <= caps[None, :, :] + 1e-9).all()
    # DONE jobs that actually moved bytes carry a finite, non-negative wait
    valid = np.asarray(res.jobs.valid)
    moved = valid & (np.asarray(res.jobs.state) == DONE) & (
        np.asarray(res.jobs.xfer_bytes) > 0
    )
    waits = np.asarray(res.jobs.xfer_wait)[moved]
    assert np.isfinite(waits).all() and (waits >= 0.0).all()


# --------------------------------------------------------------------------
# fault-injection conservation laws (ISSUE 10): the attempt ledger extended
# by walltime kills, the transfer ledger extended by injected failures, and
# every backed-off job still terminating
# --------------------------------------------------------------------------
from repro.core import make_faults  # noqa: E402


def assert_fault_laws(res, jobs0, sites0):
    """The ISSUE-2 laws restated for runs with the faults subsystem on:
    walltime kills join preemptions on the unsuccessful-attempt side, and
    injected transfer failures join the FTS ledger."""
    valid = np.asarray(res.jobs.valid)
    state = np.asarray(res.jobs.state)[valid]
    fs = res.ext["faults"]
    # 1. termination — backed-off and killed jobs still drain
    assert np.isin(state, [DONE, FAILED]).all()
    assert (np.asarray(res.jobs.state)[~valid] == DONE).all()
    # 2. resources restored
    np.testing.assert_array_equal(
        np.asarray(res.sites.free_cores), np.asarray(sites0.cores)
    )
    np.testing.assert_allclose(
        np.asarray(res.sites.free_memory), np.asarray(sites0.memory), rtol=1e-4, atol=1e-2
    )
    # 3. attempt ledger: every unsuccessful attempt is a machine failure, an
    #    outage preemption, or a walltime kill — each a resubmission or
    #    terminal; kills and preemptions share the per-job preempted counter
    n_term_failed = int((state == FAILED).sum())
    retries = int(np.asarray(res.jobs.retries)[valid].sum())
    n_pre = int(np.asarray(res.avail.n_preempted).sum()) if res.avail is not None else 0
    n_kills = int(fs.n_kills)
    assert int((state == DONE).sum()) == int(np.asarray(res.sites.n_finished).sum())
    assert (
        int(np.asarray(res.sites.n_failed).sum()) + n_pre + n_kills
        == retries + n_term_failed
    )
    assert n_pre + n_kills == int(np.asarray(res.jobs.preempted)[valid].sum())
    # 4. transfer ledger extended by injected failures; queues drained and no
    #    backoff retry left pending
    ts = (res.ext or {}).get("transfers")
    if ts is not None:
        assert int(ts.n_enq) == int(ts.n_done) + int(ts.n_cancel) + int(fs.n_xfer_fail)
        assert (np.asarray(ts.stat) == 0).all()
        assert (np.asarray(ts.active) == 0).all()
    assert not np.isfinite(np.asarray(fs.retry_at)).any()
    # 5. loss calendar applied up to the horizon (events after the last
    #    finish never fire — the engine stops with the work); catalog exact
    lt = np.asarray(fs.loss_t)
    assert np.asarray(fs.loss_done)[np.isfinite(lt) & (lt < float(res.makespan))].all()
    if res.replicas is not None:
        inv = catalog_invariants(res.replicas)
        assert inv["capacity_ok"] and inv["accounting_ok"] and inv["origins_ok"]
    # 6. timestamps ordered against the (possibly backoff-pushed) arrival
    a = np.asarray(res.jobs.arrival)[valid]
    s = np.asarray(res.jobs.t_start)[valid]
    f = np.asarray(res.jobs.t_finish)[valid]
    assert (a <= s + 1e-5).all() and (s < f).all()


@settings(max_examples=6, deadline=None)
@given(
    n_jobs=st.integers(10, 40),
    seed=st.integers(0, 2**16),
    link_p=st.sampled_from([0.0, 0.3]),
    job_backoff=st.sampled_from([0.0, 50.0]),
    walltime=st.sampled_from([np.inf, 1500.0]),
    with_blacklist=st.booleans(),
)
def test_fault_conservation_laws(n_jobs, seed, link_p, job_backoff, walltime,
                                 with_blacklist):
    """All five subsystems on (availability + workflow via the DAG-free
    degenerate case is covered elsewhere; here: avail + data + transfers +
    faults) with every fault channel randomly armed."""
    fl = make_faults(
        N_SITES, n_jobs + 3,
        link_fail_p=link_p, xfer_backoff=40.0, max_xfer_attempts=3,
        job_backoff=job_backoff, walltime=float(walltime),
        replica_loss=[(200.0, 1, 1), (600.0, 3, 2)],
        blacklist_threshold=0.7 if with_blacklist else None,
        blacklist_alpha=0.5, blacklist_cooldown=300.0,
    )
    res, jobs0, sites0, _ = build_scenario(
        n_jobs, seed, "least_loaded", fail_rate=0.15,
        with_avail=True, with_data=True, with_transfers=True,
        faults=fl,
    )
    assert_fault_laws(res, jobs0, sites0)


# --------------------------------------------------------------------------
# subsystem-API equivalence (ISSUE 4): the legacy kwargs surface and an
# explicit subsystems=(...) tuple are the same engine, bit for bit
# --------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(
    n_jobs=st.integers(10, 48),
    seed=st.integers(0, 2**16),
    with_avail=st.booleans(),
    with_data=st.booleans(),
)
def test_kwargs_and_subsystems_tuple_identical(n_jobs, seed, with_avail, with_data):
    """Running the same seed through ``availability=``/``data_policy=`` kwargs
    and through an explicit ``subsystems=((Subsystem, state0), ...)`` tuple
    must produce identical ``SimResult`` pytrees — same leaves, same
    treedef — so the kwargs surface is provably sugar over the protocol."""
    from repro.core import availability_subsystem, data_subsystem

    res1, jobs, sites, kw = build_scenario(
        n_jobs, seed, "panda_dispatch", fail_rate=0.1,
        with_avail=with_avail, with_data=with_data,
    )
    # attach the exact same state objects explicitly, in canonical order
    pairs = []
    if with_avail:
        pairs.append((availability_subsystem(), kw["availability"]))
    if with_data:
        pairs.append(
            (data_subsystem(kw["data_policy"]), (kw["network"], kw["replicas"]))
        )
    res2 = simulate(
        jobs, sites, get_policy("panda_dispatch"), jax.random.PRNGKey(seed),
        subsystems=tuple(pairs),
    )
    leaves1, tree1 = jax.tree.flatten(res1)
    leaves2, tree2 = jax.tree.flatten(res2)
    assert tree1 == tree2
    for a, b in zip(leaves1, leaves2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=8, deadline=None)
@given(
    n_jobs=st.integers(10, 48),
    seed=st.integers(0, 2**16),
    fail_rate=st.floats(0.0, 0.3),
    with_avail=st.booleans(),
    with_data=st.booleans(),
    policy=st.sampled_from(POLICIES),
)
def test_phase_skip_guard_identical(n_jobs, seed, fail_rate, with_avail, with_data, policy):
    """The phase-skip guard (ISSUE 5) must be invisible: running with the
    guard force-disabled (``phase_skip=False``, the always-execute pipeline)
    and enabled produces identical ``SimResult`` pytrees — the skipped
    assignment/start phases were provably no-ops on the skipped rounds."""
    res1, jobs, sites, kw = build_scenario(
        n_jobs, seed, policy, fail_rate=fail_rate,
        with_avail=with_avail, with_data=with_data,
    )
    res2 = simulate(
        jobs, sites, get_policy(policy), jax.random.PRNGKey(seed),
        phase_skip=False, **kw,
    )
    leaves1, tree1 = jax.tree.flatten(res1)
    leaves2, tree2 = jax.tree.flatten(res2)
    assert tree1 == tree2
    for a, b in zip(leaves1, leaves2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# workflow DAG conservation laws (ISSUE 3): dependency gating, cascade-cancel
# partition, termination
# --------------------------------------------------------------------------


def random_dag_edges(n_jobs, rng, *, p_edge=0.35, max_parents=3):
    """Random DAG over [0, n_jobs): edges only point forward (acyclic by
    construction), bounded in-degree so the parent matrix stays small."""
    edges = []
    n_par = np.zeros(n_jobs, np.int64)
    for c in range(1, n_jobs):
        for p in rng.choice(c, size=min(c, max_parents), replace=False):
            if n_par[c] < max_parents and rng.random() < p_edge:
                edges.append((int(p), int(c)))
                n_par[c] += 1
    return edges


@settings(max_examples=8, deadline=None)
@given(
    n_jobs=st.integers(8, 40),
    seed=st.integers(0, 2**16),
    fail_rate=st.sampled_from([0.0, 0.4]),
    policy=st.sampled_from(["round_robin", "panda_dispatch", "critical_path_first"]),
)
def test_conservation_laws_with_workflow(n_jobs, seed, fail_rate, policy):
    """DAG invariants: no child starts before its last parent finishes,
    DONE + FAILED + CANCELLED partitions every DAG, cancellation happens iff
    a parent died, and the run terminates with resources restored."""
    rng = np.random.default_rng(seed)
    jobs = make_jobs(
        job_id=np.arange(n_jobs),
        arrival=np.sort(rng.uniform(0, 50.0, n_jobs)),
        work=rng.lognormal(np.log(300.0), 1.0, n_jobs),
        cores=np.where(rng.random(n_jobs) < 0.3, 8, 1),
        memory=np.full(n_jobs, 2.0),
        bytes_in=rng.lognormal(np.log(1e7), 1.0, n_jobs),
        bytes_out=rng.lognormal(np.log(1e6), 1.0, n_jobs),
        capacity=n_jobs + 2,  # padding rows must stay inert
    )
    jobs, wf = make_workflow(jobs, random_dag_edges(n_jobs, rng))
    sites = make_sites(
        cores=rng.integers(8, 32, N_SITES),
        speed=rng.uniform(2.0, 20.0, N_SITES),
        memory=rng.uniform(64.0, 256.0, N_SITES),
        bw_in=rng.uniform(1e8, 1e10, N_SITES),
        bw_out=rng.uniform(1e8, 1e10, N_SITES),
        fail_rate=np.full(N_SITES, fail_rate),
    )
    res = simulate(jobs, sites, get_policy(policy), jax.random.PRNGKey(seed),
                   workflow=wf, max_retries=2)

    valid = np.asarray(res.jobs.valid)
    state = np.asarray(res.jobs.state)[valid]
    # termination + partition: every valid job ends DONE, FAILED or CANCELLED
    assert np.isin(state, [DONE, FAILED, CANCELLED]).all()
    assert (np.asarray(res.jobs.state)[~valid] == DONE).all()
    # resources restored
    np.testing.assert_array_equal(np.asarray(res.sites.free_cores), np.asarray(sites.cores))
    np.testing.assert_allclose(
        np.asarray(res.sites.free_memory), np.asarray(sites.memory), rtol=1e-4, atol=1e-2
    )
    # dependency gate: no child starts before its last parent finishes; a
    # child ran at all only if every parent is DONE
    ts = np.asarray(res.jobs.t_start)
    tf = np.asarray(res.jobs.t_finish)
    full_state = np.asarray(res.jobs.state)
    par = np.asarray(wf.parents)
    for j in np.flatnonzero(valid):
        ps = par[j][par[j] >= 0]
        if np.isfinite(ts[j]):
            assert (full_state[ps] == DONE).all()
            if ps.size:
                assert ts[j] >= tf[ps].max() - 1e-4
    # cascade exactness: cancelled iff some parent is FAILED or CANCELLED
    for j in np.flatnonzero(valid):
        ps = par[j][par[j] >= 0]
        parent_dead = ps.size and np.isin(full_state[ps], [FAILED, CANCELLED]).any()
        if full_state[j] == CANCELLED:
            assert parent_dead
        if parent_dead:
            assert full_state[j] == CANCELLED
    # counter: the WorkflowState tally matches the state partition
    assert int(res.wf.n_cancelled) == int((state == CANCELLED).sum())
    # finished/failed site counters still account exactly (no double count
    # from the workflow layer)
    n_done = int((state == DONE).sum())
    retries = int(np.asarray(res.jobs.retries)[valid].sum())
    assert int(np.asarray(res.sites.n_finished).sum()) == n_done
    assert int(np.asarray(res.sites.n_failed).sum()) == retries + int((state == FAILED).sum())


# --------------------------------------------------------------------------
# ISSUE 7: platform-calibration properties — objective geometry, seed
# determinism, and the bounds guarantee of calibrate_platform
# --------------------------------------------------------------------------
from repro.core.calibration import (  # noqa: E402
    PARAM_FIELDS,
    apply_platform_params,
    calibrate_platform,
    default_bounds,
    make_synthetic_platform_problem,
    platform_objective,
    platform_params,
)


def _perturb(params, sigma, seed):
    """Multiplicative lognormal kick on every included knob family."""
    ks = jax.random.split(jax.random.PRNGKey(seed), len(PARAM_FIELDS))
    kicked = {}
    for k, f in zip(ks, PARAM_FIELDS):
        x = getattr(params, f)
        kicked[f] = None if x is None else x * jnp.exp(
            sigma * jax.random.normal(k, x.shape)
        )
    return params._replace(**kicked)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16), include_bw=st.booleans())
def test_objective_zero_at_truth_worse_when_perturbed(seed, include_bw):
    """The closed-form objective is ~0 at the hidden truth and strictly
    worse under a large multiplicative perturbation of the true knobs."""
    include = ("speed", "bw", "overhead") if include_bw else ("speed", "overhead")
    problem, truth = make_synthetic_platform_problem(
        n_jobs=32, n_sites=3, seed=seed % 1000, include=include,
        trace="closed_form", wan_frac=0.5 if include_bw else 0.0,
    )
    at_truth = float(platform_objective(problem, truth))
    assert at_truth < 1e-5
    kicked = _perturb(truth, 1.0, seed)
    assert float(platform_objective(problem, kicked)) > at_truth + 0.05


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2**16), method=st.sampled_from(["spsa", "grad"]))
def test_calibrate_platform_seed_deterministic(seed, method):
    """Same seed -> bitwise-identical result pytree."""
    problem, _ = make_synthetic_platform_problem(
        n_jobs=24, n_sites=3, seed=seed % 1000, include=("speed",),
        trace="closed_form",
    )
    kw = dict(method=method, objective="closed_form", include=("speed",),
              n_iters=8, seed=seed % 97)
    r1 = calibrate_platform(problem, **kw)
    r2 = calibrate_platform(problem, **kw)
    for a, b in zip(jax.tree.leaves(r1), jax.tree.leaves(r2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2**16), factor=st.floats(1.05, 1.5))
def test_calibrate_platform_respects_bounds(seed, factor):
    """Results never leave the declared box — even when the box is so tight
    that the optimizer slams into the walls."""
    problem, _ = make_synthetic_platform_problem(
        n_jobs=24, n_sites=3, seed=seed % 1000, include=("speed", "overhead"),
        trace="closed_form", misconfig_sigma=0.8,
    )
    p0 = platform_params(problem, ("speed", "overhead"))
    bounds = default_bounds(p0, factor=factor)
    res = calibrate_platform(
        problem, method="spsa", objective="closed_form",
        include=("speed", "overhead"), bounds=bounds, n_iters=12,
        seed=seed % 89, a0=0.5,
    )
    for f in ("speed", "overhead"):
        x = np.asarray(getattr(res.params, f))
        lo = np.asarray(getattr(bounds.lo, f))
        hi = np.asarray(getattr(bounds.hi, f))
        assert (x >= lo - 1e-6 * lo).all() and (x <= hi + 1e-6 * hi).all()


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    k=st.integers(1, 6),
    policy=st.sampled_from(["data_locality", "fastest_site", "least_loaded"]),
)
def test_sparse_candidates_contain_dense_argmax(seed, k, policy):
    """Sparse top-k membership guarantee (DESIGN.md §12): the candidate index
    always contains the dense pre-rank argmax site whenever any site is
    feasible — the property the k<S approximation gate rests on."""
    from test_sparse_topk import check_candidates_contain_dense_argmax

    check_candidates_contain_dense_argmax(seed, k, policy)
