"""FTS transfer-queue subsystem (ISSUE 8, DESIGN.md §11).

Pins the subsystem's contract from three sides:

- queue mechanics: per-link caps serialize flows FIFO, queue-wait is
  recorded, occupancy never exceeds the cap;
- the acceptance demo: a capped hot link changes the makespan vs. the
  instantaneous equal-share model, and converges back to it as
  ``max_active -> inf`` (single wave, equal flows — the two models are
  algebraically identical there);
- composition: lane ≡ solo under ``simulate_many`` (incl. ragged/bucketed
  capacity padding) and sharded ≡ vmapped, with all four built-in
  subsystems attached.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DONE,
    Scenario,
    compute_metrics,
    get_data_policy,
    link_caps,
    make_availability,
    make_replicas,
    make_sites,
    make_transfers,
    make_workflow,
    simulate,
    simulate_many,
    stack_scenarios,
    synthetic_panda_jobs,
    uniform_network,
    zipf_dataset_sizes,
)
from repro.core.availability import availability_subsystem
from repro.core.datapolicies import data_subsystem
from repro.core.events import ml_dataset, transfer_rows
from repro.core.monitor import link_occupancy_timeline, transfer_queue_timeline
from repro.core.platform import atlas_like_platform
from repro.core.policies import get_policy
from repro.core.transfers import transfers_subsystem
from repro.core.types import pad_jobs_capacity
from repro.core.workflows import workflow_subsystem

from test_ensemble_lanes import lane, tree_equal


def hot_link_scenario(n_jobs=24, n_sites=3, *, bw=1e8, ds_bytes=2e9, work=None,
                      cores_per_site=64, seed=0):
    """Every job reads its own equal-sized dataset homed at site 0 — the
    classic data-lake fan-out that saturates the egress links."""
    jobs = synthetic_panda_jobs(n_jobs, seed=seed, duration=1.0)
    # single wave at t=0, small single-core compute
    jobs = jobs._replace(
        arrival=jnp.zeros((jobs.capacity,)),
        cores=jnp.ones((jobs.capacity,), jnp.int32),
        memory=jnp.full((jobs.capacity,), 1.0),
        work=jnp.full((jobs.capacity,), float(work if work is not None else 50.0)),
        bytes_in=jnp.zeros((jobs.capacity,)),
        bytes_out=jnp.zeros((jobs.capacity,)),
        dataset=jnp.arange(jobs.capacity, dtype=jnp.int32) % n_jobs,
    )
    # site 0 is a pure data lake (no memory -> infeasible for compute), so
    # every job lands on a remote site and stages over a 0 -> dst link
    sites = make_sites(
        cores=[cores_per_site] * n_sites, speed=[1.0] * n_sites,
        fail_rate=[0.0] * n_sites, memory=[0.0] + [1e9] * (n_sites - 1),
        bw_in=[1e12] * n_sites, bw_out=[1e12] * n_sites,
    )
    net = uniform_network(n_sites, bw=bw, latency=0.05)
    rep = make_replicas(
        np.full(n_jobs, ds_bytes, np.float32), np.full(n_sites, 1e15),
        origin=np.zeros(n_jobs, np.int32),
    )
    return jobs, sites, net, rep


def run(jobs, sites, net, rep, *, transfers=None, policy="least_loaded", seed=0, **kw):
    return simulate(
        jobs, sites, get_policy(policy), jax.random.PRNGKey(seed),
        data_policy=get_data_policy("always_remote"), network=net, replicas=rep,
        transfers=transfers, **kw,
    )


# --------------------------------------------------------------------------
# queue mechanics
# --------------------------------------------------------------------------


def test_capped_link_serializes_fifo():
    jobs, sites, net, rep = hot_link_scenario(n_jobs=12, n_sites=2, cores_per_site=32)
    ts = make_transfers(2, jobs.capacity, max_active=1)
    res = run(jobs, sites, net, rep, transfers=ts, log_rows=512)

    tse = res.ext["transfers"]
    assert int(tse.n_enq) > 1
    assert int(tse.n_enq) == int(tse.n_done)
    assert int(tse.n_cancel) == 0
    assert int(tse.n_enq) == int(tse.n_done) + int(tse.n_cancel)
    # queues drained, slots released
    assert (np.asarray(tse.stat) == 0).all()
    assert (np.asarray(tse.active) == 0).all()

    # cap=1 serializes: the k-th transfer on the link waits ~ (k-1) full
    # transfer times, so the recorded queue-waits are strictly spread out
    moved = (np.asarray(res.jobs.xfer_bytes) > 0) & np.asarray(res.jobs.valid)
    waits = np.sort(np.asarray(res.jobs.xfer_wait)[moved])
    assert waits[0] == 0.0  # someone went straight to the wire
    assert waits[-1] > 0.0  # and someone queued behind it
    assert len(np.unique(np.round(waits, 3))) > len(waits) // 2
    # queue depth seen at enqueue was recorded
    assert int(np.asarray(res.jobs.xfer_qdepth)[moved].max()) > 0

    # link occupancy never exceeds the cap, and the queue actually built up
    occ = link_occupancy_timeline(res)
    qd = transfer_queue_timeline(res)
    assert occ.shape[1:] == (2, 2) and qd.shape == occ.shape
    assert occ.max() <= 1.0
    assert qd.max() >= 1.0


class RingQueues:
    """The per-link FIFO rings the queue state replaced, in NumPy: entries
    ``(job, ticket)`` appended in ticket order, a cancelled transfer left in
    place as a tombstone (its job no longer queued under that ticket), and
    admission popping each link's head into ``cap - active`` free slots,
    tombstones on the way out for free."""

    def __init__(self, L, J, cap):
        self.q = [[] for _ in range(L)]
        self.cap = np.full(L, cap)
        self.active = np.zeros(L, np.int64)
        self.stat = np.zeros(J, np.int64)
        self.ticket = np.full(J, -1, np.int64)
        self.link = np.full(J, -1, np.int64)
        self.n_enq = 0

    def live(self, entry):
        j, t = entry
        return self.stat[j] == 1 and self.ticket[j] == t

    def enqueue(self, rows, links):
        """Rows in job-id order; returns (entries ahead, live entries ahead)."""
        depth, live_ahead = {}, {}
        for j in sorted(rows):
            l = links[j]
            depth[j] = len(self.q[l])
            live_ahead[j] = sum(self.live(e) for e in self.q[l])
            self.q[l].append((j, self.n_enq))
            self.stat[j], self.ticket[j], self.link[j] = 1, self.n_enq, l
            self.n_enq += 1
        return depth, live_ahead

    def clear(self, rows):
        for j in rows:
            if self.stat[j] == 2:
                self.active[self.link[j]] -= 1
            self.stat[j] = 0

    def admit(self):
        for l, q in enumerate(self.q):
            budget = max(self.cap[l] - self.active[l], 0)
            while q:
                if not self.live(q[0]):
                    q.pop(0)
                elif budget > 0:
                    self.stat[q.pop(0)[0]] = 2
                    self.active[l] += 1
                    budget -= 1
                else:
                    break


@pytest.mark.parametrize("cap", [1, 2])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_admission_equals_the_rings(cap, seed):
    """Seeded contended rounds on three hot links of a 3-site grid: several
    enqueuers per link in one round, landings, cancels of queued and active
    transfers, and cancelled rows enqueued again.  The per-job queue state
    admits exactly the rows the rings admit, and a new row's depth is the
    ring's count of live entries ahead of it (the ring also counted
    tombstones; the two agree wherever no cancelled transfer stood ahead)."""
    from repro.core.transfers import T_ACTIVE, T_IDLE, T_QUEUED, _admit, _enqueue

    S, J = 3, 40
    L = S * S
    rng = np.random.default_rng(seed)
    ts = make_transfers(S, J, max_active=cap)
    ring = RingQueues(L, J, cap)
    enqueue = jax.jit(_enqueue)
    admit = jax.jit(_admit)
    hot = np.array([1, 2, 5])
    n_tomb_ahead = 0
    for rnd in range(60):
        clock = jnp.float32(rnd)
        stat = np.asarray(ts.stat)
        active_rows = np.flatnonzero(stat == T_ACTIVE)
        busy = np.flatnonzero(stat != T_IDLE)
        land = active_rows[rng.random(active_rows.size) < 0.4]
        cancel = np.setdiff1d(busy[rng.random(busy.size) < 0.1], land)
        gone = np.concatenate([land, cancel])
        ring.clear(gone)
        clear = np.zeros(J, bool)
        clear[gone] = True
        link_c = np.clip(np.asarray(ts.link), 0, L - 1)
        freed = np.bincount(link_c[clear & (stat == T_ACTIVE)], minlength=L)
        ts = ts._replace(stat=jnp.where(clear, T_IDLE, ts.stat),
                         active=ts.active - jnp.asarray(freed, jnp.int32))
        ts, _ = admit(ts, clock)
        ring.admit()
        np.testing.assert_array_equal(np.asarray(ts.stat), ring.stat)
        np.testing.assert_array_equal(np.asarray(ts.active), ring.active)

        idle = np.flatnonzero(np.asarray(ts.stat) == T_IDLE)
        want_rows = idle[rng.random(idle.size) < 0.3]
        links = np.full(J, -1)
        links[want_rows] = rng.choice(hot, want_rows.size)
        want = np.zeros(J, bool)
        want[want_rows] = True
        ts = enqueue(ts, jnp.asarray(want), jnp.asarray(links, jnp.int32),
                     jnp.ones((J,), jnp.float32), jnp.zeros((J,), jnp.float32),
                     jnp.zeros((J,), bool), clock)
        depth, live_ahead = ring.enqueue(want_rows, links)
        ts, rank = admit(ts, clock)
        ring.admit()
        np.testing.assert_array_equal(np.asarray(ts.stat), ring.stat)
        np.testing.assert_array_equal(np.asarray(ts.active), ring.active)
        for j in want_rows:
            assert int(rank[j]) == live_ahead[j]
            n_tomb_ahead += depth[j] - live_ahead[j]
        assert (np.asarray(ts.stat) == T_QUEUED).sum() == sum(
            ring.live(e) for q in ring.q for e in q)
    assert ring.n_enq == int(ts.n_enq) > J  # rows were enqueued again
    assert n_tomb_ahead > 0  # and the rings held tombstones ahead of new rows


def test_transfer_state_fits_wlcg_scale():
    """300 sites and 100,000 jobs: J- and L-wide arrays only, a few MB."""
    S, J = 300, 100_000
    shapes = jax.eval_shape(lambda: make_transfers(S, J, max_active=2))
    leaves = jax.tree.leaves(shapes)
    total = sum(x.size * x.dtype.itemsize for x in leaves)
    assert total < 16 * 2**20, total
    assert max(x.size for x in leaves) <= max(S * S, J)


def test_transfer_counters_on_result_and_recorder():
    """``xfer_enqueued``/``xfer_landed``/``xfer_waited`` on the result and
    as recorder gauges of ``simulate`` and ``monitor.watch``; a transfer
    waited for a slot iff its job's queue wait is positive."""
    import io

    from repro.core.monitor import watch
    from repro.core.telemetry import TraceRecorder

    jobs, sites, net, rep = hot_link_scenario(n_jobs=12, n_sites=2, cores_per_site=32)
    ts = make_transfers(2, jobs.capacity, max_active=1)
    rec = TraceRecorder()
    res = run(jobs, sites, net, rep, transfers=ts, recorder=rec)
    tse = res.ext["transfers"]
    moved = (np.asarray(res.jobs.xfer_bytes) > 0) & np.asarray(res.jobs.valid)
    assert int(res.xfer_enqueued) == int(tse.n_enq) == int(moved.sum())
    assert int(res.xfer_landed) == int(tse.n_done) == int(moved.sum())
    assert int(res.xfer_waited) == int((np.asarray(res.jobs.xfer_wait)[moved] > 0).sum()) > 0
    for name in ("xfer_enqueued", "xfer_landed", "xfer_waited"):
        assert rec.counters[name] == int(getattr(res, name))
    rec_w = TraceRecorder()
    res_w = watch(jobs, sites, get_policy("least_loaded"), jax.random.PRNGKey(0), frames=3,
                  render=False, out=io.StringIO(), recorder=rec_w,
                  data_policy=get_data_policy("always_remote"), network=net, replicas=rep,
                  transfers=ts)
    for name in ("xfer_enqueued", "xfer_landed", "xfer_waited"):
        assert rec_w.counters[name] == int(getattr(res_w, name)) == int(getattr(res, name))
    rec_off = TraceRecorder()
    off = run(jobs, sites, net, rep, recorder=rec_off)
    assert off.xfer_enqueued is None and "xfer_landed" not in rec_off.counters


def test_transfers_requires_data_subsystem():
    jobs, sites, net, rep = hot_link_scenario(n_jobs=4, n_sites=2)
    ts = make_transfers(2, jobs.capacity)
    with pytest.raises(ValueError, match="transfers="):
        simulate(jobs, sites, get_policy("least_loaded"), jax.random.PRNGKey(0),
                 transfers=ts)


def test_link_caps_overrides():
    caps = link_caps(3, 4, {(0, 1): 1, (0, 2): 2})
    m = np.asarray(caps).reshape(3, 3)
    assert m[0, 1] == 1 and m[0, 2] == 2 and m[1, 2] == 4
    full = link_caps(2, 0, np.array([[9, 8], [7, 6]]))
    assert np.asarray(full).tolist() == [9, 8, 7, 6]
    with pytest.raises(ValueError):
        link_caps(3, 1, np.zeros((2, 2)))


# --------------------------------------------------------------------------
# acceptance demo: capped hot link vs. the equal-share model
# --------------------------------------------------------------------------


def test_hot_link_cap_changes_makespan_and_converges():
    # transfer-dominated fan-out with limited cores: a cap=1 hot link runs a
    # genuinely different trajectory than wave-batched equal share (FIFO
    # staggers releases and pipelines staging against compute, equal share
    # batches whole waves) — the makespan moves materially
    jobs, sites, net, rep = hot_link_scenario(
        n_jobs=24, n_sites=3, cores_per_site=4, work=20.0
    )
    flat = run(jobs, sites, net, rep)
    capped = run(jobs, sites, net, rep,
                 transfers=make_transfers(3, jobs.capacity, max_active=1))
    assert int((np.asarray(capped.jobs.state) == DONE).sum()) == 24
    rel = abs(float(capped.makespan) - float(flat.makespan)) / float(flat.makespan)
    assert rel > 0.05, (float(flat.makespan), float(capped.makespan))
    # and jobs demonstrably waited in the link queue
    assert float(np.asarray(capped.jobs.xfer_wait).max()) > 0.0

    # single wave with ample cores and equal-sized flows: equal-share and an
    # uncapped queue are the same closed form -> the makespans converge
    jobs, sites, net, rep = hot_link_scenario(n_jobs=24, n_sites=3, cores_per_site=64)
    flat = run(jobs, sites, net, rep)
    uncapped = run(jobs, sites, net, rep,
                   transfers=make_transfers(3, jobs.capacity, max_active=10_000))
    rel = abs(float(uncapped.makespan) - float(flat.makespan)) / float(flat.makespan)
    assert rel < 2e-2, (float(flat.makespan), float(uncapped.makespan))


# --------------------------------------------------------------------------
# preemption: cancelled transfers and retries
# --------------------------------------------------------------------------


def test_preempted_staging_jobs_cancel_and_retry():
    jobs, sites, net, rep = hot_link_scenario(n_jobs=16, n_sites=2, cores_per_site=32)
    av = make_availability(2, [dict(site=1, start=5.0, end=200.0, preempt=True)])
    ts = make_transfers(2, jobs.capacity, max_active=2)
    res = simulate(
        jobs, sites, get_policy("least_loaded"), jax.random.PRNGKey(0),
        data_policy=get_data_policy("always_remote"), network=net, replicas=rep,
        availability=av, transfers=ts,
    )
    tse = res.ext["transfers"]
    # every enqueue terminated exactly once, in bytes too
    assert int(tse.n_enq) == int(tse.n_done) + int(tse.n_cancel)
    assert int(tse.n_cancel) > 0  # the outage really cut staging jobs down
    np.testing.assert_allclose(
        float(tse.bytes_enq), float(tse.bytes_done) + float(tse.bytes_cancel),
        rtol=1e-5,
    )
    # queues drained despite the tombstones, and the workload finished
    assert (np.asarray(tse.stat) == 0).all()
    assert (np.asarray(tse.active) == 0).all()
    st = np.asarray(res.jobs.state)[np.asarray(res.jobs.valid)]
    assert (st == DONE).all()


# --------------------------------------------------------------------------
# metrics / events / export schema
# --------------------------------------------------------------------------


def test_metrics_and_export_features():
    jobs, sites, net, rep = hot_link_scenario(n_jobs=12, n_sites=2, cores_per_site=32)
    ts = make_transfers(2, jobs.capacity, max_active=1)
    r_on = run(jobs, sites, net, rep, transfers=ts)
    r_off = run(jobs, sites, net, rep)

    m_on, m_off = compute_metrics(r_on), compute_metrics(r_off)
    assert float(m_on.p99_xfer_wait) > 0.0
    assert float(m_on.p50_xfer_time) > 0.0
    assert float(m_off.p99_xfer_wait) == 0.0  # defined (0) when the subsystem is off
    assert float(m_on.p50_xfer_wait) <= float(m_on.p95_xfer_wait) <= float(m_on.p99_xfer_wait)

    rows_on, rows_off = transfer_rows(r_on), transfer_rows(r_off)
    assert {"queue_wait", "queue_depth"} <= set(rows_on[0])
    assert max(r["queue_wait"] for r in rows_on) > 0.0
    # off: defaults only, schema unchanged
    assert all(r["queue_wait"] == 0.0 and r["queue_depth"] == -1 for r in rows_off)

    ds_on, ds_off = ml_dataset(r_on), ml_dataset(r_off)
    base = list(ds_off["feature_names"])
    assert "xfer_queue_wait" not in base
    assert list(ds_on["feature_names"]) == base + [
        "xfer_queue_wait", "xfer_queue_depth", "src_link_log_bw"
    ]
    assert ds_on["features"].shape[1] == len(ds_on["feature_names"])
    wait_col = ds_on["features"][:, base.__len__()]
    assert wait_col.max() > 0.0


# --------------------------------------------------------------------------
# ensembles: lane ≡ solo, sharded ≡ vmapped, ragged padding
# --------------------------------------------------------------------------

N_DS = 8


def quad_scenarios(K=3, n=44, n_sites=3, sizes=None):
    """K scenarios running all four built-in subsystems
    (availability + workflow + data + transfers)."""
    sites = atlas_like_platform(n_sites, seed=7)
    net = uniform_network(n_sites, bw=5e8, latency=0.05)
    dp = get_data_policy("cache_on_read")
    subs = (
        availability_subsystem(), workflow_subsystem(), data_subsystem(dp),
        transfers_subsystem(),
    )
    scens, solo_kw = [], []
    for k in range(K):
        nk = n if sizes is None else sizes[k]
        jobs = synthetic_panda_jobs(nk, seed=30 + k, duration=600.0, n_datasets=N_DS)
        av = make_availability(
            n_sites,
            [
                dict(site=k % n_sites, start=100.0 * (k + 1), end=900.0, preempt=True),
                dict(site=(k + 1) % n_sites, start=50.0, end=400.0, factor=0.5),
            ],
        )
        rep = make_replicas(
            zipf_dataset_sizes(N_DS, seed=3 + k, mean_bytes=1e9),
            disk_capacity=np.full(n_sites, 1e12),
            origin=np.zeros(N_DS, np.int32),
        )
        edges = [(j - 1, j) for j in range(1, nk, 2)]
        out_ds = np.where(np.arange(nk) % 2 == 0, np.arange(nk) % N_DS, -1)
        jobs_wf, wf = make_workflow(jobs, edges, out_dataset=out_ds)
        ts = make_transfers(n_sites, jobs_wf.capacity, max_active=1 + k)
        scens.append(
            Scenario(
                jobs_wf,
                sites._replace(speed=sites.speed * (0.8 + 0.2 * k)),
                {"availability": av, "workflow": wf, "data": (net, rep), "transfers": ts},
            )
        )
        solo_kw.append(
            dict(availability=av, workflow=wf, data_policy=dp, network=net,
                 replicas=rep, transfers=ts)
        )
    return scens, subs, solo_kw


def test_quad_subsystem_lanes_equal_solo():
    scens, subs, solo_kw = quad_scenarios()
    pol = get_policy("critical_path_first")
    keys = jax.random.split(jax.random.PRNGKey(4), len(scens))
    res = simulate_many(scens, pol, jax.random.PRNGKey(4), subsystems=subs)
    for i, s in enumerate(scens):
        solo = simulate(s.jobs, s.sites, pol, keys[i], **solo_kw[i])
        assert tree_equal(lane(res, i), solo) == []
        assert int(res.ext["transfers"].n_enq[i]) > 0  # queues actually used


def test_quad_subsystem_sharded_equals_vmapped():
    from repro.core.distributed import simulate_many_sharded

    scens, subs, _ = quad_scenarios()
    pol = get_policy("panda_dispatch")
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    r_v = simulate_many(scens, pol, jax.random.PRNGKey(4), subsystems=subs)
    r_s = simulate_many_sharded(scens, pol, jax.random.PRNGKey(4), mesh, subsystems=subs)
    assert tree_equal(r_s, r_v) == []


def test_ragged_lanes_pad_transfer_state():
    """Ragged lanes exercise the pad_jobs hook; a solo run on the same
    padded ext state is bit-for-bit identical."""
    from repro.core import pad_ext_jobs

    sizes = [36, 52, 44]
    scens, subs, solo_kw = quad_scenarios(sizes=sizes)
    cap = max(sizes)
    pol = get_policy("panda_dispatch")
    keys = jax.random.split(jax.random.PRNGKey(6), len(scens))
    res = simulate_many(scens, pol, jax.random.PRNGKey(6), subsystems=subs)
    i = 0  # the most-padded lane
    ext_p = pad_ext_jobs(subs, scens[i].ext, sizes[i], cap)
    kw = dict(solo_kw[i])
    kw.update(availability=ext_p["availability"], workflow=ext_p["workflow"],
              transfers=ext_p["transfers"])
    solo = simulate(pad_jobs_capacity(scens[i].jobs, cap), scens[i].sites, pol, keys[i], **kw)
    assert tree_equal(lane(res, i), solo) == []
