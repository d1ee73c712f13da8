"""Distributed simulator: sharded == unsharded, collectives present."""
import os
import subprocess
import sys

import pytest


def test_sharded_equivalence_in_process_tiny_mesh():
    """Non-subprocess sharded-equivalence check: the distributed entry point
    (device_put + NamedSharding + mesh context) must reproduce the plain
    engine exactly on whatever mesh this process has — including the
    availability path, whose calendar is replicated like ``sites``."""
    import jax
    import numpy as np

    from repro.core import (
        atlas_like_platform,
        get_policy,
        make_availability,
        simulate,
        synthetic_panda_jobs,
    )
    from repro.core.distributed import simulate_distributed

    jobs = synthetic_panda_jobs(64, seed=0, duration=600.0)
    sites = atlas_like_platform(4, seed=1)
    pol = get_policy("shortest_wait")
    av = make_availability(4, [dict(site=0, start=50.0, end=5000.0, preempt=True)])
    mesh = jax.make_mesh((jax.device_count(),), ("data",))

    for kw in ({}, {"availability": av}):
        r1 = simulate(jobs, sites, pol, jax.random.PRNGKey(0), max_rounds=20_000, **kw)
        r2 = simulate_distributed(
            jobs, sites, pol, jax.random.PRNGKey(0), mesh, max_rounds=20_000, **kw
        )
        assert float(r1.makespan) == float(r2.makespan)
        assert int(r1.rounds) == int(r2.rounds)
        J = jobs.capacity
        np.testing.assert_array_equal(
            np.asarray(r1.jobs.state), np.asarray(r2.jobs.state)[:J]
        )
        np.testing.assert_allclose(
            np.asarray(r1.jobs.t_start), np.asarray(r2.jobs.t_start)[:J], rtol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(r1.jobs.t_finish), np.asarray(r2.jobs.t_finish)[:J], rtol=1e-6
        )
        np.testing.assert_array_equal(
            np.asarray(r1.sites.n_finished), np.asarray(r2.sites.n_finished)
        )
    assert int(r2.avail.n_preempted.sum()) == int(r1.avail.n_preempted.sum())


def test_sharded_equivalence_in_process_with_workflow_dag():
    """A DAG workload through the distributed entry point reproduces the
    plain engine exactly: the parent matrix is replicated aux (and padded to
    the sharded job capacity), the gating gather shards with the jobs."""
    import jax
    import numpy as np

    from repro.core import (
        DONE,
        chain_workflows,
        get_data_policy,
        get_policy,
        scenario_replicas,
        simulate,
        uniform_network,
    )
    from repro.core import make_sites
    from repro.core.distributed import simulate_distributed

    # 30 rows: not a multiple of the mesh axis, so the workflow pads too
    scn = chain_workflows(10, 3, seed=0, arrival_span=200.0)
    sites = make_sites(
        cores=[16, 8, 8], speed=[10.0, 8.0, 12.0], memory=[256.0] * 3,
        bw_in=[1e9] * 3, bw_out=[1e9] * 3,
    )
    net = uniform_network(3, bw=2e8, latency=0.02)
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    kw = dict(
        workflow=scn.workflow,
        data_policy=get_data_policy("cache_on_read"),
        network=net,
        replicas=scenario_replicas(scn, disk_capacity=np.full(3, 1e12)),
        max_rounds=20_000,
    )
    # workflow_locality closes over the *unpadded* parent matrix: it must
    # re-pad inside score when the distributed path grows the job capacity
    for pol in (
        get_policy("critical_path_first"),
        get_policy("workflow_locality", workflow=scn.workflow),
    ):
        r1 = simulate(scn.jobs, sites, pol, jax.random.PRNGKey(0), **kw)
        r2 = simulate_distributed(scn.jobs, sites, pol, jax.random.PRNGKey(0), mesh, **kw)
        J = scn.jobs.capacity
        assert float(r1.makespan) == float(r2.makespan)
        assert int(r1.rounds) == int(r2.rounds)
        np.testing.assert_array_equal(np.asarray(r1.jobs.state), np.asarray(r2.jobs.state)[:J])
        np.testing.assert_allclose(
            np.asarray(r1.jobs.t_start), np.asarray(r2.jobs.t_start)[:J], rtol=1e-6
        )
        assert (np.asarray(r2.jobs.state)[:J] == DONE).all()
        assert int(r1.wf.n_produced) == int(r2.wf.n_produced) == 30  # every stage materializes
        np.testing.assert_array_equal(
            np.asarray(r1.replicas.present), np.asarray(r2.replicas.present)
        )


SCRIPT = r"""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import synthetic_panda_jobs, atlas_like_platform, get_policy, simulate
from repro.core.distributed import (simulate_distributed, lower_distributed,
                                    simulate_ensemble_distributed)

assert len(jax.devices()) == 8, jax.devices()
jobs = synthetic_panda_jobs(256, seed=0, duration=1800.0)
sites = atlas_like_platform(6, seed=1)
pol = get_policy("shortest_wait")
mesh = jax.make_mesh((8,), ("data",))

r1 = simulate(jobs, sites, pol, jax.random.PRNGKey(0), max_rounds=20000)
r2 = simulate_distributed(jobs, sites, pol, jax.random.PRNGKey(0), mesh, max_rounds=20000)
assert abs(float(r1.makespan) - float(r2.makespan)) < 1e-3, (float(r1.makespan), float(r2.makespan))
assert np.allclose(np.asarray(r1.jobs.t_start), np.asarray(r2.jobs.t_start), rtol=1e-5)

lowered, compiled = lower_distributed(jobs, sites, pol, mesh, max_rounds=500)
txt = compiled.as_text()
assert txt.count("all-reduce") > 0, "expected SPMD all-reduces in the engine"

# ensemble: 8 candidate speed vectors across 8 devices
import jax.numpy as jnp
cands = sites.speed[None, :] * jnp.exp(0.2 * jax.random.normal(jax.random.PRNGKey(1), (8, sites.capacity)))
re = simulate_ensemble_distributed(jobs, sites, pol, jax.random.PRNGKey(2), cands, mesh, max_rounds=20000)
assert re.makespan.shape == (8,)
assert np.isfinite(np.asarray(re.makespan)).all()

# workflow DAG with job padding (15 rows over 8 devices -> 16) through a
# policy that closes over the unpadded parent matrix
from repro.core import DONE, chain_workflows, make_sites
scn = chain_workflows(5, 3, seed=0)
sites3 = make_sites(cores=[16]*3, speed=[10.0]*3, memory=[256.0]*3,
                    bw_in=[1e9]*3, bw_out=[1e9]*3)
wpol = get_policy("workflow_locality", workflow=scn.workflow)
rw1 = simulate(scn.jobs, sites3, wpol, jax.random.PRNGKey(0),
               workflow=scn.workflow, max_rounds=20000)
rw2 = simulate_distributed(scn.jobs, sites3, wpol, jax.random.PRNGKey(0), mesh,
                           workflow=scn.workflow, max_rounds=20000)
assert float(rw1.makespan) == float(rw2.makespan)
assert (np.asarray(rw2.jobs.state)[:15] == DONE).all()

# sharded scenario ensemble (ISSUE 5): 6 ragged lanes over 8 devices (lane
# padding path) must be bit-for-bit equal to the vmapped ensemble per lane
from repro.core import Scenario, simulate_many, stack_scenarios
from repro.core.distributed import simulate_many_sharded
scens = [Scenario(synthetic_panda_jobs(n, seed=20 + i, duration=600.0),
                  sites._replace(speed=sites.speed * (0.8 + 0.05 * i)))
         for i, n in enumerate([40, 52, 64, 48, 56, 44])]
rv = simulate_many(scens, pol, jax.random.PRNGKey(5))
rs = simulate_many_sharded(scens, pol, jax.random.PRNGKey(5), mesh)
for a, b in zip(jax.tree.leaves(rv), jax.tree.leaves(rs)):
    x, y = np.asarray(a), np.asarray(b)
    both_nan = (np.isnan(x) & np.isnan(y)) if np.issubdtype(x.dtype, np.floating) else False
    assert ((x == y) | both_nan).all()
# bucketed + sharded composes and stays exact
rb = simulate_many_sharded(stack_scenarios(scens, buckets=3), pol,
                           jax.random.PRNGKey(5), mesh)
assert float(np.abs(np.asarray(rb.makespan) - np.asarray(rv.makespan)).max()) == 0.0
# lanes the caller already placed on the (Explicit-axis) mesh are never
# donated: a second call on the same buffers still works
from jax.sharding import NamedSharding, PartitionSpec as P
st = stack_scenarios(scens[:4] * 2)
placed = jax.tree.map(lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))), st)
rp1 = simulate_many_sharded(placed, pol, jax.random.PRNGKey(5), mesh)
rp2 = simulate_many_sharded(placed, pol, jax.random.PRNGKey(5), mesh)
assert (np.asarray(rp1.makespan) == np.asarray(rp2.makespan)).all()
print("DIST-OK")
"""


@pytest.mark.slow
def test_distributed_equivalence_subprocess():
    """Runs in a subprocess: the sharded engine needs >1 device, which must be
    configured before jax initializes (host-platform device count)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True, timeout=900
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "DIST-OK" in out.stdout
