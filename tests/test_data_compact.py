"""The data hook's compact stage-in (DESIGN.md §3): the rows that start a
round are priced alone, and a round that starts more than
``datapolicies.COMPACT_ROWS`` jobs falls back to the J-wide code.  Both paths
must give bit-identical results: every job column, the replica catalog, and
the event log's ``site_net_in`` column."""
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    atlas_like_network,
    atlas_like_platform,
    get_data_policy,
    get_policy,
    make_replicas,
    make_transfers,
    simulate,
    synthetic_panda_jobs,
    zipf_dataset_sizes,
)
from repro.core import datapolicies
from repro.core.datapolicies import data_subsystem
from repro.core.engine import Scenario, _first_rows, simulate_many
from repro.core.monitor import watch
from repro.core.telemetry import TraceRecorder

J, S, D = 400, 8, 30
LOG_ROWS = 1024  # more than any run's rounds: the log holds every round


def spread_jobs(seed=1):
    """Arrivals spread over 900 s: about one start per round."""
    return synthetic_panda_jobs(J, seed=seed, duration=900.0, n_datasets=D)


def bursty_jobs(seed=1):
    """A third of the jobs arrive on 600 s edges (one round starts more than
    64 of them), the rest on 20 s edges (rounds of a few to a few dozen
    starts)."""
    jobs = spread_jobs(seed)
    q = jnp.where(jnp.arange(J) % 3 == 0, 600.0, 20.0)
    return jobs._replace(arrival=jnp.floor(jobs.arrival / q) * q)


def data_kw(policy="cache_on_read"):
    return dict(
        data_policy=get_data_policy(policy),
        network=atlas_like_network(S, seed=3),
        replicas=make_replicas(
            zipf_dataset_sizes(D, seed=4, mean_bytes=5e9),
            disk_capacity=np.full(S, 4e10),
            seed=5,
        ),
    )


SITES = atlas_like_platform(S, seed=2)
POLICY = get_policy("panda_dispatch")


def run(jobs, policy="cache_on_read", **kw):
    return simulate(
        jobs, SITES, POLICY, jax.random.PRNGKey(0), log_rows=LOG_ROWS,
        **data_kw(policy), **kw,
    )


@pytest.fixture
def compact_rows(monkeypatch):
    """Set ``COMPACT_ROWS`` for the next traces: the hook reads it while
    tracing, so the jit caches go with each change."""

    def set_rows(k):
        monkeypatch.setattr(datapolicies, "COMPACT_ROWS", k)
        jax.clear_caches()

    yield set_rows
    jax.clear_caches()


def differing_leaves(a, b, skip=("wide_rounds",)):
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(fa) == len(fb)
    bad = []
    for (path, x), (_, y) in zip(fa, fb):
        name = jax.tree_util.keystr(path)
        if any(s in name for s in skip):
            continue
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or not np.array_equal(x, y, equal_nan=True):
            bad.append(name)
    return bad


def starts_per_round(res):
    """Jobs started in each round, from the event log."""
    n = int(res.rounds)
    assert n <= LOG_ROWS
    return np.asarray(res.log.n_started)[:n]


# --------------------------------------------------------------------------
# the row-compaction helper
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "true_rows",
    [[], [3, 17, 40, 41], [0, 9, 10, 25, 31, 63], [2, 5, 8, 13, 21, 34, 55], list(range(64))],
    ids=["none", "fewer_than_k", "exactly_k", "k_plus_1", "all_J"],
)
def test_first_rows(true_rows):
    n, k = 64, 6
    mask = np.zeros(n, bool)
    mask[true_rows] = True
    got = np.asarray(jax.jit(_first_rows, static_argnums=1)(jnp.asarray(mask), k))
    want = np.full(k, n, np.int32)
    first = np.flatnonzero(mask)[:k]
    want[: first.size] = first
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------
# compact path == wide path, bit for bit
# --------------------------------------------------------------------------


@pytest.mark.parametrize("k", [64, 1])
@pytest.mark.parametrize("policy", ["cache_on_read", "always_remote", "pre_place_hot"])
def test_compact_equals_wide(compact_rows, policy, k):
    jobs = bursty_jobs()
    compact_rows(0)  # the J-wide code in every round that starts a job
    wide = run(jobs, policy)
    compact_rows(k)
    res = run(jobs, policy)
    starts = starts_per_round(res)
    # the workload has burst rounds past k and rounds of several starts
    assert starts.max() > k and ((starts > 1) & (starts <= 64)).any()
    assert int(res.replicas.n_transfers) > 0
    assert int(wide.data_wide_rounds) == int((starts > 0).sum())
    assert int(res.data_wide_rounds) == int((starts > k).sum())
    assert differing_leaves(wide, res) == []


def test_ensemble_lane_bursting_alone(compact_rows):
    """Two lanes, of which only one bursts past k in a round: the ensemble
    takes the wide path for both (``_ensemble_any``), and each lane still
    equals its solo run."""
    compact_rows(64)
    kw = data_kw()
    scens = [
        Scenario(jobs, SITES, {"data": (kw["network"], kw["replicas"])})
        for jobs in (bursty_jobs(), spread_jobs())
    ]
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    res = simulate_many(
        scens, POLICY, jax.random.PRNGKey(7), subsystems=(data_subsystem(kw["data_policy"]),),
        log_rows=LOG_ROWS,
    )
    solos = [
        simulate(s.jobs, SITES, POLICY, keys[i], log_rows=LOG_ROWS, **kw)
        for i, s in enumerate(scens)
    ]
    assert int(solos[0].data_wide_rounds) > 0
    assert int(solos[1].data_wide_rounds) == 0
    for i, solo in enumerate(solos):
        assert differing_leaves(jax.tree.map(lambda x: x[i], res), solo) == []
    # the calm lane ran the burst lane's wide rounds too
    assert int(res.data_wide_rounds[1]) > 0


# --------------------------------------------------------------------------
# the wide-round counter
# --------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["spread", "bursty", "transfers"])
def test_data_wide_rounds_counter(case):
    k = datapolicies.COMPACT_ROWS
    jobs = spread_jobs() if case == "spread" else bursty_jobs()
    extra = {"transfers": make_transfers(S, J, max_active=4)} if case == "transfers" else {}
    rec = TraceRecorder()
    res = run(jobs, recorder=rec, **extra)
    starts = starts_per_round(res)
    if case == "spread":
        assert starts.max() <= k
        assert int(res.data_wide_rounds) == 0
    elif case == "bursty":
        assert (starts > k).sum() > 0
        assert int(res.data_wide_rounds) == int((starts > k).sum())
    else:
        # the transfer queues take J-wide arrays: every round is wide
        assert int(res.data_wide_rounds) == int(res.rounds)
    assert rec.counters["data_wide_rounds"] == int(res.data_wide_rounds)
    assert rec.counters["rounds_executed"] == int(res.rounds)
    rec_w = TraceRecorder()
    res_w = watch(
        jobs, SITES, POLICY, jax.random.PRNGKey(0), frames=3, render=False,
        out=io.StringIO(), recorder=rec_w, log_rows=LOG_ROWS, **data_kw(), **extra,
    )
    assert rec_w.counters["data_wide_rounds"] == int(res_w.data_wide_rounds)
    assert int(res_w.data_wide_rounds) == int(res.data_wide_rounds)
