"""Sparse top-k scoring (engine ``topk=``, DESIGN.md §12).

Exactness contract under test:

- ``topk=None`` is the dense path — untouched, covered by the golden tests.
- ``topk=k`` with ``k >= S`` must be *bit-for-bit* equal to dense, across
  every subsystem combination of the golden matrix scenario: the candidate
  index then enumerates all statically feasible sites in dense scan order.
- ``k < S`` is a documented approximation, gated here by a ≤1% makespan
  drift on a WLCG-shaped scenario and by the membership property that the
  candidate set always contains the dense pre-rank argmax when any site is
  feasible (hypothesis-tested).
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Scenario,
    atlas_like_platform,
    build_candidates,
    bytes_per_round,
    get_policy,
    simulate,
    static_feasibility,
    synthetic_panda_jobs,
)
from repro.core.engine import (
    _packed_order_ok,
    _start_order,
    _start_order_packed,
    _static_start_rank,
)

from test_golden_trace import combo_kwargs, matrix_scenario


def assert_trees_equal(a, b):
    """Bitwise pytree equality, NaN-aware (NaN == NaN in padded float rows)."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.shape == y.shape and x.dtype == y.dtype
        assert np.array_equal(x, y, equal_nan=np.issubdtype(x.dtype, np.floating))


def test_topk_full_k_bitwise_equals_dense_all_matrix_combos():
    """topk(k=S) ≡ dense per-round across the 8 golden-matrix combos (plus
    per-round log rows, so any intermediate divergence is visible too)."""
    scn = matrix_scenario()
    pol = get_policy("panda_dispatch")
    key = jax.random.PRNGKey(0)
    S = scn["sites"].capacity
    for data, avail, wf in itertools.product((False, True), repeat=3):
        jobs, kw = combo_kwargs(scn, data, avail, wf)
        dense = simulate(jobs, scn["sites"], pol, key, log_rows=64, **kw)
        sparse = simulate(jobs, scn["sites"], pol, key, log_rows=64, topk=S, **kw)
        assert_trees_equal(dense, sparse)


def test_topk_full_k_bitwise_equals_dense_with_refresh():
    """Rebuilding the (already-complete) candidate index mid-run must not
    perturb anything: the refresh path only recomputes, never re-draws."""
    jobs = synthetic_panda_jobs(60, seed=11, duration=900.0)
    sites = atlas_like_platform(4, seed=12, fail_rate=0.05)
    pol = get_policy("panda_dispatch")
    key = jax.random.PRNGKey(0)
    dense = simulate(jobs, sites, pol, key)
    sparse = simulate(jobs, sites, pol, key, topk=sites.capacity, topk_refresh=7)
    assert_trees_equal(dense, sparse)


def test_topk_small_k_makespan_drift_under_1pct():
    """The k<S approximation acceptance gate: a WLCG-shaped scenario (many
    jobs racing for few sites, locality-driven policy) must land within 1%
    of the dense makespan at k = S/3."""
    jobs = synthetic_panda_jobs(400, seed=0, duration=3600.0)
    sites = atlas_like_platform(24, seed=1)
    pol = get_policy("data_locality")
    key = jax.random.PRNGKey(0)
    dense = simulate(jobs, sites, pol, key)
    sparse = simulate(jobs, sites, pol, key, topk=8)
    drift = abs(float(sparse.makespan) - float(dense.makespan))
    assert drift <= 0.01 * float(dense.makespan)


def test_sharded_ensemble_accepts_topk_with_ragged_lanes():
    """simulate_many_sharded(topk=) — ragged lane sizes through the sparse
    path, bit-for-bit equal per lane to solo sparse runs."""
    from jax.sharding import Mesh

    from repro.core import pad_jobs_capacity
    from repro.core.distributed import simulate_many_sharded

    sites = atlas_like_platform(4, seed=1)
    pol = get_policy("panda_dispatch")
    sizes = [24, 17, 31]
    cap = max(sizes)
    scens = [
        Scenario(
            pad_jobs_capacity(synthetic_panda_jobs(n, seed=30 + i, duration=600.0), cap),
            sites._replace(speed=sites.speed * (0.9 + 0.05 * i)),
        )
        for i, n in enumerate(sizes)
    ]
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    rs = simulate_many_sharded(scens, pol, jax.random.PRNGKey(5), mesh, topk=4)
    keys = jax.random.split(jax.random.PRNGKey(5), len(scens))
    for i, s in enumerate(scens):
        solo = simulate(s.jobs, s.sites, pol, keys[i], topk=4)
        assert float(solo.makespan) == float(np.asarray(rs.makespan)[i])
        assert (np.asarray(solo.jobs.state) == np.asarray(rs.jobs.state)[i]).all()


def check_candidates_contain_dense_argmax(seed: int, k: int, policy: str):
    """Membership guarantee behind the k<S gate: whenever a job has any
    feasible site, the candidate row contains the dense pre-rank argmax.
    Shared with the hypothesis-driven property in test_properties.py."""
    jobs = synthetic_panda_jobs(20, seed=seed, duration=600.0)
    sites = atlas_like_platform(6, seed=seed + 1)
    pol = get_policy(policy)
    key = jax.random.PRNGKey(seed)
    S = sites.capacity
    cand = np.asarray(build_candidates(jobs, sites, pol, None, 0.0, key, {}, k))
    feas = np.asarray(static_feasibility(jobs, sites))
    pre_fn = getattr(pol, "pre_rank", None) or pol.score
    masked = np.where(feas, np.asarray(pre_fn(jobs, sites, None, 0.0, key)), -np.inf)
    best = masked.argmax(-1)
    any_feas = feas.any(-1)
    # rows sorted ascending, sentinel S pads the tail
    assert (np.sort(cand, -1) == cand).all()
    in_range = np.clip(cand, 0, S - 1)
    assert ((cand == S) | feas[np.arange(len(cand))[:, None], in_range]).all()
    assert (cand[any_feas] == best[any_feas, None]).any(-1).all()


@pytest.mark.parametrize("policy", ["data_locality", "fastest_site", "least_loaded"])
@pytest.mark.parametrize("k", [1, 3, 6])
def test_candidates_always_contain_dense_argmax(policy, k):
    for seed in (0, 7, 123):
        check_candidates_contain_dense_argmax(seed, k, policy)


def test_packed_start_order_matches_lexsort():
    """The packed single-key start order (engine fast path) must reproduce
    the 5-key lexsort permutation exactly, solo and under vmap."""
    jobs = synthetic_panda_jobs(50, seed=3, duration=600.0)
    J, S = jobs.capacity, 5
    assert _packed_order_ok(get_policy("panda_dispatch"), J, S)
    srank = _static_start_rank(jobs)
    key = jax.random.PRNGKey(0)
    zeros = jnp.zeros((J,), jnp.float32)
    for i in range(4):
        sort_site = jax.random.randint(jax.random.fold_in(key, i), (J,), 0, S + 1)
        ref = _start_order(sort_site.astype(jnp.int32), jobs.priority, zeros, jobs.arrival)
        packed = _start_order_packed(sort_site.astype(jnp.int32) * J + srank)
        assert (np.asarray(ref) == np.asarray(packed)).all()
    # batched (ensemble) path: custom_vmap batch rule agrees with per-lane solo
    sort_b = jax.random.randint(key, (3, J), 0, S + 1).astype(jnp.int32)
    batched = jax.vmap(lambda ss: _start_order_packed(ss * J + srank))(sort_b)
    for lane in range(3):
        solo = _start_order_packed(sort_b[lane] * J + srank)
        assert (np.asarray(batched[lane]) == np.asarray(solo)).all()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_static_start_rank_matches_lexsort(seed):
    """The static rank's single-key i32 sorts reproduce the 3-key float
    lexsort, including ties, signed zeros, infinities and NaN."""
    rng = np.random.default_rng(seed)
    J = 700
    edge = np.array([0.0, -0.0, 1.5, -3.0, np.inf, -np.inf, np.nan, 1e-30, -1e-30],
                    np.float32)
    jobs = synthetic_panda_jobs(J, seed=seed)
    arrival = rng.choice(edge, J) if seed else np.asarray(jobs.arrival)
    priority = rng.choice(edge, J) if seed != 1 else np.asarray(jobs.priority)
    jobs = jobs._replace(arrival=jnp.asarray(arrival), priority=jnp.asarray(priority))
    perm = jnp.lexsort((jnp.arange(J), jobs.arrival, -jobs.priority))
    ref = jnp.zeros((J,), jnp.int32).at[perm].set(jnp.arange(J, dtype=jnp.int32))
    assert (np.asarray(_static_start_rank(jobs)) == np.asarray(ref)).all()


def test_rank_policy_disables_packed_order():
    """Policies with a dynamic rank hook must keep the general lexsort."""
    pol = get_policy("critical_path_first")
    if getattr(pol, "rank", None) is not None:
        assert not _packed_order_ok(pol, 100, 4)
    # key-width overflow also disables the fast path
    assert not _packed_order_ok(get_policy("panda_dispatch"), 2**28, 300)


def test_bytes_per_round_model():
    m = bytes_per_round(100_000, 300, 16)
    assert m["dense"] == 100_000 * 300 * 9
    assert m["sparse"] == 100_000 * 16 * 9 + 300
    assert m["ratio"] > 18
    assert bytes_per_round(10, 4, None)["sparse"] is None
