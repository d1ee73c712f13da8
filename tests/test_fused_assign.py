"""Fused candidate-set assignment kernel (kernels/assign/fused.py).

Three layers of parity, all exact:
  1. kernel (interpret mode on CPU) ≡ jnp oracle on random candidate sets,
  2. with candidates = all sites, fused ≡ the dense k=1 assignment oracle
     (same pick, same FIFO admission),
  3. end-to-end through the engine: ``simulate(topk=S)`` with the fused
     assigner ≡ dense ``with_capacity_assign`` bit-for-bit, oracle and
     interpret-mode kernel alike.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    atlas_like_platform,
    get_policy,
    simulate,
    synthetic_panda_jobs,
    with_capacity_assign,
    with_fused_assign,
)
from repro.kernels.assign.fused import fused_assign_pallas, fused_assign_ref
from repro.kernels.assign.ops import make_capacity_assign, make_fused_capacity_assign
from repro.kernels.assign.ref import assign_ref


def _random_case(seed, N=97, E=7, K=4):
    rng = np.random.default_rng(seed)
    scores = rng.normal(size=(N, K)).astype(np.float32)
    # candidate rows: sorted-ascending distinct site ids with sentinel E pads
    cand = np.full((N, K), E, np.int32)
    for i in range(N):
        n = rng.integers(0, K + 1)
        cand[i, :n] = np.sort(rng.choice(E, size=n, replace=False))
    sizes = rng.integers(1, 4, size=N).astype(np.float32)
    caps = rng.integers(0, 40, size=E).astype(np.float32)
    return jnp.asarray(scores), jnp.asarray(cand), jnp.asarray(sizes), jnp.asarray(caps)


def test_fused_kernel_matches_oracle_random():
    for seed in range(5):
        scores, cand, sizes, caps = _random_case(seed)
        s_ref, a_ref = fused_assign_ref(scores, cand, sizes, caps, block_n=32)
        s_ker, a_ker = fused_assign_pallas(
            scores, cand, sizes, caps, block_n=32, interpret=True
        )
        assert (np.asarray(s_ref) == np.asarray(s_ker)).all()
        assert (np.asarray(a_ref) == np.asarray(a_ker)).all()


def test_fused_empty_rows_never_admit():
    scores, cand, sizes, caps = _random_case(0)
    cand = jnp.full_like(cand, caps.shape[0])  # all-sentinel rows
    site, admit = fused_assign_ref(scores, cand, sizes, caps)
    assert (np.asarray(site) == -1).all() and not np.asarray(admit).any()


def test_fused_full_candidates_match_dense_assign():
    """cand = all sites ascending -> fused pick + admission == the dense k=1
    oracle on the equivalent masked [N, E] score matrix."""
    rng = np.random.default_rng(42)
    N, E = 64, 5
    dense = jnp.asarray(rng.normal(size=(N, E)).astype(np.float32))
    feas = jnp.asarray(rng.random((N, E)) < 0.7)
    sizes = jnp.ones((N,), jnp.float32)
    caps = jnp.asarray(rng.integers(2, 12, size=E).astype(np.float32))
    NEG = jnp.float32(-1e30)

    cand = jnp.where(feas, jnp.arange(E)[None, :], E).astype(jnp.int32)
    cand = jnp.sort(cand, axis=-1)
    scores_k = jnp.where(cand < E, jnp.take_along_axis(
        dense, jnp.clip(cand, 0, E - 1), axis=-1), NEG)
    s_f, a_f = fused_assign_ref(scores_k, cand, sizes, caps)

    idx, gate, admit, pos = assign_ref(jnp.where(feas, dense, NEG), sizes, caps, k=1)
    ok_dense = np.asarray(feas).any(-1)
    assert (np.asarray(a_f) == (np.asarray(admit)[:, 0] & ok_dense)).all()
    assert (np.asarray(s_f)[ok_dense] == np.asarray(idx)[ok_dense, 0]).all()
    assert (np.asarray(s_f)[~ok_dense] == -1).all()


def _trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if not np.array_equal(x, y, equal_nan=np.issubdtype(x.dtype, np.floating)):
            return False
    return True


def test_engine_fused_topk_full_equals_dense_capacity_assign():
    jobs = synthetic_panda_jobs(60, seed=11, duration=900.0)
    sites = atlas_like_platform(4, seed=12, fail_rate=0.05)
    key = jax.random.PRNGKey(0)
    base = get_policy("panda_dispatch")
    dense_pol = with_capacity_assign(
        base, make_capacity_assign(jobs_cores=jobs.cores, use_kernel=False)
    )
    res_dense = simulate(jobs, sites, dense_pol, key)
    for interpret in (False, True):  # jnp oracle, interpret-mode kernel
        fused_pol = with_fused_assign(
            base,
            make_fused_capacity_assign(
                jobs_cores=jobs.cores, use_kernel=interpret, interpret=interpret
            ),
        )
        res_fused = simulate(jobs, sites, fused_pol, key, topk=sites.capacity)
        assert _trees_equal(res_dense, res_fused), f"interpret={interpret}"


def test_engine_fused_small_k_runs_and_completes():
    """k < S through the fused assigner: approximation, but every job still
    terminates and capacity accounting stays consistent."""
    jobs = synthetic_panda_jobs(60, seed=11, duration=900.0)
    sites = atlas_like_platform(4, seed=12)
    pol = with_fused_assign(
        get_policy("panda_dispatch"),
        make_fused_capacity_assign(jobs_cores=jobs.cores, use_kernel=False),
    )
    res = simulate(jobs, sites, pol, jax.random.PRNGKey(0), topk=2)
    state = np.asarray(res.jobs.state)[np.asarray(res.jobs.valid)]
    assert (state >= 4).all()  # DONE or FAILED, nothing stuck
    assert int(np.asarray(res.sites.n_assigned).sum()) >= 60
