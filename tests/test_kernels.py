"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles,
shape/dtype sweeps (EXAMPLE.md contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.assign.assign import assign_pallas
from repro.kernels.assign.ops import assign, make_capacity_assign, moe_route
from repro.kernels.assign.ref import assign_ref
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ops import chunked_attention, decode_attention
from repro.kernels.flash_attention.ref import attention_ref


# ---------------------------------------------------------------- assign ---

ASSIGN_CASES = [
    # (N, E, k, block_n)
    (64, 8, 1, 32),
    (128, 16, 2, 64),
    (256, 384, 8, 256),   # kimi-k2 router shape class
    (100, 50, 1, 256),    # jobs x sites, single block
    (33, 7, 3, 16),       # ragged tail
    (512, 32, 8, 128),    # granite router shape class
]


@pytest.mark.parametrize("N,E,k,bn", ASSIGN_CASES)
def test_assign_matches_ref(N, E, k, bn):
    rng = np.random.default_rng(N * 31 + E)
    scores = rng.normal(size=(N, E)).astype(np.float32)
    scores[rng.random((N, E)) < 0.1] = -1e30
    sizes = rng.choice([1.0, 2.0, 8.0], size=N).astype(np.float32)
    caps = rng.uniform(2, 40, size=E).astype(np.float32)
    r = assign_ref(jnp.array(scores), jnp.array(sizes), jnp.array(caps), k=k, block_n=bn)
    p = assign_pallas(
        jnp.array(scores), jnp.array(sizes), jnp.array(caps), k=k, block_n=bn, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(r[0]), np.asarray(p[0]))  # idx
    np.testing.assert_allclose(np.asarray(r[1]), np.asarray(p[1]), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(r[2]), np.asarray(p[2]))  # admit
    np.testing.assert_allclose(np.asarray(r[3]), np.asarray(p[3]), rtol=1e-5, atol=1e-5)


def test_assign_respects_capacity_exactly():
    # all items want bin 0; capacity 10 units; sizes 3 => exactly 3 admitted
    N = 16
    scores = jnp.zeros((N, 4)).at[:, 0].set(10.0)
    sizes = jnp.full((N,), 3.0)
    caps = jnp.array([10.0, 100.0, 100.0, 100.0])
    idx, gate, admit, pos = assign(scores, sizes, caps, k=1, use_kernel=True, interpret=True)
    assert int(admit.sum()) == 3
    assert (np.asarray(idx)[:, 0] == 0).all()
    np.testing.assert_allclose(np.asarray(pos)[:4, 0], [0.0, 3.0, 6.0, 9.0])


def test_assign_infeasible_rows():
    scores = jnp.full((8, 4), -1e30)
    idx, gate, admit, pos = assign(scores, jnp.ones(8), jnp.full(4, 100.0), k=2, interpret=True)
    assert (np.asarray(idx) == -1).all()
    assert not np.asarray(admit).any()
    assert (np.asarray(gate) == 0).all()


def test_moe_route_slots_unique_per_expert():
    T, E, k, cap = 256, 16, 2, 24
    logits = jax.random.normal(jax.random.PRNGKey(0), (T, E))
    idx, combine, slot, keep = moe_route(logits, k=k, capacity=cap, interpret=True)
    idx, slot, keep = map(np.asarray, (idx, slot, keep))
    # kept (expert, slot) pairs must be unique and < capacity
    pairs = [(int(e), int(s)) for e, s, kp in
             zip(idx.ravel(), slot.ravel(), keep.ravel()) if kp]
    assert len(pairs) == len(set(pairs))
    assert all(0 <= s < cap for _, s in pairs)
    assert np.asarray(combine).min() >= 0


def test_capacity_assign_engine_combinator():
    from repro.core import make_sites

    sites = make_sites(cores=[4, 2], speed=[10.0, 10.0], memory=[64.0, 64.0],
                       bw_in=[1e9, 1e9], bw_out=[1e9, 1e9])
    J = 6
    scores = jnp.zeros((J, 2)).at[:, 0].set(1.0)  # all prefer site 0 (4 cores)
    queued = jnp.ones((J,), bool)
    feasible = jnp.ones((J, 2), bool)
    fn = make_capacity_assign(jobs_cores=jnp.full((J,), 2, jnp.int32))
    site, ok = fn(scores, queued, feasible, sites)
    assert int(ok.sum()) == 2          # 2x 2-core jobs fit site 0
    assert (np.asarray(site)[np.asarray(ok)] == 0).all()


# ------------------------------------------------------- flash attention ---

FLASH_CASES = [
    # (B, Hq, Hkv, S, D, window, dtype)
    (1, 4, 4, 256, 64, 0, jnp.float32),
    (2, 8, 2, 128, 64, 0, jnp.float32),      # GQA 4:1
    (1, 4, 1, 384, 128, 0, jnp.float32),     # MQA, ragged seq -> padding
    (1, 4, 2, 256, 64, 64, jnp.float32),     # sliding window
    (1, 8, 8, 256, 64, 0, jnp.bfloat16),
    (2, 4, 2, 200, 64, 96, jnp.bfloat16),    # window + padding
]


@pytest.mark.parametrize("B,Hq,Hkv,S,D,window,dtype", FLASH_CASES)
def test_flash_matches_ref(B, Hq, Hkv, S, D, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * 131 + S), 3)
    q = jax.random.normal(ks[0], (B, Hq, S, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    out = flash_attention_pallas(q, k, v, causal=True, window=window, interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("window", [0, 128])
def test_chunked_attention_matches_ref(window):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, Hq, Hkv, S, D = 2, 8, 2, 320, 64
    q = jax.random.normal(ks[0], (B, Hq, S, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, D))
    out = chunked_attention(q, k, v, causal=True, window=window, chunk=128)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_chunked_attention_is_differentiable():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32))
    k = jax.random.normal(ks[1], (1, 2, 64, 32))
    v = jax.random.normal(ks[2], (1, 2, 64, 32))
    g = jax.grad(lambda q: chunked_attention(q, k, v, chunk=32).sum())(q)
    assert np.isfinite(np.asarray(g)).all()


def test_decode_attention_matches_full_prefix():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    B, Hq, Hkv, Skv, D = 2, 4, 2, 96, 64
    cache_k = jax.random.normal(ks[0], (B, Hkv, Skv, D))
    cache_v = jax.random.normal(ks[1], (B, Hkv, Skv, D))
    q = jax.random.normal(ks[2], (B, Hq, 1, D))
    kv_len = jnp.array([64, 96])
    out = decode_attention(q, cache_k, cache_v, kv_len=kv_len)
    for b in range(B):
        L = int(kv_len[b])
        ref = attention_ref(
            q[b : b + 1], cache_k[b : b + 1, :, :L], cache_v[b : b + 1, :, :L], causal=True
        )
        np.testing.assert_allclose(
            np.asarray(out[b]), np.asarray(ref[0]), rtol=2e-5, atol=2e-5
        )


def test_decode_attention_window_matches_windowed_ref():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    B, Hq, Hkv, Skv, D, W = 1, 4, 1, 128, 32, 32
    cache_k = jax.random.normal(ks[0], (B, Hkv, Skv, D))
    cache_v = jax.random.normal(ks[1], (B, Hkv, Skv, D))
    q = jax.random.normal(ks[2], (B, Hq, 1, D))
    out = decode_attention(q, cache_k, cache_v, kv_len=Skv, window=W)
    ref = attention_ref(q, cache_k[:, :, -W:], cache_v[:, :, -W:], causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
