"""Pallas TPU kernel: fused candidate-set assignment (sparse top-k path).

The dense ``assign.py`` kernel ranks a full ``f32[N, E]`` score tile per
block.  In sparse top-k mode (engine ``topk=``, DESIGN.md §12) the per-job
score row is already compacted to ``K`` candidate sites — ``f32[N, K]``
scores plus an ``i32[N, K]`` site index with sentinel ``E`` marking empty
slots.  This kernel fuses the remaining pipeline — candidate rank, site
pick, and capacity-respecting FIFO admission — in one pass, so the dense
``[N, E]`` masked-score intermediate of ``make_capacity_assign`` never
materializes: per block only the tiny ``[bn, K]`` tiles and the one-hot
admission tile touch VMEM.

Semantics (k=1 FIFO admission, same contract as ``assign.py``):
  - per row, the best valid candidate wins; ties break to the *lowest slot*,
    which equals the dense lowest-site-id tie-break because the engine's
    candidate rows are sorted ascending by site id (``sparse.build_candidates``),
  - admission consumes per-site capacity in item order via a weighted prefix
    sum, with a ``used[1, E]`` VMEM carry across the sequential grid,
  - claims accumulate whether or not admitted (FIFO head-of-line blocking,
    matching the engine's start phase and ``ref.assign_ref``).

With candidates = all feasible sites (``k >= S``) this is bit-for-bit the
dense ``make_capacity_assign`` pick — the property ``tests/test_fused_assign``
checks against the jnp oracle and the dense kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .assign import exclusive_prefix_sum

NEG_INF = -1e30


def _fused_kernel(
    scores_ref,  # [bn, Kp] f32 VMEM: candidate scores (NEG_INF pad)
    cand_ref,    # [bn, Kp] i32 VMEM: candidate site ids (sentinel >= n_sites)
    sizes_ref,   # [bn, 1]  f32 VMEM
    caps_ref,    # [1, Ep]  f32 VMEM (same block every step)
    site_ref,    # [bn, 1]  i32 out
    admit_ref,   # [bn, 1]  i32 out (bool as int32)
    used_ref,    # [1, Ep]  f32 scratch: per-site units consumed so far
    *,
    n_sites: int,
):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        used_ref[...] = jnp.zeros_like(used_ref)

    sc = scores_ref[...]
    cd = cand_ref[...]
    bn, Kp = sc.shape
    caps = caps_ref[...]  # [1, Ep]
    Ep = caps.shape[-1]
    sz = sizes_ref[...]  # [bn, 1]

    # rank: best valid candidate per row, ties to the lowest slot (= lowest
    # site id, candidate rows are sorted ascending)
    valid = cd < n_sites
    v = jnp.where(valid, sc, NEG_INF)
    best_val = jnp.max(v, axis=-1, keepdims=True)  # [bn, 1]
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (bn, Kp), 1)
    slot = jnp.min(jnp.where(v >= best_val, iota_k, Kp), axis=-1, keepdims=True)
    site = jnp.sum(jnp.where(iota_k == slot, cd, 0), axis=-1, keepdims=True)  # [bn,1]
    ok = best_val > NEG_INF / 2

    # capacity-respecting FIFO pick: scatter to the site lane, prefix-sum
    # claims in item order, admit under cap with the cross-block used carry
    iota_e = jax.lax.broadcasted_iota(jnp.int32, (bn, Ep), 1)
    onehot = (iota_e == site) & ok  # [bn, Ep]
    w = jnp.where(onehot, sz, 0.0)
    cum_excl = exclusive_prefix_sum(w)
    used = used_ref[...]
    pos = jnp.sum(jnp.where(onehot, cum_excl + used, 0.0), axis=-1, keepdims=True)
    cap_at = jnp.sum(jnp.where(onehot, caps, 0.0), axis=-1, keepdims=True)
    admit = ok & (pos + sz <= cap_at + 1e-6)
    used_ref[...] = used + jnp.sum(w, axis=0, keepdims=True)  # FIFO claims

    site_ref[:, 0] = jnp.where(ok, site, -1)[:, 0]
    admit_ref[:, 0] = admit.astype(jnp.int32)[:, 0]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def fused_assign_pallas(
    scores_k: jax.Array,  # f32[N, K] candidate scores
    cand: jax.Array,      # i32[N, K] candidate site ids (sentinel >= E)
    sizes: jax.Array,     # f32[N]
    caps: jax.Array,      # f32[E]
    *,
    block_n: int = 256,
    interpret: bool = False,
):
    N, K = scores_k.shape
    E = caps.shape[0]
    nb = -(-N // block_n)
    pad_n = nb * block_n - N
    # lane-align both the candidate axis and the site axis; padded slots are
    # sentinel candidates, padded sites have cap 0 and are never picked
    pad_k = (-K) % 128
    pad_e = (-E) % 128
    Ep = E + pad_e
    scores_p = jnp.pad(
        scores_k.astype(jnp.float32), ((0, pad_n), (0, pad_k)), constant_values=NEG_INF
    )
    cand_p = jnp.pad(cand.astype(jnp.int32), ((0, pad_n), (0, pad_k)), constant_values=E)
    sizes_p = jnp.pad(sizes.astype(jnp.float32), ((0, pad_n),))[:, None]
    caps_p = jnp.pad(caps.astype(jnp.float32), ((0, pad_e),))[None, :]
    Kp = K + pad_k

    out_shape = (
        jax.ShapeDtypeStruct((nb * block_n, 1), jnp.int32),
        jax.ShapeDtypeStruct((nb * block_n, 1), jnp.int32),
    )
    out_spec = pl.BlockSpec((block_n, 1), lambda i: (i, 0))
    site, admit = pl.pallas_call(
        functools.partial(_fused_kernel, n_sites=E),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_n, Kp), lambda i: (i, 0)),
            pl.BlockSpec((block_n, Kp), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, Ep), lambda i: (0, 0)),
        ],
        out_specs=(out_spec, out_spec),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((1, Ep), jnp.float32)],
        interpret=interpret,
    )(scores_p, cand_p, sizes_p, caps_p)
    return site[:N, 0], admit[:N, 0].astype(bool)


def fused_assign_ref(scores_k, cand, sizes, caps, *, block_n: int = 256):
    """jnp oracle with identical block-sequential semantics (see module doc).

    Returns ``(site i32[N], admit bool[N])``; ``site`` is -1 when no valid
    candidate exists.
    """
    N, K = scores_k.shape
    E = caps.shape[0]
    scores_k = scores_k.astype(jnp.float32)
    sizes = sizes.astype(jnp.float32)
    caps = caps.astype(jnp.float32)

    valid = cand < E
    v = jnp.where(valid, scores_k, NEG_INF)
    best_slot = jnp.argmax(v, axis=-1)  # first max = lowest slot = lowest site
    site = jnp.take_along_axis(cand, best_slot[:, None], axis=-1)[:, 0]
    ok = jnp.take_along_axis(v, best_slot[:, None], axis=-1)[:, 0] > NEG_INF / 2
    site_c = jnp.clip(site, 0, E - 1).astype(jnp.int32)

    nb = -(-N // block_n)
    pad = nb * block_n - N
    site_b = jnp.pad(site_c, ((0, pad),)).reshape(nb, block_n)
    ok_b = jnp.pad(ok, ((0, pad),)).reshape(nb, block_n)
    sz_b = jnp.pad(sizes, ((0, pad),)).reshape(nb, block_n)

    def block_step(used, blk):
        st, okb, szb = blk  # [bn] each
        iota = jnp.arange(E)[None, :]
        onehot = (iota == st[:, None]) & okb[:, None]
        w = onehot * szb[:, None]
        cum_excl = jnp.cumsum(w, axis=0) - w
        pos = (cum_excl * onehot).sum(-1) + used[st]
        admit = okb & (pos + szb <= caps[st] + 1e-6)
        # claims accumulate whether or not admitted: FIFO head-of-line
        used = used + w.sum(0)
        return used, admit

    used0 = jnp.zeros((E,), jnp.float32)
    _, admit_b = jax.lax.scan(block_step, used0, (site_b, ok_b, sz_b))
    admit = admit_b.reshape(nb * block_n)[:N]
    return jnp.where(ok, site, -1).astype(jnp.int32), admit & ok
