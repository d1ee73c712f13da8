"""Pallas TPU kernel: capacity-constrained greedy assignment.

The CGSim ``assignJob`` hot loop (jobs x sites) and the MoE router
(tokens x experts) are the same computation: score every item against every
bin, pick the best feasible bin per slot, admit under per-bin capacity
(DESIGN.md §3).  SimGrid walks pointers; on TPU we tile the score matrix
through VMEM and keep a per-bin ``used`` accumulator in scratch across the
sequential grid.

Tiling: grid = (N // block_n,); each step owns a [block_n, E] score tile.
E (bins: <=256 sites, <=512 experts) fits one VMEM tile, so only items are
tiled; the per-bin carry makes admission exact across tiles.  block_n and E
are padded to multiples of 128 to stay MXU/VPU aligned on the v5e target:
a 256x512 f32 tile is 512 KB — far inside the ~16 MB VMEM budget even with
the mask copy and outputs.

Semantics match ``ref.assign_ref`` exactly (same block-sequential order).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def exclusive_prefix_sum(w):
    """Exclusive running sum of ``w [bn, E]`` down the rows, in a kernel.

    Mosaic has no cumsum, so the sum is a strictly-lower-triangular
    ``[bn, bn]`` matmul.  At ``HIGHEST`` precision every f32 operand is split
    into exact bf16 parts and accumulated in f32, so integer core counts sum
    exactly (below 2**24), as the oracle's ``jnp.cumsum`` does."""
    bn = w.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
    tri = (col < row).astype(jnp.float32)
    return jnp.dot(tri, w, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _assign_kernel(
    scores_ref,  # [bn, E] f32 VMEM
    sizes_ref,   # [bn, 1] f32 VMEM
    caps_ref,    # [1, E]  f32 VMEM (same block every step)
    idx_ref,     # [bn, k] i32 out
    gate_ref,    # [bn, k] f32 out
    admit_ref,   # [bn, k] i32 out (bool as int32)
    pos_ref,     # [bn, k] f32 out
    used_ref,    # [1, E]  f32 scratch: per-bin units consumed so far
    *,
    k: int,
):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        used_ref[...] = jnp.zeros_like(used_ref)

    s = scores_ref[...]
    bn, E = s.shape
    sz = sizes_ref[...]  # [bn, 1]
    caps = caps_ref[...]  # [1, E]
    iota_e = jax.lax.broadcasted_iota(jnp.int32, (bn, E), 1)

    # row softmax over feasible bins (gate values for chosen bins)
    feas = s > NEG_INF / 2
    m = jnp.max(jnp.where(feas, s, -jnp.inf), axis=-1, keepdims=True)
    p = jnp.where(feas, jnp.exp(s - m), 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    gates = p / denom

    masked = s
    used = used_ref[...]  # [1, E]
    for slot in range(k):
        best_val = jnp.max(masked, axis=-1, keepdims=True)        # [bn, 1]
        is_best = masked >= best_val
        idx = jnp.min(jnp.where(is_best, iota_e, E), axis=-1, keepdims=True)  # [bn,1]
        ok = best_val > NEG_INF / 2                                # [bn, 1]
        onehot = (iota_e == idx) & ok                              # [bn, E]
        w = jnp.where(onehot, sz, 0.0)                             # [bn, E]
        cum_excl = exclusive_prefix_sum(w)                         # [bn, E]
        pos = jnp.sum(jnp.where(onehot, cum_excl + used, 0.0), axis=-1, keepdims=True)
        admit = ok & (pos + sz <= jnp.sum(jnp.where(onehot, caps, 0.0), -1, keepdims=True) + 1e-6)
        used = used + jnp.sum(w, axis=0, keepdims=True)            # FIFO claims
        gate = jnp.sum(jnp.where(onehot, gates, 0.0), -1, keepdims=True)

        idx_ref[:, slot] = jnp.where(ok, idx, -1)[:, 0]
        gate_ref[:, slot] = jnp.where(ok, gate, 0.0)[:, 0]
        admit_ref[:, slot] = admit.astype(jnp.int32)[:, 0]
        pos_ref[:, slot] = jnp.where(ok, pos, 0.0)[:, 0]
        masked = jnp.where(onehot, NEG_INF, masked)

    used_ref[...] = used


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
def assign_pallas(
    scores: jax.Array,  # f32[N, E]
    sizes: jax.Array,   # f32[N]
    caps: jax.Array,    # f32[E]
    *,
    k: int = 1,
    block_n: int = 256,
    interpret: bool = False,
):
    N, E = scores.shape
    nb = -(-N // block_n)
    pad_n = nb * block_n - N
    # lane-align E for the VPU; padded bins are infeasible (-inf, cap 0)
    pad_e = (-E) % 128
    Ep = E + pad_e
    scores_p = jnp.pad(
        scores.astype(jnp.float32), ((0, pad_n), (0, pad_e)), constant_values=NEG_INF
    )
    sizes_p = jnp.pad(sizes.astype(jnp.float32), ((0, pad_n),))[:, None]
    caps_p = jnp.pad(caps.astype(jnp.float32), ((0, pad_e),))[None, :]

    out_shape = (
        jax.ShapeDtypeStruct((nb * block_n, k), jnp.int32),
        jax.ShapeDtypeStruct((nb * block_n, k), jnp.float32),
        jax.ShapeDtypeStruct((nb * block_n, k), jnp.int32),
        jax.ShapeDtypeStruct((nb * block_n, k), jnp.float32),
    )
    out_spec = pl.BlockSpec((block_n, k), lambda i: (i, 0))
    idx, gate, admit, pos = pl.pallas_call(
        functools.partial(_assign_kernel, k=k),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_n, Ep), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, Ep), lambda i: (0, 0)),
        ],
        out_specs=(out_spec, out_spec, out_spec, out_spec),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((1, Ep), jnp.float32)],
        interpret=interpret,
    )(scores_p, sizes_p, caps_p)
    clip = lambda x: x[:N]
    return clip(idx), clip(gate), clip(admit).astype(bool), clip(pos)
