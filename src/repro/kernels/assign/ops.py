"""Jitted wrappers: the assignment kernel as (a) a simulator dispatch
combinator and (b) an MoE routing primitive."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .assign import assign_pallas
from .fused import fused_assign_pallas, fused_assign_ref
from .ref import assign_ref


def _resolve(use_kernel: bool | None, interpret: bool) -> bool:
    """``use_kernel=None`` means the kernel on TPU or under ``interpret=True``,
    and the jnp oracle otherwise.  The Pallas interpreter runs only when a
    caller passes ``interpret=True``; the kernel off TPU without it fails to
    lower, loudly."""
    if use_kernel is None:
        return interpret or jax.default_backend() == "tpu"
    return use_kernel


@functools.partial(jax.jit, static_argnames=("k", "block_n", "use_kernel", "interpret"))
def assign(scores, sizes, caps, *, k: int = 1, block_n: int = 256, use_kernel: bool = True,
           interpret: bool = False):
    """Capacity-constrained greedy assignment (see assign.py for semantics)."""
    if use_kernel:
        return assign_pallas(scores, sizes, caps, k=k, block_n=block_n, interpret=interpret)
    return assign_ref(scores, sizes, caps, k=k, block_n=block_n)


def make_capacity_assign(
    jobs_cores: jax.Array | None = None, *, use_kernel: bool | None = None,
    interpret: bool = False, block_n: int = 256
):
    """Build an engine-compatible ``Policy.assign`` fn: jobs -> sites under
    free-core capacity; jobs beyond capacity stay QUEUED at the main server.

    ``use_kernel=None`` (the default) resolves by backend: the compiled
    Mosaic kernel on TPU, the jnp oracle elsewhere.  ``use_kernel=False``
    forces the oracle; ``interpret=True`` runs the kernel in the Pallas
    interpreter, the CPU smoke configuration (``bench_assign_kernel --tiny``).
    """
    use_kernel = _resolve(use_kernel, interpret)

    def assign_fn(scores, queued, feasible, sites):
        NEG = jnp.float32(-1e30)
        masked = jnp.where(feasible & queued[:, None], scores, NEG)
        sizes = jnp.ones((scores.shape[0],), jnp.float32) if jobs_cores is None else (
            jobs_cores.astype(jnp.float32)
        )
        sizes = jnp.where(queued, sizes, 0.0)
        caps = jnp.where(sites.active, sites.free_cores, 0).astype(jnp.float32)
        idx, gate, admit, pos = assign(
            masked, sizes, caps, k=1, block_n=block_n, use_kernel=use_kernel,
            interpret=interpret,
        )
        ok = admit[:, 0] & queued
        return jnp.where(ok, idx[:, 0], -1), ok

    return assign_fn


@functools.partial(jax.jit, static_argnames=("block_n", "use_kernel", "interpret"))
def fused_topk_assign(scores_k, cand, sizes, caps, *, block_n: int = 256, use_kernel: bool = True,
                      interpret: bool = False):
    """Fused candidate-set rank + capacity pick (see fused.py for semantics)."""
    if use_kernel:
        return fused_assign_pallas(
            scores_k, cand, sizes, caps, block_n=block_n, interpret=interpret
        )
    return fused_assign_ref(scores_k, cand, sizes, caps, block_n=block_n)


def make_fused_capacity_assign(
    jobs_cores: jax.Array | None = None, *, use_kernel: bool | None = None,
    interpret: bool = False, block_n: int = 256
):
    """Build an engine-compatible ``Policy.assign_cand`` fn for sparse top-k
    mode (engine ``topk=``): rank the per-job candidate set and admit under
    free-core capacity in one fused pass, without ever materializing the
    dense ``[J, S]`` masked-score matrix that ``make_capacity_assign`` builds.

    With candidates covering all feasible sites (``topk >= S``) the result is
    bit-for-bit equal to the dense ``make_capacity_assign`` path.
    ``use_kernel`` and ``interpret`` resolve as in ``make_capacity_assign``.
    """
    use_kernel = _resolve(use_kernel, interpret)

    def assign_cand(scores_k, queued, feas_k, cand, sites):
        S = sites.capacity
        cand_eff = jnp.where(feas_k & queued[:, None], cand, S).astype(jnp.int32)
        sizes = jnp.ones((scores_k.shape[0],), jnp.float32) if jobs_cores is None else (
            jobs_cores.astype(jnp.float32)
        )
        sizes = jnp.where(queued, sizes, 0.0)
        caps = jnp.where(sites.active, sites.free_cores, 0).astype(jnp.float32)
        site, admit = fused_topk_assign(
            scores_k, cand_eff, sizes, caps, block_n=block_n, use_kernel=use_kernel,
            interpret=interpret,
        )
        ok = admit & queued
        return jnp.where(ok, site, -1), ok

    return assign_cand


@functools.partial(jax.jit, static_argnames=("k", "capacity", "use_kernel", "interpret", "block_n"))
def moe_route(router_logits, *, k: int, capacity: int, use_kernel: bool = True,
              interpret: bool = False, block_n: int = 256):
    """Token->expert routing for the MoE layer.

    router_logits f32[T, E] -> (expert i32[T,k], combine f32[T,k],
    slot i32[T,k], keep bool[T,k]) where ``slot`` is the token's position in
    its expert's capacity buffer.  Combine weights are renormalised over kept
    slots (Switch/GShard convention).
    """
    T, E = router_logits.shape
    sizes = jnp.ones((T,), jnp.float32)
    caps = jnp.full((E,), float(capacity), jnp.float32)
    idx, gate, admit, pos = assign(
        router_logits, sizes, caps, k=k, block_n=block_n, use_kernel=use_kernel,
        interpret=interpret,
    )
    keep = admit
    combine = gate * keep
    norm = jnp.maximum(combine.sum(-1, keepdims=True), 1e-9)
    combine = combine / norm * gate.sum(-1, keepdims=True).clip(0.0, 1.0)
    slot = pos.astype(jnp.int32)
    return idx, combine, slot, keep
