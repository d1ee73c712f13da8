"""Beyond-paper: the assignment kernel family (CGSim assignJob == MoE router,
DESIGN.md §3) — jnp oracle vs the Pallas kernel on simulator- and
router-shaped problems.  The full configuration runs the compiled kernel and
needs a TPU; ``--tiny`` runs the kernel in the Pallas interpreter, which
measures semantics, not speed."""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.assign.ops import assign, make_capacity_assign
from repro.kernels.assign.ref import assign_ref

from .common import csv_row, timed


def main():
    tiny = "--tiny" in sys.argv
    cases = [
        ("jobs_x_sites", 4096, 64, 1),      # simulator dispatch shape
        ("tokens_x_experts_granite", 8192, 32, 8),
        ("tokens_x_experts_kimi", 4096, 384, 8),
    ]
    if tiny:
        # seconds-sized CI smoke: still drives the Pallas kernel (interpret
        # mode on CPU) against the jnp oracle, just on a small shape
        cases = [("tiny_smoke", 256, 8, 1)]
    print("# assignment kernel (jobs->sites == tokens->experts)")
    for name, N, E, k in cases:
        rng = np.random.default_rng(0)
        scores = jnp.asarray(rng.normal(size=(N, E)).astype(np.float32))
        sizes = jnp.ones((N,), jnp.float32)
        caps = jnp.full((E,), max(4.0, N * k / E * 1.25), jnp.float32)
        f_ref = jax.jit(lambda s: assign_ref(s, sizes, caps, k=k))
        t_ref = timed(f_ref, scores)
        print(csv_row(f"assign_ref_{name}", t_ref * 1e6, f"N={N};E={E};k={k}"))
        # kernel correctness spot check vs oracle on this shape
        out_k = assign(scores, sizes, caps, k=k, use_kernel=True, interpret=tiny)
        out_r = assign(scores, sizes, caps, k=k, use_kernel=False)
        ok = all(
            np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)
            for a, b in zip(out_k, out_r)
        )
        print(csv_row(f"assign_pallas_match_{name}", 0.0, f"allclose={ok}"))

    if tiny:
        # the engine-facing combinator: backend-aware default (kernel on TPU,
        # jnp oracle elsewhere) plus a forced-kernel interpret-mode row so CI
        # exercises the Pallas path end-to-end through the Policy API
        from repro.core import get_policy, simulate, with_capacity_assign
        from repro.core.platform import atlas_like_platform
        from repro.core.workload import synthetic_panda_jobs

        jobs = synthetic_panda_jobs(48, seed=0, duration=300.0)
        sites = atlas_like_platform(3, seed=1)
        auto = jax.default_backend() == "tpu"
        results = {}
        for tag, flag in (("backend_default", None), ("forced_kernel", True)):
            pol = with_capacity_assign(
                get_policy("panda_dispatch"),
                make_capacity_assign(jobs_cores=jobs.cores, use_kernel=flag,
                                     interpret=bool(flag)),
            )
            t0 = time.perf_counter()
            res = simulate(jobs, sites, pol, jax.random.PRNGKey(0))
            ms = float(res.makespan)
            results[tag] = ms
            print(csv_row(
                f"capacity_assign_{tag}", (time.perf_counter() - t0) * 1e6,
                f"use_kernel={'tpu-auto' if flag is None else flag};"
                f"backend={jax.default_backend()};auto_resolves={auto}",
            ))
        match = results["backend_default"] == results["forced_kernel"]
        print(csv_row("capacity_assign_kernel_match", 0.0, f"equal={match}"))

        # fused candidate-set kernel (sparse top-k path, fused.py): interpret
        # -mode smoke through the engine — topk=S with the fused assign must
        # reproduce the dense makespan bit-for-bit, kernel and oracle alike
        from repro.core import with_fused_assign
        from repro.kernels.assign.ops import make_fused_capacity_assign

        dense_pol = with_capacity_assign(
            get_policy("panda_dispatch"),
            make_capacity_assign(jobs_cores=jobs.cores, use_kernel=False),
        )
        res_d = simulate(jobs, sites, dense_pol, jax.random.PRNGKey(0))
        ms_dense = float(res_d.makespan)
        fused = {}
        for tag, flag in (("oracle", False), ("interpret_kernel", True)):
            pol = with_fused_assign(
                get_policy("panda_dispatch"),
                make_fused_capacity_assign(jobs_cores=jobs.cores, use_kernel=flag,
                                           interpret=flag),
            )
            t0 = time.perf_counter()
            res = simulate(jobs, sites, pol, jax.random.PRNGKey(0),
                           topk=sites.capacity)
            fused[tag] = float(res.makespan)
            print(csv_row(
                f"fused_assign_{tag}", (time.perf_counter() - t0) * 1e6,
                f"use_kernel={flag};topk={sites.capacity}",
            ))
        ok = all(v == ms_dense for v in fused.values())
        print(csv_row("fused_assign_match", 0.0, f"equal_dense={ok}"))


if __name__ == "__main__":
    main()
