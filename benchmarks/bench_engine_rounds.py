"""Engine round-loop throughput + scenario-ensemble scaling (ISSUE 4/5).

Numbers the perf trajectory tracks across commits:

- ``rounds_per_sec``: raw event-round throughput of one ``simulate`` call —
  the denominator every subsystem's overhead is priced against.
- ``ensemble_speedup_16``: end-to-end throughput of ``simulate_many`` over a
  Python loop of ``simulate`` calls for the same 16-scenario ensemble.  The
  ensemble is *ragged* — every scenario has a different workload size, the
  normal shape of surrogate-dataset generation — so the loop retraces and
  recompiles per scenario while ``stack_scenarios`` pads the batch to one
  static shape and the whole ensemble runs from a single compile (the ISSUE 4
  acceptance row; target >= 3x, measured end-to-end including compilation,
  which dominates exactly like it does in real sweep workloads).
- ``ensemble_bucketed_16``: the same ragged ensemble through
  ``stack_scenarios(buckets=4)`` — a few padded shape buckets instead of one
  global-max pad, trading a handful of compiles for fewer wasted dense rows
  (DESIGN.md §8).
- ``ensemble_steady_*`` and ``ensemble_sharded_*``: the warm-cache steady
  state, measured over the accelerator's devices in this process, or on CPU
  in a subprocess whose host platform is forced to ``--devices`` (default 4)
  devices.  ``ensemble_steady_many_16`` runs
  the ensemble through ``simulate_many_sharded`` on the full mesh — each
  device retires its own lane block in its own while_loop (no global
  lock-step) — and its ratio against the solo-``simulate`` loop *measured in
  the same process* is the ISSUE 5 acceptance row (target >= 1.0).  The
  ``ensemble_sharded_{n}dev`` rows scale the mesh 1 -> ``--devices`` inside
  that fixed environment to show the near-linear shard scaling.

``--tiny`` is the seconds-sized CI smoke configuration.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    Scenario,
    atlas_like_platform,
    get_policy,
    simulate,
    simulate_many,
    stack_scenarios,
    synthetic_panda_jobs,
)

from .common import csv_row

K = 16
N_BUCKETS = 4


def _timed(fn, iters=3):
    fn()  # compile
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _once(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _arg_after(flag: str, default: str) -> str:
    if flag in sys.argv:
        i = sys.argv.index(flag)
        if i + 1 < len(sys.argv):
            return sys.argv[i + 1]
    return default


def _ragged_ensemble(tiny: bool):
    """The shared ragged 16-scenario ensemble: every scenario a different
    workload size (all distinct static shapes), the natural raggedness of
    scenario sweeps."""
    n_sites = 4 if tiny else 8
    rag_sizes = range(48, 48 + 2 * K, 2) if tiny else range(200, 200 + 8 * K, 8)
    sites = atlas_like_platform(n_sites, seed=1)
    factors = jnp.linspace(0.5, 2.0, K)
    scenarios = [
        Scenario(
            synthetic_panda_jobs(n, seed=10 + i, duration=1800.0),
            sites._replace(speed=sites.speed * factors[i]),
        )
        for i, n in enumerate(rag_sizes)
    ]
    return scenarios, rag_sizes


def _ensemble_worker(tiny: bool) -> None:
    """Runs over every device of this process (on CPU, a subprocess whose
    host platform is forced to N devices): the steady-state (warm jit cache) ensemble rows, all measured in this one
    fixed environment so loop / vmap / sharded compare apples-to-apples.

    - ``ensemble_sharded_{d}dev`` rows share the *same* flat stacked input
      across mesh sizes — pure device scaling, nothing else varies.
    - ``ensemble_steady_many_16`` is the recommended ensemble configuration
      (bucketed stacking + sharding over the full mesh + lane-sequential
      lock-step-free execution), compared against both the solo-``simulate``
      loop (the ISSUE 5 >=1.0 ratio) and the 1-device ensemble run (the
      >=2x sharded-scaling acceptance).
    """
    from repro.core.distributed import simulate_many_sharded

    n_dev = jax.device_count()
    pol = get_policy("panda_dispatch")
    scenarios, _ = _ragged_ensemble(tiny)
    stacked = stack_scenarios(scenarios)
    bucketed = stack_scenarios(scenarios, buckets=N_BUCKETS)
    keys = jax.random.split(jax.random.PRNGKey(2), K)
    iters = 2 if tiny else 5

    warm = [jax.tree.map(lambda x: x[i], Scenario(stacked.jobs, stacked.sites, {}))
            for i in range(K)]

    def loop():
        for i in range(K):
            jax.block_until_ready(
                simulate(warm[i].jobs, warm[i].sites, pol, keys[i]).makespan
            )

    t_loop = _timed(loop, iters)
    print(csv_row("ensemble_steady_loop_16", t_loop * 1e6, f"devices={n_dev}"))

    # the status-quo single-device ensemble: plain vmapped simulate_many
    # (global lock-step, batched rounds) — the "1 device" the sharded stack
    # is measured against
    t_vmap1 = _timed(
        lambda: jax.block_until_ready(
            simulate_many(stacked, pol, jax.random.PRNGKey(2)).makespan
        ),
        iters,
    )
    print(csv_row(
        "ensemble_steady_vmap_1dev", t_vmap1 * 1e6,
        f"ratio_vs_loop=x{t_loop / t_vmap1:.2f}",
    ))

    # mesh scaling 1 -> n_dev: same flat stacked input over each mesh size
    t_by_dev = {}
    d = 1
    sizes = []
    while d <= n_dev:
        sizes.append(d)
        d *= 2
    if sizes[-1] != n_dev:
        sizes.append(n_dev)
    # donate=False + pre-placed inputs throughout: steady-state throughput
    # reuses the stacked lane buffers call-to-call, so the on-mesh placement
    # is paid once instead of re-copied (for donation) every iteration
    from jax.sharding import NamedSharding, PartitionSpec

    def place(tree, mesh):
        sh = NamedSharding(mesh, PartitionSpec("data"))
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    for d in sizes:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:d]), ("data",))
        placed = place(stacked, mesh)
        t = _timed(
            lambda: jax.block_until_ready(
                simulate_many_sharded(
                    placed, pol, jax.random.PRNGKey(2), mesh, donate=False
                ).makespan
            ),
            iters,
        )
        t_by_dev[d] = t
        print(csv_row(
            f"ensemble_sharded_{d}dev", t * 1e6,
            f"speedup_vs_1dev=x{t_by_dev[1] / t:.2f}",
        ))

    # the full ISSUE 5 stack: bucketed + sharded + lane-sequential
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    bucketed = type(bucketed)(
        tuple(place(b, mesh) for b in bucketed.buckets), bucketed.index
    )
    t_many = _timed(
        lambda: jax.block_until_ready(
            simulate_many_sharded(
                bucketed, pol, jax.random.PRNGKey(2), mesh, donate=False
            ).makespan
        ),
        iters,
    )
    r_loop = t_loop / t_many
    r_1dev = t_vmap1 / t_many
    if tiny:
        # the acceptance targets apply to the full configuration (the tiny
        # smoke's lanes are too small for sharding to pay) — print the
        # ratios without a verdict
        derived = (f"bucketed+sharded_{n_dev}dev;ratio_vs_loop=x{r_loop:.2f};"
                   f"vs_1dev_vmap=x{r_1dev:.2f}")
    else:
        derived = (
            f"bucketed+sharded_{n_dev}dev;ratio_vs_loop=x{r_loop:.2f} target>=1.0 "
            f"{'OK' if r_loop >= 1.0 else 'MISS'};vs_1dev_vmap=x{r_1dev:.2f} target>=2.0 "
            f"{'OK' if r_1dev >= 2.0 else 'MISS'}"
        )
    print(csv_row("ensemble_steady_many_16", t_many * 1e6, derived))

    # --- per-lane occupancy: the lock-step/padding tax, measured ----------
    # informational row (us=0 rows are skipped by the perf gate): quantifies
    # what the bucketed+sharded configuration saves on this exact ensemble
    from repro.core.telemetry import lane_occupancy

    res = simulate_many_sharded(
        bucketed, pol, jax.random.PRNGKey(2), mesh, donate=False
    )
    occ = lane_occupancy(res, buckets=bucketed)
    s, pad = occ["summary"], occ["buckets"]["summary"]
    print(csv_row(
        "ensemble_lane_occupancy", 0.0,
        f"active_frac_mean={s['active_frac_mean']:.3f};"
        f"lockstep_waste={s['lockstep_waste_frac']:.3f};"
        f"bucket_pad_waste={pad['waste_frac']:.3f};"
        f"flat_pad_waste={pad['flat_waste_frac']:.3f};"
        f"saved_rows={pad['saved_rows']}",
    ))


def main():
    tiny = "--tiny" in sys.argv
    if "--ensemble-worker" in sys.argv:
        _ensemble_worker(tiny)
        return
    n_dev = int(_arg_after("--devices", "4"))
    n_jobs, n_sites = (120, 4) if tiny else (400, 8)
    pol = get_policy("panda_dispatch")
    scenarios, rag_sizes = _ragged_ensemble(tiny)
    sites = atlas_like_platform(n_sites, seed=1)
    keys = jax.random.split(jax.random.PRNGKey(2), K)

    # --- ragged 16-scenario ensemble, end-to-end (compile included) -------
    t_loop = _once(
        lambda: [
            jax.block_until_ready(simulate(s.jobs, s.sites, pol, keys[i]).makespan)
            for i, s in enumerate(scenarios)
        ]
    )
    stacked = stack_scenarios(scenarios)  # pads ragged jobs to one shape
    t_many = _once(
        lambda: jax.block_until_ready(
            simulate_many(stacked, pol, jax.random.PRNGKey(2)).makespan
        )
    )
    speedup = t_loop / t_many
    print(f"# ragged ensemble (K={K}, jobs {rag_sizes.start}..{rag_sizes[-1]}): "
          "loop recompiles per size, simulate_many compiles once")
    print(csv_row("ensemble_loop_16", t_loop * 1e6, f"compiles={K}"))
    print(csv_row("ensemble_simulate_many_16", t_many * 1e6, "compiles=1"))
    print(csv_row("ensemble_speedup_16", speedup,
                  f"target>=3.0 {'OK' if speedup >= 3.0 else 'MISS'}"))

    # --- bucketed stacking: a few padded shapes instead of one global max --
    buckets = stack_scenarios(scenarios, buckets=N_BUCKETS)
    dense_flat = K * max(rag_sizes)
    dense_buck = sum(len(ix) * s.jobs.capacity
                     for s, ix in zip(buckets.buckets, buckets.index))
    t_buck = _once(
        lambda: jax.block_until_ready(
            simulate_many(buckets, pol, jax.random.PRNGKey(2)).makespan
        )
    )
    print(csv_row(
        "ensemble_bucketed_16", t_buck * 1e6,
        f"compiles={N_BUCKETS};padded_rows={dense_buck}vs{dense_flat};"
        f"speedup_vs_loop=x{t_loop / t_buck:.2f}",
    ))

    # --- steady state + shard scaling ------------------------------------
    # on an accelerator, in this process over its own devices (a chip serves
    # one process); on CPU, in a child whose host platform is forced to
    # ``n_dev`` devices (the count must be fixed before jax initializes)
    if jax.default_backend() != "cpu":
        _ensemble_worker(tiny)
    else:
        env = dict(os.environ)
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "host_platform_device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={n_dev}"])
        src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        cmd = [sys.executable, "-m", "benchmarks.bench_engine_rounds", "--ensemble-worker"]
        if tiny:
            cmd.append("--tiny")
        out = subprocess.run(
            cmd, env=env, capture_output=True, text=True, timeout=1800,
            cwd=os.path.dirname(src),
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"ensemble worker failed (devices={n_dev}):\n{out.stderr[-2000:]}")
        sys.stdout.write(out.stdout)

    # --- single-run round throughput -------------------------------------
    jobs = synthetic_panda_jobs(n_jobs, seed=0, duration=1800.0)
    res = simulate(jobs, sites, pol, jax.random.PRNGKey(0))
    rounds = int(res.rounds)
    t_one = _timed(
        lambda: jax.block_until_ready(
            simulate(jobs, sites, pol, jax.random.PRNGKey(1)).makespan
        )
    )
    print(f"# engine rounds: J={n_jobs} S={n_sites}, {rounds} rounds/run")
    print(csv_row("simulate_one", t_one * 1e6, f"rounds_per_sec={rounds / t_one:.0f}"))

    # --- telemetry overhead: recorder on vs off on the same warm run ------
    # ``*_overhead_pct`` rows gate on their fresh value (<= 5% budget) in
    # ``summarize_results --check-bench`` — the flight recorder must be
    # effectively free around the jit boundary (ISSUE 6)
    from repro.core.telemetry import TraceRecorder

    def run_plain():
        jax.block_until_ready(simulate(jobs, sites, pol, jax.random.PRNGKey(1)).makespan)

    def run_rec():
        jax.block_until_ready(
            simulate(jobs, sites, pol, jax.random.PRNGKey(1),
                     recorder=TraceRecorder()).makespan
        )

    # interleave the two variants and compare minima, so cache-warmth and
    # host jitter hit both sides equally
    run_plain(), run_rec()
    # a tiny run is ~20ms, so ms-scale host jitter flakes a 5% gate on
    # single-call samples: each sample aggregates ``reps`` calls and the two
    # variants interleave, then compare minima
    iters, reps = 10, 3
    t_off, t_on = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(reps):
            run_plain()
        t_off.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        for _ in range(reps):
            run_rec()
        t_on.append(time.perf_counter() - t0)
    t_off_m, t_on_m = min(t_off) / reps, min(t_on) / reps
    overhead = (t_on_m / t_off_m - 1.0) * 100.0
    print(csv_row("telemetry_overhead_pct", overhead,
                  f"recorder_on={t_on_m * 1e6:.0f}us;off={t_off_m * 1e6:.0f}us"))


if __name__ == "__main__":
    main()
