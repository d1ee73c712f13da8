"""Smoke run of the simulator on a TPU, through the entry points users call.

    python chip_smoke.py             # one chip: phases A-D
    python chip_smoke.py --chips 4   # four chips: the sharded paths, phases E-F

One chip:

- A. ``simulate`` at WLCG scale (300 sites, 100k jobs over six hours) with
  the data subsystem on (cache-on-read, WAN matrix, Zipf catalog of 1000
  datasets), for a fixed round budget; conservation checks on the state.
- B. The same scenario in sparse mode (``topk=16``) through the fused Pallas
  assignment kernel, compiled for the chip, against the jnp oracle:
  per-job state, site and start time must agree bit for bit.
- C. ``simulate_many`` over 16 ragged what-if scenarios (100 sites, 2k-8k
  jobs, four shape buckets, a per-lane flaky-site calendar), run until every
  lane drains.
- D. The golden-matrix scenarios of ``tests/test_golden_trace.py`` on the
  chip and on the host CPU in this process.  Per-job terminal states and
  state counts must agree exactly; float fields within ``FLOAT_RTOL``.

Four chips:

- E. ``simulate_many_sharded`` of phase C's lanes over a 4-device mesh
  against ``simulate_many`` on one device: every lane bit for bit.
- F. ``simulate_distributed`` of 20k jobs on 100 sites over 4 devices
  against ``simulate`` on one: equal makespan and per-job states.

Each phase prints one JSON line (device kind, shapes, set-up and execute
seconds, rounds, peak device bytes).  The last line is
``{"ok": true, "device": {...}}``.  Any failed phase, or a JAX with no TPU,
exits non-zero and prints no such line.  Compile and execute seconds here
are smoke figures, not benchmark figures.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    DONE,
    FAILED,
    PENDING,
    QUEUED,
    RUNNING,
    ASSIGNED,
    Scenario,
    atlas_like_network,
    atlas_like_platform,
    availability_subsystem,
    flaky_sites,
    get_data_policy,
    get_policy,
    make_replicas,
    simulate,
    simulate_many,
    stack_scenarios,
    synthetic_panda_jobs,
    with_fused_assign,
    zipf_dataset_sizes,
)
from repro.core.replicas import catalog_invariants  # noqa: E402
from repro.core.types import N_STATES  # noqa: E402
from repro.core.telemetry import enable_compile_cache  # noqa: E402
from repro.kernels.assign.ops import make_fused_capacity_assign  # noqa: E402

# Phase D float tolerance (relative): see CHANGES.md for why.
FLOAT_RTOL = 1e-5

WLCG = dict(n_sites=300, n_jobs=100_000, n_datasets=1000, max_rounds=2000, topk=16)
ENSEMBLE = dict(n_sites=100, n_lanes=16, jobs_lo=2000, jobs_hi=8000, buckets=4)
DISTRIBUTED = dict(n_sites=100, n_jobs=20_000)
DRAIN_ROUNDS = 1_000_000  # "until drained": far above any phase's need


class PhaseFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailure(msg)


def report(phase: str, **fields) -> None:
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    print(json.dumps({
        "phase": phase,
        "device_kind": dev.device_kind,
        **fields,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }), flush=True)


def timed(fn):
    """``(result, setup_s, execute_s)``.  The call traces, compiles and
    dispatches (set-up); ``block_until_ready`` then waits for the device."""
    t0 = time.perf_counter()
    out = fn()
    t1 = time.perf_counter()
    jax.block_until_ready(out)
    return out, t1 - t0, time.perf_counter() - t1


def leaves_equal(a, b) -> list[str]:
    """Paths of the leaves where two result pytrees differ (NaN == NaN)."""
    bad = []
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        eq_nan = np.issubdtype(x.dtype, np.floating)
        if x.shape != y.shape or not np.array_equal(x, y, equal_nan=eq_nan):
            bad.append(jax.tree_util.keystr(path))
    return bad


# --------------------------------------------------------------------------
# conservation checks on one (unbatched) result
# --------------------------------------------------------------------------


def check_state(res, max_rounds: int, *, drained: bool, what: str) -> dict:
    valid = np.asarray(res.jobs.valid)
    state = np.asarray(res.jobs.state)
    st = state[valid]
    check(((st >= 0) & (st < N_STATES)).all(), f"{what}: job state out of range")
    check((state[~valid] == DONE).all(), f"{what}: a padding row moved")
    counts = np.bincount(st, minlength=N_STATES)
    check(counts.sum() == valid.sum(), f"{what}: state counts do not cover every job")
    cores = np.asarray(res.sites.cores)
    free = np.asarray(res.sites.free_cores)
    check(((free >= 0) & (free <= cores)).all(), f"{what}: free cores outside [0, total]")
    site = np.asarray(res.jobs.site)
    running = valid & (state == RUNNING)
    used = np.bincount(site[running], weights=np.asarray(res.jobs.cores)[running],
                       minlength=cores.shape[0])
    check((free + used == cores).all(), f"{what}: running cores + free cores != total")
    n_done = int(counts[DONE])
    check(int(np.asarray(res.sites.n_finished).sum()) == n_done,
          f"{what}: site finish counters != DONE jobs")
    rounds = int(res.rounds)
    active = np.isin(st, [PENDING, QUEUED, ASSIGNED, RUNNING]).any()
    check(rounds == max_rounds or not active,
          f"{what}: stopped at round {rounds} < {max_rounds} with jobs still active")
    if drained:
        check(not active and rounds < max_rounds, f"{what}: did not drain")
        check((free == cores).all(), f"{what}: cores not all returned after draining")
    if res.replicas is not None:
        inv = catalog_invariants(res.replicas)
        check(all(bool(v) for v in inv.values()), f"{what}: replica catalog {inv}")
    return {"rounds": rounds, "done": n_done, "failed": int(counts[FAILED]),
            "active": int(np.isin(st, [PENDING, QUEUED, ASSIGNED, RUNNING]).sum())}


# --------------------------------------------------------------------------
# phases A and B: one WLCG-scale scenario
# --------------------------------------------------------------------------


def wlcg_scenario(n_sites: int, n_jobs: int, n_datasets: int):
    sites = atlas_like_platform(n_sites, seed=1)
    jobs = synthetic_panda_jobs(n_jobs, seed=0, duration=6 * 3600.0, n_datasets=n_datasets)
    data = dict(
        data_policy=get_data_policy("cache_on_read"),
        network=atlas_like_network(n_sites),
        replicas=make_replicas(
            zipf_dataset_sizes(n_datasets, seed=3),
            disk_capacity=np.asarray(sites.memory) * 1e9,
        ),
    )
    return jobs, sites, data


def phase_a(n_sites, n_jobs, n_datasets, max_rounds, **_):
    jobs, sites, data = wlcg_scenario(n_sites, n_jobs, n_datasets)
    pol = get_policy("panda_dispatch")
    res, setup, execute = timed(lambda: simulate(
        jobs, sites, pol, jax.random.PRNGKey(0), max_rounds=max_rounds, **data))
    out = check_state(res, max_rounds, drained=False, what="A")
    report("A", shapes=f"J={n_jobs} S={n_sites} D={n_datasets} dense",
           setup_s=setup, execute_s=execute, **out)


def phase_b(n_sites, n_jobs, n_datasets, max_rounds, topk):
    from repro.core.engine import _simulate
    from repro.core.subsystems import resolve_subsystems

    jobs, sites, data = wlcg_scenario(n_sites, n_jobs, n_datasets)
    base = get_policy("panda_dispatch")
    kernel = with_fused_assign(base, make_fused_capacity_assign(jobs_cores=jobs.cores))
    oracle = with_fused_assign(
        base, make_fused_capacity_assign(jobs_cores=jobs.cores, use_kernel=False))
    key = jax.random.PRNGKey(0)
    kw = dict(max_rounds=max_rounds, topk=topk)

    # the engine program of the default policy must hold the compiled Mosaic
    # kernel: a custom call, not the interpreter's or the oracle's jnp ops
    subs, ext0 = resolve_subsystems(jobs=jobs, sites=sites, **data)
    hlo = _simulate.lower(jobs, sites, kernel, key, ext0, subsystems=subs, **kw).as_text()
    check("tpu_custom_call" in hlo, "B: engine program holds no compiled Pallas kernel")

    runs = {}
    for name, pol in (("kernel", kernel), ("oracle", oracle)):
        res, setup, execute = timed(lambda: simulate(jobs, sites, pol, key, **data, **kw))
        runs[name] = res
        out = check_state(res, max_rounds, drained=False, what=f"B/{name}")
        report(f"B/{name}", shapes=f"J={n_jobs} S={n_sites} D={n_datasets} topk={topk}",
               setup_s=setup, execute_s=execute, **out)
    k, o = runs["kernel"], runs["oracle"]
    for field in ("state", "site", "t_start"):
        check(np.array_equal(np.asarray(getattr(k.jobs, field)),
                             np.asarray(getattr(o.jobs, field))),
              f"B: kernel and oracle differ in jobs.{field}")
    check(int(k.rounds) == int(o.rounds), "B: kernel and oracle ran different rounds")
    report("B", kernel_equals_oracle=["jobs.state", "jobs.site", "jobs.t_start", "rounds"],
           other_leaves_differing=leaves_equal(k, o))


# --------------------------------------------------------------------------
# phase C (and E): the ragged what-if ensemble
# --------------------------------------------------------------------------


def ensemble_scenarios(n_sites, n_lanes, jobs_lo, jobs_hi, buckets):
    """Ragged lanes: workload size, site speeds and the flaky-site calendar
    vary per lane.  Calendars are padded to one window count so lanes stack."""
    sites = atlas_like_platform(n_sites, seed=1)
    sizes = np.linspace(jobs_lo, jobs_hi, n_lanes).astype(int)
    horizon = 6 * 3600.0
    cals = [
        flaky_sites(n_sites, np.arange(i % 4, n_sites, 10), horizon=2 * horizon,
                    mtbf=4 * 3600.0, seed=100 + i)
        for i in range(n_lanes)
    ]
    w = max(c.win_start.shape[-1] for c in cals)
    cals = [
        flaky_sites(n_sites, np.arange(i % 4, n_sites, 10), horizon=2 * horizon,
                    mtbf=4 * 3600.0, seed=100 + i, max_windows=w)
        for i in range(n_lanes)
    ]
    speed = np.linspace(0.7, 1.3, n_lanes)
    scens = [
        Scenario(
            synthetic_panda_jobs(int(n), seed=10 + i, duration=horizon),
            sites._replace(speed=sites.speed * float(speed[i])),
            {"availability": cals[i]},
        )
        for i, n in enumerate(sizes)
    ]
    subs = (availability_subsystem(),)
    return stack_scenarios(scens, subsystems=subs, buckets=buckets), subs, sizes


def lane(res, i):
    return jax.tree.map(lambda x: x[i], res)


def phase_c(n_sites, n_lanes, jobs_lo, jobs_hi, buckets):
    sb, subs, sizes = ensemble_scenarios(n_sites, n_lanes, jobs_lo, jobs_hi, buckets)
    pol = get_policy("panda_dispatch")
    res, setup, execute = timed(lambda: simulate_many(
        sb, pol, jax.random.PRNGKey(2), subsystems=subs, max_rounds=DRAIN_ROUNDS))
    rounds = [check_state(lane(res, i), DRAIN_ROUNDS, drained=True, what=f"C/lane{i}")["rounds"]
              for i in range(n_lanes)]
    report("C", shapes=f"K={n_lanes} S={n_sites} J={sizes.min()}..{sizes.max()} "
                       f"buckets={buckets}",
           setup_s=setup, execute_s=execute, rounds=rounds,
           preempted=int(np.asarray(res.avail.n_preempted).sum()))
    return res


# --------------------------------------------------------------------------
# phase D: the golden-matrix scenarios, chip against host CPU
# --------------------------------------------------------------------------


def golden_cases():
    path = ROOT / "tests" / "test_golden_trace.py"
    spec = importlib.util.spec_from_file_location("golden_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return [("trace/" + c[0], *c[1:]) for c in mod.trace_cases()] + [
        ("matrix/" + c[0], *c[1:]) for c in mod.matrix_cases()
    ]


def run_on(device, jobs, sites, kw):
    """One golden run with every input committed to ``device``."""
    args = jax.tree.map(
        lambda x: jax.device_put(x, device) if isinstance(x, (jax.Array, np.ndarray)) else x,
        (jobs, sites, kw))
    with jax.default_device(device):
        res = simulate(args[0], args[1], get_policy("panda_dispatch"),
                       jax.device_put(jax.random.PRNGKey(0), device), **args[2])
        return jax.device_get(res)


def phase_d():
    chip, host = jax.devices()[0], jax.devices("cpu")[0]
    t0 = time.perf_counter()
    rows, failures, worst = [], [], 0.0
    for name, jobs, sites, kw in golden_cases():
        a = run_on(chip, jobs, sites, kw)
        b = run_on(host, jobs, sites, kw)
        valid = np.asarray(a.jobs.valid)
        sa, sb_ = np.asarray(a.jobs.state)[valid], np.asarray(b.jobs.state)[valid]
        if not np.array_equal(sa, sb_):
            failures.append(f"{name}: per-job terminal states differ")
        if not np.array_equal(np.bincount(sa, minlength=N_STATES),
                              np.bincount(sb_, minlength=N_STATES)):
            failures.append(f"{name}: state counts differ")
        differ = leaves_equal(a, b)
        for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a), jax.tree.leaves(b)):
            x, y = np.asarray(x), np.asarray(y)
            if not np.issubdtype(x.dtype, np.floating) or x.shape != y.shape:
                continue
            fin = np.isfinite(x) & np.isfinite(y)
            if not np.array_equal(np.isfinite(x), np.isfinite(y)):
                failures.append(f"{name}: {jax.tree_util.keystr(path)} finite on one side only")
                continue
            if fin.any():
                rel = np.abs(x[fin] - y[fin]) / np.maximum(np.abs(y[fin]), 1.0)
                worst = max(worst, float(rel.max()))
                if rel.max() > FLOAT_RTOL:
                    failures.append(f"{name}: {jax.tree_util.keystr(path)} rel diff "
                                    f"{float(rel.max())!r} > {FLOAT_RTOL}")
        int_differ = [p for p in differ
                      if not np.issubdtype(np.asarray(_leaf(a, p)).dtype, np.floating)]
        rows.append({"case": name, "bitwise_equal": not differ,
                     "float_leaves_differing": [p for p in differ if p not in int_differ],
                     "int_leaves_differing": int_differ})
    for r in rows:
        print(json.dumps({"phase": "D/case", **r}), flush=True)
    report("D", cases=len(rows), bitwise_equal=sum(r["bitwise_equal"] for r in rows),
           worst_float_rel_diff=worst, float_rtol=FLOAT_RTOL,
           seconds=time.perf_counter() - t0)
    check(not failures, "D: " + "; ".join(failures[:10]))


def _leaf(tree, keystr: str):
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        if jax.tree_util.keystr(path) == keystr:
            return x
    raise KeyError(keystr)


# --------------------------------------------------------------------------
# phases E and F: four chips
# --------------------------------------------------------------------------


def phase_e(n_sites, n_lanes, jobs_lo, jobs_hi, buckets):
    from repro.core.distributed import simulate_many_sharded

    sb, subs, sizes = ensemble_scenarios(n_sites, n_lanes, jobs_lo, jobs_hi, buckets)
    pol = get_policy("panda_dispatch")
    mesh = jax.make_mesh((4,), ("data",))
    one, setup1, exec1 = timed(lambda: simulate_many(
        sb, pol, jax.random.PRNGKey(2), subsystems=subs, max_rounds=DRAIN_ROUNDS))
    many, setup4, exec4 = timed(lambda: simulate_many_sharded(
        sb, pol, jax.random.PRNGKey(2), mesh, subsystems=subs, max_rounds=DRAIN_ROUNDS))
    bad = {i: leaves_equal(lane(one, i), lane(many, i)) for i in range(n_lanes)}
    bad = {i: v for i, v in bad.items() if v}
    report("E", shapes=f"K={n_lanes} S={n_sites} J={sizes.min()}..{sizes.max()} "
                       f"buckets={buckets} mesh=4",
           setup_s_1dev=setup1, execute_s_1dev=exec1,
           setup_s_4dev=setup4, execute_s_4dev=exec4,
           lanes_bitwise_equal=n_lanes - len(bad),
           rounds=np.asarray(many.rounds).tolist())
    check(not bad, f"E: sharded lanes differ from one device: {bad}")


def phase_f(n_sites, n_jobs):
    from repro.core.distributed import simulate_distributed

    sites = atlas_like_platform(n_sites, seed=1)
    jobs = synthetic_panda_jobs(n_jobs, seed=0, duration=6 * 3600.0)
    pol = get_policy("panda_dispatch")
    mesh = jax.make_mesh((4,), ("data",))
    key = jax.random.PRNGKey(0)
    one, setup1, exec1 = timed(lambda: simulate(
        jobs, sites, pol, key, max_rounds=DRAIN_ROUNDS))
    dist, setup4, exec4 = timed(lambda: simulate_distributed(
        jobs, sites, pol, key, mesh, max_rounds=DRAIN_ROUNDS))
    J = jobs.capacity
    out = check_state(one, DRAIN_ROUNDS, drained=True, what="F/1dev")
    report("F", shapes=f"J={n_jobs} S={n_sites} mesh=4",
           setup_s_1dev=setup1, execute_s_1dev=exec1,
           setup_s_4dev=setup4, execute_s_4dev=exec4,
           makespan_1dev=float(one.makespan), makespan_4dev=float(dist.makespan),
           rounds_1dev=int(one.rounds), rounds_4dev=int(dist.rounds),
           other_leaves_differing=leaves_equal(
               one.jobs, jax.tree.map(lambda x: x[:J], dist.jobs)),
           **out)
    check(float(one.makespan) == float(dist.makespan), "F: makespan differs")
    check(np.array_equal(np.asarray(one.jobs.state), np.asarray(dist.jobs.state)[:J]),
          "F: per-job states differ")


# --------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: need {args.chips} chips, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    cache = pathlib.Path(enable_compile_cache())
    warm = cache.is_dir() and any(cache.iterdir())
    print(json.dumps({"compile_cache": str(cache), "cache_warm": warm,
                      "jax": jax.__version__, "devices": len(devices)}), flush=True)

    if args.chips == 1:
        phases = [("A", lambda: phase_a(**WLCG)), ("B", lambda: phase_b(**WLCG)),
                  ("C", lambda: phase_c(**ENSEMBLE)), ("D", phase_d)]
    else:
        phases = [("E", lambda: phase_e(**ENSEMBLE)), ("F", lambda: phase_f(**DISTRIBUTED))]
    failed = []
    for name, fn in phases:
        try:
            fn()
        except Exception:  # noqa: BLE001 - every phase runs; any failure fails the smoke
            traceback.print_exc()
            print(f"chip_smoke: phase {name} FAILED", file=sys.stderr, flush=True)
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
