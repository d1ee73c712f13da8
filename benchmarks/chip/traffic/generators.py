"""The benchmark's own copy of the scenario generators (plain NumPy).

Copied from ``repro.core`` (``synthetic_panda_jobs``, ``atlas_like_platform``,
``atlas_like_network``, ``zipf_dataset_sizes``, the origin draw of
``make_replicas`` and ``flaky_sites``) so that the yardstick cannot move when
the program's generators change.  Each returns plain arrays; the harness
turns them into the program's state types, and the reference reads them as
they are.  ``tests/test_generators.py`` checks that the copies drew the same
numbers as the program's generators when they were copied.
"""
from __future__ import annotations

import numpy as np

LOCAL_BW = 1e15  # bytes/s of the intra-site path (no WAN hop)
GBIT = 1e9 / 8   # bytes/s in one Gbit/s


def panda_jobs(n_jobs: int, *, seed: int, duration: float, multicore_frac: float = 0.5,
               mean_walltime_hours: float = 4.0, burstiness: float = 0.3,
               n_datasets: int | None = None, zipf_alpha: float = 1.2) -> dict:
    """ATLAS-PanDA-shaped jobs: half 8-core, log-normal work, bursty Poisson
    arrivals over ``duration`` seconds, Zipf dataset popularity."""
    rng = np.random.default_rng(seed)
    dataset = np.full(n_jobs, -1, np.int32)
    if n_datasets is not None:
        p = 1.0 / np.arange(1, n_datasets + 1) ** zipf_alpha
        dataset = rng.choice(n_datasets, size=n_jobs, p=p / p.sum()).astype(np.int32)
    multicore = rng.random(n_jobs) < multicore_frac
    cores = np.where(multicore, 8, 1).astype(np.int32)
    base_work = 10.0 * mean_walltime_hours * 3600.0
    work = rng.lognormal(mean=np.log(base_work), sigma=0.8, size=n_jobs)
    work = work * np.where(multicore, 8.0, 1.0)
    gaps = rng.exponential(duration / max(n_jobs, 1), size=n_jobs)
    arrival = np.cumsum(gaps)
    arrival *= duration / max(arrival[-1], 1e-9)
    arrival += burstiness * duration / 20.0 * np.sin(arrival / duration * 12 * np.pi)
    arrival = np.clip(arrival, 0.0, None)
    arrival.sort()
    memory = np.where(multicore, 16.0, 2.0) * rng.uniform(0.8, 1.2, n_jobs)
    bytes_in = rng.lognormal(np.log(2e9), 1.0, n_jobs)
    bytes_out = rng.lognormal(np.log(5e8), 1.0, n_jobs)
    priority = rng.choice([0.0, 1.0, 2.0], size=n_jobs, p=[0.7, 0.2, 0.1])
    f32 = np.float32
    return dict(
        arrival=arrival.astype(f32), work=work.astype(f32), cores=cores,
        memory=memory.astype(f32), bytes_in=bytes_in.astype(f32),
        bytes_out=bytes_out.astype(f32), priority=priority.astype(f32), dataset=dataset,
    )


JOB_ATTRS = ("work", "cores", "memory", "bytes_in", "bytes_out", "priority", "dataset")


def permute_jobs(jobs: dict, seed: int) -> dict:
    """Deal the same set of jobs onto the same arrival times in an order drawn
    from ``seed``: every seed then carries the same work and the same arrival
    process, and only which job arrives when differs."""
    perm = np.random.default_rng(seed).permutation(jobs["arrival"].shape[0])
    return {k: (v if k not in JOB_ATTRS else v[perm]) for k, v in jobs.items()}


def atlas_platform(n_sites: int, *, seed: int, fail_rate: float = 0.0,
                   speed_range=(5.0, 25.0), cores_range=(100, 2000)) -> dict:
    """WLCG-like sites: 100-2000 cores, a tenth at Tier-1 scale, HS23-like
    speeds, 1-100 Gbit/s links, 2 GB of memory per core."""
    rng = np.random.default_rng(seed)
    cores = rng.integers(cores_range[0], cores_range[1] + 1, size=n_sites)
    tier1 = rng.choice(n_sites, size=max(1, n_sites // 10), replace=False)
    cores[tier1] = rng.integers(cores_range[1], 4 * cores_range[1], size=tier1.size)
    speed = rng.uniform(*speed_range, size=n_sites)
    bw = rng.choice([1.0, 10.0, 40.0, 100.0], size=n_sites, p=[0.15, 0.45, 0.25, 0.15]) * GBIT
    f32 = np.float32
    return dict(
        cores=cores.astype(np.int32), speed=speed.astype(f32),
        memory=(2.0 * cores).astype(f32), bw_in=bw.astype(f32), bw_out=bw.astype(f32),
        latency=rng.uniform(0.005, 0.12, size=n_sites).astype(f32),
        par_gamma=rng.uniform(0.0, 0.05, size=n_sites).astype(f32),
        fail_rate=np.full(n_sites, fail_rate, f32),
    )


def atlas_network(n_sites: int, *, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Tiered WAN, ``(bw[src, dst], latency[src, dst])`` in float32: a tenth
    of the sites on Tier-1 uplinks, log-normal jitter on every bandwidth."""
    rng = np.random.default_rng(seed)
    f32 = np.float32
    tier = np.full(n_sites, 2, np.int32)
    tier[rng.choice(n_sites, size=max(1, n_sites // 10), replace=False)] = 1
    tier_bw = (np.array([400.0, 100.0, 10.0]) * GBIT).astype(f32)
    hi = np.maximum(tier[:, None], tier[None, :])
    hops = (tier[:, None] + tier[None, :] + 2).astype(f32)
    latency = hops * f32(0.015)
    eye = np.eye(n_sites, dtype=bool)
    latency[eye] = 0.0
    bw = tier_bw[np.clip(hi, 0, 2)]
    bw[eye] = f32(LOCAL_BW)
    jitter = rng.lognormal(0.0, 0.25, size=(n_sites, n_sites)).astype(f32)
    bw = bw * jitter
    np.fill_diagonal(bw, LOCAL_BW)
    return bw.astype(f32), latency.astype(f32)


def zipf_sizes(n_datasets: int, *, seed: int, mean_bytes: float = 20e9,
               sigma: float = 1.0) -> np.ndarray:
    """Log-normal dataset sizes in bytes (float32)."""
    rng = np.random.default_rng(seed)
    return rng.lognormal(np.log(mean_bytes), sigma, n_datasets).astype(np.float32)


def replica_origins(disk_cap: np.ndarray, n_datasets: int, *, seed: int) -> np.ndarray:
    """One pinned origin site per dataset, drawn by storage capacity."""
    rng = np.random.default_rng(seed)
    w = np.maximum(np.asarray(disk_cap, np.float64), 0.0)
    w = w / max(w.sum(), 1e-9)
    return rng.choice(disk_cap.shape[0], size=n_datasets, p=w).astype(np.int32)


def flaky_calendar(n_sites: int, flaky, *, horizon: float, mtbf: float,
                   mean_down: float = 1800.0, seed: int, preempt: bool = True,
                   max_windows: int | None = None) -> dict:
    """Unannounced outages at the ``flaky`` sites: Poisson failures with mean
    time between them ``mtbf``, log-normal repair around ``mean_down``.
    Returns ``[S, W]`` window arrays, each site's windows in start order,
    unused slots at ``inf``."""
    rng = np.random.default_rng(seed)
    per_site = [[] for _ in range(n_sites)]
    for s in np.asarray(flaky, np.int64):
        t = float(rng.exponential(mtbf))
        while t < horizon:
            down = float(rng.lognormal(np.log(mean_down), 0.5))
            per_site[int(s)].append((t, t + down))
            t += down + float(rng.exponential(mtbf))
    W = max_windows or max(1, max(len(p) for p in per_site))
    if any(len(p) > W for p in per_site):
        raise ValueError(f"a site has more than max_windows={W} windows")
    start = np.full((n_sites, W), np.inf, np.float32)
    end = np.full((n_sites, W), np.inf, np.float32)
    for s, rows in enumerate(per_site):
        for i, (t0, t1) in enumerate(sorted(rows)):
            start[s, i], end[s, i] = t0, t1
    used = np.isfinite(start)
    return dict(win_start=start, win_end=end,
                win_factor=np.where(used, 0.0, 1.0).astype(np.float32),
                win_preempt=used & preempt)


def calendar_windows(n_sites: int, flaky, *, horizon: float, mtbf: float, seed: int) -> int:
    """The largest per-site window count ``flaky_calendar`` draws."""
    cal = flaky_calendar(n_sites, flaky, horizon=horizon, mtbf=mtbf, seed=seed)
    return int(np.isfinite(cal["win_start"]).sum(-1).max())
