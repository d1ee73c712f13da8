"""One grid: a platform, a job stream and, where the configuration has a
``data`` block, replica-aware stage-in over a WAN with a dataset catalog.

``--seed`` deals the configuration's jobs onto its arrival times in a new
order (``generators.permute_jobs``), so every seed runs the same set of
jobs and arrivals; the work per round does not depend on the order
(measured on the chip, PR 12).
"""
from __future__ import annotations

import numpy as np

import scenario
from traffic import generators as gen


def lanes(cfg: dict, seed: int, copies: int = 1) -> list[dict]:
    """``copies`` lanes of the one grid, each with its own job order."""
    scenario.check_modelled(cfg)
    plat = cfg["platform"]
    S = plat["n_sites"]
    sites = gen.atlas_platform(S, seed=plat["seed"], fail_rate=cfg["failure_rate"])
    data = None
    if "data" in cfg:
        dc = cfg["data"]
        bw, lat = gen.atlas_network(S, seed=dc["network_seed"])
        size = gen.zipf_sizes(dc["n_datasets"], seed=dc["size_seed"])
        disk_cap = (sites["memory"].astype(np.float32) * np.float32(dc["disk_bytes_per_gb_memory"]))
        data = dict(bw=bw, latency=lat, size=size, disk_cap=disk_cap,
                    origin=gen.replica_origins(disk_cap, dc["n_datasets"], seed=dc["origin_seed"]))
    jc = cfg["jobs"]
    jobs = gen.panda_jobs(jc["n_jobs"], seed=jc["seed"], duration=cfg["arrival_span_s"],
                          n_datasets=cfg.get("data", {}).get("n_datasets"), **jc.get("shape", {}))
    return [dict(jobs=gen.permute_jobs(jobs, scenario.lane_seed(seed, c)), sites=sites,
                 data=data, avail=None) for c in range(copies)]
