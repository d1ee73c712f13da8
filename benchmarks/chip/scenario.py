"""Shared pieces of the deployment builders (``builders/<name>.py``).

A configuration file names its builder (``"builder"``); the builder's
``lanes(cfg, seed, copies)`` returns one scenario per lane as plain arrays
(the reference reads these), and ``to_program`` turns a scenario into the
program's state types.  A new deployment is a new configuration file and,
where the existing builders cannot draw it, a new builder file.
"""
from __future__ import annotations

import numpy as np

# what the plain reference models; a configuration asking for more is refused
MODELLED = dict(policy="panda_dispatch", data_policy="cache_on_read", topk=None, failure_rate=0.0)


def lane_seed(seed: int, *path: int) -> int:
    """A 63-bit seed for one lane (and copy) derived from the run's seed."""
    return int(np.random.SeedSequence([seed, *path]).generate_state(2, np.uint64)[0] >> 1)


def check_modelled(cfg: dict) -> None:
    """Refuse a configuration whose options the plain reference does not model."""
    asked = dict(policy=cfg["policy"], data_policy=cfg.get("data", {}).get("policy", "cache_on_read"),
                 topk=cfg["topk"], failure_rate=cfg["failure_rate"])
    if asked != MODELLED:
        raise ValueError(f"the reference models {MODELLED}, the configuration asks {asked}")


def to_program(lane: dict):
    """``(jobs, sites, kwargs)`` in the program's types for one lane; the
    kwargs are those ``init_sim``/``simulate`` take for its subsystems."""
    import jax.numpy as jnp
    from repro.core import make_jobs, make_sites
    from repro.core.availability import AvailabilityState
    from repro.core.network import NetworkState
    from repro.core.replicas import make_replicas

    j, s = lane["jobs"], lane["sites"]
    n = j["arrival"].shape[0]
    jobs = make_jobs(job_id=np.arange(n, dtype=np.int32), **j)
    sites = make_sites(**s)
    kw = {}
    if lane["data"] is not None:
        from repro.core import get_data_policy

        d = lane["data"]
        kw.update(
            data_policy=get_data_policy("cache_on_read"),
            network=NetworkState(bw=jnp.asarray(d["bw"]), latency=jnp.asarray(d["latency"])),
            replicas=make_replicas(d["size"], d["disk_cap"], origin=d["origin"]),
        )
    if lane["avail"] is not None:
        a = lane["avail"]
        kw["availability"] = AvailabilityState(
            win_start=jnp.asarray(a["win_start"]), win_end=jnp.asarray(a["win_end"]),
            win_factor=jnp.asarray(a["win_factor"]), win_preempt=jnp.asarray(a["win_preempt"]),
            n_preempted=jnp.zeros((s["cores"].shape[0],), jnp.int32))
    return jobs, sites, kw
