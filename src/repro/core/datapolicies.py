"""Data-movement policies — the second CGSim plugin family (DESIGN.md §3).

The paper promises a "modular plugin mechanism for testing custom workflow
scheduling *and data movement policies*"; ``policies.Policy`` covers the
scheduling half, this module covers data.  A ``DataPolicy`` is a pytree of
pure functions with the same extension-point shape as ``Policy``:

    paper hook               | DataPolicy field
    -------------------------+-------------------------------------------------
    getResourceInformation   | init(jobs, sites, network, replicas)
                             |   -> (replicas, data_state)   (pre-placement)
    assignJob (data half)    | select_source(jobs, sites, network, replicas,
                             |   state, dst, clock) -> i32[J] replica site
                             | should_cache(jobs, sites, network, replicas,
                             |   state, dst, clock) -> bool[J] cache-on-read
    onJobEnd                 | on_step(state, jobs, replicas, started, xfer,
                             |   clock) -> state
    onSimulationEnd          | on_end(state, jobs, replicas, clock) -> state

All fields are jit-traceable, so ``engine.simulate`` with a DataPolicy keeps
vmapping under ``simulate_ensemble``.

``select_source`` and ``should_cache`` are row-wise: row j of the result
depends on row j of ``jobs`` and ``dst`` (and on the shared catalog, network
and state), and the length of the result is ``jobs.capacity``.  The stage-in
hook runs them on the rows that start this round only: on a gathered row set
that holds every starting row, padded with other rows whose results are
ignored, or on all J rows when a round starts more than ``COMPACT_ROWS``
jobs (the wide fallback).  ``on_step`` always sees J-wide ``jobs``,
``started`` and ``xfer``.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from .replicas import ReplicaState, insert_mask, nearest_source


class DataPolicy(NamedTuple):
    name: str
    init: Callable
    select_source: Callable
    should_cache: Callable
    on_step: Callable
    on_end: Callable


def _default_init(jobs, sites, network, replicas):
    return replicas, ()


def _default_select(jobs, sites, network, replicas, state, dst, clock):
    return nearest_source(replicas, network, jobs.dataset, dst)


def _never_cache(jobs, sites, network, replicas, state, dst, clock):
    return jnp.zeros((jobs.capacity,), bool)


def _always_cache(jobs, sites, network, replicas, state, dst, clock):
    return jnp.ones((jobs.capacity,), bool)


def _keep_state(state, *_):
    return state


def make_data_policy(
    name: str,
    *,
    init=None,
    select_source=None,
    should_cache=None,
    on_step=None,
    on_end=None,
) -> DataPolicy:
    return DataPolicy(
        name=name,
        init=init or _default_init,
        select_source=select_source or _default_select,
        should_cache=should_cache or _never_cache,
        on_step=on_step or _keep_state,
        on_end=on_end or _keep_state,
    )


# --------------------------------------------------------------------------
# the data Subsystem (DESIGN.md §7): replica-aware stage-in as hooks on the
# composable round-loop protocol.  The DataPolicy rides in ``sub.config``;
# the ext slot carries (network, catalog, policy state, WAN-ingress accum).
# --------------------------------------------------------------------------

# Starting rows the stage-in hook prices per round on its compact path.  A
# round that starts more takes the J-wide path.
COMPACT_ROWS = 64


class DataExt(NamedTuple):
    """The data subsystem's ``EngineState.ext["data"]`` slot."""

    network: object      # NetworkState link matrices (read-only in the loop)
    replicas: ReplicaState
    state: object        # DataPolicy-defined pytree
    net_acc: jax.Array   # f32[S] WAN bytes staged since the last log write
    wide_rounds: jax.Array  # i32[] rounds whose stage-in ran on all J rows


def _data_init(sub, state0, jobs, sites):
    network, replicas = state0
    replicas, dstate = sub.config.init(jobs, sites, network, replicas)
    return DataExt(
        network=network,
        replicas=replicas,
        state=dstate,
        net_acc=jnp.zeros((sites.capacity,), jnp.float32),
        wide_rounds=jnp.zeros((), jnp.int32),
    )


def _data_on_start(sub, ctx):
    """Replica-aware stage-in (engine step 5b, DESIGN.md §3): dataset jobs
    swap the flat latency+stage-in terms for a WAN transfer from the
    policy-selected replica, with catalog bookkeeping (LRU touch,
    cache-on-read insertion, hit/transfer counters).

    Only the rows that start this round are priced: ``engine._first_rows``
    finds up to ``COMPACT_ROWS`` of them, and the results go back into the
    J-wide columns by scatters of that many rows.  A round that starts more
    jobs (in any lane of an ensemble) runs the same code on all J rows, and
    so does every round of a run with the transfer queues, which take J-wide
    arrays; the ext slot's ``wide_rounds`` counts those rounds."""
    from .engine import _ensemble_any, _first_rows

    policy = sub.config
    k = min(ctx.J, COMPACT_ROWS)
    dext = ctx.ext["data"]
    if "transfers" in ctx.ext:
        wide = jnp.bool_(True)
        out = _stage_in(policy, ctx, dext, None)
    else:
        wide = _ensemble_any(ctx.started.sum() > k)
        out = jax.lax.cond(
            wide,
            lambda: _stage_in(policy, ctx, dext, None),
            lambda: _stage_in(policy, ctx, dext, _first_rows(ctx.started, k)),
        )
    ctx.t_serv, ctx.jobs, dext, xfer = out
    dstate = policy.on_step(dext.state, ctx.jobs, dext.replicas, ctx.started, xfer, ctx.clock)
    ctx.ext["data"] = dext._replace(
        state=dstate, wide_rounds=dext.wide_rounds + wide.astype(jnp.int32)
    )


def _stage_in(policy, ctx, dext: DataExt, idx):
    """The stage-in of this round's starting rows among ``idx``, ascending
    row indices padded with J (``None``: all J rows).  The row math runs on
    those rows only; returns ``(t_serv, jobs, dext, xfer)`` with the J-wide
    columns updated on them.  The engine reads ``t_serv`` only where
    ``started``, so on the compact path the other rows read 0, and the
    engine's J-wide flat-link ``t_serv`` goes unused."""
    from .engine import _site_sum, service_time, stage_in_time
    from .network import shared_transfer_times
    from .replicas import insert_replicas, touch

    network, rep, dstate = dext.network, dext.replicas, dext.state
    sites, S, J, clock = ctx.sites, ctx.S, ctx.J, ctx.clock
    if idx is None:
        def rows(col):
            return col

        def put(col, val):
            return val

        started = ctx.started
    else:
        safe = jnp.minimum(idx, J - 1)

        def rows(col):
            return col[safe]

        def put(col, val):
            return col.at[idx].set(val, mode="drop")

        started = idx < J
    jobs = jax.tree.map(rows, ctx.jobs)
    site_c, start_site = rows(ctx.site_c), rows(ctx.start_site)
    share = ctx.start_count[site_c].astype(jnp.float32)

    has_ds = jobs.dataset >= 0
    # only flat-link stage-ins contend for the site ingress link; dataset
    # jobs stage over the WAN matrix instead
    n_flat_start = _site_sum((started & ~has_ds).astype(jnp.int32), start_site, S)
    share_in = n_flat_start[site_c].astype(jnp.float32)
    t_serv = service_time(jobs, ctx.sites_serv, site_c, share_in, share)
    D = rep.present.shape[0]
    d_c = jnp.clip(jobs.dataset, 0, D - 1)
    ds_bytes = rep.size[d_c]
    local = rep.present[d_c, site_c]
    read = started & has_ds
    src = policy.select_source(jobs, sites, network, rep, dstate, site_c, clock)
    src_c = jnp.clip(src, 0, S - 1)
    xfer = read & ~local
    # swap the flat latency+stage-in terms for the WAN transfer
    in_flat = stage_in_time(jobs, ctx.sites_serv, site_c, share_in)
    # static specialization: with the transfer-queue subsystem registered, WAN
    # reads are deferred to its link queues (DESIGN.md §11) instead of being
    # priced instantly — the staging gate and landing happen in transfers.py
    defer = "transfers" in ctx.ext
    if defer:
        t_start = jnp.where(has_ds, t_serv - in_flat, t_serv)
    else:
        t_net, _ = shared_transfer_times(network, src_c, site_c, ds_bytes, xfer)
        t_start = jnp.where(has_ds, t_serv - in_flat + t_net, t_serv)
    # catalog bookkeeping: touch LRU clocks, cache-on-read insertion
    rep = touch(rep, jobs.dataset, src_c, xfer, clock)
    rep = touch(rep, jobs.dataset, site_c, read & local, clock)
    want_cache = policy.should_cache(jobs, sites, network, rep, dstate, site_c, clock) & xfer
    moved = jnp.where(xfer, ds_bytes, 0.0)
    rep = rep._replace(n_hits=rep.n_hits + (read & local).sum().astype(jnp.int32))
    net_in_now = dext.net_acc
    if defer:
        # hand this round's WAN reads to the transfer queues; replica
        # insertion and WAN counters land at transfer completion
        ctx.scratch["transfers"] = {
            "xfer": xfer,
            "link": src_c * S + site_c,
            "bytes": moved,
            "resid": jnp.maximum(t_serv - in_flat, 0.0) + network.latency[src_c, site_c],
            "cache": want_cache,
        }
        t_net_col = jnp.zeros((jobs.capacity,), jnp.float32)
    else:
        rep = insert_replicas(rep, jobs.dataset, site_c, want_cache, clock)
        rep = rep._replace(
            n_transfers=rep.n_transfers + xfer.sum().astype(jnp.int32),
            # summed over the J-wide column, so the float adds group alike
            # on both paths
            bytes_moved=rep.bytes_moved + put(jnp.zeros((J,), jnp.float32), moved).sum(),
        )
        net_in_now = net_in_now + _site_sum(moved, jnp.where(xfer, jobs.site, S), S)
        t_net_col = t_net
    jobs = ctx.jobs._replace(
        xfer_src=put(ctx.jobs.xfer_src, jnp.where(read, src_c, jobs.xfer_src)),
        xfer_bytes=put(ctx.jobs.xfer_bytes, jnp.where(read, moved, jobs.xfer_bytes)),
        xfer_time=put(ctx.jobs.xfer_time, jnp.where(read, t_net_col, jobs.xfer_time)),
    )
    dext = dext._replace(replicas=rep, net_acc=net_in_now)
    t_serv = put(jnp.zeros((J,), jnp.float32), t_start)
    return t_serv, jobs, dext, put(jnp.zeros((J,), bool), xfer)


def land_deferred(dext: DataExt, jobs, done, cache, clock, S):
    """Deferred landing for queue-managed transfers (DESIGN.md §11): the
    catalog/WAN bookkeeping that ``_data_on_start`` skips in defer mode,
    applied by the transfer subsystem on the ``done`` rows at completion —
    replica materialization at the destination, transfer/byte counters, and
    per-site WAN-ingress accumulation for the event log."""
    from .engine import _site_sum
    from .replicas import insert_replicas

    rep = insert_replicas(dext.replicas, jobs.dataset, jnp.clip(jobs.site, 0, S - 1), done & cache, clock)
    moved = jnp.where(done, jobs.xfer_bytes, 0.0)
    rep = rep._replace(
        n_transfers=rep.n_transfers + done.sum().astype(jnp.int32),
        bytes_moved=rep.bytes_moved + moved.sum(),
    )
    net_in = _site_sum(moved, jnp.where(done, jobs.site, S), S)
    return dext._replace(replicas=rep, net_acc=dext.net_acc + net_in)


def _data_log_spec(sub, dext: DataExt, jobs, sites):
    return {"site_disk": dext.replicas.disk_used, "site_net_in": dext.net_acc}


def _data_log_columns(sub, ctx, write):
    dext = ctx.ext["data"]
    cols = {"site_disk": dext.replicas.disk_used, "site_net_in": dext.net_acc}
    # WAN ingress accumulates between log writes so monitor_every > 1 still
    # conserves bytes in the exported timeline; reset on write
    ctx.ext["data"] = dext._replace(net_acc=jnp.where(write, 0.0, dext.net_acc))
    return cols


def _data_finalize(sub, dext: DataExt, jobs, sites, clock):
    dstate = sub.config.on_end(dext.state, jobs, dext.replicas, clock)
    dext = dext._replace(state=dstate)
    return dext, {
        "replicas": dext.replicas,
        "data_state": dstate,
        "data_wide_rounds": dext.wide_rounds,
    }


def data_subsystem(policy: DataPolicy) -> "Subsystem":
    """Data movement as a composable engine subsystem.  Initial state is the
    ``(NetworkState, ReplicaState)`` pair; the DataPolicy (static functions)
    rides in ``config`` so identically-configured subsystems share jit cache
    entries."""
    from .subsystems import Subsystem

    return Subsystem(
        name="data",
        config=policy,
        init=_data_init,
        on_start=_data_on_start,
        log_spec=_data_log_spec,
        log_columns=_data_log_columns,
        finalize=_data_finalize,
    )


# --------------------------------------------------------------------------
# built-in data policies
# --------------------------------------------------------------------------


def always_remote() -> DataPolicy:
    """Read from the nearest replica, never cache: every job whose dataset is
    not already local pays a WAN transfer (the Begy et al. 'remote access'
    baseline)."""
    return make_data_policy("always_remote")


def cache_on_read() -> DataPolicy:
    """Nearest-replica reads, and every remote read inserts a replica at the
    compute site (LRU-evicting under storage pressure) — the Rucio-style
    volatile cache."""
    return make_data_policy("cache_on_read", should_cache=_always_cache)


def pre_place_hot(hot_frac: float = 0.1, n_copies: int = 3, cache: bool = False) -> DataPolicy:
    """Replicate the hottest ``hot_frac`` of datasets (by job count in the
    submitted workload) to the ``n_copies`` largest storage elements before
    the run — PanDA PD2P-flavoured pre-placement."""

    def init(jobs, sites, network, replicas: ReplicaState):
        D, S = replicas.present.shape
        d = jnp.clip(jobs.dataset, 0, D - 1)
        has = jobs.valid & (jobs.dataset >= 0)
        counts = jax.ops.segment_sum(has.astype(jnp.int32), jnp.where(has, d, D), num_segments=D + 1)[:D]
        k = max(int(round(hot_frac * D)), 1)
        rank = jnp.argsort(-counts)
        hot = jnp.zeros((D,), bool).at[rank[:k]].set(True)
        targets = jnp.argsort(-replicas.disk_cap)[:n_copies]
        target_mask = jnp.zeros((S,), bool).at[targets].set(True)
        want = hot[:, None] & target_mask[None, :]
        return insert_mask(replicas, want, 0.0), ()

    return make_data_policy(
        f"pre_place_hot({hot_frac},{n_copies})",
        init=init,
        should_cache=_always_cache if cache else _never_cache,
    )


DATA_REGISTRY: dict[str, Callable[..., DataPolicy]] = {
    "always_remote": always_remote,
    "cache_on_read": cache_on_read,
    "pre_place_hot": pre_place_hot,
}


def get_data_policy(name: str, **params) -> DataPolicy:
    if name not in DATA_REGISTRY:
        raise KeyError(f"unknown data policy {name!r}; have {sorted(DATA_REGISTRY)}")
    return DATA_REGISTRY[name](**params)


def register_data(name: str):
    """Decorator: plug a user data-policy factory into the registry."""

    def deco(fn):
        DATA_REGISTRY[name] = fn
        return fn

    return deco


# --------------------------------------------------------------------------
# Abstract-class adapter mirroring ``policies.AllocationPlugin``.
# --------------------------------------------------------------------------


class DataPlugin:
    """Subclass and override, then call ``.build()`` to get a DataPolicy."""

    name = "custom_data"

    def get_resource_information(self, jobs, sites, network, replicas):
        return replicas, ()

    def select_source(self, jobs, sites, network, replicas, state, dst, clock):
        return nearest_source(replicas, network, jobs.dataset, dst)

    def should_cache(self, jobs, sites, network, replicas, state, dst, clock):
        return jnp.zeros((jobs.capacity,), bool)

    def on_transfer(self, state, jobs, replicas, started, xfer, clock):
        return state

    def on_simulation_end(self, state, jobs, replicas, clock):
        return state

    def build(self) -> DataPolicy:
        return DataPolicy(
            name=self.name,
            init=self.get_resource_information,
            select_source=self.select_source,
            should_cache=self.should_cache,
            on_step=self.on_transfer,
            on_end=self.on_simulation_end,
        )
