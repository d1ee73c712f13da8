"""Golden-trace regression: a fixed-seed scenario's exact outcome snapshot.

Engine refactors that silently change semantics — a reordered round step, a
different tie-break, an extra RNG draw — shift these numbers and fail tier-1
immediately, instead of surfacing months later as a calibration drift.

The snapshot lives in ``tests/data/golden_trace.json`` and is compared for
*exact* equality (float32 values round-trip exactly through ``float``/JSON).
After an intentional semantics change, regenerate with

    REGEN_GOLDEN=1 pytest tests/test_golden_trace.py

and commit the diff alongside the change that caused it.
"""
import itertools
import json
import os
import pathlib

import jax
import numpy as np
import pytest

from repro.core import (
    atlas_like_platform,
    get_data_policy,
    get_policy,
    make_availability,
    make_faults,
    make_replicas,
    make_transfers,
    make_workflow,
    simulate,
    synthetic_panda_jobs,
    uniform_network,
    zipf_dataset_sizes,
)

GOLDEN = pathlib.Path(__file__).parent / "data" / "golden_trace.json"
GOLDEN_MATRIX = pathlib.Path(__file__).parent / "data" / "golden_matrix.json"


def _snapshot_one(res) -> dict:
    valid = np.asarray(res.jobs.valid)
    state = np.asarray(res.jobs.state)[valid]
    return dict(
        makespan=float(res.makespan),
        rounds=int(res.rounds),
        state_counts={str(s): int((state == s).sum()) for s in range(6)},
        site_n_assigned=np.asarray(res.sites.n_assigned).tolist(),
        site_n_finished=np.asarray(res.sites.n_finished).tolist(),
        site_n_failed=np.asarray(res.sites.n_failed).tolist(),
        sum_retries=int(np.asarray(res.jobs.retries)[valid].sum()),
        # exact per-job timestamps for a probe subset (full arrays would bloat
        # the snapshot without adding sensitivity)
        t_start_head=[float(t) for t in np.asarray(res.jobs.t_start)[:8]],
        t_finish_head=[float(t) for t in np.asarray(res.jobs.t_finish)[:8]],
        n_preempted=(
            np.asarray(res.avail.n_preempted).tolist() if res.avail is not None else None
        ),
    )


def trace_cases() -> list:
    """The golden-trace scenarios as ``(name, jobs, sites, simulate kwargs)``,
    all run with the ``panda_dispatch`` policy and ``PRNGKey(0)``."""
    jobs = synthetic_panda_jobs(60, seed=11, duration=900.0)
    sites = atlas_like_platform(4, seed=12, fail_rate=0.05)
    # site 3 carries the whole workload under this seed: hit it mid-run
    av = make_availability(
        4,
        [
            dict(site=3, start=2000.0, end=20000.0, preempt=True),
            dict(site=2, start=500.0, end=5000.0, factor=0.5),
        ],
    )
    return [("baseline", jobs, sites, {}), ("outage", jobs, sites, {"availability": av})]


def compute_snapshot() -> dict:
    pol = get_policy("panda_dispatch")
    key = jax.random.PRNGKey(0)
    return {
        name: _snapshot_one(simulate(jobs, sites, pol, key, **kw))
        for name, jobs, sites, kw in trace_cases()
    }


def test_golden_trace_exact():
    snap = compute_snapshot()
    if os.environ.get("REGEN_GOLDEN"):
        GOLDEN.parent.mkdir(parents=True, exist_ok=True)
        GOLDEN.write_text(json.dumps(snap, indent=2) + "\n")
        pytest.skip(f"regenerated {GOLDEN}")
    expected = json.loads(GOLDEN.read_text())
    assert snap == expected


# --------------------------------------------------------------------------
# subsystem on/off matrix (ISSUE 4): every combination of the data-movement,
# availability, and workflow subsystems must stay bit-for-bit stable
# --------------------------------------------------------------------------

N_DS = 12


def _snapshot_combo(res) -> dict:
    """Per-combo snapshot: the base engine probe plus each subsystem's own
    counters, so a regression in any one layer shifts its combo rows."""
    snap = _snapshot_one(res)
    snap["state_counts"]["6"] = int(
        (np.asarray(res.jobs.state)[np.asarray(res.jobs.valid)] == 6).sum()
    )
    rep = res.replicas
    snap["data"] = (
        dict(
            n_hits=int(rep.n_hits),
            n_transfers=int(rep.n_transfers),
            bytes_moved=float(rep.bytes_moved),
            disk_used=np.asarray(rep.disk_used).tolist(),
        )
        if rep is not None
        else None
    )
    wf = res.wf
    snap["workflow"] = (
        dict(n_cancelled=int(wf.n_cancelled), n_produced=int(wf.n_produced))
        if wf is not None
        else None
    )
    # transfer-queue counters only appear when the subsystem ran, so the
    # pre-transfers combo rows keep their exact committed shape
    ts = (getattr(res, "ext", None) or {}).get("transfers")
    if ts is not None:
        snap["transfers"] = dict(
            n_enq=int(ts.n_enq),
            n_done=int(ts.n_done),
            n_cancel=int(ts.n_cancel),
            bytes_done=float(ts.bytes_done),
        )
    # fault counters likewise only appear when the faults subsystem ran
    fs = (getattr(res, "ext", None) or {}).get("faults")
    if fs is not None:
        snap["faults"] = dict(
            n_xfer_fail=int(fs.n_xfer_fail),
            n_xfer_retry=int(fs.n_xfer_retry),
            n_xfer_exhaust=int(fs.n_xfer_exhaust),
            n_kills=int(fs.n_kills),
            n_lost_replicas=int(fs.n_lost_replicas),
            n_bl_trips=int(fs.n_bl_trips),
            n_probes=int(fs.n_probes),
            time_lost=float(fs.time_lost),
        )
    return snap


def matrix_scenario():
    """One deterministic scenario feeding all 8 subsystem combinations.

    Every catalogued dataset is materialized at t=0 (origins at site 0's data
    lake), so the data subsystem is valid with or without the workflow gate;
    the DAG chains half the jobs pairwise so cancellation, gating, and (with
    data on) output materialization all fire.
    """
    jobs = synthetic_panda_jobs(60, seed=11, duration=900.0, n_datasets=N_DS)
    sites = atlas_like_platform(4, seed=12, fail_rate=0.05)
    availability = make_availability(
        4,
        [
            dict(site=3, start=2000.0, end=20000.0, preempt=True),
            dict(site=2, start=500.0, end=5000.0, factor=0.5),
            dict(site=1, start=8000.0, end=12000.0, factor=0.0, preempt=False),
        ],
    )
    network = uniform_network(4, bw=5e8, latency=0.05)
    replicas = make_replicas(
        zipf_dataset_sizes(N_DS, seed=3, mean_bytes=2e9),
        disk_capacity=np.array([1e13, 6e9, 6e9, 6e9]),
        origin=np.zeros(N_DS, np.int32),
    )
    data_policy = get_data_policy("cache_on_read")
    # pairwise chains over consecutive jobs; even rows materialize an output
    # the odd child job consumes through the catalog when data is on
    edges = [(j - 1, j) for j in range(1, 60, 2)]
    out_dataset = np.where(np.arange(60) % 2 == 0, np.arange(60) % N_DS, -1)
    jobs_wf, workflow = make_workflow(jobs, edges, out_dataset=out_dataset)
    return dict(
        jobs=jobs,
        jobs_wf=jobs_wf,
        sites=sites,
        availability=availability,
        network=network,
        replicas=replicas,
        data_policy=data_policy,
        workflow=workflow,
    )


def combo_kwargs(scn: dict, data: bool, avail: bool, wf: bool):
    jobs = scn["jobs_wf"] if wf else scn["jobs"]
    kw = {}
    if data:
        kw.update(
            data_policy=scn["data_policy"],
            network=scn["network"],
            replicas=scn["replicas"],
        )
    if avail:
        kw["availability"] = scn["availability"]
    if wf:
        kw["workflow"] = scn["workflow"]
    return jobs, kw


def matrix_cases() -> list:
    """Every golden-matrix combo as ``(name, jobs, sites, simulate kwargs)``,
    all run with the ``panda_dispatch`` policy and ``PRNGKey(0)``."""
    scn = matrix_scenario()
    out = []
    for data, avail, wf in itertools.product((False, True), repeat=3):
        name = "+".join(
            n for n, on in (("data", data), ("avail", avail), ("wf", wf)) if on
        ) or "plain"
        jobs, kw = combo_kwargs(scn, data, avail, wf)
        out.append((name, jobs, scn["sites"], kw))
    # transfer-queue combos (ISSUE 8): the queued WAN model rides on the data
    # subsystem, so only the data-on half of the matrix composes with it
    for avail, wf in itertools.product((False, True), repeat=2):
        name = "+".join(
            n for n, on in (("data", True), ("tr", True), ("avail", avail), ("wf", wf))
            if on
        )
        jobs, kw = combo_kwargs(scn, True, avail, wf)
        kw["transfers"] = make_transfers(4, jobs.capacity, max_active=2)
        out.append((name, jobs, scn["sites"], kw))
    # fault-injection combos (ISSUE 10): all four channels armed at once —
    # flaky WAN links, resubmission backoff, walltime kills, replica loss
    # targeting cached (non-origin) copies, and the circuit breaker
    def faults_state(jobs):
        return make_faults(
            4, jobs.capacity,
            link_fail_p=0.3, xfer_backoff=120.0, max_xfer_attempts=3,
            job_backoff=60.0, walltime=4000.0,
            replica_loss=[(3000.0, 1, 1), (3000.0, 1, 2), (6000.0, 2, 3)],
            blacklist_threshold=0.5, blacklist_alpha=0.5,
            blacklist_cooldown=1800.0,
        )
    for combo in ((False, False, False), (False, True, False),
                  (True, False, False), (True, True, True)):
        data, avail, wf = combo
        name = "+".join(
            n for n, on in (("data", data), ("tr", data), ("avail", avail),
                            ("wf", wf)) if on
        )
        name = f"{name}+faults" if name else "faults"
        jobs, kw = combo_kwargs(scn, data, avail, wf)
        if data:
            kw["transfers"] = make_transfers(4, jobs.capacity, max_active=2)
        kw["faults"] = faults_state(jobs)
        out.append((name, jobs, scn["sites"], kw))
    return out


def compute_matrix_snapshot() -> dict:
    pol = get_policy("panda_dispatch")
    key = jax.random.PRNGKey(0)
    return {
        name: _snapshot_combo(simulate(jobs, sites, pol, key, **kw))
        for name, jobs, sites, kw in matrix_cases()
    }


def test_golden_matrix_exact():
    """Bit-for-bit parity for all 8 subsystem on/off combinations."""
    snap = compute_matrix_snapshot()
    if os.environ.get("REGEN_GOLDEN"):
        GOLDEN_MATRIX.parent.mkdir(parents=True, exist_ok=True)
        GOLDEN_MATRIX.write_text(json.dumps(snap, indent=2) + "\n")
        pytest.skip(f"regenerated {GOLDEN_MATRIX}")
    expected = json.loads(GOLDEN_MATRIX.read_text())
    assert snap == expected


def test_golden_matrix_is_sensitive():
    """Each subsystem must leave a visible fingerprint in its combo rows."""
    expected = json.loads(GOLDEN_MATRIX.read_text())
    assert set(expected) == {
        "plain", "data", "avail", "wf", "data+avail", "data+wf", "avail+wf",
        "data+avail+wf", "data+tr", "data+tr+avail", "data+tr+wf",
        "data+tr+avail+wf", "faults", "avail+faults", "data+tr+faults",
        "data+tr+avail+wf+faults",
    }
    # availability preempts; data moves bytes; the coupled combo materializes
    assert sum(expected["avail"]["n_preempted"]) > 0
    assert expected["data"]["data"]["n_transfers"] > 0
    assert expected["data+avail+wf"]["workflow"]["n_produced"] > 0
    # the transfer queue actually carried flows, and accounts for all of them
    for name in ("data+tr", "data+tr+avail", "data+tr+wf", "data+tr+avail+wf"):
        ts = expected[name]["transfers"]
        assert ts["n_enq"] > 0
        assert ts["n_enq"] == ts["n_done"] + ts["n_cancel"]
    # transfers-off rows never grow the counter block
    assert "transfers" not in expected["data"]
    # fault channels leave fingerprints: backoff shifts retries into waits,
    # flaky links fail transfers, and the extended ledger still balances
    assert "faults" not in expected["plain"]
    assert expected["faults"]["faults"]["time_lost"] > 0
    for name in ("data+tr+faults", "data+tr+avail+wf+faults"):
        ts, fs = expected[name]["transfers"], expected[name]["faults"]
        assert fs["n_xfer_fail"] > 0
        assert ts["n_enq"] == ts["n_done"] + ts["n_cancel"] + fs["n_xfer_fail"]
    # subsystems genuinely interact: no two combos collapse to the same run
    spans = {k: (v["makespan"], v["rounds"]) for k, v in expected.items()}
    assert len(set(spans.values())) == len(spans)


def test_golden_scenario_is_sensitive():
    """The committed scenario must actually exercise the dynamics it guards:
    the outage run preempts jobs and takes longer than the baseline."""
    expected = json.loads(GOLDEN.read_text())
    assert sum(expected["outage"]["n_preempted"]) > 0
    assert expected["outage"]["makespan"] > expected["baseline"]["makespan"]
    assert expected["baseline"]["n_preempted"] is None
