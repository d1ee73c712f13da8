"""The comparison that decides ``correct``: the program's job tables against
the reference's, unit by unit (a frame's snapshot, or a lane's result).

- ``rows_differing``: job rows, summed over the units, whose state, site,
  retry count, preemption count or replica source differ, or whose start or
  finish time is set on one side only;
- ``rounds_differing``: units whose round counter differs (the clock
  min-reduction ran a different number of rounds);
- ``time_gap_rel``: the widest relative gap ``|p - r| / max(|r|, 1 s)``
  between start or finish times that both sides set;
- ``ties_flipped``: rounding ties that the reference broke the other way,
  summed over the units, to match the program (``frame_pairs``).
"""
from __future__ import annotations

import numpy as np

DISCRETE = ("state", "site", "retries", "preempted", "xfer_src")
TIMES = ("t_start", "t_finish")


def numbers(pairs) -> dict:
    """``pairs``: ``[(program_unit, reference_unit), ...]``, each a dict of
    per-job arrays (the fields above) plus a scalar ``round``."""
    rows = rounds = flipped = 0
    gap = 0.0
    first = None
    for u, (p, r) in enumerate(pairs):
        bad = np.zeros(len(r["state"]), bool)
        for k in DISCRETE:
            bad |= np.asarray(p[k]).astype(np.int64) != np.asarray(r[k]).astype(np.int64)
        for k in TIMES:
            a = np.asarray(p[k], np.float64)
            b = np.asarray(r[k], np.float64)
            bad |= np.isfinite(a) != np.isfinite(b)
            both = np.isfinite(a) & np.isfinite(b)
            if both.any():
                gap = max(gap, float((np.abs(a[both] - b[both]) / np.maximum(np.abs(b[both]), 1.0)).max()))
        rows += int(bad.sum())
        rounds += int(int(p["round"]) != int(r["round"]))
        flipped += int(r.get("ties_flipped", 0))
        if first is None and (bad.any() or int(p["round"]) != int(r["round"])):
            j = int(np.flatnonzero(bad)[0]) if bad.any() else -1
            first = dict(unit=u, job=j, program_round=int(p["round"]), reference_round=int(r["round"]))
            if j >= 0:
                first.update({f"program_{k}": np.asarray(p[k])[j].item() for k in DISCRETE + TIMES})
                first.update({f"reference_{k}": np.asarray(r[k])[j].item() for k in DISCRETE + TIMES})
    return dict(rows_differing=rows, rounds_differing=rounds, time_gap_rel=gap,
                ties_flipped=flipped, units_compared=len(pairs), first_difference=first)


def _unit(sim, flipped: int = 0) -> dict:
    return dict(sim.snapshot(), round=sim.rounds, ties_flipped=flipped)


TIE_ULPS = 4       # a tie: two floats that differ by at most this many units in the last place
MATCH_GAP = 1e-5   # times that agree to rounding (see time_gap_rel)


def _same(p: dict, r: dict) -> bool:
    n = numbers([(p, r)])
    return n["rows_differing"] == 0 and n["rounds_differing"] == 0 and n["time_gap_rel"] <= MATCH_GAP


def _flip_sets(first: int, last: int, tries: int):
    """Sets of tie ordinals in ``[first, last)``: each alone, then pairs."""
    ties = range(first, last)
    out = [frozenset([i]) for i in ties]
    out += [frozenset([i, j]) for i in ties for j in ties if i < j]
    return out[:tries]


def frame_pairs(sim, sample: list, frame_s: float, tries: int = 16) -> list:
    """Run the reference ``sim`` frame by frame beside the program's frame
    snapshots.  Where a frame differs and the reference met rounding ties in
    it, the frame is run again with the ties broken the other way (each
    alone, then in pairs, at most ``tries`` runs); the first run that
    matches is kept, and the ties it flipped are counted.  Returns the
    ``(program, reference)`` pairs to compare."""
    import copy

    pairs = []
    for k, snap in enumerate(sample, start=1):
        before = copy.deepcopy(sim)
        sim.run_until(k * frame_s)
        flipped = 0
        if not _same(snap, _unit(sim)):
            for flips in _flip_sets(before.n_ties, sim.n_ties, tries):
                trial = copy.deepcopy(before)
                trial.flips = before.flips | flips
                trial.run_until(k * frame_s)
                if _same(snap, _unit(trial)):
                    sim, flipped = trial, len(flips)
                    break
        pairs.append((snap, _unit(sim, flipped)))
    return pairs


def lane_pair(make_sim, prog: dict, tries: int = 16) -> tuple:
    """One lane run to the end beside the program's result, with the same
    search over rounding ties as ``frame_pairs``."""
    sim = make_sim()
    sim.run_until(np.inf)
    if not _same(prog, _unit(sim)):
        for flips in _flip_sets(0, sim.n_ties, tries):
            trial = make_sim()
            trial.flips = flips
            trial.run_until(np.inf)
            if _same(prog, _unit(trial)):
                return prog, _unit(trial, len(flips))
    return prog, _unit(sim)
