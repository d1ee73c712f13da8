"""The control of the comparison: the plain reference computed in bfloat16
(the precision below the float32 the configurations state), put in the
program's place, compared with the float32 reference on the units a run
checks.  It has to come out as not correct; its readings set the upper end
of each limit.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 [--frames N]

Host only (NumPy); ``--frames`` bounds the frames compared in a frames cell
(default: all of a pass).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import ml_dtypes

CHIP = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402
from reference import compare  # noqa: E402


def readings(cfg: dict, traffic: dict, seed: int, frames: int | None = None,
             ftype=ml_dtypes.bfloat16) -> dict:
    """The compared numbers for the reference in ``ftype`` against float32,
    on the units the cell's driver checks (``control_pairs``)."""
    lanes = harness.load("builders", cfg["builder"]).lanes(cfg, seed, int(traffic.get("copies", 1)))
    driver = harness.load("drivers", traffic["driver"])
    out = compare.numbers(driver.control_pairs(lanes, cfg, traffic, ftype, frames))
    out.pop("first_difference")
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--frames", type=int, default=None)
    args = ap.parse_args(argv)
    _, cfg, traffic = harness.cell_spec(harness.load_bench(), args.workload)
    for seed in args.seeds:
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              **readings(cfg, traffic, seed, args.frames))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
