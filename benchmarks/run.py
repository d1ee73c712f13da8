"""Benchmark harness: one module per paper table/figure (+ beyond-paper).

Prints ``name,us_per_call,derived`` CSV rows.  With ``--json`` each suite
additionally writes a ``BENCH_<name>.json`` result file (parsed rows +
status) so the perf trajectory is machine-readable across commits:

    python -m benchmarks.run [suite] [--json] [--out DIR]
"""
from __future__ import annotations

import contextlib
import io
import json
import pathlib
import sys
import time
import traceback

from . import (
    bench_assign_kernel,
    bench_availability,
    bench_calibration,
    bench_data_movement,
    bench_distributed,
    bench_engine_rounds,
    bench_ensemble,
    bench_events,
    bench_faults,
    bench_job_scaling,
    bench_site_scaling,
    bench_transfers,
    bench_wlcg_scale,
    bench_workflow,
)

SUITES = {
    "fig4a_job_scaling": bench_job_scaling.main,
    "fig4b_site_scaling": bench_site_scaling.main,
    "fig3_calibration": bench_calibration.main,
    "abstract_6x_distributed": bench_distributed.main,
    "table1_events": bench_events.main,
    "assign_kernel": bench_assign_kernel.main,
    "engine_rounds": bench_engine_rounds.main,
    "ensemble_vmap": bench_ensemble.main,
    "data_movement": bench_data_movement.main,
    "transfers": bench_transfers.main,
    "faults": bench_faults.main,
    "availability": bench_availability.main,
    "workflow": bench_workflow.main,
    "wlcg_scale": bench_wlcg_scale.main,
}


def parse_rows(text: str) -> list[dict]:
    """Recover structured rows from the ``csv_row`` lines a suite printed."""
    rows = []
    for line in text.splitlines():
        parts = line.split(",", 2)
        if len(parts) < 2 or line.startswith(("#", "=")):
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        rows.append(dict(name=parts[0], us_per_call=us,
                         derived=parts[2] if len(parts) > 2 else ""))
    return rows


def env_manifest() -> dict:
    """The telemetry ``RunManifest`` for this bench process: backend, device
    count, package versions — embedded in every ``BENCH_*.json`` so the perf
    gate can tell env drift from perf drift."""
    from repro.core.telemetry import run_manifest

    return run_manifest(extra=dict(tiny="--tiny" in sys.argv))


def write_json(name: str, fn, out_dir: pathlib.Path, manifest=None) -> list[str]:
    """Run one suite with stdout captured; write ``BENCH_<name>.json``."""
    buf = io.StringIO()
    t0 = time.perf_counter()
    err = None
    try:
        with contextlib.redirect_stdout(buf):
            fn()
    except Exception as e:  # noqa: BLE001
        err = f"{type(e).__name__}: {e}"
        traceback.print_exc()
    text = buf.getvalue()
    sys.stdout.write(text)
    payload = dict(
        suite=name,
        status="failed" if err else "ok",
        error=err,
        wall_s=round(time.perf_counter() - t0, 3),
        manifest=manifest,
        rows=parse_rows(text),
    )
    path = out_dir / f"BENCH_{name}.json"
    path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {path} ({len(payload['rows'])} rows)")
    return [name] if err else []


def main() -> None:
    from repro.core.telemetry import enable_compile_cache

    enable_compile_cache()
    args = [a for a in sys.argv[1:]]
    as_json = "--json" in args
    out_dir = pathlib.Path(".")
    if "--out" in args:
        i = args.index("--out")
        if i + 1 >= len(args) or args[i + 1].startswith("-"):
            raise SystemExit("--out needs a directory argument")
        out_dir = pathlib.Path(args[i + 1])
        out_dir.mkdir(parents=True, exist_ok=True)
        del args[i: i + 2]
    # --tiny stays visible in sys.argv: each suite reads it there for its
    # seconds-sized CI smoke configuration
    args = [a for a in args if a not in ("--json", "--tiny")]
    only = args[0] if args else None
    failures = []
    manifest = None
    if as_json:
        manifest = env_manifest()
        mpath = out_dir / "RUN_MANIFEST.json"
        mpath.write_text(json.dumps(manifest, indent=2) + "\n")
        print(f"wrote {mpath}")
    for name, fn in SUITES.items():
        if only and only != name:
            continue
        print(f"\n=== {name} ===")
        if as_json:
            failures += write_json(name, fn, out_dir, manifest)
            continue
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            print(f"FAILED {name}: {type(e).__name__}: {e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
