"""The benchmark harness: one cell of ``BENCHMARK.json``, one run, one line.

Everything a cell needs is found by name, each piece in a file of its own:

- its configuration (the ``file`` of its ``configs`` entry), whose
  ``builder`` names ``builders/<builder>.py``: ``lanes(cfg, seed, copies)``
  draws the scenarios from the seed;
- its traffic mix ``traffic/<traffic>.json``, a data file whose ``driver``
  names ``drivers/<driver>.py`` and whose other keys are that driver's
  parameters and the check's ``limits``;
- one reader per per-layer metric, ``metrics/<metric>.py``: ``read(run)``
  returns a number, or None where it finds nothing to read.

A driver's ``drive(ctx)`` builds the cell's inputs (``ctx.lanes(copies)``,
then ``scenario.to_program``), warms up every shape it will use, runs units of
work until the first unit that ends after ``ctx.seconds``, and returns

- ``t_first``: host clock when the first timed unit began (set-up ends there);
- ``window_s``: from then to the end of the last unit;
- ``counters``: exact counts of the window (events, rounds, ...); with
  ``ctx.trace_dir`` set, the profiler records the first units, up to
  ``ctx.trace_seconds``, and counters ending in ``_traced`` cover them;
- ``attempted`` / ``failed``: units of work tried, and those that failed;
- ``check``: once the window has closed, runs the plain reference on a unit
  drawn from the seed and returns the compared numbers.

``ctx`` also carries ``cfg``, ``traffic``, ``seed``, ``key``, ``chips`` and
``mark(stage)``, which records when a stage of set-up ended.

An end-to-end metric named ``<count>_per_s`` is the run's counter
``<count>`` over the window's seconds; ``setup_s`` is the time from process
start to the first timed unit.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import shutil
import sys
import time
import types

import numpy as np

CHIP = pathlib.Path(__file__).resolve().parent
ROOT = CHIP.parents[1]
CACHE_DIR = CHIP / ".jax_cache"
OUT_DIR = CHIP / "out"
TRAFFIC_DIR = CHIP / "traffic"
TRACE_SECONDS = 4.0  # the traced stretch at the start of a --trace 1 window


class NoChip(RuntimeError):
    pass


def load_bench(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_spec(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """``(cell, configuration, traffic)`` of one cell, read from their files."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((TRAFFIC_DIR / f"{cell['traffic']}.json").read_text())
    return cell, cfg, traffic


def cell_metrics(bench: dict, workload: str) -> tuple[list, list]:
    """The end-to-end and per-layer metrics a cell reports."""
    def applies(m):
        return workload in m["workloads"] if "workloads" in m else True

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return e2e, layer


def load(kind: str, name: str) -> types.ModuleType:
    """The module ``<kind>/<name>.py`` of the benchmark (a driver, a builder
    or a metric reader)."""
    path = CHIP / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no {kind} file {path.relative_to(CHIP)}")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(name: str, run: dict):
    return load("metrics", name).read(run)


def seed_key(seed: int):
    import jax

    k = int(np.random.SeedSequence(seed % 2**64).generate_state(1)[0])
    return jax.random.PRNGKey(k)


def check_devices(chips: int) -> list:
    """The chips the cell asks for, or ``NoChip``: never the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    kinds = json.loads((CHIP / "peaks.json").read_text())["kinds"]
    if devices[0].device_kind not in kinds:
        raise NoChip(f"device kind {devices[0].device_kind!r} is not in peaks.json")
    return devices[:chips]


def use_compile_cache(cache_dir: pathlib.Path = CACHE_DIR) -> None:
    """Keep every compiled program in JAX's persistent cache at ``cache_dir``.
    Call before anything compiles (importing the program does): JAX fixes
    the cache's directory at its first compile."""
    import jax

    cache_dir.mkdir(parents=True, exist_ok=True)  # on the TPU, JAX writes no entry into a missing one
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(bench: dict, workload: str, seed: int, seconds: float, trace: bool, t0: float,
             *, devices: list, resize=None) -> dict:
    """One run of one cell on ``devices``; returns the result line's dict.
    ``resize`` (tests only) maps the configuration to a smaller one."""
    import jax

    cell, cfg, traffic = cell_spec(bench, workload)
    if resize is not None:
        cfg = resize(cfg)
    e2e, layer = cell_metrics(bench, workload)
    trace_dir = None
    if trace:
        trace_dir = OUT_DIR / "trace" / workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    builder = load("builders", cfg["builder"])
    marks = {"start": time.perf_counter() - t0}  # set-up's stages, seconds from process start
    ctx = types.SimpleNamespace(
        cfg=cfg, traffic=traffic, seed=seed, seconds=seconds, key=seed_key(seed),
        chips=cell["chips"], trace_dir=str(trace_dir) if trace else None,
        trace_seconds=min(TRACE_SECONDS, seconds),
        lanes=lambda copies=1: builder.lanes(cfg, seed, copies),
        mark=lambda stage: marks.__setitem__(stage, time.perf_counter() - t0))
    run = load("drivers", traffic["driver"]).drive(ctx)
    setup_s = run["t_first"] - t0
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    red = {}
    if trace:
        from trace_reduce import find_xplane, reduce_file

        path = find_xplane(str(trace_dir))
        red = reduce_file(path) if path else {}
    view = dict(counters=run["counters"], trace=red, window_s=run["window_s"])
    metrics = {}
    if not trace:
        for m in e2e:
            if m["name"] == "setup_s":
                v = setup_s
            else:
                v = run["counters"][m["name"].removesuffix("_per_s")] / run["window_s"]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in layer:
            v = read_metric(m["name"], view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    found = run["check"]()
    limits = traffic["limits"]
    checks = {k: {"value": found[k], "limit": lim} for k, lim in limits.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    dev = devices[0]
    out = dict(correct=correct, attempted=run["attempted"], failed=run["failed"],
               metrics=metrics,
               device=dict(platform=dev.platform, kind=dev.device_kind, count=len(jax.devices()),
                           memory_peak_bytes=peak))
    if trace and red:
        out["device"].update(busy_s=red["busy_s_mean"], window_s=red["window_s"])
        out["breakdown"] = dict(device_ops=[list(x) for x in red["device_ops"]],
                                idle_gaps=[list(x) for x in red["idle_gaps"]])
    out["counters"] = run["counters"]
    out["setup_marks"] = marks
    out["first_difference"] = found.get("first_difference")
    out["checks"] = checks
    return out


def main(argv, t0: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_bench()
    cell, _, _ = cell_spec(bench, args.workload)
    use_compile_cache()
    import repro.core  # noqa: F401  the system under test, from the checkout's src/
    try:
        devices = check_devices(cell["chips"])
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    out = run_cell(bench, args.workload, args.seed, args.seconds, bool(args.trace), t0,
                   devices=devices)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
