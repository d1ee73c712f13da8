"""The harness at a tiny size on the CPU: every cell's traffic driver, every
metric reader, the result line's keys, and the shape of ``BENCHMARK.json``.
The chip check of the command is skipped by calling ``harness.run_cell``
directly with the CPU device."""
import copy
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys
import time

import pytest

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = harness.load_bench()
DATA = harness.CHIP / "tests" / "data"


def held_bench() -> dict:
    """``BENCHMARK.json`` with the held what-if cells put back, so that their
    driver and builder stay tested (their configuration and traffic files
    are in ``tests/data``; run them with ``harness.TRAFFIC_DIR`` there)."""
    bench = copy.deepcopy(BENCH)
    bench["configs"].append(dict(name="whatif_ensemble", source="held", reduced=[], why="held",
                                 file="benchmarks/chip/tests/data/whatif_ensemble.json"))
    bench["workloads"] += [
        dict(name="whatif16.q60", config="whatif_ensemble", traffic="batches16", chips=1, why="held"),
        dict(name="whatif64.q60.shard4", config="whatif_ensemble", traffic="batches16x4", chips=4,
             why="held")]
    held = ["whatif16.q60", "whatif64.q60.shard4"]
    bench["end_to_end"].append(dict(name="scenarios_per_s", unit="scenarios/s", better="higher",
                                    bound=0.01, source="host_clock", workloads=held))
    for name, unit in (("round_us.whatif", "us"), ("lockstep_waste.whatif", "%"),
                       ("padding_waste.whatif", "%"), ("device_idle_pct.whatif", "%")):
        bench["per_layer"].append(dict(name=name, unit=unit, better="lower", source="device_trace",
                                       layer="held", moves="scenarios_per_s", workloads=held))
    return bench


HELD = held_bench()
HELD_CELLS = {"whatif16.q60", "whatif64.q60.shard4"}


def use_traffic_of(workload, monkeypatch):
    """Point the harness at the directory that holds ``workload``'s traffic."""
    if workload in HELD_CELLS:
        monkeypatch.setattr(harness, "TRAFFIC_DIR", DATA)


def tiny(cfg):
    """The same deployment at a size the CPU runs in seconds."""
    cfg = copy.deepcopy(cfg)
    cfg["platform"]["n_sites"] = 16
    if cfg["builder"] == "single":
        cfg["jobs"]["n_jobs"] = 2000
        cfg["data"]["n_datasets"] = 40
    else:
        cfg["ensemble"].update(n_lanes=4, jobs_lo=150, jobs_hi=400, buckets=2)
    return cfg


def test_benchmark_json_is_well_formed():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    root = harness.ROOT
    for p in BENCH["paths"]:
        assert (root / p).is_dir() and not p.startswith("/") and ".." not in p
    configs = {c["name"]: c for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        cfg = json.loads((root / c["file"]).read_text())
        assert cfg["name"] == c["name"] and set(c["reduced"]) == set(cfg["reduced"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
    cells = {w["name"] for w in BENCH["workloads"]}
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        _, cfg, traffic = harness.cell_spec(BENCH, w["name"])
        assert callable(harness.load("drivers", traffic["driver"]).drive)
        assert callable(harness.load("builders", cfg["builder"]).lanes)
        assert set(traffic["limits"]) == {"rows_differing", "rounds_differing", "time_gap_rel",
                                          "ties_flipped"}
        own_e2e, own_layer = harness.cell_metrics(BENCH, w["name"])
        assert "setup_s" in {m["name"] for m in own_e2e} and len(own_e2e) >= 2 and own_layer
    for m in BENCH["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.25
        assert set(m.get("workloads", [])) <= cells
    layers = set()
    for m in BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        for w in m["workloads"]:
            assert m["moves"] in {x["name"] for x in harness.cell_metrics(BENCH, w)[0]}
        assert (harness.CHIP / "metrics" / f"{m['name']}.py").is_file()
        layers.add(m["layer"])
    names = [x["name"] for x in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("workload", [w["name"] for w in HELD["workloads"] if w["chips"] == 1])
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_at_tiny_size(workload, trace, tmp_path, monkeypatch):
    import jax

    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    use_traffic_of(workload, monkeypatch)
    out = harness.run_cell(HELD, workload, 2**31 + 99, 1.0, trace, time.perf_counter(),
                           devices=jax.devices(), resize=tiny)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    e2e, layer = harness.cell_metrics(HELD, workload)
    if trace:
        # the CPU trace has no TPU plane: only the exact counts read anything
        assert set(out["metrics"]) <= {m["name"] for m in layer}
    else:
        assert set(out["metrics"]) == {m["name"] for m in e2e}
        for m in e2e:
            assert out["metrics"][m["name"]]["value"] > 0
    json.dumps(out)


def test_metric_readers_read_or_return_nothing():
    trace = dict(busy_s_mean=0.9, window_s=1.0, idle_share=0.1)
    counters = dict(rounds_traced=100, bucket_rounds_traced=50, lane_rounds=90,
                    lockstep_rounds=100, used_rows=90, padded_rows=10)
    files = {p.stem for p in (harness.CHIP / "metrics").glob("*.py")}
    assert {m["name"] for m in HELD["per_layer"]} == files
    for name in files:
        assert harness.read_metric(name, dict(counters={}, trace={}, window_s=1.0)) is None
        v = harness.read_metric(name, dict(counters=counters, trace=trace, window_s=1.0))
        assert v is not None and v > 0


def _command(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", BENCH["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_fails_without_a_tpu():
    r = _command(harness.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(harness.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("out", ".jax_cache", "__pycache__"))
    r = _command(tmp_path, {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "No module named 'repro'" in r.stderr


CACHE_CHECK = """
import os, pathlib, sys, time
sys.path[:0] = [{chip!r}, {src!r}, {tests!r}]
import harness
harness.use_compile_cache(pathlib.Path(sys.argv[1]))
import jax
import repro.core  # compiles at import
from test_harness import BENCH, tiny
w = next(w["name"] for w in BENCH["workloads"] if w["chips"] == 1)
harness.OUT_DIR = pathlib.Path(sys.argv[1]) / "out"
harness.run_cell(BENCH, w, 5, 0.5, False, time.perf_counter(), devices=jax.devices(), resize=tiny)
"""


def test_compile_cache_lives_in_the_given_directory(tmp_path):
    """The cache is set before the program's import compiles anything, so
    compiled programs land in the benchmark's directory and not in one that
    the environment names."""
    script = tmp_path / "cache_check.py"
    script.write_text(CACHE_CHECK.format(chip=str(harness.CHIP), src=str(harness.ROOT / "src"),
                                         tests=str(harness.CHIP / "tests")))
    mine, env_dir = tmp_path / "mine", tmp_path / "env"
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(env_dir))
    r = subprocess.run([sys.executable, str(script), str(mine)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert any(p.is_file() for p in mine.rglob("*") if "out" not in p.parts)
    assert not env_dir.exists() or not any(env_dir.iterdir())


def test_compile_cache_directory_is_made(tmp_path):
    """A fresh checkout has no cache directory (it is not committed); the
    harness makes it, since on the TPU JAX writes no entry into a missing one."""
    import jax

    old = jax.config.jax_compilation_cache_dir
    try:
        harness.use_compile_cache(tmp_path / "a" / "cache")
        assert (tmp_path / "a" / "cache").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
