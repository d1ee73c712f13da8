"""Flight recorder (ISSUE 6): spans/sinks, manifests, watch(), lane tracing."""
import io
import json

import jax
import numpy as np
import pytest

from repro.core import (
    Scenario,
    atlas_like_platform,
    get_policy,
    simulate,
    stack_scenarios,
    synthetic_panda_jobs,
)
from repro.core.monitor import follow_stream, watch
from repro.core.telemetry import (
    CallbackSink,
    MemorySink,
    NDJSONSink,
    NullRecorder,
    TraceRecorder,
    iter_ndjson,
    lane_occupancy,
    manifest_drift,
    manifest_path,
    read_manifest,
    run_manifest,
    scenario_hash,
    write_manifest,
)


def tiny_scenario(n=60, seed=0):
    jobs = synthetic_panda_jobs(n, seed=seed, duration=900.0)
    sites = atlas_like_platform(4, seed=1)
    return jobs, sites, get_policy("panda_dispatch"), jax.random.PRNGKey(0)


# --------------------------------------------------------------------------
# recorder + sinks
# --------------------------------------------------------------------------


def test_recorder_span_counter_roundtrip():
    sink = MemorySink()
    rec = TraceRecorder(sink=sink)
    with rec.span("a"):
        pass
    with rec.span("a"):
        pass
    rec.count("hits")
    rec.count("hits", 2)
    rec.gauge("lanes", 16)
    rec.note("mode", "scan")
    s = rec.summary()
    assert s["spans"]["a"]["count"] == 2
    assert s["spans"]["a"]["total_s"] >= 0
    assert s["counters"] == {"hits": 3, "lanes": 16}
    assert s["notes"] == {"mode": "scan"}
    # every closed span streamed to the sink
    assert [r["type"] for r in sink.records] == ["span", "span"]
    assert rec.total("a") >= 0 and rec.total("missing") == 0.0


def test_null_recorder_is_inert():
    rec = NullRecorder()
    with rec.span("x"):
        pass
    rec.count("c")
    rec.gauge("g", 1)
    assert rec.summary() == dict(spans={}, counters={}, notes={})


def test_callback_and_ndjson_sinks(tmp_path):
    seen = []
    cb = CallbackSink(seen.append)
    cb.emit({"a": 1})
    assert seen == [{"a": 1}]

    path = tmp_path / "run.ndjson"
    with NDJSONSink(path) as sink:
        sink.emit({"type": "frame", "i": 0})
        sink.emit({"type": "end"})
    recs = list(iter_ndjson(path))
    assert [r["type"] for r in recs] == ["frame", "end"]
    # stops at the end record even with trailing garbage lines
    with open(path, "a") as f:
        f.write(json.dumps({"type": "frame", "i": 99}) + "\n")
    assert len(list(iter_ndjson(path))) == 2


# --------------------------------------------------------------------------
# manifests
# --------------------------------------------------------------------------


def test_scenario_hash_stable_and_sensitive():
    jobs, sites, *_ = tiny_scenario()
    h1 = scenario_hash(jobs, sites)
    assert h1 == scenario_hash(jobs, sites)  # deterministic
    jobs2, *_ = tiny_scenario(seed=7)
    assert h1 != scenario_hash(jobs2, sites)  # content-sensitive
    assert h1 != scenario_hash(jobs, sites, None)  # structure-sensitive


def test_manifest_roundtrip_and_drift(tmp_path):
    jobs, sites, pol, key = tiny_scenario()
    rec = TraceRecorder()
    simulate(jobs, sites, pol, key, recorder=rec)
    man = run_manifest(jobs=jobs, sites=sites, recorder=rec, extra={"k": 1})
    assert man["schema"] == "cgsim.run_manifest/v1"
    assert man["jax"]["backend"] == jax.default_backend()
    assert man["scenario"]["n_jobs"] == 60
    assert man["scenario"]["hash"] == scenario_hash(jobs, sites, None)
    assert "execute" in man["telemetry"]["spans"]

    artifact = tmp_path / "run.ndjson"
    artifact.write_text("")
    side = write_manifest(artifact, man)
    assert side == manifest_path(artifact)
    assert side.name == "run.ndjson.manifest.json"
    man2 = read_manifest(artifact)
    assert manifest_drift(man2, man) == []
    stale = json.loads(json.dumps(man))
    stale["jax"]["device_count"] = 512
    diffs = manifest_drift(man, stale)
    assert [d["key"] for d in diffs] == ["jax.device_count"]


# --------------------------------------------------------------------------
# engine instrumentation
# --------------------------------------------------------------------------


def test_simulate_with_recorder_matches_and_records():
    jobs, sites, pol, key = tiny_scenario()
    base = simulate(jobs, sites, pol, key)
    rec = TraceRecorder()
    res = simulate(jobs, sites, pol, key, recorder=rec)
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s = rec.summary()
    # one dispatch span, then the wait for the result
    assert s["spans"]["dispatch"]["count"] == 1
    assert s["spans"]["execute"]["count"] == 1
    assert s["counters"]["rounds_executed"] == int(base.rounds)
    # the first call above compiled: this one found the program
    assert "compiles" not in s["counters"]
    assert s["notes"]["subsystems"] == []
    # a new static configuration compiles once, then is found again
    rec2 = TraceRecorder()
    simulate(jobs, sites, pol, key, max_rounds=54_321, recorder=rec2)
    simulate(jobs, sites, pol, key, max_rounds=54_321, recorder=rec2)
    s2 = rec2.summary()
    assert s2["counters"]["compiles"] == 1
    assert s2["spans"]["dispatch"]["count"] == 2


def test_program_spans_land_in_the_profiler_trace(tmp_path):
    """The segmented API's spans are profiler annotations in the trace's
    host plane; ``advance_sim`` says whether the call compiled."""
    from jax.profiler import ProfileData

    from repro.core import advance_sim, finish_sim, init_sim

    jobs, sites, pol, key = tiny_scenario()
    rec = TraceRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        # a static configuration no other test uses: the first call compiles
        h = init_sim(jobs, sites, pol, key, max_rounds=43_210)
        h = advance_sim(h, 600.0, recorder=rec)
        h = advance_sim(h, recorder=rec)
        finish_sim(h)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    host = [(e.name, dict(e.stats)) for p in ProfileData.from_file(str(path)).planes
            if p.name.startswith("/host") for line in p.lines for e in line.events
            if e.name in ("init_sim", "advance_sim", "finish_sim")]
    assert [n for n, _ in host].count("init_sim") == 1
    assert [n for n, _ in host].count("finish_sim") == 1
    assert [a["compiled"] for n, a in host if n == "advance_sim"] == [1, 0]
    s = rec.summary()
    assert s["spans"]["advance_sim"]["count"] == 2
    assert s["counters"]["compiles"] == 1


# --------------------------------------------------------------------------
# watch(): the segmented driver
# --------------------------------------------------------------------------


def test_watch_is_bitwise_identical_to_simulate():
    jobs, sites, pol, key = tiny_scenario()
    base = simulate(jobs, sites, pol, key, log_rows=32)
    sink = MemorySink()
    res = watch(jobs, sites, pol, key, frames=6, render=False, sink=sink, log_rows=32)
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    types = [r["type"] for r in sink.records]
    assert types[0] == "run_meta" and types[-1] == "end"
    frames = [r for r in sink.records if r["type"] == "frame"]
    assert frames, "watch emitted no frames"
    need = {"round", "time", "counts", "site_free", "site_queued", "site_running"}
    assert need <= set(frames[0])
    assert sink.records[-1]["rounds"] == int(base.rounds)


def test_watch_respects_horizon():
    jobs, sites, pol, key = tiny_scenario()
    hz = 5000.0
    base = simulate(jobs, sites, pol, key, horizon=hz)
    res = watch(jobs, sites, pol, key, frames=4, horizon=hz, render=False)
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_watch_ndjson_stream_renders_via_follow(tmp_path):
    jobs, sites, pol, key = tiny_scenario()
    path = tmp_path / "run.ndjson"
    rec = TraceRecorder()
    with NDJSONSink(path) as sink:
        res = watch(jobs, sites, pol, key, frames=5, render=False, sink=sink,
                    recorder=rec)
    write_manifest(path, run_manifest(jobs=jobs, sites=sites, recorder=rec))
    # a separate consumer renders the stream from the file alone
    out = io.StringIO()
    shown = follow_stream(path, clear=False, out=out)
    assert shown > 0
    text = out.getvalue()
    assert "cores" in text and "end:" in text
    assert f"rounds={int(res.rounds)}" in text
    s = rec.summary()
    assert s["counters"]["watch_segments"] > 0
    assert s["counters"]["rounds_executed"] == int(res.rounds)
    # one advance_sim span per segment, each inside its watch_segment span
    assert s["spans"]["advance_sim"]["count"] == s["spans"]["watch_segment"]["count"]
    assert s["spans"]["advance_sim"]["total_s"] <= s["spans"]["watch_segment"]["total_s"]
    assert s["counters"].get("compiles", 0) <= 1
    assert read_manifest(path)["scenario"]["n_jobs"] == 60


def test_watch_renders_frames_to_out():
    jobs, sites, pol, key = tiny_scenario(n=20)
    out = io.StringIO()
    watch(jobs, sites, pol, key, frames=3, out=out)
    assert "t=" in out.getvalue()


# --------------------------------------------------------------------------
# lane occupancy + padding stats
# --------------------------------------------------------------------------


def _lane_pair():
    """Two-lane ensemble where lane 0 is deliberately near-idle: 5 jobs vs
    60, stacked (so lane 0 is also mostly padding)."""
    sites = atlas_like_platform(3, seed=1)
    idle = Scenario(synthetic_panda_jobs(5, seed=2, duration=200.0), sites)
    busy = Scenario(synthetic_panda_jobs(60, seed=3, duration=2000.0), sites)
    return [idle, busy]


def test_lane_occupancy_idle_lane():
    from repro.core.distributed import simulate_many_sharded

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    stacked = stack_scenarios(_lane_pair())
    rec = TraceRecorder()
    res = simulate_many_sharded(
        stacked, get_policy("panda_dispatch"), jax.random.PRNGKey(0), mesh,
        lane_mode="scan", recorder=rec, log_rows=64,
    )
    occ = lane_occupancy(res)
    lanes = occ["lanes"]
    assert lanes[1]["active_frac"] == 1.0
    # the idle lane retires in a fraction of the busy lane's rounds
    assert lanes[0]["active_frac"] < 0.5
    assert lanes[0]["rounds"] < lanes[1]["rounds"]
    assert lanes[0]["padding_frac"] > 0.8  # 5 valid rows padded to 60
    # frame log present -> phase-skip work-round rate per lane
    assert 0.0 <= lanes[0]["work_round_frac"] <= 1.0
    assert lanes[0]["skip_frac"] == pytest.approx(1.0 - lanes[0]["work_round_frac"])
    s = occ["summary"]
    assert s["n_lanes"] == 2
    assert 0.0 < s["lockstep_waste_frac"] < 1.0
    # the sharded-run recorder timed the stacking and the run
    spans = rec.summary()["spans"]
    assert "ensemble_stack" not in spans  # the scenarios came stacked
    assert spans["ensemble_run"]["count"] == 1
    assert rec.summary()["counters"] == {}


def test_padding_stats_bucketed_beats_flat():
    sites = atlas_like_platform(3, seed=1)
    scenarios = [
        Scenario(synthetic_panda_jobs(n, seed=i, duration=500.0), sites)
        for i, n in enumerate((8, 10, 48, 50))
    ]
    buckets = stack_scenarios(scenarios, buckets=2)
    stats = buckets.padding_stats()
    assert [r["lanes"] for r in stats["buckets"]] == [2, 2]
    s = stats["summary"]
    assert s["n_scenarios"] == 4
    assert s["used_rows"] == 8 + 10 + 48 + 50
    # bucketing strictly reduces dense rows on this ragged ensemble
    assert s["saved_rows"] > 0
    assert s["waste_frac"] < s["flat_waste_frac"]
    for r in stats["buckets"]:
        assert 0.0 <= r["waste_frac"] < 1.0


@pytest.mark.parametrize("env_dir", ["/elsewhere/jax-cache", None])
def test_enable_compile_cache(monkeypatch, env_dir):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and no config changes; without it
    the cache goes to the fixed ``<repo>/.jax_cache``."""
    import pathlib

    from repro.core.telemetry import enable_compile_cache

    repo = pathlib.Path(__file__).resolve().parents[1]
    before = jax.config.jax_compilation_cache_dir
    sentinel = "/unchanged"
    try:
        jax.config.update("jax_compilation_cache_dir", sentinel)
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert enable_compile_cache() == str(repo / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
            assert enable_compile_cache() == env_dir
            assert jax.config.jax_compilation_cache_dir == sentinel
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
