"""Flight recorder: run telemetry, manifests, sinks, and lane tracing.

The paper ships "interactive real-time visualization dashboards" and
event-level run datasets (CGSim §4.3.3, Table 1); what it never records is
why a run was fast or slow.  This module is the observability substrate for
the whole harness (DESIGN.md §9):

- ``span`` — the program's one span primitive: a profiler annotation on the
  device trace's clock (``init_sim``, ``advance_sim``, ``finish_sim``, the
  ``simulate`` and ensemble entry points), also timed into a
  ``TraceRecorder`` when one is passed.  The round loop's device phases are
  ``jax.named_scope``s instead (``engine.PHASES``).
- ``TraceRecorder`` — a host-side span/counter recorder wrapped around the
  jit boundary (``with rec.span("execute"): ...``): per-name seconds and
  counts, counters, notes.
- ``Sink`` — a tiny streaming-record protocol (``emit(dict)``/``close()``)
  with NDJSON-file, in-memory, and callback implementations.  Monitor frames,
  telemetry spans, and event rows all stream through sinks, so export memory
  is bounded per record, not per run (``events.stream_rows``).
- ``RunManifest`` — a Tracekit-style self-describing sidecar JSON
  (``<artifact>.manifest.json``) recording the environment (jax version /
  backend / device count, package versions), the scenario content hash, the
  subsystem set, and the recorder's wall-clock breakdown.  ``manifest_drift``
  diffs two manifests' environment blocks — env drift explains perf drift
  (``benchmarks/summarize_results --check-bench``).
- ``lane_occupancy`` — per-lane ensemble tracing: active-round fraction per
  lane, per-bucket padding waste, and the phase-skip work-round rate, so the
  DESIGN.md §8 lock-step-tax win is a measured quantity on every sharded run.
"""
from __future__ import annotations

import hashlib
import json
import pathlib
import time
from typing import Any, Callable, Protocol, runtime_checkable

import numpy as np
from jax.profiler import TraceAnnotation

MANIFEST_SCHEMA = "cgsim.run_manifest/v1"
MANIFEST_SUFFIX = ".manifest.json"


# --------------------------------------------------------------------------
# sinks: streaming record consumers
# --------------------------------------------------------------------------


@runtime_checkable
class Sink(Protocol):
    """Anything that accepts a stream of JSON-able record dicts."""

    def emit(self, record: dict) -> None: ...

    def close(self) -> None: ...


class NullSink:
    """Drops every record (the default when observability is off)."""

    def emit(self, record: dict) -> None:
        pass

    def close(self) -> None:
        pass


class MemorySink:
    """Collects records in a list — tests, notebooks, small runs."""

    def __init__(self):
        self.records: list[dict] = []

    def emit(self, record: dict) -> None:
        self.records.append(record)

    def close(self) -> None:
        pass

    def __len__(self) -> int:
        return len(self.records)


class CallbackSink:
    """Forwards each record to a callable (dashboard push, queue producer)."""

    def __init__(self, fn: Callable[[dict], None]):
        self.fn = fn

    def emit(self, record: dict) -> None:
        self.fn(record)

    def close(self) -> None:
        pass


class NDJSONSink:
    """Streams records as newline-delimited JSON, one object per line.

    Accepts a path (opened/owned here) or any ``.write()``-able.  Each record
    is flushed on emit so a separate process can tail the file live
    (``python -m repro.monitor --follow run.ndjson``).
    """

    def __init__(self, target, *, flush_every: int = 1):
        if hasattr(target, "write"):
            self._f, self._owns = target, False
        else:
            self.path = pathlib.Path(target)
            self._f, self._owns = open(self.path, "w"), True
        self._flush_every = max(int(flush_every), 1)
        self._n = 0

    def emit(self, record: dict) -> None:
        self._f.write(json.dumps(record, separators=(",", ":")) + "\n")
        self._n += 1
        if self._n % self._flush_every == 0:
            self._f.flush()

    def close(self) -> None:
        self._f.flush()
        if self._owns:
            self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def iter_ndjson(source, *, follow: bool = False, poll_s: float = 0.2,
                timeout_s: float | None = None):
    """Yield records from an NDJSON file (or file-like), optionally tailing.

    With ``follow=True`` the generator keeps polling for appended lines —
    the decoupled-dashboard half of ``monitor.watch``: the simulator writes
    through an ``NDJSONSink`` while a separate ``python -m repro.monitor
    --follow`` process renders.  Stops at a ``{"type": "end"}`` record, at
    ``timeout_s`` without new data, or (follow off) at EOF.
    """
    f = source if hasattr(source, "readline") else open(source)
    owns = f is not source
    waited = 0.0
    try:
        buf = ""
        while True:
            line = f.readline()
            if not line:
                if not follow:
                    return
                if timeout_s is not None and waited >= timeout_s:
                    return
                time.sleep(poll_s)
                waited += poll_s
                continue
            buf += line
            if not buf.endswith("\n"):
                continue  # partial line from a concurrent writer: wait for the rest
            waited = 0.0
            rec = json.loads(buf)
            buf = ""
            yield rec
            if rec.get("type") == "end":
                return
    finally:
        if owns:
            f.close()


# --------------------------------------------------------------------------
# spans, and the TraceRecorder: spans + counters around the jit boundary
# --------------------------------------------------------------------------


class Span:
    """One named span of the program (see ``span``)."""

    __slots__ = ("name", "args", "_rec", "_ann", "_t0")

    def __init__(self, name: str, recorder=None, **args):
        self.name = name
        self.args = args
        self._rec = recorder

    def __enter__(self):
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **args) -> None:
        """Add arguments known only inside the span, such as ``compiled``."""
        self.args.update(args)
        self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._rec is not None:
            self._rec.record(self.name, seconds)
            if self.args.get("compiled"):
                self._rec.count("compiles")
        return False


def span(name: str, recorder=None, **args) -> Span:
    """The program's one way to open a span: ``with span("advance_sim"):``.

    It always opens a ``jax.profiler.TraceAnnotation`` named ``name`` with
    ``args`` as its arguments, so under an active profiler the span lands in
    the trace's host plane, on the device trace's clock; with no profiler it
    costs about a microsecond.  With a ``recorder`` the span's wall-clock
    seconds are also added to it under ``name``, and a span that ends with
    ``compiled=1`` counts one of the recorder's ``compiles``."""
    return Span(name, recorder, **args)


class TraceRecorder:
    """Host-side flight recorder: named wall-clock spans, counters, notes.

    Spans accumulate (total seconds, call count) per name; counters are
    either monotonic (``count``) or last-write-wins gauges (``gauge``).  An
    optional sink receives every span as a record the moment it closes, so a
    long run's telemetry streams out live alongside its monitor frames.
    """

    def __init__(self, sink: Sink | None = None):
        self.spans: dict[str, list] = {}  # name -> [total_s, count]
        self.counters: dict[str, float] = {}
        self.notes: dict[str, Any] = {}
        self._sink = sink

    def span(self, name: str, **args) -> Span:
        return span(name, self, **args)

    def record(self, name: str, seconds: float) -> None:
        e = self.spans.get(name)
        if e is None:
            self.spans[name] = [seconds, 1]
        else:
            e[0] += seconds
            e[1] += 1
        if self._sink is not None:
            self._sink.emit({"type": "span", "name": name, "s": round(seconds, 6)})

    def count(self, name: str, inc: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + inc

    def gauge(self, name: str, value: float) -> None:
        self.counters[name] = value

    def note(self, name: str, value: Any) -> None:
        self.notes[name] = value

    def total(self, name: str) -> float:
        e = self.spans.get(name)
        return e[0] if e else 0.0

    def summary(self) -> dict:
        return dict(
            spans={
                n: dict(total_s=round(t, 6), count=c)
                for n, (t, c) in self.spans.items()
            },
            counters={n: (v if isinstance(v, (int, bool)) else float(v))
                      for n, v in self.counters.items()},
            notes=dict(self.notes),
        )


class NullRecorder:
    """API-compatible no-op recorder; its spans are profiler annotations
    only."""

    spans: dict = {}
    counters: dict = {}
    notes: dict = {}

    def span(self, name: str, **args) -> Span:
        return span(name, **args)

    def record(self, name: str, seconds: float) -> None:
        pass

    def count(self, name: str, inc: float = 1) -> None:
        pass

    def gauge(self, name: str, value: float) -> None:
        pass

    def note(self, name: str, value: Any) -> None:
        pass

    def total(self, name: str) -> float:
        return 0.0

    def summary(self) -> dict:
        return dict(spans={}, counters={}, notes={})


# --------------------------------------------------------------------------
# RunManifest: self-describing sidecar JSON
# --------------------------------------------------------------------------


def scenario_hash(*trees) -> str:
    """Deterministic content hash over pytrees (workload, platform, ext).

    Hashes tree structure, leaf shapes/dtypes, and leaf bytes, so two runs
    share a hash iff they simulate the same scenario — the key manifests are
    compared by.  ``None`` trees hash to a fixed token (subsystem off)."""
    import jax

    h = hashlib.sha256()
    for tree in trees:
        if tree is None:
            h.update(b"<none>")
            continue
        leaves, treedef = jax.tree.flatten(tree)
        h.update(repr(treedef).encode())
        for x in leaves:
            a = np.asarray(x)
            h.update(f"{a.shape}{a.dtype}".encode())
            h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


def jsonable(tree):
    """Pytree -> plain JSON-serializable Python (dicts / lists / scalars).

    NamedTuples become dicts keyed by field, arrays become (nested) lists,
    ``None`` passes through — how calibration results and parameter pytrees
    land inside a RunManifest sidecar without a custom encoder.
    """
    if tree is None:
        return None
    if hasattr(tree, "_asdict"):
        return {k: jsonable(v) for k, v in tree._asdict().items()}
    if isinstance(tree, dict):
        return {str(k): jsonable(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [jsonable(v) for v in tree]
    if isinstance(tree, (str, bool, int, float)):
        return tree
    a = np.asarray(tree)
    return a.item() if a.ndim == 0 else a.tolist()


def run_manifest(
    *,
    jobs=None,
    sites=None,
    ext=None,
    subsystems: tuple = (),
    recorder=None,
    extra: dict | None = None,
) -> dict:
    """Build a RunManifest dict: environment + scenario identity + telemetry.

    Everything a perf regression hunt asks first: which jax/backend/device
    count produced this artifact, what scenario hash it simulated, which
    subsystems were attached, and where the wall-clock went.  Written next to
    any exported artifact by ``write_manifest`` (Tracekit-style sidecars)."""
    import platform as _platform
    import sys

    import jax

    devices = jax.devices()
    m: dict[str, Any] = {
        "schema": MANIFEST_SCHEMA,
        "created_unix": round(time.time(), 3),
        "jax": {
            "version": jax.__version__,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "device_kinds": sorted({d.device_kind for d in devices}),
        },
        "versions": {
            "python": _platform.python_version(),
            "numpy": np.__version__,
            "jax": jax.__version__,
        },
        "platform": _platform.platform(),
        "argv": list(sys.argv),
    }
    if jobs is not None or sites is not None or ext is not None:
        names = [s.name for s in subsystems] if subsystems else sorted(ext or {})
        m["scenario"] = {
            "hash": scenario_hash(jobs, sites, ext),
            "n_jobs": int(np.asarray(jobs.valid).sum()) if jobs is not None else None,
            "job_capacity": jobs.capacity if jobs is not None else None,
            "n_sites": sites.capacity if sites is not None else None,
            "subsystems": names,
        }
    if recorder is not None:
        m["telemetry"] = recorder.summary()
    if extra:
        m["extra"] = extra
    return m


REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs in JAX's persistent cache across processes.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing.  Otherwise the cache goes to ``<repo>/.jax_cache``: a fixed
    path, since the path is part of the cache key.  Returns the directory in
    use.  Entry points call this; importing ``repro`` does not."""
    import os

    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def manifest_path(artifact_path) -> pathlib.Path:
    """Sidecar path convention: ``run.ndjson`` -> ``run.ndjson.manifest.json``."""
    p = pathlib.Path(artifact_path)
    if p.name.endswith(MANIFEST_SUFFIX):
        return p
    return p.with_name(p.name + MANIFEST_SUFFIX)


def write_manifest(artifact_path, manifest: dict) -> pathlib.Path:
    """Write ``manifest`` as the sidecar of ``artifact_path``; returns the
    sidecar path.  Never touches the artifact itself."""
    path = manifest_path(artifact_path)
    path.write_text(json.dumps(manifest, indent=2, sort_keys=True) + "\n")
    return path


def read_manifest(artifact_path) -> dict:
    return json.loads(manifest_path(artifact_path).read_text())


# environment keys whose drift between two manifests explains perf drift
_DRIFT_KEYS = (
    ("jax", "version"),
    ("jax", "backend"),
    ("jax", "device_count"),
    ("jax", "device_kinds"),
    ("versions", "python"),
    ("versions", "numpy"),
)


def manifest_drift(fresh: dict, baseline: dict) -> list[dict]:
    """Environment diffs between two manifests (empty = same environment).

    Only compares the perf-relevant environment block — scenario hashes and
    telemetry are expected to differ run-to-run."""
    diffs = []
    for section, key in _DRIFT_KEYS:
        a = (fresh.get(section) or {}).get(key)
        b = (baseline.get(section) or {}).get(key)
        if a != b:
            diffs.append({"key": f"{section}.{key}", "fresh": a, "baseline": b})
    return diffs


# --------------------------------------------------------------------------
# lane-occupancy tracing for scenario ensembles (DESIGN.md §8/§9)
# --------------------------------------------------------------------------


def lane_occupancy(result, buckets=None) -> dict:
    """Per-lane occupancy metrics for an ensemble ``SimResult`` (leading K).

    Reports, per lane: rounds executed, ``active_frac`` (this lane's rounds
    over the slowest lane's — the lock-step tax a *vmapped* ensemble pays for
    the lane, and the work a sharded lane avoids), valid-job count and
    padding fraction.  When the run logged frames (``log_rows > 0``), each
    lane also reports ``work_round_frac`` — the fraction of its logged rounds
    with QUEUED/ASSIGNED rows outstanding, i.e. rounds the phase-skip guard
    could *not* skip (``skip_frac`` is its complement, the guard's hit-rate).

    ``buckets`` (a ``ScenarioBuckets``) adds the per-bucket padding-waste
    breakdown from ``ScenarioBuckets.padding_stats``.
    """
    from .types import ASSIGNED, QUEUED

    rounds = np.atleast_1d(np.asarray(result.rounds)).reshape(-1)
    K = rounds.size
    valid = np.asarray(result.jobs.valid).reshape(K, -1)
    cap = valid.shape[-1]
    n_valid = valid.sum(-1)
    max_r = max(int(rounds.max()), 1)

    # per-lane work-round rate from the in-sim frame log, when captured
    work_frac = [None] * K
    log = getattr(result, "log", None)
    if log is not None and np.asarray(log.time).ndim >= 1:
        counts = np.asarray(log.counts).reshape(K, -1, np.asarray(log.counts).shape[-1])
        ridx = np.asarray(log.round_idx).reshape(K, -1)
        for i in range(K):
            m = ridx[i] >= 0
            if m.any():
                work = (counts[i, m, QUEUED] + counts[i, m, ASSIGNED]) > 0
                work_frac[i] = float(work.mean())

    lanes = []
    for i in range(K):
        lane = dict(
            lane=i,
            rounds=int(rounds[i]),
            active_frac=round(float(rounds[i]) / max_r, 4),
            n_jobs=int(n_valid[i]),
            padded_rows=int(cap - n_valid[i]),
            padding_frac=round(1.0 - float(n_valid[i]) / max(cap, 1), 4),
        )
        if work_frac[i] is not None:
            lane["work_round_frac"] = round(work_frac[i], 4)
            lane["skip_frac"] = round(1.0 - work_frac[i], 4)
        lanes.append(lane)

    wf = [w for w in work_frac if w is not None]
    out = dict(
        lanes=lanes,
        summary=dict(
            n_lanes=K,
            rounds_max=int(rounds.max()),
            rounds_total=int(rounds.sum()),
            # lock-step tax: rounds a vmapped ensemble executes per lane vs
            # the rounds the lanes actually need
            active_frac_mean=round(float(rounds.mean()) / max_r, 4),
            lockstep_waste_frac=round(1.0 - float(rounds.sum()) / (K * max_r), 4),
            padding_frac_mean=round(1.0 - float(n_valid.mean()) / max(cap, 1), 4),
            **({"work_round_frac_mean": round(float(np.mean(wf)), 4),
                "skip_frac_mean": round(1.0 - float(np.mean(wf)), 4)} if wf else {}),
        ),
    )
    if buckets is not None:
        out["buckets"] = buckets.padding_stats()
    return out
