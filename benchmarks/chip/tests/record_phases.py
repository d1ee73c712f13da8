"""Record the small chip trace that ``test_scopes.py`` reads.

    python3 benchmarks/chip/tests/record_phases.py   # on one TPU chip

The scenario and spans of ``record_trace.py`` (three 60 s dashboard frames of
a 20-site, 600-job scenario with the data subsystem on, under the profiler,
with the harness's ``frame`` and ``snapshot`` spans), run by a program that
names its round loop's phases and opens its own ``advance_sim`` spans.
Writes ``tests/data/phases.xplane.pb``.
"""
import json
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import scenario  # noqa: E402
import scopes  # noqa: E402
from trace_reduce import find_xplane, reduce_file  # noqa: E402
from traffic import generators as gen  # noqa: E402

OUT = HERE / "data" / "phases.xplane.pb"


def main() -> int:
    from repro.core import advance_sim, get_policy, init_sim

    if jax.devices()[0].platform != "tpu":
        print("record_phases: no TPU", file=sys.stderr)
        return 2
    sites = gen.atlas_platform(20, seed=1)
    bw, lat = gen.atlas_network(20, seed=0)
    size = gen.zipf_sizes(40, seed=3)
    cap = sites["memory"] * np.float32(1e9)
    lane = dict(jobs=gen.panda_jobs(600, seed=0, duration=3600.0, n_datasets=40), sites=sites,
                avail=None, data=dict(bw=bw, latency=lat, size=size, disk_cap=cap,
                                      origin=gen.replica_origins(cap, 40, seed=0)))
    jobs, sites_p, kw = scenario.to_program(lane)
    h = init_sim(jobs, sites_p, get_policy("panda_dispatch"), jax.random.PRNGKey(0),
                 max_rounds=10**7, **kw)
    jax.block_until_ready(advance_sim(h, 0.0).state.jobs.state)
    tmp = tempfile.mkdtemp(dir=HERE)
    try:
        jax.profiler.start_trace(tmp)
        for k in (1, 2, 3):
            with jax.profiler.TraceAnnotation("frame"):
                h = advance_sim(h, 60.0 * k)
                jax.block_until_ready(h.state.jobs.state)
            with jax.profiler.TraceAnnotation("snapshot"):
                jax.device_get(h.state.jobs.state)
        jax.profiler.stop_trace()
        src = find_xplane(tmp)
        OUT.parent.mkdir(exist_ok=True)
        shutil.copy(src, OUT)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    red = reduce_file(str(OUT))
    split = scopes.reduce_file(str(OUT))
    print(json.dumps(dict(split, busy_s=red["busy_s_mean"], rounds=int(h.state.round))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
