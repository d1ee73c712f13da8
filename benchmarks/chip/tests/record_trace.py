"""Record the small chip trace that ``test_trace_reduce.py`` reads.

    python3 benchmarks/chip/tests/record_trace.py   # on one TPU chip

Three 60 s dashboard frames of a 20-site, 600-job scenario with the data
subsystem on, under the profiler, with the harness's ``frame`` and
``snapshot`` spans.  Writes ``tests/data/frames.xplane.pb``.
"""
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import scenario  # noqa: E402
from trace_reduce import find_xplane, reduce_file  # noqa: E402
from traffic import generators as gen  # noqa: E402


def main() -> int:
    from repro.core import advance_sim, get_policy, init_sim

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
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
        (HERE / "data").mkdir(exist_ok=True)
        shutil.copy(src, HERE / "data" / "frames.xplane.pb")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    red = reduce_file(str(HERE / "data" / "frames.xplane.pb"))
    print({k: v for k, v in red.items() if k not in ("device_ops",)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
