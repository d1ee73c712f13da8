"""Run the held sharded what-if cell at a tiny size on four virtual CPU devices and print
its result line; ``--drop-chip`` first leaves out the exchange of the last
chip's lanes (their results never come back).  Used by ``test_sharded.py``."""
import os
import sys
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
T0 = time.perf_counter()

import pathlib  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parents[2] / "src")]

import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import harness  # noqa: E402
from test_harness import DATA, HELD, tiny  # noqa: E402


def main() -> int:
    import repro.core.distributed as dist

    if "--drop-chip" in sys.argv:
        real = dist.simulate_many_sharded

        def dropped(sb, policy, key, mesh, **kw):
            res = real(sb, policy, key, mesh, **kw)
            n_dev = mesh.devices.size
            lost = np.concatenate([np.array_split(np.asarray(ix), n_dev)[-1] for ix in sb.index])
            mask = jnp.zeros(res.rounds.shape[0], bool).at[lost].set(True)[:, None]
            jobs = res.jobs
            return res._replace(jobs=jobs._replace(state=jnp.where(mask & jobs.valid, 0, jobs.state)))

        dist.simulate_many_sharded = dropped
    harness.TRAFFIC_DIR = DATA
    out = harness.run_cell(HELD, "whatif64.q60.shard4", 77, 1.0, False, T0, devices=jax.devices()[:4],
                           resize=tiny)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
