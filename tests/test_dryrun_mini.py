"""Mini multi-pod dry-run: the launch/dryrun plumbing (specs, shardings,
lower+compile, roofline extraction) on an 8-device (2,2,2) pod/data/model
mesh with smoke configs — CI-sized proof that the 512-device path is
coherent."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

assert len(jax.devices()) == 8
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)

from repro.configs import get_smoke
from repro.models import build_model
from repro.parallel.sharding import cache_shardings, params_shardings
from repro.launch.roofline import collective_bytes
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step
from repro.serve.serve_step import make_decode_step

for arch in ["deepseek-7b", "kimi-k2-1t-a32b", "mamba2-130m", "recurrentgemma-2b"]:
    cfg = get_smoke(arch).replace(vocab_size=512)
    if cfg.family == "moe":
        cfg = cfg.replace(router_groups=4)
    model = build_model(cfg)

    # ---- train step, sharded state, donated ------------------------------
    abs_state = jax.eval_shape(lambda: init_train_state(model, jax.random.PRNGKey(0)))
    psh = params_shardings(abs_state.params, mesh)
    state_sh = type(abs_state)(params=psh,
                               opt={"m": psh, "v": psh, "count": NamedSharding(mesh, P())},
                               err=None)
    state_structs = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), abs_state, state_sh)
    B, S = 8, 64
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32,
             sharding=NamedSharding(mesh, P(("pod", "data"), None)))}
    step = make_train_step(model, AdamWConfig(), microbatches=2)
    with jax.set_mesh(mesh):
        lowered = jax.jit(step, donate_argnums=0).lower(state_structs, batch)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text())
    assert sum(coll.values()) > 0, (arch, "expected collectives in train step")
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    assert float(ca.get("flops", 0)) > 0

    # ---- decode step with sharded cache -----------------------------------
    abs_params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pstructs = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abs_params, params_shardings(abs_params, mesh))
    abs_cache = jax.eval_shape(lambda: model.init_cache(B, 128))
    cstructs = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abs_cache, cache_shardings(abs_cache, mesh, batch=("pod", "data")))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32,
                               sharding=NamedSharding(mesh, P(("pod", "data"), None)))
    decode = make_decode_step(model)
    with jax.set_mesh(mesh):
        dec_compiled = jax.jit(lambda p, t, c: decode(p, t, c),
                               donate_argnums=2).lower(pstructs, tok, cstructs).compile()
    assert dec_compiled.memory_analysis() is not None
    print("MINI-OK", arch)
print("ALL-OK")
"""


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True, timeout=1800
    )
    assert out.returncode == 0, out.stderr[-5000:]
    assert "ALL-OK" in out.stdout
