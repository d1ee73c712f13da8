"""Ahead-of-time compiles for a described TPU v5e at WLCG widths.

Nothing runs: the TPU compiler, installed with jax, compiles for a chip that
is described and not attached.  That catches what interpret-mode tests
cannot — primitives Mosaic does not lower, misaligned tiles, VMEM overuse,
programs that do not fit the device — on any host, at no chip time.  The
topology is described inside a fixture (never at import) so that only the
test process that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import (
    atlas_like_platform,
    get_data_policy,
    get_policy,
    make_replicas,
    make_transfers,
    synthetic_panda_jobs,
)
from repro.core.network import NetworkState
from repro.core.engine import _simulate
from repro.core.subsystems import resolve_subsystems
from repro.kernels.assign.assign import assign_pallas
from repro.kernels.assign.fused import fused_assign_pallas

N, K, E = 100_000, 16, 300  # WLCG widths: jobs, candidate sites, sites
D = 1_000                    # WLCG catalog: datasets
HBM_BYTES = 16e9             # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


def test_fused_assign_kernel_compiles_for_v5e(one_chip):
    args = _shapes(
        (
            jax.ShapeDtypeStruct((N, K), jnp.float32),
            jax.ShapeDtypeStruct((N, K), jnp.int32),
            jax.ShapeDtypeStruct((N,), jnp.float32),
            jax.ShapeDtypeStruct((E,), jnp.float32),
        ),
        one_chip,
    )
    compiled = jax.jit(lambda *a: fused_assign_pallas(*a)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [1, 2])
def test_dense_assign_kernel_compiles_for_v5e(one_chip, k):
    args = _shapes(
        (
            jax.ShapeDtypeStruct((N, E), jnp.float32),
            jax.ShapeDtypeStruct((N,), jnp.float32),
            jax.ShapeDtypeStruct((E,), jnp.float32),
        ),
        one_chip,
    )
    compiled = jax.jit(lambda *a: assign_pallas(*a, k=k)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wlcg_simulate_program_fits_one_v5e(one_chip):
    """The jitted engine at S=300, J=100k compiles for the chip and fits its
    memory (arguments, outputs and temporaries of the one program)."""
    jobs, sites = jax.eval_shape(
        lambda: (synthetic_panda_jobs(N, seed=0, duration=6 * 3600.0),
                 atlas_like_platform(E, seed=1))
    )
    subs, ext0 = resolve_subsystems()
    compiled = _simulate.lower(
        _shapes(jobs, one_chip),
        _shapes(sites, one_chip),
        get_policy("panda_dispatch"),
        _shapes(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip),
        ext0,
        subsystems=subs,
        max_rounds=2000,
    ).compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < total < HBM_BYTES, total


def test_wlcg_transfer_queues_program_fits_one_v5e(one_chip):
    """The engine with replica-aware stage-in and FTS link queues (cap 2) at
    S=300, J=100k compiles for the chip and fits its memory: the queue
    state is J- and L-wide, with no [S*S, J] ring."""
    jobs, sites = jax.eval_shape(
        lambda: (synthetic_panda_jobs(N, seed=0, duration=6 * 3600.0, n_datasets=D),
                 atlas_like_platform(E, seed=1))
    )
    net = jax.eval_shape(lambda: NetworkState(bw=jnp.ones((E, E)), latency=jnp.ones((E, E))))
    rep = jax.eval_shape(lambda: make_replicas(jnp.ones((D,)), jnp.ones((E,)),
                                               origin=jnp.zeros((D,), jnp.int32)))
    ts = jax.eval_shape(lambda: make_transfers(E, N, max_active=2))
    subs, ext0 = resolve_subsystems(data_policy=get_data_policy("cache_on_read"), network=net,
                                    replicas=rep, transfers=ts, validate=False)
    compiled = _simulate.lower(
        _shapes(jobs, one_chip),
        _shapes(sites, one_chip),
        get_policy("panda_dispatch"),
        _shapes(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip),
        _shapes(ext0, one_chip),
        subsystems=subs,
        max_rounds=2000,
    ).compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < total < HBM_BYTES, total
