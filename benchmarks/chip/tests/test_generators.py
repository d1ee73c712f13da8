"""The benchmark's copied generators draw what the program's own generators
drew when they were copied.  This guards the copy's fidelity at copy time;
a later change to the program's generators may break it without touching
the benchmark's yardstick."""
import numpy as np
import pytest

from traffic import generators as gen


def _eq(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_jobs_match_program(seed):
    from repro.core import synthetic_panda_jobs

    for n_datasets in (None, 40):
        mine = gen.panda_jobs(500, seed=seed, duration=6 * 3600.0, n_datasets=n_datasets)
        prog = synthetic_panda_jobs(500, seed=seed, duration=6 * 3600.0, n_datasets=n_datasets)
        for k, v in mine.items():
            _eq(v, getattr(prog, k))


@pytest.mark.parametrize("seed", [1, 3])
def test_platform_match_program(seed):
    from repro.core import atlas_like_platform

    mine = gen.atlas_platform(57, seed=seed)
    prog = atlas_like_platform(57, seed=seed)
    for k, v in mine.items():
        _eq(v, getattr(prog, k))


def test_wlcg_platform_has_the_stated_cores():
    assert gen.atlas_platform(300, seed=1)["cores"].sum() == 444_218


@pytest.mark.parametrize("seed", [0, 4])
def test_network_catalog_and_origins_match_program(seed):
    from repro.core import atlas_like_network, make_replicas, zipf_dataset_sizes

    bw, lat = gen.atlas_network(23, seed=seed)
    net = atlas_like_network(23, seed=seed)
    _eq(bw, net.bw)
    _eq(lat, net.latency)
    _eq(gen.zipf_sizes(50, seed=seed), zipf_dataset_sizes(50, seed=seed))
    cap = gen.atlas_platform(23, seed=1)["memory"] * np.float32(1e9)
    _eq(gen.replica_origins(cap, 50, seed=seed),
        make_replicas(gen.zipf_sizes(50, seed=seed), cap, seed=seed).origin)


@pytest.mark.parametrize("seed", [100, 113])
def test_flaky_calendar_matches_program(seed):
    from repro.core import flaky_sites

    flaky = np.arange(1, 40, 10)
    mine = gen.flaky_calendar(40, flaky, horizon=43200.0, mtbf=14400.0, seed=seed, max_windows=6)
    prog = flaky_sites(40, flaky, horizon=43200.0, mtbf=14400.0, seed=seed, max_windows=6)
    for k, v in mine.items():
        _eq(v, getattr(prog, k))


def test_permutation_keeps_the_work_and_the_arrivals():
    jobs = gen.panda_jobs(300, seed=0, duration=3600.0)
    a, b = gen.permute_jobs(jobs, 1), gen.permute_jobs(jobs, 2)
    _eq(a["arrival"], jobs["arrival"])
    _eq(np.sort(a["work"]), np.sort(jobs["work"]))
    assert not np.array_equal(a["work"], b["work"])
