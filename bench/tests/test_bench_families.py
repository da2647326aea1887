"""The problem families, found by the name a configuration gives: the
same seed gives the same instances, and each has its stated shape."""
import json

import numpy as np
import pytest

from bench.lib import drive, manifest

CONFIGS = {c["name"] for c in manifest.load()["configs"]}


def _config(name, **small):
    with open(manifest.config_path(name)) as f:
        return {**json.load(f), **small}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_same_seed_same_instances(name):
    cfg = _config(name, n=64 if name == "chip64" else 200, edges=1999)
    a = drive.instances(cfg, drive.rng_for(2**31 + 5, 1), 2)
    b = drive.instances(cfg, drive.rng_for(2**31 + 5, 1), 2)
    c = drive.instances(cfg, drive.rng_for(2**31 + 6, 1), 2)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    for J in a:
        assert J.dtype == np.int16 and np.array_equal(J, J.T)
        assert not np.any(np.diag(J))


def test_random_qubo_levels_and_density():
    cfg = _config("chip64")
    (J,) = drive.instances(cfg, drive.rng_for(7, 1), 1)
    iu = np.triu_indices(64, k=1)
    w = J[iu]
    assert np.abs(w).max() <= cfg["max_level"]
    assert abs(np.mean(w != 0) - cfg["density"]) < 0.05


def test_gset_er_is_a_unit_maxcut_of_the_stated_degree():
    cfg = _config("gset2000")
    (J,) = drive.instances(cfg, drive.rng_for(7, 1), 1)
    assert set(np.unique(J)) <= {-cfg["weight"], 0}
    edges = int(np.count_nonzero(J)) // 2
    assert abs(edges - cfg["edges"]) < 5 * np.sqrt(cfg["edges"])


def test_unknown_family_is_an_error():
    with pytest.raises(KeyError):
        drive.instances({"family": "no-such-family", "n": 8},
                        drive.rng_for(1, 1), 1)
