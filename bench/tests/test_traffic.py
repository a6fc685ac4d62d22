"""The seeded generators are deterministic, and the rows the reference
follows are the rows the program draws."""
import jax
import numpy as np
import pytest

from bench import traffic as tr
from bench.tests import tiny


def images():
    return {"data": "images", "data_seed": 3, "n_clients": 5,
            "per_client": 8, "n_test": 6, "sample_frac": 0.25}


def test_same_seed_same_data():
    a = tr.federation_data(images())
    b = tr.federation_data(images())
    for k in ("x", "y", "test_x", "test_y"):
        np.testing.assert_array_equal(a[k], b[k])
    c = tr.federation_data(dict(images(), data_seed=4))
    assert not np.array_equal(a["x"], c["x"])
    t = tiny.traffic()
    np.testing.assert_array_equal(tr.federation_data(t, 256)["x"],
                                  tr.federation_data(t, 256)["x"])


def test_sorted_shards_cut_by_class():
    d = tr.federation_data(images())
    y = np.asarray(d["y"]).ravel()
    assert d["x"].shape == (5, 8, 32, 32, 3)
    assert (np.diff(y) >= 0).all()


def test_token_split():
    t = tiny.traffic()
    d = tr.federation_data(t, 256)
    assert d["x"].shape == (4, 4, 7) and d["y"].shape == (4, 4)
    assert int(np.max(d["x"])) < 256


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 9])
def test_seed_keys(seed):
    a, b = tr.seed_keys(seed), tr.seed_keys(seed)
    np.testing.assert_array_equal(a["params"], b["params"])
    assert not np.array_equal(a["params"], a["train"])
    assert not np.array_equal(tr.seed_keys(seed)["params"],
                              tr.seed_keys(seed + 1)["params"])


def test_rows_follow_the_program():
    """client_rows / sealed_rows / byzantine_mask give the program's own
    draws (FederatedData.minibatch, Federation.create)."""
    from repro.core.attacks import make_byzantine_mask
    from repro.data import FederatedData
    from repro.fl import FLConfig, Federation
    t = tiny.traffic()
    d = tr.federation_data(t, 256)
    fd = FederatedData(x=d["x"], y=d["y"], n_classes=256)
    key = tr.seed_keys(11)["train"]
    _, subs = tr.round_keys(key, 3)
    rows = tr.client_rows(subs[2], 4, 4, 2)
    kb = jax.random.split(subs[2], 4)[0]
    xb, _ = fd.minibatch(kb, 2)
    np.testing.assert_array_equal(
        xb, jax.vmap(lambda a, i: a[i])(d["x"], rows))
    cfg = FLConfig(n_clients=4, f=1, sample_frac=t["sample_frac"])
    fed = Federation.create(None, fd, d["test_x"], d["test_y"], cfg,
                            tr.data_keys(t)["federation"])
    gx, _ = fed.server.guide_batches()
    sealed = jax.vmap(lambda a, i: a[i])(d["x"], tr.sealed_rows(t))
    np.testing.assert_array_equal(np.asarray(gx), np.asarray(sealed,
                                                             np.float32))
    for n, f in ((23, 5), (32, 7), (4, 1)):
        np.testing.assert_array_equal(tr.byzantine_mask(n, f),
                                      make_byzantine_mask(n, f))
