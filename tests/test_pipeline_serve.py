"""Datasets, the DILI record store, and the serving session table."""
import numpy as np
import pytest

from repro.data.datasets import ALL_DATASETS, generate
from repro.data.record_store import RecordStore
from repro.serve.sessions import SessionTable


@pytest.mark.parametrize("name", ALL_DATASETS)
def test_datasets_shape_and_determinism(name):
    a = generate(name, 5000, seed=3)
    b = generate(name, 5000, seed=3)
    assert len(a) == 5000
    assert np.all(np.diff(a) > 0)
    np.testing.assert_array_equal(a, b)


def test_record_store_roundtrip():
    rng = np.random.default_rng(6)
    keys = np.unique(rng.uniform(0, 1e6, 500))
    docs = [rng.integers(0, 100, rng.integers(5, 40)).astype(np.int32)
            for _ in keys]
    store = RecordStore(keys, docs)
    order = np.argsort(keys)
    for i in rng.integers(0, len(keys), 50):
        got = store.fetch(float(keys[i]))
        np.testing.assert_array_equal(got, docs[i])
    # batched lookup agreement
    off, ln, f = store.lookup(keys[:64])
    assert f.all()
    # write path + publish
    store.add(2e6, np.arange(7, dtype=np.int32))
    store.publish()
    np.testing.assert_array_equal(store.fetch(2e6), np.arange(7))


def test_session_table_admit_lookup_evict():
    t = SessionTable(16)
    s1 = t.admit(100.5)
    s2 = t.admit(200.5)
    assert s1 != s2
    v, f = t.lookup_batch([100.5, 200.5, 300.5])
    assert list(f) == [True, True, False]
    assert list(v[:2]) == [s1, s2]
    t.evict(100.5)
    v, f = t.lookup_batch([100.5])
    assert not f[0]
    # slot is recycled
    s3 = t.admit(300.5)
    assert s3 == s1
    with pytest.raises(KeyError):
        t.admit(300.5)
    with pytest.raises(KeyError):
        t.evict(999.0)


def test_session_table_exhaustion():
    t = SessionTable(3)         # 2 warm ids + 1 free
    t.admit(50.0)
    with pytest.raises(RuntimeError):
        t.admit(60.0)
