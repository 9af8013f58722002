"""Pallas kernel validation: interpret-mode vs ref.py oracle vs host truth,
swept over shapes/dtypes/distributions (per the kernel-testing contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.flat import TAG_CHILD, TAG_PAIR, FlatDILI, flatten
from repro.kernels import ops
from repro.kernels.dili_search import dili_search_pallas
from repro.kernels.ref import dili_search_ref
from repro.workloads.oracle import SortedOracle
from tests.conftest import make_keys


def build(dist, n, seed=21):
    rng = np.random.default_rng(seed)
    keys = make_keys(dist, n, rng)
    d, keys32 = ops.build_f32_index(keys)
    f = flatten(d)
    return keys32, f, ops.kernel_arrays(f)


@pytest.mark.parametrize("dist", ["logn", "uniform", "fb", "wikits"])
@pytest.mark.parametrize("n", [2000, 30000])
def test_kernel_matches_truth(dist, n):
    keys32, f, arrs = build(dist, n)
    rng = np.random.default_rng(22)
    qi = rng.integers(0, len(keys32), 4096)
    q = jnp.asarray(keys32[qi], jnp.float32)
    v, fnd = ops.dili_search(arrs, q)
    v, fnd = np.asarray(v), np.asarray(fnd)
    assert fnd.all()
    assert np.array_equal(v, qi)


@pytest.mark.parametrize("block_q", [512, 2048])
def test_kernel_matches_ref_oracle(block_q):
    keys32, f, arrs = build("logn", 20000)
    rng = np.random.default_rng(23)
    qi = rng.integers(0, len(keys32), 4096)
    q = jnp.asarray(keys32[qi], jnp.float32)
    vk, fk, fbk = dili_search_pallas(
        arrs["a"], arrs["b"], arrs["base"], arrs["fo"], arrs["dense"],
        arrs["tag"], arrs["key"], arrs["val"], arrs["root"], q,
        max_depth=f.max_depth, interpret=True, block_q=block_q)
    vr, fr, fbr = dili_search_ref(
        arrs["a"], arrs["b"], arrs["base"], arrs["fo"], arrs["dense"],
        arrs["tag"], arrs["key"], arrs["val"], arrs["root"][0], q,
        f.max_depth)
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(fk), np.asarray(fr))
    np.testing.assert_array_equal(np.asarray(fbk), np.asarray(fbr))


def test_kernel_misses_no_false_positives():
    keys32, f, arrs = build("uniform", 20000)
    rng = np.random.default_rng(24)
    qi = rng.integers(0, len(keys32) - 1, 2048)
    mids = ((keys32[qi].astype(np.float64)
             + keys32[qi + 1].astype(np.float64)) / 2).astype(np.float32)
    ok = (mids != keys32[qi]) & (mids != keys32[qi + 1])
    v, fnd = ops.dili_search(arrs, jnp.asarray(mids))
    assert not np.asarray(fnd)[ok].any()


def test_kernel_pads_ragged_batch():
    keys32, f, arrs = build("logn", 5000)
    q = jnp.asarray(keys32[:777], jnp.float32)      # not a block multiple
    v, fnd = ops.dili_search(arrs, q)
    assert np.asarray(fnd).all()
    assert np.array_equal(np.asarray(v), np.arange(777))


def test_vmem_budget_fallback_path():
    """Oversized tables must route to the XLA path and stay correct."""
    keys32, f, arrs = build("uniform", 30000)
    import repro.kernels.ops as O
    old = O.VMEM_BUDGET_BYTES
    try:
        O.VMEM_BUDGET_BYTES = 1   # force fallback
        q = jnp.asarray(keys32[:1024], jnp.float32)
        v, fnd = O.dili_search(arrs, q)
        assert np.asarray(fnd).all()
        assert np.array_equal(np.asarray(v), np.arange(1024))
    finally:
        O.VMEM_BUDGET_BYTES = old



# -- dense (DILI-LO) leaves: the kernel's rank count against the bisection ----

#: leaf sizes that straddle a lane row (128 slots) and an aligned
#: (8, 128) chunk (1,024 slots), up to the largest leaf of the multiget
#: cell's index (4,400 pairs)
DENSE_FANOUTS = (1, 2, 127, 128, 129, 1023, 1024, 1025, 4400)
_SPAN = 1 << 14     # key span per leaf: a power of two, so b is exact in f32


def _dense_leaves(fanouts):
    """A model root over dense leaves of the given sizes, as flat tables.
    Leaf i holds the odd keys i * _SPAN + 1, + 3, ...  One more leaf is
    sized so the slot table fills whole (8, 128) tiles: its rows end at
    the last row of the kernel's padded table."""
    fanouts = list(fanouts)
    m = len(fanouts) + 1
    fanouts.append(-(m + sum(fanouts)) % 1024 or 1024)
    n_slots = m + sum(fanouts)
    a, b = np.zeros(m + 1), np.zeros(m + 1)
    b[0] = 1.0 / _SPAN
    base = np.cumsum([0, m] + fanouts[:-1]).astype(np.int32)
    tag = np.full(n_slots, TAG_PAIR, np.int8)
    tag[:m] = TAG_CHILD
    key = np.zeros(n_slots)
    for i, f in enumerate(fanouts):
        key[base[i + 1]:base[i + 1] + f] = i * _SPAN + 1 + 2 * np.arange(f)
    val = np.concatenate([np.arange(1, m + 1),
                          np.arange(n_slots - m) * 7 + 3])
    return FlatDILI(a=a, b=b, base=base, fo=np.array([m] + fanouts, np.int32),
                    dense=np.array([0] + [1] * m, np.int8), tag=tag,
                    key=key, val=val, pair_key=key[m:], pair_val=val[m:],
                    pair_slot=np.arange(m, n_slots, dtype=np.int32),
                    root=0, max_depth=2, key_lo=0.0, key_hi=m * _SPAN)


def _dense_case(case):
    if case == "fanouts_up":
        return _dense_leaves(DENSE_FANOUTS)
    if case == "fanouts_down":
        return _dense_leaves(DENSE_FANOUTS[::-1])
    if case == "ordered_f32":       # the multiget cell's shape, smaller
        d, _ = ops.build_f32_index(np.arange(40000.0), sample_stride=4)
    else:                           # DILI-LO: every leaf dense
        keys = make_keys(case.split("_")[-1], 20000,
                         np.random.default_rng(25))
        d, _ = ops.build_f32_index(keys, local_optimized=False)
    return flatten(d)


@pytest.mark.parametrize("case", ["fanouts_up", "fanouts_down", "ordered_f32",
                                  "dili_lo_logn", "dili_lo_wikits"])
def test_kernel_dense_leaf_rank_matches_bisection(case):
    """Every dense-leaf shape: exact hits, the f32 neighbours of each key
    (below a leaf's first key, above its last, between two keys) and +inf
    padding lanes answer as the bisecting oracle and the loaded pairs do."""
    f = _dense_case(case)
    leaves = np.nonzero(f.dense > 0)[0]
    assert len(leaves) and (f.fo[leaves] > 1).any()
    keys = f.pair_key.astype(np.float32)
    rng = np.random.default_rng(26)
    ends = np.concatenate([f.base[leaves], f.base[leaves] + f.fo[leaves] - 1])
    pick = np.concatenate([rng.choice(len(keys), min(len(keys), 3000),
                                      replace=False),
                           np.searchsorted(keys, f.key[ends])])
    k = keys[pick]
    q = np.concatenate([k, np.nextafter(k, -np.inf), np.nextafter(k, np.inf)])
    q = np.pad(q, (0, -len(q) % 512 + 512), constant_values=np.inf)
    arrs = ops.kernel_arrays(f)
    tables = [arrs[t] for t in ("a", "b", "base", "fo", "dense", "tag", "key",
                                "val")]
    vk, fk, fbk = dili_search_pallas(*tables, arrs["root"], jnp.asarray(q),
                                     max_depth=f.max_depth, interpret=True,
                                     block_q=512)
    vr, fr, fbr = dili_search_ref(*tables, arrs["root"][0], jnp.asarray(q),
                                  f.max_depth)
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(fk), np.asarray(fr))
    np.testing.assert_array_equal(np.asarray(fbk), np.asarray(fbr))
    want_v, want_f = SortedOracle(keys, f.pair_val).lookup(q)
    fk = np.asarray(fk)
    np.testing.assert_array_equal(fk, want_f)
    np.testing.assert_array_equal(np.asarray(vk)[fk], want_v[fk])
    assert want_f.any() and not want_f.all() and not np.asarray(fbk).any()
