"""The workload engine + differential oracle (DESIGN.md section 11).

Three layers of defense, cheapest first: distribution samplers are checked
for shape/skew/determinism in isolation; the `SortedOracle` is checked
against a brute-force dict model (the oracle must be above suspicion — it
is the ground truth everything else is diffed against); then the
acceptance grid replays seeded YCSB-style preset streams through ALL THREE
engines with per-batch oracle diffing and asserts zero divergence.  A
fault-injection test proves the diff actually bites.

The differential contract uses the integer-key convention
(tests/test_api_engines.py): integer-valued keys below 2^24 are exact
under the pallas engine's f32 quantization, so every comparison is
bit-exact on every engine — no tolerances.  The grid also replays the
served cells' YCSB key sets (`bench/keys.py`): fnv64 hashes up to ~9.2e18,
beyond f32's exact range (the pallas engine rejects them by design), and
dense record numbers, where every insert lands at the tail.
"""
import numpy as np
import pytest

from repro.api import IndexConfig, LearnedIndex, MaintenanceConfig
from repro.workloads import (PRESETS, SortedOracle, WorkloadDivergence,
                             WorkloadRunner, WorkloadSpec, generate_stream,
                             run_preset, sample_indices, stream_op_counts)
from repro.workloads.distributions import ZetaCache, zipfian_ranks
from tests.conftest import ycsb_keys

ENGINES = ("local", "pallas", "sharded")
UNIVERSE = np.arange(0, 6000, 2, dtype=np.float64)    # f32-exact even ints


# ---------------------------------------------------------------------------
# distributions
# ---------------------------------------------------------------------------


def test_zipfian_is_skewed_and_deterministic():
    z = ZetaCache(0.99)
    r1 = zipfian_ranks(np.random.default_rng(3), 10000, 40000, 0.99, z)
    r2 = zipfian_ranks(np.random.default_rng(3), 10000, 40000, 0.99,
                       ZetaCache(0.99))
    np.testing.assert_array_equal(r1, r2)
    assert r1.min() >= 0 and r1.max() < 10000
    # YCSB-grade skew: the 10 hottest ranks draw >20% of accesses
    # (uniform would give 0.1%)
    top = np.sort(np.bincount(r1, minlength=10000))[-10:].sum()
    assert top / len(r1) > 0.20


def test_zeta_cache_incremental_matches_direct():
    z = ZetaCache(0.7)
    assert np.isclose(z(100), np.sum(np.arange(1, 101) ** -0.7))
    # shrink then regrow: prefix array answers any n seen so far
    assert np.isclose(z(10), np.sum(np.arange(1, 11) ** -0.7))
    assert np.isclose(z(250), np.sum(np.arange(1, 251) ** -0.7))


def test_hotspot_and_uniform_shapes():
    rng = np.random.default_rng(0)
    hot = sample_indices(rng, "hotspot", 1000, 20000,
                         hot_frac=0.2, hot_weight=0.8)
    assert 0.75 < (hot < 200).mean() < 0.85
    uni = sample_indices(rng, "uniform", 1000, 20000)
    assert (np.bincount(uni, minlength=1000) > 0).mean() > 0.99


def test_unknown_distribution_rejected():
    with pytest.raises(ValueError, match="unknown distribution"):
        sample_indices(np.random.default_rng(0), "pareto", 10, 5)
    with pytest.raises(ValueError, match="unknown distribution"):
        WorkloadSpec(distribution="pareto")


def test_spec_mix_must_sum_to_one():
    with pytest.raises(ValueError, match="sum to 1"):
        WorkloadSpec(lookup=0.5, upsert=0.2)


# ---------------------------------------------------------------------------
# generator
# ---------------------------------------------------------------------------


def _streams_equal(a, b) -> bool:
    if len(a) != len(b):
        return False
    for x, y in zip(a, b):
        if x.op != y.op:
            return False
        for f in ("keys", "vals", "lo", "hi"):
            u, v = getattr(x, f), getattr(y, f)
            if (u is None) != (v is None):
                return False
            if u is not None and not np.array_equal(u, v):
                return False
    return True


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_streams_replay_byte_identically(preset):
    spec = PRESETS[preset].scaled(n_ops=800, batch_size=64, seed=5)
    s1 = generate_stream(spec, UNIVERSE)
    s2 = generate_stream(spec, UNIVERSE)
    assert _streams_equal(s1, s2)
    s3 = generate_stream(spec.scaled(seed=6), UNIVERSE)
    assert not _streams_equal(s1, s3)


def test_stream_respects_mix_and_key_contracts():
    spec = PRESETS["dili_paper"].scaled(n_ops=6400, batch_size=64, seed=2)
    batches = generate_stream(spec, UNIVERSE)
    counts = stream_op_counts(batches)
    total = sum(counts.values())
    # delete batches dedupe victims, so the realized count may fall a few
    # ops short of the target — but never overshoot
    assert spec.n_ops * 0.98 <= total <= spec.n_ops
    # batch-granular mixing: fractions converge at the stream scale
    assert counts["lookup"] / total > 0.7
    assert counts["upsert"] > 0 and counts["range"] > 0
    loaded = set(UNIVERSE.tolist())
    live = set(loaded)
    for b in batches:
        if b.op == "upsert":
            new = set(b.keys.tolist()) - live
            # inserts come from the odd-integer pool, never colliding
            assert all(int(k) % 2 == 1 for k in new)
            live |= set(b.keys.tolist())
        elif b.op == "delete":
            # victims are live at generation time, and unique
            assert len(np.unique(b.keys)) == len(b.keys)
            assert set(b.keys.tolist()) <= live
            live -= set(b.keys.tolist())
        elif b.op == "range":
            assert (b.hi > b.lo).all()


def test_latest_distribution_prefers_recent_inserts():
    spec = WorkloadSpec(name="latest_mix", lookup=0.5, upsert=0.5,
                        insert_frac=1.0, distribution="latest",
                        n_ops=4000, batch_size=64, seed=9, miss_frac=0.0)
    batches = generate_stream(spec, UNIVERSE)
    inserted: set = set()
    hits_new = hits_loaded = 0
    for b in batches:
        if b.op == "upsert":
            inserted |= set(b.keys.tolist())
        elif b.op == "lookup" and inserted:
            ks = set(b.keys.tolist())
            hits_new += len(ks & inserted)
            hits_loaded += len(ks - inserted)
    # the loaded set outnumbers inserts ~20:1, yet "latest" lookups must
    # concentrate on the newest keys
    assert hits_new > hits_loaded


def test_shift_preset_moves_insert_distribution():
    """shift_fb_logn: fresh keys before the shift point stay inside the
    phase-1 odd-integer pool; after it they come from the disjoint
    lognormal cluster beyond the loaded range (the fb -> logn drift)."""
    spec = PRESETS["shift_fb_logn"].scaled(n_ops=2000, batch_size=64,
                                           seed=3)
    batches = generate_stream(spec, UNIVERSE)
    loaded = set(UNIVERSE.tolist())
    phase1_hi = UNIVERSE.max() + 2 * spec.n_ops     # phase-1 pool ceiling
    n_b = len(batches)
    early_new, late_new = [], []
    for i, b in enumerate(batches):
        if b.op != "upsert":
            continue
        fresh = [k for k in b.keys.tolist() if k not in loaded]
        loaded |= set(b.keys.tolist())
        (early_new if i < n_b // 2 else late_new).extend(fresh)
    assert early_new and late_new
    assert max(early_new) < phase1_hi               # pre-shift: fb pool
    late = np.asarray(late_new)
    assert (late > phase1_hi).mean() > 0.9          # post-shift: logn pool
    # integer-valued below 2^24: the f32 bit-exactness convention holds
    assert np.all(late == np.rint(late)) and late.max() < 2 ** 24


def test_ttl_storm_waves_and_oldest_victims():
    """ttl_storm: the deterministic wave schedule emits contiguous upsert
    waves then delete storms, and every delete storm expires the OLDEST
    live keys (TTL order), not popular ones."""
    spec = PRESETS["ttl_storm"].scaled(n_ops=1280, batch_size=64, seed=5)
    batches = generate_stream(spec, UNIVERSE)
    ops = [b.op for b in batches]
    # wave apportionment of (0.2, 0.5, 0.3) over wave_len=10
    assert ops[:10] == ["lookup"] * 2 + ["upsert"] * 5 + ["delete"] * 3
    age = list(UNIVERSE.tolist())                   # oldest-first live list
    saw_delete = False
    for b in batches:
        if b.op == "upsert":
            age.extend(k for k in b.keys.tolist() if k not in set(age))
        elif b.op == "delete":
            saw_delete = True
            want = set(np.sort(np.asarray(age[: len(b.keys)])).tolist())
            assert set(b.keys.tolist()) == want     # exactly the oldest
            age = [k for k in age if k not in want]
    assert saw_delete


def test_spec_scenario_field_validation():
    with pytest.raises(ValueError, match="delete_policy"):
        WorkloadSpec(delete_policy="newest")
    with pytest.raises(ValueError, match="shift_frac"):
        WorkloadSpec(shift_frac=1.0)
    with pytest.raises(ValueError, match="wave_len"):
        WorkloadSpec(wave_len=-1)


# ---------------------------------------------------------------------------
# oracle
# ---------------------------------------------------------------------------


def test_oracle_matches_brute_force_dict():
    rng = np.random.default_rng(4)
    keys = np.unique(rng.integers(0, 2000, 300)).astype(np.float64)
    oc = SortedOracle(keys, np.arange(len(keys), dtype=np.int64))
    model = dict(zip(keys.tolist(), range(len(keys))))
    for step in range(30):
        ks = rng.integers(0, 2000, 20).astype(np.float64)
        if step % 3 == 0:
            vs = rng.integers(0, 1 << 30, 20)
            oc.upsert(ks, vs)
            model.update(zip(ks.tolist(), vs.tolist()))
        elif step % 3 == 1:
            oc.delete(ks)
            for k in ks.tolist():
                model.pop(k, None)
        q = rng.integers(0, 2000, 50).astype(np.float64)
        v, f = oc.lookup(q)
        for qi, vi, fi in zip(q.tolist(), v, f):
            assert fi == (qi in model)
            if fi:
                assert vi == model[qi]
    want = np.array(sorted(model), np.float64)
    got_k, got_v = oc.items()
    np.testing.assert_array_equal(got_k, want)
    np.testing.assert_array_equal(got_v, [model[k] for k in want.tolist()])


def test_oracle_range_padding_conventions():
    oc = SortedOracle(np.array([1.0, 3.0, 5.0, 7.0]),
                      np.array([10, 30, 50, 70]))
    ks, vs, cnt = oc.range([2.0, 0.0], [6.0, 100.0], max_hits=3)
    np.testing.assert_array_equal(cnt, [2, 3])            # saturates at 3
    np.testing.assert_array_equal(ks[0], [3.0, 5.0, np.inf])
    np.testing.assert_array_equal(vs[0], [30, 50, -1])
    np.testing.assert_array_equal(ks[1], [1.0, 3.0, 5.0])


# ---------------------------------------------------------------------------
# differential acceptance grid: presets x engines, zero divergence
# ---------------------------------------------------------------------------

# per-engine sizing: the contract is identical; the pallas interpret-mode
# kernel and the mesh collectives just pay more per batch on CPU
GRID_SIZES = {"local": (1500, 64), "pallas": (600, 64), "sharded": (480, 32)}
GRID_PRESETS = ("ycsb_a", "ycsb_e", "dili_paper",
                "shift_fb_logn", "ttl_storm")
# the PR-5 scenario presets replay with the adaptive maintenance pipeline
# on (incremental splice-flatten + drift/tombstone retrains) — the grid is
# what pins its exactness engine-by-engine
MAINT_PRESETS = ("shift_fb_logn", "ttl_storm")
# key shapes and the engines that serve them: the even-integer UNIVERSE,
# and YCSB's key sets of GRID_RECORDS records, whose inserts are YCSB's
# own (the next records), so the default odd-integer pool is never built
GRID_SHAPES = {"even": ENGINES, "ycsb_hashed": ("local", "sharded"),
               "ycsb_ordered": ENGINES}
GRID_RECORDS = 3000
GRID_CASES = [pytest.param(preset, engine, shape, id="-".join(
                  [preset, engine] + ([shape] if shape != "even" else [])))
              for shape, engines in GRID_SHAPES.items()
              for preset in GRID_PRESETS for engine in engines]


@pytest.mark.parametrize("preset,engine,shape", GRID_CASES)
def test_differential_grid(preset, engine, shape):
    """Replay a seeded preset stream through the engine with per-batch
    oracle diffing (lookup hits AND misses, range contents, write/delete
    visibility, final items()).  strict=True: any divergence raises."""
    n_ops, bs = GRID_SIZES[engine]
    spec = PRESETS[preset].scaled(n_ops=n_ops, batch_size=bs, seed=13)
    keys, pool = ((UNIVERSE, None) if shape == "even" else
                  ycsb_keys(shape.removeprefix("ycsb_"), GRID_RECORDS, n_ops))
    ix = LearnedIndex.build(keys, config=IndexConfig(
        engine=engine, overlay_cap=512,
        maintenance=(MaintenanceConfig()
                     if preset in MAINT_PRESETS else None)))
    report = WorkloadRunner(ix).run(generate_stream(spec, keys, pool),
                                    spec=spec)
    assert report.divergences == []
    assert spec.n_ops * 0.95 <= report.n_ops <= spec.n_ops
    assert report.final_stats["engine"] == engine


def test_write_heavy_mix_exercises_merge_pressure():
    """ycsb_a at a small overlay capacity must drive the overlay ->
    merge -> republish lifecycle (not pile writes up unfolded), and stay
    oracle-exact across the epoch flips."""
    ix = LearnedIndex.build(UNIVERSE, config=IndexConfig(
        engine="local", overlay_cap=64))
    rep = run_preset(ix, PRESETS["ycsb_a"].scaled(n_ops=2000, batch_size=64,
                                                  seed=21))
    assert rep.divergences == []
    assert rep.final_stats["n_merges"] >= 1
    assert rep.final_stats["epoch"] >= 2


class _FaultyIndex:
    """Engine-protocol wrapper that corrupts one lookup lane per batch —
    the runner must catch it (differential harness self-test)."""

    def __init__(self, ix):
        self._ix = ix

    def __getattr__(self, name):
        return getattr(self._ix, name)

    def lookup(self, queries):
        v, f = self._ix.lookup(queries)
        v = np.array(v)
        v[0] += 1                       # silent payload corruption
        return v, f


def test_runner_catches_injected_corruption():
    spec = PRESETS["ycsb_c"].scaled(n_ops=256, batch_size=64, seed=1)
    ix = _FaultyIndex(LearnedIndex.build(UNIVERSE,
                                         config=IndexConfig(engine="local")))
    batches = generate_stream(spec, UNIVERSE)
    report = WorkloadRunner(ix, strict=False).run(batches, spec=spec)
    assert report.divergences            # every batch caught
    with pytest.raises(WorkloadDivergence):
        WorkloadRunner(ix).run(batches, spec=spec)


def test_runner_check_false_is_pure_throughput():
    """check=False: no oracle, no diffs — the perf-sweep mode for key sets
    that are not exactly representable on every engine."""
    ix = LearnedIndex.build(UNIVERSE, config=IndexConfig(engine="local"))
    spec = PRESETS["ycsb_b"].scaled(n_ops=256, batch_size=64, seed=2)
    runner = WorkloadRunner(ix, check=False)
    assert runner.oracle is None
    r = runner.run(generate_stream(spec, UNIVERSE), spec=spec)
    assert r.divergences == [] and r.n_ops == 256 and r.wall_s > 0
    d = r.to_json_dict()
    assert d["ops_per_s"] > 0 and d["n_divergences"] == 0
