"""Serving front-end suite (DESIGN.md section 15).

Three layers:

  * batcher mechanics WITHOUT an engine (a stub index records calls):
    coalescing homogeneity + pow2 buckets, admission shedding, AIMD
    convergence, FIFO dispatch, error fan-out;
  * the tier-1 concurrency contract on every engine: >= 4 seeded client
    threads drive mixed ops through one frontend, each client asserts
    read-your-acknowledged-writes inline, and the committed journal
    replayed through `WorkloadRunner` on a fresh index must reproduce
    the concurrent run's final `items()` bit-exactly;
  * facade thread-safety: `stats()`/`metrics()`/frontend stats hammered
    from sampler threads while the batcher serves writes.

Client write keys are odd (the generator convention: the loaded universe
is even integers), disjoint per client, and < 2^24 so the pallas
engine's f32 quantization is exact.
"""

import json
import threading
from collections import deque

import numpy as np
import pytest

from repro.api import IndexConfig, LearnedIndex
from repro.obs.tracing import (ENGINE_SPANS, GC_SPAN, MERGE_SPANS,
                               RECOVERY_SPANS, SERVE_SPANS)
from repro.serve import (AdaptiveBatchSizer, RejectedError, Request,
                         RequestBatcher, ServeConfig, ServeFrontend,
                         SessionTable, coalesce, open_loop, pow2_bucket)
from repro.workloads.runner import WorkloadRunner
from tests.conftest import ycsb_keys

ENGINES = ("local", "pallas", "sharded")


# -- stub-index layer (no engine) ---------------------------------------------

class StubIndex:
    """Records facade calls; optionally blocks inside the first call so a
    test can fill the admission queue while the worker is busy."""

    telemetry = None

    def __init__(self, gate: threading.Event | None = None):
        self.calls: list[tuple] = []
        self.gate = gate
        self.entered = threading.Event()

    def _enter(self):
        self.entered.set()
        if self.gate is not None:
            assert self.gate.wait(30.0)
            self.gate = None            # only the first call blocks

    def lookup(self, q):
        self._enter()
        self.calls.append(("lookup", len(q)))
        return np.asarray(q, np.int64), np.ones(len(q), bool)

    def range(self, lo, hi, max_hits=64):
        self._enter()
        self.calls.append(("range", len(lo), max_hits))
        n = len(lo)
        return (np.full((n, max_hits), np.inf),
                np.full((n, max_hits), -1, np.int64),
                np.zeros(n, np.int64))

    def upsert(self, keys, vals):
        self._enter()
        self.calls.append(("upsert", len(keys)))

    def delete(self, keys):
        self._enter()
        self.calls.append(("delete", len(keys)))


def req(op, n=1, max_hits=64, **kw):
    if op == "range":
        return Request(op, lo=np.zeros(n), hi=np.ones(n),
                       max_hits=max_hits, **kw)
    return Request(op, keys=np.arange(n, dtype=np.float64),
                   vals=np.zeros(n, np.int64) if op == "upsert" else None,
                   **kw)


def test_pow2_bucket_matches_facade_padding():
    ix = LearnedIndex.build(np.arange(8.0))
    try:
        for n in (1, 3, 64, 65, 100, 128, 1000):
            assert pow2_bucket(n) == ix._pad_batch(n), n
    finally:
        ix.close()


def test_coalesce_op_homogeneity_and_cap():
    d = deque([req("lookup", 10), req("lookup", 20), req("upsert", 5),
               req("lookup", 3)])
    g = coalesce(d, cap_ops=64)
    assert [r.op for r in g] == ["lookup", "lookup"]   # stops at upsert
    assert coalesce(d, 64)[0].op == "upsert"
    # cap: the head is always taken, the next 20-op req would exceed 25
    d = deque([req("lookup", 10), req("lookup", 20)])
    assert len(coalesce(d, cap_ops=25)) == 1 and len(d) == 1
    # oversized head still dispatches alone
    d = deque([req("lookup", 100)])
    assert len(coalesce(d, cap_ops=64)) == 1
    # ranges only coalesce on matching max_hits
    d = deque([req("range", 4, max_hits=64), req("range", 4, max_hits=64),
               req("range", 4, max_hits=8)])
    assert len(coalesce(d, 64)) == 2 and d[0].max_hits == 8


def test_aimd_sizer_converges_and_pow2_caps():
    cfg = ServeConfig(min_batch_ops=64, max_batch_ops=2048,
                      latency_slo_s=0.010, aimd_add_ops=64)
    s = AdaptiveBatchSizer(cfg)
    # scripted arrivals: sustained queue pressure, fast service -> grow
    # additively to the ceiling
    for _ in range(100):
        s.observe(queue_depth_ops=4096, service_s=0.001)
    assert s.target == cfg.max_batch_ops
    # one slow batch halves; floor is respected under repeated violations
    s.observe(4096, 0.100)
    assert s.target == cfg.max_batch_ops // 2
    for _ in range(20):
        s.observe(0, 0.100)
    assert s.target == cfg.min_batch_ops
    # the dispatch cap is always a pow2 facade bucket within bounds
    for depth in (0, 100, 500, 5000):
        s.observe(depth, 0.001)
        cap = s.cap
        assert cap & (cap - 1) == 0
        assert cfg.min_batch_ops <= cap <= cfg.max_batch_ops


def test_admission_control_sheds_above_bound():
    gate = threading.Event()
    stub = StubIndex(gate=gate)
    b = RequestBatcher(stub, ServeConfig(queue_cap_ops=8, dwell_s=0.0))
    try:
        b.submit(req("lookup", 1))          # worker picks this up...
        assert stub.entered.wait(10.0)      # ...and blocks inside it
        for _ in range(8):                  # fill the queue to the bound
            b.submit(req("lookup", 1))
        with pytest.raises(RejectedError):
            b.submit(req("lookup", 1))
        assert b.n_shed == 1
        gate.set()
        b.drain(30.0)
        assert b.n_completed == 9 and b.n_failed == 0
        s = b.stats()
        assert s["shed_ops"] == 1 and 0 < s["shed_frac"] < 1
    finally:
        gate.set()
        b.close()


def test_batcher_fifo_coalescing_and_journal():
    gate = threading.Event()
    stub = StubIndex(gate=gate)
    b = RequestBatcher(stub, ServeConfig(dwell_s=0.0))
    try:
        b.submit(req("lookup", 1))          # occupy the worker
        assert stub.entered.wait(10.0)
        rs = [b.submit(r) for r in
              (req("lookup", 2), req("lookup", 3), req("upsert", 4),
               req("lookup", 5), req("delete", 6))]
        gate.set()
        b.drain(30.0)
        # deterministic grouping of the queued prefix: the two lookups
        # coalesce, the write ops break the runs
        assert stub.calls == [("lookup", 1), ("lookup", 5), ("upsert", 4),
                              ("lookup", 5), ("delete", 6)]
        assert [(j.op, j.n_ops) for j in b.journal] == \
            [("lookup", 1), ("lookup", 5), ("upsert", 4), ("lookup", 5),
             ("delete", 6)]
        v, f = rs[0].wait(1.0)
        assert len(v) == 2 and f.all()      # sliced back per request
        v, f = rs[1].wait(1.0)
        assert len(v) == 3
    finally:
        gate.set()
        b.close()


def test_batcher_error_fans_out_to_waiters():
    class Exploding(StubIndex):
        def upsert(self, keys, vals):
            raise RuntimeError("boom")

    b = RequestBatcher(Exploding(), ServeConfig(dwell_s=0.0))
    try:
        r = b.submit(req("upsert", 3))
        with pytest.raises(RuntimeError, match="boom"):
            r.wait(10.0)
        assert b.n_failed == 3
        v, f = b.submit(req("lookup", 2)).wait(10.0)   # worker survives
        assert f.all()
    finally:
        b.close()


def test_closed_batcher_rejects_submits():
    b = RequestBatcher(StubIndex(), ServeConfig(dwell_s=0.0))
    b.close()
    with pytest.raises(RuntimeError):
        b.submit(req("lookup", 1))


def test_serve_spans_declared_only_on_attach():
    ix = LearnedIndex.build(np.arange(32.0), config=IndexConfig(
        engine="local", telemetry=True))
    try:
        base_snap = ix.metrics()
        bare = set(MERGE_SPANS + RECOVERY_SPANS + ENGINE_SPANS)
        assert set(base_snap["spans"]) == bare
        assert base_snap["serve"] == {}      # bare index: no serve block
        fe = ServeFrontend(ix)
        fe.client("c").lookup([0.0])
        fe.drain()              # the worker records after waking clients
        snap = ix.metrics()
        assert set(snap["spans"]) == bare | set(SERVE_SPANS) | {GC_SPAN}
        for op in ("lookup", "range", "upsert", "delete"):
            assert f"serve.e2e.{op}" in snap["serve"]
        assert snap["serve"]["serve.e2e.lookup"]["count"] >= 1
        assert fe.stats()["batch_ops_mean"] >= 1
        assert snap["spans"]["serve.exec"]["count"] >= 1
        fe.close()
    finally:
        ix.close()


def _host_events(xplane_dir):
    """{host line: [(name, start_ns, end_ns), ...]} of the program's spans
    in a `jax.profiler` trace."""
    import glob
    import os
    from jax.profiler import ProfileData
    pb = glob.glob(os.path.join(xplane_dir, "**", "*.xplane.pb"),
                   recursive=True)
    out = {}
    for plane in ProfileData.from_file(pb[0]).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                ev = [(e.name, e.start_ns, e.end_ns) for e in line.events
                      if e.name.startswith(("serve.", "engine.", "host."))]
                if ev:
                    out[(plane.name, i)] = ev
    return out


def test_worker_spans_on_the_profiler_trace_nest_and_order(tmp_path):
    """With telemetry on, the worker's spans are profiler annotations: on
    one host line, each `serve.exec` lies inside a `serve.dispatch` and
    follows a `serve.wait_for_work` or `serve.dwell` that ended before it
    began, and every `engine.*` and `serve.complete` span lies inside a
    `serve.exec`."""
    import jax
    ix = LearnedIndex.build(np.arange(0.0, 512.0, 2.0), config=IndexConfig(
        engine="local", telemetry=True))
    ix.lookup(np.arange(8.0))                  # compile outside the trace
    fe = ServeFrontend(ix, ServeConfig(dwell_s=0.001))
    c = fe.client("c")
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(6):
            c.lookup([2.0 * i])
        c.upsert([3.0], [30])
        c.lookup([3.0])
    finally:
        # a call returns inside the worker's `serve.exec`; its
        # `serve.dispatch` closes later, so drain and join the worker
        # before the trace stops
        try:
            fe.close()
        finally:
            jax.profiler.stop_trace()
        ix.close()
    lines = [ev for ev in _host_events(tmp_path).values()
             if any(n == "serve.exec" for n, _, _ in ev)]
    assert len(lines) == 1                     # the one worker thread
    ev = sorted(lines[0], key=lambda e: e[1])
    execs = [(s, e) for n, s, e in ev if n == "serve.exec"]
    turns = [(s, e) for n, s, e in ev if n == "serve.dispatch"]
    assert len(execs) == len(turns) == 8
    for n, s, e in ev:
        if n.startswith("engine.") or n == "serve.complete":
            assert any(x0 <= s and e <= x1 for x0, x1 in execs), (n, s, e)
    for x0, x1 in execs:
        assert any(t0 <= x0 and x1 <= t1 for t0, t1 in turns)
    names = {n for n, _, _ in ev}
    assert {"engine.prep", "engine.upload", "engine.launch",
            "engine.fetch", "engine.write", "serve.complete"} <= names
    for x0, x1 in execs:
        waits = [e for n, s, e in ev
                 if n in ("serve.wait_for_work", "serve.dwell") and e <= x0]
        assert waits, x0
        assert not [n for n, s, e in ev if n in ("serve.wait_for_work",
                                                 "serve.dwell")
                    and s < x1 and e > x0]


def test_gc_spans_hooked_while_served_and_unhooked_at_close():
    import gc
    ix = LearnedIndex.build(np.arange(64.0), config=IndexConfig(
        engine="local", telemetry=True))
    try:
        fe = ServeFrontend(ix)
        hook = ix.telemetry._gc_hook
        assert hook in gc.callbacks
        gc.collect()
        n = ix.metrics()["spans"][GC_SPAN]["count"]
        assert n >= 1
        assert {"generation": 2} in [
            sp.attrs for sp in ix.telemetry.spans.spans(GC_SPAN)]
        fe.close()
        assert hook not in gc.callbacks
        gc.collect()
        assert ix.metrics()["spans"][GC_SPAN]["count"] == n
    finally:
        ix.close()


@pytest.mark.parametrize("telemetry", [True, False])
def test_worker_idle_time_in_stats(telemetry):
    """`stats()` carries the worker's idle seconds (finished waits) and
    the start of the wait in progress, while telemetry is on."""
    import time
    ix = LearnedIndex.build(np.arange(64.0), config=IndexConfig(
        engine="local", telemetry=telemetry))
    fe = ServeFrontend(ix, ServeConfig(dwell_s=0.0))
    try:
        time.sleep(0.05)
        s0 = fe.stats()
        fe.client("c").lookup([1.0])
        time.sleep(0.05)
        s1 = fe.stats()
    finally:
        fe.close()
        ix.close()
    if not telemetry:
        assert s1["worker_idle_s"] == 0.0
        assert s1["worker_idle_since"] is None
        return
    assert s0["worker_idle_since"] is not None   # waiting for work
    assert s0["worker_idle_s"] == 0.0            # no wait has ended yet
    assert s1["worker_idle_s"] >= 0.05           # that wait, now ended
    assert s1["worker_idle_since"] > s0["worker_idle_since"]
    assert s1["batch_ops_mean"] == 1.0


# -- engine layer: the concurrency contract -----------------------------------

N_CLIENT_STEPS = 24


def _client_program(fe, ci, keys, width, own, errors, writes_log):
    """One seeded client stream: lookups/ranges of `width` over the loaded
    keys, upserts/deletes over the client-private keys `own` (one per
    step), with inline read-your-acknowledged-writes assertions."""
    n = len(keys)
    try:
        c = fe.client(f"client-{ci}")
        r = np.random.default_rng(1000 + ci)
        live: list[tuple[float, int]] = []
        for step in range(N_CLIENT_STEPS):
            choice = int(r.integers(0, 4))
            if choice == 0:
                q = keys[r.integers(0, n, 8)]
                v, f = c.lookup(q)
                assert f.all(), "loaded keys are never deleted"
            elif choice == 1:
                lo = keys[r.integers(0, n, 4)]
                ks, vs, cnt = c.range(lo, lo + width)
                assert (cnt >= 1).all()              # lo itself is live
            elif choice == 2:
                k, v = float(own[step]), ci * 1000 + step
                c.upsert([k], [v])
                live.append((k, v))
                got = c.get(k)                       # read-your-writes
                assert got == v, (ci, step, got, v)
            elif live:
                k, _ = live.pop(int(r.integers(0, len(live))))
                c.delete([k])
                assert c.get(k) is None, (ci, k)
        writes_log[ci] = live
    except BaseException as e:                       # noqa: BLE001
        errors.append((ci, e))


def _multi_client_keys(shape, n, n_clients):
    """The loaded keys, a range width, and each client's private write
    keys: even integers with odd writes from disjoint ranges below 2^24,
    or the local_hashed cells' YCSB record hashes with writes from
    disjoint runs of the next records' hashes."""
    if shape == "even":
        keys = np.arange(0, 2 * n, 2, dtype=np.float64)
        own = [2 * n + 1 + 2_000_000 * ci + 2 * np.arange(N_CLIENT_STEPS)
               for ci in range(n_clients)]
        return keys, 64.0, own
    keys, new = ycsb_keys("hashed", n, n_clients * N_CLIENT_STEPS)
    return keys, 32 * float(np.diff(keys).mean()), np.split(new, n_clients)


@pytest.mark.parametrize("engine,shape", [
    *(pytest.param(e, "even", id=e) for e in ENGINES),
    *(pytest.param(e, "ycsb_hashed", id=f"{e}-ycsb_hashed")
      for e in ("local", "sharded"))])
def test_multi_client_oracle_equivalence(engine, shape):
    """>= 4 concurrent client streams; the journal's serialization
    replayed on a fresh index must match the served index bit-exactly."""
    n = 4000 if engine == "local" else 1500
    keys, width, own = _multi_client_keys(shape, n, 4)
    vals = np.arange(n, dtype=np.int64)
    cfg = IndexConfig(engine=engine)
    ix = LearnedIndex.build(keys, vals, config=cfg)
    fe = ServeFrontend(ix, ServeConfig(dwell_s=2e-4))
    errors: list = []
    writes_log: dict = {}
    threads = [threading.Thread(target=_client_program,
                                args=(fe, ci, keys, width, own[ci], errors,
                                      writes_log))
               for ci in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120.0)
    fe.drain()
    journal = fe.journal_batches()
    stats = fe.stats()
    fe.close()
    assert not errors, errors[:2]
    assert stats["failed_ops"] == 0 and stats["shed_ops"] == 0
    assert stats["n_batches"] >= 1 and journal

    # replay the committed interleaving, oracle-checked batch by batch
    fresh = LearnedIndex.build(keys, vals, config=cfg)
    try:
        rep = WorkloadRunner(fresh).run(journal, name=f"serve-{engine}")
        assert rep.n_ops == stats["completed_ops"]
        k1, v1 = ix.items()
        k2, v2 = fresh.items()
        assert np.array_equal(k1, k2) and np.array_equal(v1, v2), \
            "concurrent run diverged from its own journal's replay"
        # every surviving acknowledged write is in the final content
        for ci, live in writes_log.items():
            for k, v in live:
                i = np.searchsorted(k1, k)
                assert i < len(k1) and k1[i] == k and v1[i] == v, (ci, k)
    finally:
        fresh.close()
        ix.close()


def test_stats_metrics_safe_to_sample_under_load():
    """Satellite: hammer `stats()`/`metrics()`/frontend stats from
    sampler threads while the batcher serves a write-heavy mix."""
    n = 2000
    keys = np.arange(0, 2 * n, 2, dtype=np.float64)
    ix = LearnedIndex.build(keys, config=IndexConfig(
        engine="local", telemetry=True,
        overlay_cap=64))
    fe = ServeFrontend(ix, ServeConfig(dwell_s=1e-4))
    stop = threading.Event()
    errors: list = []

    def sampler():
        try:
            while not stop.is_set():
                json.dumps(ix.metrics())     # full snapshot must be JSON-able
                ix.stats()
                fe.stats()
        except BaseException as e:           # noqa: BLE001
            errors.append(e)

    def writer(ci):
        try:
            c = fe.client(f"w{ci}")
            base = 2 * n + 1 + 100_000 * ci
            for i in range(60):
                c.upsert([float(base + 2 * i)], [i])
                c.lookup(keys[(7 * i) % n: (7 * i) % n + 4])
                if i % 3 == 2:
                    c.delete([float(base + 2 * (i - 1))])
        except BaseException as e:           # noqa: BLE001
            errors.append(e)

    samplers = [threading.Thread(target=sampler) for _ in range(2)]
    writers = [threading.Thread(target=writer, args=(ci,))
               for ci in range(2)]
    for t in samplers + writers:
        t.start()
    for t in writers:
        t.join(120.0)
    stop.set()
    for t in samplers:
        t.join(30.0)
    fe.close()
    ix.close()
    assert not errors, errors[:2]


def test_open_loop_low_rate_completes_everything():
    from repro.workloads.generator import PRESETS, generate_stream
    n = 2000
    keys = np.arange(0, 2 * n, 2, dtype=np.float64)
    ix = LearnedIndex.build(keys, config=IndexConfig(engine="local"))
    fe = ServeFrontend(ix, ServeConfig(dwell_s=1e-4), journal=False)
    try:
        spec = PRESETS["ycsb_a"].scaled(n_ops=400, batch_size=8, seed=3)
        stream = generate_stream(spec, keys)
        rep = open_loop(fe, stream, rate_ops_per_s=2000.0, n_clients=4,
                        timeout_s=60.0)
        assert rep.shed_ops == 0 and rep.failed_ops == 0
        assert rep.done_ops == rep.n_ops
        lat = rep.latency_ms()
        assert lat["lookup"]["count"] > 0
        assert lat["lookup"]["ms_p99"] >= lat["lookup"]["ms_p50"] > 0
        json.dumps(rep.to_json_dict())
    finally:
        fe.close()
        ix.close()


# -- session table under concurrent frontend threads --------------------------

def test_session_table_concurrent_admit_evict():
    st = SessionTable(n_slots=64)
    fe = ServeFrontend(st.index)
    try:
        st.serve_through(fe)
        ids = [float(100 + i) for i in range(40)]
        slots: dict = {}
        errors: list = []

        def admit_some(chunk):
            try:
                for sid in chunk:
                    slots[sid] = st.admit(sid)
            except BaseException as e:       # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=admit_some, args=(ids[i::4],))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert not errors, errors[:2]
        # no slot handed out twice (incl. the warm sessions' slots)
        assert len(set(slots.values())) == len(ids)
        got, found = st.lookup_batch(ids)
        assert found.all()
        assert {float(s) for s in got} == {float(s)
                                           for s in slots.values()}

        # same-id contention: exactly one admit wins
        outcomes: list = []

        def race():
            try:
                outcomes.append(st.admit(999.0))
            except KeyError:
                outcomes.append("dup")

        racers = [threading.Thread(target=race) for _ in range(6)]
        for t in racers:
            t.start()
        for t in racers:
            t.join(60.0)
        assert sum(1 for o in outcomes if o != "dup") == 1

        def evict_some(chunk):
            for sid in chunk:
                st.evict(sid)

        threads = [threading.Thread(target=evict_some, args=(ids[i::4],))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        _, found = st.lookup_batch(ids)
        assert not found.any()
    finally:
        fe.close()
        st.index.close()
