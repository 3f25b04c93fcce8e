"""Event-driven clock: profiles, queue, determinism, resume, critical path."""

import dataclasses
import tempfile

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import fetchsgd as F
from repro.fed import (AsyncBufferedAggregator, ClientProfile, Event,
                       EventQueue, FederationConfig, FlatAggregator,
                       HeterogeneityConfig, HeterogeneityModel, Orchestrator,
                       SimTimeConfig, StragglerModel, TreeAggregator,
                       checkpoint as ckpt, run_federated)

CFG = F.FetchSGDConfig(rows=3, cols=1 << 10, k=64)

SKEWED = HeterogeneityConfig(compute_median=1.0, compute_sigma=0.5,
                             bandwidth_median=1e5, bandwidth_sigma=2.0)


@pytest.fixture(scope="module")
def micro():
    from repro.launch import simulate
    cfg = simulate.micro_cfg()
    return cfg, simulate.micro_dataset(cfg)


def _records_equal(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert dataclasses.asdict(ra) == dataclasses.asdict(rb)


class TestClientProfile:
    def test_always_available(self):
        p = ClientProfile(compute_seconds=1.0, bandwidth=100.0)
        assert p.next_available(17.3) == 17.3
        assert p.finish_time(2.0, 300) == pytest.approx(2.0 + 1.0 + 3.0)

    def test_availability_window(self):
        # up for the first 25% of each 100s period
        p = ClientProfile(compute_seconds=1.0, bandwidth=100.0,
                          avail_period=100.0, avail_duty=0.25)
        assert p.next_available(10.0) == 10.0           # inside window
        assert p.next_available(30.0) == 100.0          # deferred to next
        assert p.next_available(199.0) == 200.0
        assert p.finish_time(30.0, 100) == pytest.approx(100.0 + 1.0 + 1.0)

    def test_straggle_scale(self):
        p = ClientProfile(compute_seconds=2.0, bandwidth=100.0)
        assert p.finish_time(0.0, 100, compute_scale=3.0) == \
            pytest.approx(6.0 + 1.0)

    def test_validation(self):
        with pytest.raises(ValueError):
            ClientProfile(compute_seconds=1.0, bandwidth=0.0)
        with pytest.raises(ValueError):
            ClientProfile(compute_seconds=1.0, bandwidth=1.0, avail_duty=0.0)


class TestHeterogeneityModel:
    def test_deterministic_per_seed_and_client(self):
        m1 = HeterogeneityModel(SKEWED, seed=3)
        m2 = HeterogeneityModel(SKEWED, seed=3)
        m3 = HeterogeneityModel(SKEWED, seed=4)
        for c in (0, 7, 255):
            assert m1.profile(c) == m2.profile(c)
        assert m1.profile(0) != m3.profile(0)
        assert m1.profile(0) != m1.profile(1)

    def test_sigma_zero_is_homogeneous(self):
        m = HeterogeneityModel(HeterogeneityConfig(
            compute_sigma=0.0, bandwidth_sigma=0.0), seed=0)
        p0, p1 = m.profile(0), m.profile(1)
        assert p0.compute_seconds == p1.compute_seconds
        assert p0.bandwidth == p1.bandwidth


class TestEventQueue:
    def _ev(self, t, r=0, slot=0):
        return Event(time=t, round_produced=r, slot=slot, client=slot,
                     produced=0.0, weight=1.0, loss=0.0, table=None)

    def test_pop_order_and_tie_break(self):
        q = EventQueue()
        for t, r, s in [(2.0, 1, 0), (1.0, 0, 1), (1.0, 0, 0)]:
            q.push(self._ev(t, r=r, slot=s))
        popped = [q.pop() for _ in range(3)]
        # same arrival time: (round, slot) breaks the tie in dispatch order
        assert [(e.time, e.slot) for e in popped] == \
            [(1.0, 0), (1.0, 1), (2.0, 0)]
        assert len(q) == 0 and q.peek_time() is None

    def test_state_roundtrip(self):
        q = EventQueue()
        for t in (3.0, 1.0, 2.0):
            q.push(self._ev(t))
        q2 = EventQueue()
        q2.load_state(q.state())
        assert [e.time for e in q2.events()] == [1.0, 2.0, 3.0]
        assert len(q2) == 3


class TestTimedStaleness:
    def test_exponential_discount_and_max_age(self, rng):
        t = [jnp.asarray(rng.normal(size=(CFG.rows, CFG.cols))
                         .astype(np.float32)) for _ in range(3)]
        agg = AsyncBufferedAggregator(CFG, staleness_lambda=0.5, max_age=10.0)
        agg.submit(t[0], produced_round=15.0, arrival_round=16.0)
        agg.submit(t[1], produced_round=0.0, arrival_round=2.0)   # too old:
        merged, stats = agg.aggregate([t[2]], round_idx=20.0)     # age 20 > 10
        w0 = float(np.exp(-0.5 * 5.0))          # t[0]: age = 20 - 15 = 5
        assert stats.n_late == 1 and agg.pending() == 0
        assert stats.max_staleness == pytest.approx(5.0)
        expect = (np.asarray(t[2]) + w0 * np.asarray(t[0])) / (1 + w0)
        np.testing.assert_allclose(np.asarray(merged), expect, atol=1e-6)

    def test_round_mode_unchanged_by_default(self, rng):
        agg = AsyncBufferedAggregator(CFG)
        assert not agg.timed


class TestCriticalPath:
    def test_flat_critical_path_is_slowest_edge(self):
        tables = [jnp.zeros((CFG.rows, CFG.cols))] * 3
        _, stats = FlatAggregator(CFG).aggregate(
            tables, bandwidths=[1e6, 1e3, 1e5])
        tb = F.upload_bytes(CFG)
        # the slowest uplink sets the clock, not the byte total
        assert stats.critical_path_s == pytest.approx(tb / 1e3)
        assert stats.upload_bytes == 3 * tb

    def test_tree_critical_path_differs_from_flat_bytes(self):
        """Acceptance: wall-clock critical path != flat-bytes accounting."""
        n, tb = 8, F.upload_bytes(CFG)
        bws = [1e6] * (n - 1) + [1e3]          # one straggler uplink
        tables = [jnp.zeros((CFG.rows, CFG.cols))] * n
        agg = TreeAggregator(CFG, fanout=2, link_bandwidth=1e6)
        _, stats = agg.aggregate(tables, bandwidths=bws)
        # bytes accounting: more total bytes than flat...
        assert stats.upload_bytes > n * tb
        # ...but the clock is leaf-bottlenecked + one backbone hop per level
        n_internal = len(stats.levels) - 1
        assert stats.critical_path_s == \
            pytest.approx(tb / 1e3 + n_internal * tb / 1e6)
        naive = stats.upload_bytes / 1e6       # "bytes / median bw" estimate
        assert stats.critical_path_s > 2 * naive


class TestEventOrchestration:
    def test_sync_policies_agree_under_event_clock(self, micro):
        """Same barrier, same merges: flat == tree wall-clock and losses."""
        cfg, ds = micro
        sim = SimTimeConfig(heterogeneity=SKEWED, link_bandwidth=1e8)
        runs = {}
        for policy in ("flat", "tree"):
            runs[policy] = run_federated(
                cfg, ds, fs_cfg=CFG, fed_cfg=FederationConfig(
                    rounds=3, clients_per_round=3, aggregate=policy,
                    clock="event", simtime=sim, tree_fanout=2, seed=2))
        np.testing.assert_allclose(runs["tree"].losses, runs["flat"].losses,
                                   atol=1e-4)
        for ra, rb in zip(runs["flat"].records, runs["tree"].records):
            assert ra.t_virtual == rb.t_virtual

    def test_async_overlaps_rounds(self, micro):
        """quorum < cohort: slow uploads stay in flight across updates."""
        cfg, ds = micro
        res = run_federated(cfg, ds, fs_cfg=CFG, fed_cfg=FederationConfig(
            rounds=4, clients_per_round=3, aggregate="async", clock="event",
            simtime=SimTimeConfig(staleness_lambda=0.01, quorum=2,
                                  heterogeneity=SKEWED), seed=3))
        assert res.extras["in_flight"] > 0
        assert all(r.n_late <= 2 for r in res.records)
        times = [r.t_virtual for r in res.records]
        assert times == sorted(times)            # the clock only moves forward
        assert all(np.isfinite(l) for l in res.losses)

    def test_async_upload_charged_at_dispatch(self, micro):
        """In-flight/stale-dropped uploads still consumed uplink bytes:
        the ledger charges every dispatched leaf upload exactly once, even
        when the run ends with tables still in the air."""
        cfg, ds = micro
        res = run_federated(cfg, ds, fs_cfg=CFG, fed_cfg=FederationConfig(
            rounds=3, clients_per_round=3, aggregate="async", clock="event",
            simtime=SimTimeConfig(quorum=1, heterogeneity=SKEWED), seed=4))
        assert res.extras["in_flight"] > 0   # some uploads never merged
        total_up = sum(r.upload_bytes for r in res.records)
        n_sent = sum(len(r.cohort) - r.n_dropped for r in res.records)
        assert total_up == n_sent * F.upload_bytes(CFG)
        assert res.traffic["upload_bytes"] == total_up

    def test_event_records_are_deterministic(self, micro):
        cfg, ds = micro
        fed_cfg = FederationConfig(
            rounds=3, clients_per_round=2, aggregate="async", clock="event",
            simtime=SimTimeConfig(quorum=1, heterogeneity=SKEWED), seed=5)
        a = run_federated(cfg, ds, fs_cfg=CFG, fed_cfg=fed_cfg)
        b = run_federated(cfg, ds, fs_cfg=CFG, fed_cfg=fed_cfg)
        _records_equal(a.records, b.records)


class TestDeterministicResume:
    """Same (seed, config) => byte-identical RoundRecord stream across a
    mid-run checkpoint/restore — async late buffer and event queue included.
    """

    def _run_split(self, micro, base, split, total):
        from repro.optim import triangular
        cfg, ds = micro
        lr_fn = triangular(0.2, total)
        uninterrupted = Orchestrator(cfg, CFG, FederationConfig(**base), ds,
                                     lr_fn=lr_fn).run()
        with tempfile.TemporaryDirectory() as d:
            Orchestrator(cfg, CFG, FederationConfig(
                **{**base, "rounds": split}, checkpoint_dir=d,
                checkpoint_every=split), ds, lr_fn=lr_fn).run()
            resumed = Orchestrator(cfg, CFG, FederationConfig(
                **base, checkpoint_dir=d, checkpoint_every=split), ds,
                lr_fn=lr_fn)
            assert resumed.start_round == split
            res = resumed.run()
        _records_equal(res.records, uninterrupted.records[split:])

    def test_round_clock_async_with_late_buffer(self, micro):
        self._run_split(micro, dict(
            rounds=6, clients_per_round=3, aggregate="async",
            straggler=StragglerModel(straggle_prob=0.6, max_delay=3),
            seed=5), split=3, total=6)

    def test_event_clock_async_with_event_queue(self, micro):
        self._run_split(micro, dict(
            rounds=6, clients_per_round=3, aggregate="async", clock="event",
            simtime=SimTimeConfig(staleness_lambda=0.02, quorum=2,
                                  heterogeneity=SKEWED), seed=7),
            split=3, total=6)

    def test_event_clock_sync_barrier(self, micro):
        self._run_split(micro, dict(
            rounds=4, clients_per_round=2, aggregate="tree", clock="event",
            simtime=SimTimeConfig(heterogeneity=SKEWED), seed=1),
            split=2, total=4)


class TestSimtimeCheckpoint:
    def test_event_queue_roundtrip(self, tmp_path, rng):
        state = F.init_state(CFG)
        evs = [Event(time=3.5, round_produced=1, slot=0, client=9,
                     produced=1.25, weight=0.7, loss=2.5,
                     table=jnp.asarray(rng.normal(size=(CFG.rows, CFG.cols))
                                       .astype(np.float32))),
               Event(time=1.5, round_produced=0, slot=1, client=4,
                     produced=0.0, weight=1.0, loss=3.0,
                     table=jnp.zeros((CFG.rows, CFG.cols)))]
        ckpt.save(str(tmp_path), {"w": jnp.zeros((2,))}, state, 2,
                  simtime={"now": 2.25, "events": evs})
        out = ckpt.restore(str(tmp_path), {"w": jnp.zeros((2,))}, state)
        assert out.simtime["now"] == 2.25
        loaded = out.simtime["events"]
        assert [e.time for e in loaded] == [3.5, 1.5]
        for orig, got in zip(evs, loaded):
            assert orig.meta() == got.meta()
            np.testing.assert_array_equal(np.asarray(orig.table),
                                          np.asarray(got.table))

    def test_no_simtime_is_none(self, tmp_path):
        state = F.init_state(CFG)
        ckpt.save(str(tmp_path), {"w": jnp.zeros((2,))}, state, 0)
        out = ckpt.restore(str(tmp_path), {"w": jnp.zeros((2,))}, state)
        assert out.simtime is None


def test_weighted_mesh_aggregate_single_device():
    """psum(w*t)/psum(w) on a size-1 axis reduces to the identity."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.fed import mesh_aggregate
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    t = jnp.full((3, 4), 5.0)
    w = jnp.asarray([2.0])

    def body(t, w):
        return mesh_aggregate(t, ("data",), "tree", weight=w[0])

    out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(), P("data")),
                                out_specs=P(), axis_names={"data"},
                                check_vma=False))(t, w)
    np.testing.assert_allclose(np.asarray(out), 5.0, rtol=1e-6)


def test_config_validation():
    with pytest.raises(ValueError):
        FederationConfig(clock="warp")
    with pytest.raises(ValueError):
        FederationConfig(weight_by="entropy")
    with pytest.raises(ValueError):
        SimTimeConfig(quorum=0)
    with pytest.raises(ValueError):
        HeterogeneityConfig(avail_duty_min=0.0)
