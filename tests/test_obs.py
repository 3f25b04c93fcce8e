"""Telemetry layer: metrics semantics, sinks, schema, spans, and the two
invariants the whole design hangs on — observability is *free* when
disabled and *invisible* when enabled (instrumented runs produce
byte-identical RoundRecords)."""

import dataclasses
import json
import math

import numpy as np
import pytest

from repro import fed, obs
from repro.core import fetchsgd as F
from repro.core import layout as layout_lib


# ---------------------------------------------------------------- metrics

class TestCounter:
    def test_monotonic(self):
        c = obs.Counter()
        assert c.value == 0
        c.inc()
        c.inc(5)
        c.inc(0)
        assert c.value == 6

    def test_negative_increment_raises(self):
        with pytest.raises(ValueError):
            obs.Counter().inc(-1)


class TestGauge:
    def test_last_write_wins(self):
        g = obs.Gauge()
        assert g.value is None
        g.set(3)
        g.set(1.5)
        assert g.value == 1.5


class TestHistogram:
    def test_basic_stats(self):
        h = obs.Histogram()
        for v in (0.1, 0.2, 0.3, 10.0):
            h.observe(v)
        assert h.count == 4
        assert h.sum == pytest.approx(10.6)
        assert h.min == 0.1 and h.max == 10.0

    def test_empty_quantile_is_nan(self):
        assert math.isnan(obs.Histogram().quantile(0.5))

    def test_quantile_monotone_and_clamped(self):
        h = obs.Histogram()
        rng = np.random.default_rng(0)
        data = rng.lognormal(0.0, 2.0, size=2000)
        for v in data:
            h.observe(v)
        qs = [h.quantile(q) for q in (0.0, 0.25, 0.5, 0.75, 0.9, 0.99, 1.0)]
        assert qs == sorted(qs)
        assert all(h.min <= q <= h.max for q in qs)
        # the interpolated estimate should land near the true quantile
        assert h.quantile(0.5) == pytest.approx(
            float(np.quantile(data, 0.5)), rel=0.35)

    def test_quantile_out_of_range_raises(self):
        with pytest.raises(ValueError):
            obs.Histogram().quantile(1.5)

    def test_snapshot_roundtrips_through_json(self):
        h = obs.Histogram()
        for v in (1.0, 2.0, 4.0, 8.0, 1000.0):
            h.observe(v)
        snap = json.loads(json.dumps(h.snapshot()))
        assert snap["count"] == 5
        assert obs.quantile_from_snapshot(snap, 0.5) == pytest.approx(
            h.quantile(0.5))

    def test_default_buckets_sorted_and_125(self):
        b = obs.default_buckets(1e-3, 1e3, per_decade=3)
        assert list(b) == sorted(b)
        assert 1.0 in b and 2.0 in b and 5.0 in b


class TestRegistry:
    def test_instruments_memoized(self):
        reg = obs.MetricsRegistry()
        assert reg.counter("a") is reg.counter("a")
        assert reg.gauge("b") is reg.gauge("b")
        assert reg.histogram("c") is reg.histogram("c")
        assert len(reg) == 3

    def test_snapshot_shape(self):
        reg = obs.MetricsRegistry()
        reg.counter("n").inc(2)
        reg.gauge("x").set(7)
        reg.histogram("h").observe(0.5)
        snap = reg.snapshot()
        assert snap["counters"] == {"n": 2}
        assert snap["gauges"] == {"x": 7.0}
        assert snap["histograms"]["h"]["count"] == 1


# ------------------------------------------------------------ noop / spans

class TestNoop:
    def test_noop_is_stateless_and_shared(self):
        t = obs.NOOP
        assert t.enabled is False and t.trace_enabled is False
        assert t.counter("a") is t.counter("b")          # one shared object
        assert t.span("s") is obs.NULL_SPAN
        t.counter("a").inc(10)
        t.gauge("g").set(1)
        t.histogram("h").observe(2)
        t.emit("round", anything=1)
        t.close()                                        # all no-ops

    def test_null_span_sync_is_identity(self):
        x = object()
        with obs.NULL_SPAN as sp:
            assert sp.sync(x) is x

    def test_disabled_telemetry_spans_are_null(self):
        tele = obs.Telemetry([obs.MemorySink()], trace=False)
        assert tele.span("x") is obs.NULL_SPAN


class TestSpans:
    def test_nesting_depth_and_parent(self):
        sink = obs.MemorySink()
        tele = obs.Telemetry([sink], trace=True)
        with tele.span("outer"):
            with tele.span("inner") as sp:
                sp.sync([1, 2, 3])       # plain python: block is a no-op
        spans = [e for e in sink.events if e["type"] == "span"]
        by_name = {s["name"]: s for s in spans}
        assert by_name["outer"]["depth"] == 0
        assert by_name["outer"]["parent"] is None
        assert by_name["inner"]["depth"] == 1
        assert by_name["inner"]["parent"] == "outer"
        # inner exits first
        assert spans[0]["name"] == "inner"
        assert all(s["dur_s"] >= 0 for s in spans)

    def test_span_records_error_type(self):
        sink = obs.MemorySink()
        tele = obs.Telemetry([sink], trace=True)
        with pytest.raises(RuntimeError):
            with tele.span("boom"):
                raise RuntimeError("x")
        (ev,) = [e for e in sink.events if e["type"] == "span"]
        assert ev["error"] == "RuntimeError"
        assert tele._span_stack == []    # stack unwound despite the raise

    def test_span_lands_on_the_profilers_host_plane(self, tmp_path):
        """A live span is a profiler annotation too: on the trace's host
        plane, on the clock of the device ops."""
        import glob
        import jax
        import jax.numpy as jnp
        tele = obs.Telemetry([obs.MemorySink()], trace=True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tele.span("train.step", round=0) as sp:
                sp.sync(jnp.arange(4.0) * 2)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
        host = [ev.name for plane in data.planes
                if plane.name.startswith("/host")
                for line in plane.lines for ev in line.events]
        assert "train.step" in host


# ------------------------------------------------------------------ sinks

class TestSinks:
    def test_jsonl_roundtrip(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        tele = obs.Telemetry([obs.JsonlSink(path)], trace=True)
        tele.emit_meta(run="test")
        tele.counter("fed.rounds").inc(3)
        tele.histogram("lat").observe(0.25)
        with tele.span("work"):
            pass
        tele.emit("train_round", round=0, loss=float(np.float32(1.5)),
                  step_seconds=0.1)
        tele.close()
        events = obs.parse_jsonl(path)
        assert obs.validate_events(events) == []
        assert events[0]["type"] == "meta"
        assert events[-1]["type"] == "metrics"
        assert events[-1]["counters"]["fed.rounds"] == 3
        # numpy scalar was coerced to a plain JSON number
        tr = next(e for e in events if e["type"] == "train_round")
        assert isinstance(tr["loss"], float) and tr["loss"] == 1.5

    def test_jsonl_emit_after_close_raises(self, tmp_path):
        s = obs.JsonlSink(str(tmp_path / "x.jsonl"))
        s.emit({"type": "meta", "t": 0.0, "env": {}})
        s.close()
        s.close()                                        # idempotent
        with pytest.raises(ValueError):
            s.emit({"type": "meta", "t": 0.0, "env": {}})

    def test_telemetry_close_idempotent(self):
        sink = obs.MemorySink()
        tele = obs.Telemetry([sink])
        tele.close()
        tele.close()
        assert sink.closed
        assert sum(1 for e in sink.events if e["type"] == "metrics") == 1

    def test_stdout_summary_sink(self, capsys):
        sink = obs.StdoutSummarySink()
        sink.emit({"type": "round", "t": 0.0})
        sink.emit({"type": "span", "t": 0.0, "name": "s", "dur_s": 0.5,
                   "depth": 0, "parent": None})
        sink.close()
        out = capsys.readouterr().out
        assert "1 rounds" in out and "span s" in out


# ----------------------------------------------------------------- schema

class TestSchema:
    GOOD_ROUND = {"type": "round", "t": 0.1, "round": 0, "loss": 1.0,
                  "cohort_size": 4, "n_fresh": 3, "n_late": 0,
                  "n_dropped": 1, "n_straggling": 0, "upload_bytes": 100,
                  "download_bytes": 50, "dense_equiv_upload_bytes": 4000,
                  "dense_equiv_download_bytes": 4000,
                  "upload_compression_x": 40.0,
                  "total_compression_x": 53.3}

    def test_valid_round(self):
        assert obs.validate_event(self.GOOD_ROUND) == []

    def test_extra_fields_allowed(self):
        ev = dict(self.GOOD_ROUND, queue_depth=3, policy="async")
        assert obs.validate_event(ev) == []

    def test_missing_field_rejected(self):
        ev = dict(self.GOOD_ROUND)
        del ev["upload_bytes"]
        assert any("upload_bytes" in e for e in obs.validate_event(ev))

    def test_wrong_type_rejected(self):
        ev = dict(self.GOOD_ROUND, n_fresh="three")
        assert any("n_fresh" in e for e in obs.validate_event(ev))

    def test_unknown_type_rejected(self):
        errs = obs.validate_event({"type": "mystery", "t": 0.0})
        assert any("unknown event type" in e for e in errs)

    def test_missing_t_rejected(self):
        errs = obs.validate_event({"type": "meta", "env": {}})
        assert any("'t'" in e for e in errs)

    def test_empty_stream_rejected(self):
        assert obs.validate_events([]) != []

    def test_none_loss_allowed(self):
        ev = dict(self.GOOD_ROUND, loss=None)
        assert obs.validate_event(ev) == []


# ----------------------------------------------- instrumented federation

CFG = F.FetchSGDConfig(rows=3, cols=1 << 10, k=64, momentum=0.9)


@pytest.fixture(scope="module")
def micro():
    from repro.launch import simulate
    cfg = simulate.micro_cfg()
    return cfg, simulate.micro_dataset(cfg)


def _run(micro, *, telemetry=None, health_every=0, dataset=None,
         **fed_kw):
    from repro.launch import simulate
    cfg, ds = micro
    ds = dataset if dataset is not None else ds
    fed_kw.setdefault("rounds", 3)
    fed_kw.setdefault("clients_per_round", 2)
    return simulate.run_simulation(
        cfg, method="fetchsgd", rounds=fed_kw["rounds"],
        clients_per_round=fed_kw["clients_per_round"], dataset=ds,
        fs_cfg=CFG, fed_cfg=fed.FederationConfig(**fed_kw),
        telemetry=telemetry, health_every=health_every)


class TestInstrumentedRun:
    @pytest.fixture(scope="class")
    def instrumented(self, micro):
        sink = obs.MemorySink()
        tele = obs.Telemetry([sink], trace=True)
        res = _run(micro, telemetry=tele, health_every=1,
                   aggregate="flat", rounds=3, clients_per_round=2)
        tele.close()
        return res, sink.events

    def test_events_schema_valid(self, instrumented):
        _, events = instrumented
        assert obs.validate_events(events) == []

    def test_round_events_match_records(self, instrumented):
        res, events = instrumented
        rounds = [e for e in events if e["type"] == "round"]
        assert len(rounds) == 3
        for ev, rec in zip(rounds, res.extras["fed_records"]):
            assert ev["round"] == rec.round_idx
            assert ev["loss"] == pytest.approx(rec.loss)
            assert ev["upload_bytes"] == rec.upload_bytes

    def test_compression_ratio_pinned(self, micro, instrumented):
        """Regression: the round event's accounting is self-describing and
        matches the closed form.  With flat aggregation and n fresh
        clients, upload = n * rows * cols * 4 and dense-equivalent =
        n * d * 4, so upload_compression_x == d / (rows * cols)."""
        from repro.models import transformer
        import jax
        cfg, _ = micro
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        d = layout_lib.build_layout(params).total
        _, events = instrumented
        for ev in (e for e in events if e["type"] == "round"):
            n = ev["n_fresh"]
            assert ev["upload_bytes"] == n * F.upload_bytes(CFG)
            assert ev["dense_equiv_upload_bytes"] == n * d * 4
            assert ev["upload_compression_x"] == pytest.approx(
                d / (CFG.rows * CFG.cols))
            assert ev["total_compression_x"] == pytest.approx(
                2 * ev["dense_equiv_upload_bytes"]
                / (ev["upload_bytes"] + ev["download_bytes"]))

    def test_sketch_health_emitted(self, instrumented):
        _, events = instrumented
        health = [e for e in events if e["type"] == "sketch_health"]
        assert len(health) == 3                       # health_every=1
        for h in health:
            assert np.isfinite(h["agg_table_norm"])
            assert h["recovery_rel_err"] is not None
            assert 0.0 <= h["heavy_hitter_overlap"] <= 1.0

    def test_spans_cover_the_round(self, instrumented):
        _, events = instrumented
        names = {e["name"] for e in events if e["type"] == "span"}
        assert {"fed.round", "fed.clients", "fed.aggregate",
                "fed.server_update"} <= names
        inner = [e for e in events if e["type"] == "span"
                 and e["name"] == "fed.aggregate"]
        assert all(s["parent"] == "fed.round" and s["depth"] == 1
                   for s in inner)

    def test_final_metrics_snapshot(self, instrumented):
        _, events = instrumented
        snap = events[-1]
        assert snap["type"] == "metrics"
        assert snap["counters"]["fed.rounds"] == 3
        assert snap["counters"]["fed.upload_bytes"] > 0
        assert snap["histograms"]["fed.cohort_size"]["count"] == 3


class TestDeterminism:
    """Observability must not perturb the run: instrumented and
    uninstrumented executions produce byte-identical RoundRecords."""

    @pytest.mark.parametrize("clock", ["round", "event"])
    def test_instrumented_records_identical(self, micro, clock):
        kw = dict(aggregate="async", rounds=3, clients_per_round=2,
                  straggler=fed.StragglerModel(straggle_prob=0.4,
                                               max_delay=2),
                  clock=clock, seed=7)
        if clock == "event":
            kw["simtime"] = fed.SimTimeConfig(
                heterogeneity=fed.HeterogeneityConfig(bandwidth_sigma=1.5))
        base = _run(micro, telemetry=None, health_every=0, **kw)

        sink = obs.MemorySink()
        tele = obs.Telemetry([sink], trace=True)
        inst = _run(micro, telemetry=tele, health_every=1, **kw)
        tele.close()

        assert len(sink.events) > 0                   # actually instrumented
        recs_base = [dataclasses.asdict(r) for r in
                     base.extras["fed_records"]]
        recs_inst = [dataclasses.asdict(r) for r in
                     inst.extras["fed_records"]]
        assert recs_base == recs_inst
        assert base.losses == inst.losses
        assert base.traffic == inst.traffic


# ------------------------------------------------------------- trajectory

class TestTrajectory:
    ROWS = [("bench_a_n1024", 12.5, "81.9Melem_per_s"),
            ("bench_b", 7.0, "")]

    def test_write_load_roundtrip(self, tmp_path):
        import benchmarks.trajectory as tj
        path = tj.write("kernels", self.ROWS, out_dir=str(tmp_path))
        assert path.endswith("BENCH_kernels.json")
        payload = tj.load(path)
        assert payload["bench"] == "kernels"
        assert payload["results"][0]["us_per_call"] == 12.5
        assert "python" in payload["env"]

    def test_label_sanitized(self, tmp_path):
        import benchmarks.trajectory as tj
        path = tj.write("fig3/4/5", self.ROWS, out_dir=str(tmp_path))
        assert path.endswith("BENCH_fig3_4_5.json")
        assert tj.load(path)["bench"] == "fig3/4/5"

    def test_validate_rejects_garbage(self):
        import benchmarks.trajectory as tj
        assert tj.validate({"schema": 99}) != []
        assert tj.validate({"schema": 1, "bench": "x",
                            "created_utc": "t", "env": {},
                            "results": [{"name": 1}]}) != []

    def test_compare(self):
        import benchmarks.trajectory as tj
        old = {"results": [{"name": "a", "us_per_call": 10.0}]}
        new = {"results": [{"name": "a", "us_per_call": 5.0},
                           {"name": "b", "us_per_call": 1.0}]}
        (row,) = tj.compare(old, new)
        assert row == ("a", 10.0, 5.0, 0.5)


# ------------------------------------------------------------ CLI plumbing

class TestFromArgs:
    def test_all_flags_off_is_noop(self):
        import argparse
        ap = argparse.ArgumentParser()
        obs.add_cli_flags(ap)
        args = ap.parse_args([])
        assert obs.from_args(args) is obs.NOOP

    def test_metrics_flag_builds_jsonl(self, tmp_path):
        import argparse
        ap = argparse.ArgumentParser()
        obs.add_cli_flags(ap)
        path = str(tmp_path / "m.jsonl")
        args = ap.parse_args(["--metrics", path, "--trace"])
        tele = obs.from_args(args, run="test")
        assert tele.trace_enabled
        tele.close()
        events = obs.parse_jsonl(path)
        assert obs.validate_events(events) == []
        assert events[0]["type"] == "meta"
        assert events[0]["run"] == "test"


# ------------------------------------------------- population-scale path

class TestPopulationPath:
    """The vectorized 10^4+-client event loop speaks the same telemetry
    schema as the per-object path — no new event types, the existing JSONL
    gate passes, and the population size is visible as a gauge."""

    @pytest.fixture(scope="class")
    def pop_run(self, micro):
        from repro.launch import simulate
        cfg, _ = micro
        ds = simulate.micro_dataset(cfg, n_clients=10_000)
        sink = obs.MemorySink()
        tele = obs.Telemetry([sink], trace=True)
        res = _run(micro, telemetry=tele, health_every=1, aggregate="async",
                   rounds=3, clients_per_round=16, clock="event",
                   vectorized=True, seed=3,
                   simtime=fed.SimTimeConfig(
                       heterogeneity=fed.HeterogeneityConfig(
                           bandwidth_sigma=1.5)),
                   dataset=ds)
        tele.close()
        return res, sink.events

    def test_round_events_follow_existing_schema(self, pop_run):
        res, events = pop_run
        assert obs.validate_events(events) == []
        rounds = [e for e in events if e["type"] == "round"]
        assert len(rounds) == 3
        for ev, rec in zip(rounds, res.extras["fed_records"]):
            assert ev["round"] == rec.round_idx
            assert ev["population_size"] == 10_000

    def test_population_size_gauge(self, pop_run):
        _, events = pop_run
        snap = [e for e in events if e["type"] == "metrics"][-1]
        assert snap["gauges"]["fed.population_size"] == 10_000

    def test_jsonl_gate_passes_on_10k_run(self, micro, tmp_path):
        from repro.launch import simulate
        from repro.obs import schema
        path = str(tmp_path / "pop.jsonl")
        simulate.main(["--clock", "event", "--population", "10000",
                       "--rounds", "2", "--clients-per-round", "8",
                       "--metrics", path])
        assert schema.main([path]) == 0
