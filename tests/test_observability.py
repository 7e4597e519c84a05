"""Observability (PR 10): end-to-end request tracing, the structured event
journal, and OpenMetrics export across the serving fabric and training
programs.

Covers the span ring (bounded, lock-free, ordered), the typed journal with
its JSONL sink, Chrome trace_event export, Histogram.merge correctness
(merged percentiles == np.percentile over concatenated windows) and the
fabric-wide RouterMetrics roll-up, shape-stable latency formatting, the
OpenMetrics renderer/parser round trip with its rejection paths, the stdlib
scrape endpoint, the checkmetrics CLI, single-trace_id span trees through a
2-engine fleet (decode and continual), snapshot consistency under
concurrent mutation, restart survival with journaled EngineRestart events,
train-program phase spans with host/device attribution, and the
zero-cost-off contract.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.runtime import (
    EngineRestart,
    EventJournal,
    Histogram,
    MetricsServer,
    OpenMetricsError,
    RouterMetrics,
    ServiceConfig,
    ServiceMetrics,
    TraceConfig,
    Tracer,
    build_tracer,
    format_latency_line,
    parse_openmetrics,
    render_openmetrics,
)
from repro.runtime.router import Router, RouterConfig, TenantConfig
from repro.runtime.service import ServePlan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------- plan fixtures
class SleepyPlan(ServePlan):
    """Streaming plan with pure-sleep infer: deterministic fabric tests."""

    name = "streaming"

    def __init__(self, config, metrics=None, delay_s=0.002):
        super().__init__(config, metrics=metrics)
        self.delay_s = delay_s

    def infer(self, x):
        time.sleep(self.delay_s)
        return int(x)


class _Boom(BaseException):
    """Escapes the per-item Exception handler: kills the engine loop."""


def sleepy_factory(delay_s=0.002, crash_on=(), armed=None):
    def factory(config, metrics):
        plan = SleepyPlan(config, metrics=metrics, delay_s=delay_s)
        if crash_on:
            orig = plan.infer

            def infer(x):
                if int(x) in crash_on and armed.pop("on", None):
                    raise _Boom(f"injected crash at {int(x)}")
                return orig(x)

            plan.infer = infer
        return plan

    return factory


def traced_fleet(n=2, trace=None, max_queue=8, **factory_kw):
    router = Router(
        RouterConfig(
            routing="round_robin",
            trace=trace if trace is not None else TraceConfig(),
        )
    )
    for i in range(n):
        router.add_engine(
            f"e{i}", sleepy_factory(**factory_kw),
            ServiceConfig(max_queue=max_queue),
        )
    return router


# ------------------------------------------------------------ tracer core
class TestTracerCore:
    def test_build_tracer_gates(self):
        assert build_tracer(None) is None
        assert build_tracer(TraceConfig(enabled=False)) is None
        assert isinstance(build_tracer(TraceConfig()), Tracer)

    def test_config_validation(self):
        with pytest.raises(ValueError):
            TraceConfig(ring_size=0)
        with pytest.raises(ValueError):
            TraceConfig(journal_size=0)

    def test_ring_bounded_and_ordered(self):
        tr = Tracer(TraceConfig(ring_size=8))
        for i in range(20):
            tr.record(1, f"s{i}", float(i), float(i) + 0.5)
        spans = tr.spans()
        assert len(spans) == 8  # bounded: oldest 12 overwritten
        assert [s.name for s in spans] == [f"s{i}" for i in range(12, 20)]
        assert all(b.seq > a.seq for a, b in zip(spans, spans[1:]))

    def test_trace_filters_and_sorts(self):
        tr = Tracer()
        a, b = tr.new_trace(), tr.new_trace()
        tr.record(a, "late", 5.0, 6.0)
        tr.record(b, "other", 0.5, 1.0)
        tr.record(a, "early", 1.0, 2.0, engine="e0")
        got = tr.trace(a)
        assert [s.name for s in got] == ["early", "late"]  # t_start order
        assert got[0].attrs == {"engine": "e0"}
        assert all(s.trace_id == b for s in tr.trace(b))

    def test_span_names_filter(self):
        tr = Tracer()
        tr.record(1, "router.sched", 0.0, 1.0)
        tr.record(1, "engine.inbox", 0.0, 1.0)
        assert [s.name for s in tr.spans("router.sched")] == ["router.sched"]

    def test_chrome_trace_shape(self):
        tr = Tracer()
        t = tr.new_trace()
        tr.record(t, "router.sched", 1.0, 2.0, tenant="a")
        tr.record(t, "engine.inbox", 2.0, 3.0, engine="e0")
        tr.emit(EngineRestart(engine="e0", restarts=1, leftover=0))
        doc = tr.chrome_trace()
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        xs = [e for e in evs if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"router.sched", "engine.inbox"}
        for e in xs:
            assert e["args"]["trace_id"] == t
            assert e["dur"] >= 0
        # engine attr names the lane; router spans get the name prefix lane
        metas = {e["args"]["name"] for e in evs if e["ph"] == "M"}
        assert {"router", "e0"} <= metas
        instants = [e for e in evs if e["ph"] == "i"]
        assert len(instants) == 1 and instants[0]["name"] == "engine_restart"
        # round-trips as JSON (the Perfetto contract)
        json.loads(json.dumps(doc))

    def test_write_chrome_trace(self, tmp_path):
        tr = Tracer()
        tr.record(tr.new_trace(), "x", 0.0, 1.0)
        path = str(tmp_path / "trace.json")
        tr.write_chrome_trace(path)
        with open(path) as f:
            assert json.load(f)["traceEvents"]


# ---------------------------------------------------------------- journal
class TestJournal:
    def test_typed_events_bounded_and_filtered(self):
        j = EventJournal(size=4)
        for i in range(6):
            j.emit(EngineRestart(engine=f"e{i}", restarts=i))
        rows = j.events()
        assert len(rows) == 4  # bounded deque
        assert [e.engine for _, _, e in rows] == ["e2", "e3", "e4", "e5"]
        assert [s for s, _, _ in rows] == [2, 3, 4, 5]  # seqs survive wrap
        assert j.events(kind="merge_applied") == []

    def test_jsonl_sink(self, tmp_path):
        path = str(tmp_path / "journal.jsonl")
        j = EventJournal(size=8, path=path)
        j.emit(EngineRestart(engine="e0", restarts=2, leftover=1))
        j.close()
        lines = [json.loads(x) for x in open(path)]
        assert len(lines) == 1
        row = lines[0]
        assert row["kind"] == "engine_restart"
        assert row["engine"] == "e0" and row["restarts"] == 2
        assert row["seq"] == 0 and row["ts"] > 0


# ------------------------------------------------------- histogram merge
class TestHistogramMerge:
    def test_merged_percentiles_match_concatenated_windows(self):
        rng = np.random.default_rng(0)
        a, b = Histogram(window=256), Histogram(window=256)
        va, vb = rng.exponential(1.0, 100), rng.exponential(2.0, 150)
        for v in va:
            a.observe(float(v))
        for v in vb:
            b.observe(float(v))
        merged = Histogram(window=512).merge(a).merge(b)
        snap = merged.snapshot()
        both = np.concatenate([va, vb])
        assert snap["count"] == 250
        for q, key in ((50, "p50"), (95, "p95"), (99, "p99")):
            assert snap[key] == pytest.approx(
                float(np.percentile(both, q)), rel=1e-6
            )
        assert snap["max"] == pytest.approx(float(both.max()))

    def test_merge_truncates_to_window_keeping_newest(self):
        src = Histogram(window=256)
        for v in range(200):
            src.observe(float(v))
        small = Histogram(window=100).merge(src)
        snap = small.snapshot()
        assert snap["count"] == 200  # lifetime count still adds
        # window holds only the newest 100 source observations
        assert snap["p50"] == pytest.approx(
            float(np.percentile(np.arange(100, 200), 50))
        )

    def test_merge_same_lock_no_deadlock(self):
        m = ServiceMetrics()
        h1, h2 = m.hist("queue_wait_s"), m.hist("e2e_s")
        h1.observe(1.0)
        h2.observe(2.0)
        h1.merge(h2)  # shared bundle RLock: single acquisition path
        assert h1.snapshot()["count"] == 2

    def test_self_merge_rejected(self):
        h = Histogram()
        with pytest.raises(ValueError):
            h.merge(h)

    def test_fleet_rollup_exposes_fabric_quantiles(self):
        rm = RouterMetrics()
        e0 = rm.register_engine("e0")
        e1 = rm.register_engine("e1")
        v0, v1 = [0.01 * i for i in range(50)], [0.5 + 0.01 * i for i in range(50)]
        for v in v0:
            e0.e2e_s.observe(v)
        for v in v1:
            e1.e2e_s.observe(v)
        snap = rm.snapshot()
        assert "fleet" in snap
        fleet = snap["fleet"]["e2e_s"]
        both = np.asarray(v0 + v1)
        assert fleet["count"] == 100
        assert fleet["p95"] == pytest.approx(
            float(np.percentile(both, 95)), rel=1e-6
        )


# -------------------------------------------------------- latency formats
class TestFormatLatencyLine:
    def test_explicit_names_shape_stable_at_zero(self):
        snap = ServiceMetrics().snapshot()
        line = format_latency_line(snap, "queue_wait_s", "e2e_s")
        # both requested histograms render even with zero observations
        assert "queue_wait p50=0.00ms p95=0.00ms p99=0.00ms" in line
        assert "e2e p50=0.00ms" in line

    def test_no_names_empty_still_summarizes(self):
        line = format_latency_line(ServiceMetrics().snapshot())
        assert "no latency samples" in line


# ------------------------------------------------------------ openmetrics
class TestOpenMetrics:
    def test_service_render_parse_round_trip(self):
        m = ServiceMetrics()
        m.submitted.inc(3)
        m.completed.inc(2)
        m.e2e_s.observe(0.1)
        m.online_updates.inc()
        fams = parse_openmetrics(render_openmetrics(m.snapshot()))
        assert fams["repro_submitted"]["type"] == "counter"
        samples = {
            name: v
            for name, _labels, v in fams["repro_submitted"]["samples"]
        }
        assert samples["repro_submitted_total"] == 3.0
        assert fams["repro_e2e_seconds"]["type"] == "summary"
        names = {n for n, _, _ in fams["repro_e2e_seconds"]["samples"]}
        assert "repro_e2e_seconds_count" in names
        assert "repro_online_updates" in fams

    def test_router_render_parse_round_trip(self):
        rm = RouterMetrics()
        rm.dispatched.inc(5)
        tm = rm.tenant("paid")
        tm.submitted.inc(5)
        tm.e2e_s.observe(0.2)
        em = rm.register_engine("e0")
        em.e2e_s.observe(0.2)
        fams = parse_openmetrics(render_openmetrics(rm.snapshot()))
        assert "repro_router_dispatched" in fams
        tenant_samples = fams["repro_tenant_submitted"]["samples"]
        assert any(
            labels.get("tenant") == "paid" for _, labels, _ in tenant_samples
        )
        engine_samples = fams["repro_e2e_seconds"]["samples"]
        assert any(
            labels.get("engine") == "e0" for _, labels, _ in engine_samples
        )
        assert "repro_fleet_e2e_seconds" in fams

    @pytest.mark.parametrize(
        "text",
        [
            "repro_x_total 1\n",                       # no EOF terminator
            "# TYPE repro_x counter\nrepro_x_total one\n# EOF\n",  # bad value
            "# TYPE repro_x bogus\n# EOF\n",           # unknown type
            "# TYPE repro_x counter\n# TYPE repro_x counter\n# EOF\n",  # dupe
            "# TYPE repro_x counter\nrepro_y_total 1\n# EOF\n",  # orphan
            "# EOF\ntrailing 1\n",                     # content after EOF
        ],
    )
    def test_parser_rejects_invalid(self, text):
        with pytest.raises(OpenMetricsError):
            parse_openmetrics(text)

    def test_metrics_server_scrape(self):
        m = ServiceMetrics()
        m.submitted.inc(7)
        tracer = Tracer()
        tracer.record(tracer.new_trace(), "x", 0.0, 1.0)
        server = MetricsServer(m.snapshot, tracer=tracer, port=0)
        try:
            with urllib.request.urlopen(
                f"{server.url}/metrics", timeout=10
            ) as resp:
                assert resp.status == 200
                fams = parse_openmetrics(resp.read().decode())
            samples = {
                n: v for n, _, v in fams["repro_submitted"]["samples"]
            }
            assert samples["repro_submitted_total"] == 7.0
            with urllib.request.urlopen(
                f"{server.url}/trace.json", timeout=10
            ) as resp:
                assert json.loads(resp.read())["traceEvents"]
        finally:
            server.close()

    def test_checkmetrics_cli(self, tmp_path):
        m = ServiceMetrics()
        m.submitted.inc()
        path = tmp_path / "metrics.txt"
        path.write_text(render_openmetrics(m.snapshot()))
        tool = os.path.join(REPO, "tools", "checkmetrics")
        ok = subprocess.run(
            [sys.executable, tool, str(path), "--require", "repro_submitted"],
            capture_output=True, text=True, timeout=60,
        )
        assert ok.returncode == 0, ok.stdout + ok.stderr
        assert "checkmetrics: OK" in ok.stdout
        bad = subprocess.run(
            [sys.executable, tool, str(path), "--require", "repro_missing"],
            capture_output=True, text=True, timeout=60,
        )
        assert bad.returncode == 1
        invalid = tmp_path / "bad.txt"
        invalid.write_text("repro_x 1\n")
        broken = subprocess.run(
            [sys.executable, tool, str(invalid)],
            capture_output=True, text=True, timeout=60,
        )
        assert broken.returncode == 1


# ------------------------------------------------------------- fleet traces
class TestFleetTracing:
    def test_single_trace_id_spans_full_path(self):
        r = traced_fleet(n=2).start()
        futs = [r.submit(i, tenant="a") for i in range(8)]
        [f.result(timeout=10) for f in futs]
        tids = [f.trace_id for f in futs]
        assert sorted(tids) == list(range(1, 9))  # minted per request
        tr = r.tracer
        for tid in tids:
            names = {s.name for s in tr.trace(tid)}
            assert {"router.sched", "engine.inbox", "router.e2e",
                    "engine.e2e"} <= names
        # the sched span names tenant + chosen engine
        sched = tr.trace(tids[0])[0]
        assert sched.name == "router.sched"
        assert sched.attrs["tenant"] == "a"
        assert sched.attrs["target"] in ("e0", "e1")
        r.drain_and_stop(timeout=10)
        doc = tr.chrome_trace()
        assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) >= 32

    def test_tenant_queue_full_journals_tenant_shed(self):
        r = traced_fleet(
            n=1, max_queue=1, delay_s=0.05,
        )
        # tiny per-tenant queue: the 3rd queued submit bounces
        r.config = r.config  # (router already built with default tenants)
        from repro.runtime.router import TenantQueueFull

        rr = Router(
            RouterConfig(
                tenants={"t": TenantConfig(max_queue=2)}, trace=TraceConfig()
            )
        )
        rr.add_engine("e0", sleepy_factory(delay_s=0.05),
                      ServiceConfig(max_queue=1))
        futs = [rr.submit(i, tenant="t") for i in range(2)]
        with pytest.raises(TenantQueueFull):
            rr.submit(99, tenant="t")
        events = rr.tracer.events(kind="tenant_shed")
        assert len(events) == 1
        _, _, ev = events[0]
        assert ev.tenant == "t" and ev.reason == "queue_full"
        assert ev.trace_id is not None
        rr.start()
        [f.result(timeout=10) for f in futs]
        rr.drain_and_stop(timeout=10)
        r.drain_and_stop(timeout=10)

    def test_doa_deadline_journals_deadline_shed(self):
        r = traced_fleet(n=1)
        fut = r.submit(1, deadline_s=0.0)
        with pytest.raises(Exception):
            fut.result(timeout=5)
        events = r.tracer.events(kind="deadline_shed")
        assert len(events) == 1
        assert events[0][2].trace_id == fut.trace_id
        r.drain_and_stop(timeout=10)

    def test_restart_survival_journals_engine_restart(self):
        armed = {"on": True}
        r = Router(RouterConfig(routing="round_robin", trace=TraceConfig()))
        r.add_engine(
            "e0", sleepy_factory(delay_s=0.001, crash_on={3}, armed=armed),
            ServiceConfig(max_queue=2),
        )
        r.start()
        futs = [r.submit(i) for i in range(8)]
        res = [f.result(timeout=15) for f in futs]
        assert sorted(res) == list(range(8))  # crash victim redispatched
        r.drain_and_stop(timeout=15)
        assert r.metrics.snapshot()["restarts"] == 1
        events = r.tracer.events(kind="engine_restart")
        assert len(events) == 1
        ev = events[0][2]
        assert ev.engine == "e0" and ev.restarts == 1
        # per-engine telemetry bundle survived the restart (same object)
        snap = r.metrics.snapshot()
        assert snap["engines"]["e0"]["completed"] >= 1

    def test_tracing_disabled_is_zero_cost_and_unset(self):
        r = Router(RouterConfig(routing="round_robin"))
        r.add_engine("e0", sleepy_factory(), ServiceConfig(max_queue=4))
        r.start()
        futs = [r.submit(i) for i in range(4)]
        [f.result(timeout=10) for f in futs]
        assert r.tracer is None
        assert all(getattr(f, "trace_id", None) is None for f in futs)
        r.drain_and_stop(timeout=10)


# -------------------------------------------------- snapshot consistency
class TestSnapshotConsistency:
    def test_hammered_snapshots_never_tear(self):
        rm = RouterMetrics()
        bundles = [rm.register_engine(f"e{i}") for i in range(3)]
        stop = threading.Event()
        errors = []

        def writer(m):
            k = 0
            while not stop.is_set():
                m.submitted.inc()
                m.completed.inc()
                m.e2e_s.observe(0.001 * (k % 50))
                rm.dispatched.inc()
                k += 1

        def reader():
            last_dispatched = 0
            try:
                while not stop.is_set():
                    snap = rm.snapshot()
                    # counters are monotone across snapshots
                    assert snap["dispatched"] >= last_dispatched
                    last_dispatched = snap["dispatched"]
                    for eng in snap["engines"].values():
                        # per-bundle consistency: completed never exceeds
                        # submitted (both incremented under one lock)
                        assert eng["completed"] <= eng["submitted"]
                        assert eng["e2e_s"]["count"] >= 0
                    for h in snap["fleet"].values():
                        assert h["count"] >= 0
            except AssertionError as e:  # surfaced after join
                errors.append(e)

        threads = [
            threading.Thread(target=writer, args=(m,)) for m in bundles
        ] + [threading.Thread(target=reader) for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.4)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert errors == []

    def test_histogram_window_lengths_bounded_under_merge_race(self):
        src = Histogram(window=64)
        dst = Histogram(window=32)
        stop = threading.Event()

        def observe():
            k = 0
            while not stop.is_set():
                src.observe(float(k % 10))
                k += 1

        t = threading.Thread(target=observe)
        t.start()
        try:
            for _ in range(200):
                dst.merge(src)
                snap = dst.snapshot()
                vals = dst._window_values()
                assert len(vals) <= 32
                assert snap["count"] >= len(vals)
        finally:
            stop.set()
            t.join(timeout=10)


# --------------------------------------------------------- continual fleet
@pytest.mark.slow
class TestContinualFleetTrace:
    def test_feedback_trace_covers_learn_hops(self):
        """The acceptance path: one trace id through a continual fleet
        covers router sched -> engine inbox -> learn, with plan.update /
        plan.merge spans and merge_applied journal events correlated."""
        from tests.test_continual import _cc, _fitted
        from repro.runtime import Feedback

        compiled, xs, ys = _fitted()

        def factory(config, metrics):
            from repro.runtime.continual import ContinualPlan

            return ContinualPlan(compiled, config, metrics)

        router = Router(
            RouterConfig(routing="round_robin", trace=TraceConfig())
        )
        cfg = ServiceConfig(continual=_cc(update_batch=2, merge_every=2))
        router.add_engine("cl0", factory, cfg)
        router.start()
        futs = [
            router.submit(Feedback(xs[k], int(ys[k])), pool="continual")
            for k in range(8)
        ]
        acks = [f.result(timeout=30) for f in futs]
        router.drain_and_stop(timeout=30)
        assert any(a["applied"] for a in acks)
        assert any(a["merged"] for a in acks)
        tr = router.tracer
        # the sample that applied an update carries the full hop chain
        applied_tid = futs[[a["applied"] for a in acks].index(True)].trace_id
        names = {s.name for s in tr.trace(applied_tid)}
        assert {"router.sched", "engine.inbox", "engine.learn",
                "plan.update"} <= names
        merged_tid = futs[[a["merged"] for a in acks].index(True)].trace_id
        assert "plan.merge" in {s.name for s in tr.trace(merged_tid)}
        merges = tr.events(kind="merge_applied")
        assert merges and merges[0][2].trace_id == merged_tid
        # the whole thing exports as valid Chrome trace JSON
        json.loads(json.dumps(tr.chrome_trace()))


# ------------------------------------------------------------ train spans
@pytest.mark.slow
class TestTrainTracing:
    def _fit(self, trace=None, profile_dir=None):
        from repro.core import (
            DenseLayer,
            ExecutionConfig,
            Network,
            StructuralPlasticityLayer,
            UnitLayout,
            onehot_layout,
        )
        from repro.data import complementary_code, mnist_like

        ds = mnist_like(n_train=128, n_test=32, n_features=32, seed=0)
        x, layout = complementary_code(ds.x_train)
        xs = np.asarray(x, np.float32)
        hidden = UnitLayout(4, 8)
        net = Network(seed=0).add(
            StructuralPlasticityLayer(layout, hidden, fan_in=16, lam=0.05)
        ).add(DenseLayer(hidden, onehot_layout(10), lam=0.05))
        compiled = net.compile(
            ExecutionConfig(trace=trace, profile_dir=profile_dir)
        )
        res = compiled.fit(
            (xs, ds.y_train), epochs_hidden=2, epochs_readout=2,
            batch_size=64,
        )
        return compiled, res

    def test_history_splits_host_and_device_time(self):
        _, res = self._fit()
        epochs = [h for h in res.history if "epoch" in h]
        assert epochs
        for h in epochs:
            assert h["host_s"] >= 0 and h["device_wait_s"] >= 0
            assert h["seconds"] == pytest.approx(
                h["host_s"] + h["device_wait_s"], rel=1e-6, abs=1e-9
            )

    def test_phase_spans_recorded_on_train_trace(self):
        compiled, res = self._fit(trace=TraceConfig())
        tr = compiled.tracer
        spans = tr.trace(tr.TRAIN_TRACE_ID)
        names = {s.name for s in spans}
        assert "train.hidden0" in names and "train.readout" in names
        hidden = [s for s in spans if s.name == "train.hidden0"]
        assert {s.attrs["epoch"] for s in hidden} == {0, 1}
        assert all("device_wait_s" in s.attrs for s in hidden)
        # one train.<phase> span per history entry that carries timings
        # (the ring also holds train.fit and each epoch's input spans)
        timed = [h for h in res.history if "seconds" in h]
        phases = {f"train.{h['phase']}" for h in timed}
        assert len([s for s in spans if s.name in phases]) == len(timed)

    def test_profile_dir_writes_device_profile(self, tmp_path):
        pdir = str(tmp_path / "prof")
        self._fit(profile_dir=pdir)
        dumped = [
            os.path.join(root, f)
            for root, _, files in os.walk(pdir) for f in files
        ]
        assert dumped  # jax.profiler.trace produced artifacts

    def test_jit_cache_sizes_surface(self):
        compiled, _ = self._fit()
        sizes = compiled.plan.jit_cache_sizes()
        assert sizes and all(
            isinstance(v, int) and v >= 1 for v in sizes.values()
        )
