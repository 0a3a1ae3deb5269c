"""Tests for the observability layer (repro.obs) and its integrations.

The contract under test: spans record nested, thread-aware intervals
exported as valid Chrome/Perfetto ``trace_event`` JSON; metrics are
cheap streaming instruments whose snapshots are copies, never views;
``TopoRequest(trace=True)`` produces a timeline AND a diagram
bit-identical to the untraced run (tracing observes, never perturbs);
StageReport — now a thin view over spans — keeps its public shape
(``flat()``, ``to_dict()``, front/back/comm attribution)."""

import json
import threading
import time

import numpy as np
import pytest

from repro.core.diagram import diff_report, same_offdiagonal
from repro.core.grid import Grid
from repro.fields import make_field
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry, Span,
                       Trace, current_trace, global_metrics, maybe_span,
                       set_enabled, spans_overlap, thread_names,
                       trace_active, validate_trace_events)
from repro.pipeline import PersistencePipeline, TopoRequest
from repro.pipeline.stages import StageReport
from repro.stream import ArraySource, HaloExchange, HaloExchangeTimeout


# --------------------------------------------------------------------------
# Trace / Span
# --------------------------------------------------------------------------

class TestTrace:
    def test_span_nesting_and_attrs(self):
        tr = Trace()
        with tr.span("outer", depth=0) as sp:
            sp.args["extra"] = 1
            with tr.span("inner"):
                time.sleep(0.001)
        evs = tr.events()
        assert [e.name for e in evs] == ["outer", "inner"]
        outer, inner = evs
        assert outer.args == {"depth": 0, "extra": 1}
        # exact time containment: inner nests inside outer
        assert outer.ts <= inner.ts
        assert inner.ts + inner.dur <= outer.ts + outer.dur
        assert inner.dur >= 0.001

    @pytest.mark.parametrize("n", [8, 16])
    def test_d1_round_spans_nest_one_per_round(self, n):
        """D1 rounds are ``with`` spans: one per round on both D1 paths
        (8^3 takes the burst path, 16^3 the wavefront), each inside the
        ``d1`` stage span, and the exported timeline stays well nested."""
        g = Grid.of(n, n, n)
        res = PersistencePipeline(backend="jax").run(TopoRequest(
            field=make_field("random", (n, n, n), seed=1), grid=g,
            trace=True))
        evs = res.trace.events()
        rounds = [e for e in evs if e.name == "d1_round"]
        (d1,) = [e for e in evs if e.name == "d1"]
        assert len(rounds) == res.stats["d1_rounds"] > 0
        assert [e.args["round"] for e in rounds] == \
            list(range(1, len(rounds) + 1))
        assert all(d1.ts <= e.ts and e.ts + e.dur <= d1.ts + d1.dur
                   for e in rounds)
        validate_trace_events(res.trace.to_dict())

    def test_instant_marker(self):
        tr = Trace()
        sp = tr.instant("mark", k=1)
        assert sp.dur == 0.0
        assert tr.events() == [sp]

    def test_threads_get_own_tids_and_names(self):
        tr = Trace()

        def work():
            with tr.span("worker_span"):
                pass

        t = threading.Thread(target=work, name="my-worker")
        with tr.span("main_span"):
            t.start()
            t.join()
        names = tr.thread_names()
        assert len(names) == 2
        assert "my-worker" in names.values()
        tids = {e.tid for e in tr.events()}
        assert len(tids) == 2

    def test_to_dict_is_valid_perfetto(self, tmp_path):
        tr = Trace()
        with tr.span("a", n=np.int64(3)):
            with tr.span("b"):
                pass
        doc = tr.to_dict()
        xs = validate_trace_events(doc)
        assert [e["name"] for e in xs] == ["a", "b"]
        # numpy attrs must land as plain JSON scalars
        assert doc["traceEvents"][1]["args"]["n"] == 3
        path = tmp_path / "t.trace.json"
        tr.to_perfetto(path)
        reread = json.loads(path.read_text())
        validate_trace_events(reread)
        assert thread_names(reread) == tr.thread_names()

    def test_export_under_concurrent_late_thread_registration(self):
        """Exporting while new threads register their first span must
        never emit a span whose tid lacks a ``thread_name`` metadata
        event (spans are snapshotted before thread metadata)."""
        tr = Trace()
        stop = threading.Event()
        started = threading.Event()

        def late_joiners():
            # a stream of short-lived threads, each registering a fresh
            # buffer mid-export
            k = 0
            while not stop.is_set():
                def one(k=k):
                    with tr.span(f"late{k}"):
                        pass
                t = threading.Thread(target=one, name=f"late-{k}")
                t.start()
                t.join()
                started.set()
                k += 1

        spawner = threading.Thread(target=late_joiners)
        spawner.start()
        try:
            assert started.wait(5.0)
            for _ in range(50):         # race the exporter against them
                doc = tr.to_dict()
                named = {e["tid"] for e in doc["traceEvents"]
                         if e["ph"] == "M" and e["name"] == "thread_name"}
                span_tids = {e["tid"] for e in doc["traceEvents"]
                             if e["ph"] == "X"}
                assert span_tids <= named, \
                    f"spans on unnamed tids: {span_tids - named}"
        finally:
            stop.set()
            spawner.join()
        validate_trace_events(tr.to_dict())

    def test_validator_rejects_partial_overlap(self):
        bad = {"traceEvents": [
            {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0,
             "dur": 100.0},
            {"name": "b", "ph": "X", "pid": 1, "tid": 1, "ts": 50.0,
             "dur": 100.0}]}
        with pytest.raises(ValueError, match="overlap"):
            validate_trace_events(bad)

    def test_validator_rejects_missing_fields(self):
        with pytest.raises(ValueError, match="traceEvents"):
            validate_trace_events({"nope": []})
        with pytest.raises(ValueError, match="missing"):
            validate_trace_events(
                {"traceEvents": [{"name": "a", "ph": "X", "pid": 1}]})

    def test_spans_overlap_query(self):
        evs = [{"name": "a", "ph": "X", "pid": 1, "tid": 1,
                "ts": 0.0, "dur": 10.0},
               {"name": "b", "ph": "X", "pid": 1, "tid": 2,
                "ts": 5.0, "dur": 10.0},
               {"name": "c", "ph": "X", "pid": 1, "tid": 3,
                "ts": 20.0, "dur": 5.0}]
        assert spans_overlap(evs, "a", "b")
        assert not spans_overlap(evs, "a", "c")
        assert not spans_overlap(evs, "a", "missing")


class TestActivation:
    def test_trace_active_is_thread_local(self):
        tr = Trace()
        seen = {}

        def other():
            seen["other"] = current_trace()

        with trace_active(tr):
            assert current_trace() is tr
            t = threading.Thread(target=other)
            t.start()
            t.join()
        assert current_trace() is None
        assert seen["other"] is None       # never leaks across threads

    def test_set_enabled_kill_switch(self):
        tr = Trace()
        try:
            with trace_active(tr):
                set_enabled(False)
                assert current_trace() is None
                set_enabled(True)
                assert current_trace() is tr
        finally:
            set_enabled(True)

    def test_maybe_span_none_is_noop(self):
        with maybe_span(None, "x") as sp:
            assert sp is None
        tr = Trace()
        with maybe_span(tr, "y", k=1) as sp:
            assert sp.name == "y"
        assert [e.name for e in tr.events()] == ["y"]


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------

class TestMetrics:
    def test_counter_gauge(self):
        c = Counter("n")
        c.inc()
        c.inc(4)
        assert c.value == 5
        g = Gauge("depth")
        g.set(3)
        g.set(1.5)
        assert g.value == 1.5

    def test_histogram_percentiles_bounded_error(self):
        h = Histogram("lat")
        vals = np.linspace(1e-3, 1.0, 1000)
        for v in vals:
            h.observe(float(v))
        snap = h.snapshot()
        assert snap["count"] == 1000
        assert snap["min"] == pytest.approx(1e-3)
        assert snap["max"] == pytest.approx(1.0)
        # log-bucket estimate: relative error bounded by the growth
        # factor (1.6 default)
        for q, ref in (("p50", 0.5), ("p95", 0.95), ("p99", 0.99)):
            assert snap[q] == pytest.approx(ref, rel=0.6)
        assert snap["p50"] <= snap["p95"] <= snap["p99"]

    def test_histogram_empty_and_extremes(self):
        h = Histogram("x")
        assert h.snapshot()["count"] == 0
        assert h.snapshot()["p50"] is None
        h.observe(0.0)          # underflow bucket
        h.observe(1e9)          # overflow bucket
        snap = h.snapshot()
        assert snap["count"] == 2
        assert snap["min"] == 0.0 and snap["max"] == 1e9

    def test_registry_get_or_create_and_kind_check(self):
        reg = MetricsRegistry()
        c = reg.counter("a")
        assert reg.counter("a") is c
        with pytest.raises(TypeError):
            reg.gauge("a")
        snap = reg.snapshot()
        assert snap == {"a": 0}
        snap["a"] = 99          # snapshots are copies, not views
        assert reg.counter("a").value == 0
        reg.reset()
        assert reg.snapshot() == {}

    def test_global_registry_is_shared(self):
        a = global_metrics().counter("test_obs.shared")
        b = global_metrics().counter("test_obs.shared")
        assert a is b


# --------------------------------------------------------------------------
# StageReport (span-backed view; public shape preserved)
# --------------------------------------------------------------------------

class TestStageReport:
    def test_nesting_and_counter_accumulation(self):
        rep = StageReport("run")
        with rep.stage("gradient") as r:
            r.count(n_critical=5)
            r.count(n_critical=2, planes=1)
            with r.stage("comm") as c:
                c.count(comm_total_s=1.0, comm_hidden_s=0.75)
        assert rep.children[0].name == "gradient"
        assert rep.children[0].counters == {"n_critical": 7, "planes": 1}
        assert rep.children[0].children[0].name == "comm"
        assert rep.children[0].seconds > 0

    def test_front_back_comm_split_and_overlap_fraction(self):
        rep = StageReport("run")
        for name in ("order", "gradient", "extract_sort", "d0"):
            with rep.stage(name) as r:
                if name == "gradient":
                    with r.stage("comm") as c:
                        c.count(comm_total_s=2.0, comm_hidden_s=1.0)
                time.sleep(0.001)
        assert rep.front_seconds > 0
        assert rep.back_seconds > 0
        assert rep.comm_seconds > 0
        assert rep.overlap_fraction == pytest.approx(0.5)
        # no comm counters -> None, not a division error
        assert StageReport("empty").overlap_fraction is None

    def test_flat_and_to_dict_round_trip(self):
        rep = StageReport("run")
        with rep.stage("gradient") as r:
            r.count(n_critical=3)
            with r.stage("comm"):
                pass
        flat = rep.flat()
        assert "gradient" in flat and "gradient.comm" in flat
        assert flat["n_critical"] == 3
        d = rep.to_dict()
        # JSON round-trip stable (BENCH_pipeline.json consumers)
        assert json.loads(json.dumps(d)) == d
        assert d["children"][0]["counters"] == {"n_critical": 3}

    def test_traced_report_emits_matching_spans(self):
        tr = Trace()
        with trace_active(tr):
            rep = StageReport("run")       # binds the active trace
        with rep.stage("gradient") as r:
            r.count(n_critical=4)
        evs = tr.events()
        assert [e.name for e in evs] == ["gradient"]
        assert evs[0].args["n_critical"] == 4
        assert evs[0].dur == pytest.approx(rep.children[0].seconds,
                                           rel=0.5, abs=5e-3)

    def test_untraced_report_records_no_spans(self):
        rep = StageReport("run")
        assert rep.trace is None
        with rep.stage("gradient"):
            pass
        assert rep.children[0].seconds >= 0


# --------------------------------------------------------------------------
# pipeline integration: TopoRequest(trace=True)
# --------------------------------------------------------------------------

class TestTracedPipeline:
    def test_in_memory_traced_run_bit_identical(self):
        dims = (6, 6, 6)
        g = Grid.of(*dims)
        f = make_field("random", dims, seed=3)
        pipe = PersistencePipeline(backend="np")
        ref = pipe.run(TopoRequest(field=f, grid=g))
        res = pipe.run(TopoRequest(field=f, grid=g, trace=True))
        assert ref.trace is None
        assert res.trace is not None
        assert same_offdiagonal(res.diagram, ref.diagram), \
            diff_report(res.diagram, ref.diagram)
        for p in range(g.dim + 1):
            assert np.array_equal(res.diagram.essential_orders(p),
                                  ref.diagram.essential_orders(p))
        doc = res.trace.to_dict()
        validate_trace_events(doc)
        names = {e["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "X"}
        for stage in ("order", "gradient", "extract_sort", "d0",
                      "d_top", "d1"):
            assert stage in names, f"missing {stage} span: {names}"

    def test_traced_run_does_not_leak_activation(self):
        dims = (4, 4, 4)
        g = Grid.of(*dims)
        pipe = PersistencePipeline(backend="np")
        pipe.run(TopoRequest(field=make_field("random", dims, seed=0),
                             grid=g, trace=True))
        assert current_trace() is None

    def test_sharded_stream_traced_run(self):
        dims = (8, 8, 16)
        g = Grid.of(*dims)
        f = make_field("wavelet", dims, seed=0)
        src = ArraySource(f.reshape(dims[::-1]))
        pipe = PersistencePipeline(backend="jax")
        ref = pipe.run(TopoRequest(field=f, grid=g))
        res = pipe.run(TopoRequest(field=src, stream=True, chunk_z=4,
                                   n_blocks=2, trace=True))
        assert same_offdiagonal(res.diagram, ref.diagram), \
            diff_report(res.diagram, ref.diagram)
        doc = res.trace.to_dict()
        validate_trace_events(doc)
        tnames = set(thread_names(doc).values())
        assert any(n.startswith("shard_") for n in tnames), tnames
        span_names = {e["name"] for e in doc["traceEvents"]
                      if e.get("ph") == "X"}
        for required in ("chunk_load", "chunk_compute", "halo_publish",
                         "halo_recv"):
            assert required in span_names, span_names


# --------------------------------------------------------------------------
# profiler timeline: every span is also a ``stage.<name>`` annotation
# --------------------------------------------------------------------------

def _profiled(log_dir, fn):
    """Run ``fn`` in a ``jax.profiler`` session; returns its result and
    the ``stage.*`` host events as ``(name, start_ns, end_ns)``."""
    import glob
    import os

    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.end_ns)
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("stage.")]
    return out, spans


def _run_profiled(log_dir, backend, n):
    """One untraced diagram of a random n^3 field under the profiler
    (after an unprofiled run that compiles)."""
    g = Grid.of(n, n, n)
    req = TopoRequest(field=make_field("random", (n, n, n), seed=1),
                      grid=g)
    pipe = PersistencePipeline(backend=backend)
    pipe.run(req)
    return _profiled(log_dir, lambda: pipe.run(req))


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """``jax`` backend: 8^3 runs the burst D1 path, 16^3 the wavefront."""
    return {n: _run_profiled(tmp_path_factory.mktemp(f"prof{n}"), "jax", n)
            for n in (8, 16)}


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(span, outers):
    return any(o[1] <= span[1] and span[2] <= o[2] for o in outers)


class TestProfilerTimeline:
    def test_every_stage_is_on_the_profiler_timeline(self, profiled):
        res, spans = profiled[8]
        names = {s[0] for s in spans}
        for stage in ("order", "gradient", "extract_sort", "d0", "d_top",
                      "d1"):
            assert len(_named(spans, "stage." + stage)) == 1, names
        assert res.trace is None            # no Trace was asked for

    @pytest.mark.parametrize("sub", ["h2d", "kernel", "d2h", "scatter"])
    def test_gradient_sub_spans_nest(self, profiled, sub):
        _, spans = profiled[8]
        inner = _named(spans, "stage.gradient." + sub)
        assert len(inner) == 1
        assert _inside(inner[0], _named(spans, "stage.gradient"))

    @pytest.mark.parametrize("sub", ["critical", "edge_keys", "rank"])
    def test_extract_sub_spans_nest(self, profiled, sub):
        _, spans = profiled[8]
        inner = _named(spans, "stage.extract_sort." + sub)
        assert len(inner) == 1
        assert _inside(inner[0], _named(spans, "stage.extract_sort"))

    def test_d0_rounds_run_under_d0_and_d_top(self, profiled):
        _, spans = profiled[8]
        rounds = _named(spans, "stage.d0_round")
        d0, dtop = _named(spans, "stage.d0"), _named(spans, "stage.d_top")
        in_d0 = [r for r in rounds if _inside(r, d0)]
        in_dtop = [r for r in rounds if _inside(r, dtop)]
        assert in_d0 and in_dtop
        assert len(in_d0) + len(in_dtop) == len(rounds)

    @pytest.mark.parametrize("n", [8, 16])
    def test_d1_round_spans_match_the_counter(self, profiled, n):
        res, spans = profiled[n]
        rounds = _named(spans, "stage.d1_round")
        assert len(rounds) == res.stats["d1_rounds"] > 0
        assert all(_inside(r, _named(spans, "stage.d1")) for r in rounds)

    def test_kill_switch_emits_no_annotation(self, tmp_path):
        set_enabled(False)
        try:
            res, spans = _run_profiled(tmp_path, "jax", 6)
        finally:
            set_enabled(True)
        assert res.diagram is not None
        assert spans == []

    def test_pallas_unpack_nests_in_gradient(self, tmp_path):
        """The fused path builds the fields on the device: its gradient
        stage has no host unpack, and the scatter span holds only the
        per-field views."""
        res, spans = _run_profiled(tmp_path, "pallas", 4)
        grad = _named(spans, "stage.gradient")
        for sub in ("h2d", "kernel", "d2h", "scatter"):
            inner = _named(spans, "stage.gradient." + sub)
            assert len(inner) == 1 and _inside(inner[0], grad), sub
        assert _named(spans, "stage.gradient.unpack") == []
        assert res.stats["device_fields"] == 1


# --------------------------------------------------------------------------
# halo timeout diagnostics (satellite: name waiter/neighbor/plane)
# --------------------------------------------------------------------------

class TestHaloTimeoutDiagnostics:
    def test_timeout_names_waiter_neighbor_and_plane(self):
        ex = HaloExchange(n_shards=3)
        with pytest.raises(HaloExchangeTimeout) as ei:
            ex.recv(2, "first", timeout=0.01, waiter=1, plane_z=7)
        msg = str(ei.value)
        assert "shard 1 waiting" in msg
        assert "from shard 2" in msg
        assert "'first'" in msg
        assert "z=7" in msg

    def test_timeout_without_diagnostics_still_names_neighbor(self):
        ex = HaloExchange(n_shards=2)
        with pytest.raises(HaloExchangeTimeout, match="from shard 0"):
            ex.recv(0, "last", timeout=0.01)


# --------------------------------------------------------------------------
# service + cache telemetry
# --------------------------------------------------------------------------

class TestServiceTelemetry:
    def test_plan_cache_global_counters_move(self):
        from repro.pipeline import PlanCache
        before = global_metrics().snapshot()
        cache = PlanCache()
        # the rows program is the plan's one cached artifact (np has none)
        pipe = PersistencePipeline(backend="jax", plan_cache=cache)
        dims = (4, 4, 4)
        g = Grid.of(*dims)
        req = TopoRequest(field=make_field("random", dims, seed=0), grid=g)
        pipe.run(req)
        pipe.run(req)
        after = global_metrics().snapshot()
        assert after["plan_cache.misses"] >= before.get(
            "plan_cache.misses", 0) + 1
        assert after["plan_cache.hits"] >= before.get(
            "plan_cache.hits", 0) + 1

    def test_topo_service_stats_snapshot_isolated(self):
        from repro.serve import TopoService, stats_payload
        dims = (4, 4, 4)
        g = Grid.of(*dims)
        with TopoService(backend="np", max_batch=2) as svc:
            futs = [svc.submit(TopoRequest(
                field=make_field("random", dims, seed=s), grid=g))
                for s in range(3)]
            for fu in futs:
                fu.result(timeout=60)
            snap = svc.stats()
            blob = stats_payload(svc)
        assert snap["requests"] == 3
        assert snap["metrics"]["request_latency_s"]["count"] == 3
        assert snap["metrics"]["queue_depth"] == 0
        # the snapshot is a copy: mutating it never touches live state
        snap["requests"] = 10**6
        snap["metrics"]["queue_depth"] = -1
        assert svc.stats()["requests"] == 3
        # attribute access on the live stats object still works
        assert svc.stats.errors == 0
        wire = json.loads(blob.decode("utf-8"))
        assert wire["requests"] == 3
        assert "request_latency_s" in wire["metrics"]

    def test_traced_request_counted_by_service(self):
        from repro.serve import TopoService
        dims = (4, 4, 4)
        g = Grid.of(*dims)
        f = make_field("random", dims, seed=0)
        with TopoService(backend="np", max_batch=2) as svc:
            res = svc.submit(TopoRequest(field=f, grid=g,
                                         trace=True)).result(timeout=60)
            assert res.trace is not None
            assert svc.stats()["traced_requests"] == 1
