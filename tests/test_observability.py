"""Observability tests: metrics counters + histograms, prometheus text
exposition, flow-correlated logging, and the cycle tracer (span pairing,
Perfetto-loadable export, per-tid monotonicity)."""

import json
import logging

import pytest

from scheduler_plugins_tpu.api.objects import Container, Node, Pod
from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable
from scheduler_plugins_tpu.state.cluster import Cluster
from scheduler_plugins_tpu.utils import observability as obs
from tools.trace_smoke import validate_trace

gib = 1 << 30


@pytest.fixture(autouse=True)
def _tracer_off():
    yield
    obs.tracer.stop()


class TestMetrics:
    def test_cycle_counters(self):
        obs.metrics.reset()
        c = Cluster()
        c.add_node(Node(name="n0", allocatable={CPU: 1000, MEMORY: 4 * gib, PODS: 10}))
        c.add_pod(Pod(name="ok", creation_ms=1, containers=[Container(requests={CPU: 100})]))
        c.add_pod(Pod(name="huge", creation_ms=2, containers=[Container(requests={CPU: 99_000})]))
        run_cycle(Scheduler(Profile(plugins=[NodeResourcesAllocatable()])), c, now=1000)
        snap = obs.metrics.snapshot()
        assert snap[obs.SCHEDULING_CYCLES] == 1
        assert snap[obs.PODS_BOUND] == 1
        assert snap[obs.PODS_FAILED] == 1

    def test_flow_markers_emitted(self, caplog):
        obs.metrics.reset()
        with caplog.at_level(logging.DEBUG, logger="scheduler_plugins_tpu"):
            with obs.flow("cycle", generation=7, pending=3):
                pass
        text = caplog.text
        assert "FlowBegin" in text and "FlowEnd" in text
        assert "generation=7" in text and "durationMs" in text
        assert "status=ok" in text

    def test_flow_failure_marked_on_flow_end(self, caplog):
        # an exception inside the span must NOT look like a clean FlowEnd
        with caplog.at_level(logging.DEBUG, logger="scheduler_plugins_tpu"):
            with pytest.raises(ValueError):
                with obs.flow("resync", generation=3):
                    raise ValueError("boom")
        end_line = next(
            r.getMessage() for r in caplog.records
            if obs.FLOW_END in r.getMessage()
        )
        assert "status=error" in end_line
        assert "error=ValueError" in end_line
        assert "durationMs" in end_line


class TestHistograms:
    def test_observe_keeps_legacy_summary_keys(self):
        m = obs.Metrics()
        m.observe_ms("scheduler_cycle", 12.4)
        m.observe_ms("scheduler_cycle", 3.2)
        snap = m.snapshot()
        assert snap["scheduler_cycle_ms_total"] == 15
        assert snap["scheduler_cycle_count"] == 2
        assert snap["scheduler_cycle_ms_max"] == 12

    def test_bucket_counts_cumulative_in_text(self):
        m = obs.Metrics()
        for ms in (0.5, 3.0, 30.0, 20_000.0):
            m.observe_ms("lat", ms)
        text = m.prometheus_text()
        samples = dict(
            line.rsplit(" ", 1)
            for line in text.splitlines()
            if line and not line.startswith("#")
        )
        assert samples['lat_bucket{le="1"}'] == "1"
        assert samples['lat_bucket{le="5"}'] == "2"
        assert samples['lat_bucket{le="50"}'] == "3"
        assert samples['lat_bucket{le="10000"}'] == "3"
        assert samples['lat_bucket{le="+Inf"}'] == "4"
        assert samples["lat_count"] == "4"
        assert float(samples["lat_sum"]) == pytest.approx(20_033.5)
        assert "# TYPE lat histogram" in text

    def test_labeled_histograms_and_counters(self):
        m = obs.Metrics()
        m.observe_ms(obs.PLUGIN_EXECUTION, 7.0, plugin="Coscheduling",
                     extension_point="QueueSort")
        m.inc(obs.UNSCHEDULABLE_BY_PLUGIN, plugin="NodeAffinity")
        m.inc(obs.UNSCHEDULABLE_BY_PLUGIN, plugin="NodeAffinity")
        assert m.get(obs.UNSCHEDULABLE_BY_PLUGIN, plugin="NodeAffinity") == 2
        text = m.prometheus_text()
        assert (
            'scheduler_unschedulable_by_plugin_total{plugin="NodeAffinity"} 2'
            in text
        )
        assert (
            'scheduler_plugin_execution_ms_bucket{extension_point='
            '"QueueSort",plugin="Coscheduling",le="10"} 1' in text
        )

    def test_label_values_escaped(self):
        m = obs.Metrics()
        m.inc("weird_total", plugin='a"b\\c')
        assert '{plugin="a\\"b\\\\c"}' in m.prometheus_text()

    def test_counter_type_lines(self):
        m = obs.Metrics()
        m.inc("x_total", 3)
        text = m.prometheus_text()
        assert "# TYPE x_total counter" in text
        assert "x_total 3" in text

    def test_no_duplicate_samples_for_observed_names(self):
        # the legacy <name>_count summary counter and the histogram's
        # _count child are the SAME sample: a scrape must contain each
        # sample key exactly once or prometheus rejects it wholesale
        m = obs.Metrics()
        m.observe_ms("scheduler_cycle", 4.2)
        m.inc("scheduler_pods_bound_total", 2)
        lines = [
            line for line in m.prometheus_text().splitlines()
            if line and not line.startswith("#")
        ]
        keys = [line.rsplit(" ", 1)[0] for line in lines]
        assert len(keys) == len(set(keys)), keys
        assert keys.count("scheduler_cycle_count") == 1
        # ...while the JSON snapshot keeps the legacy key for panels
        assert m.snapshot()["scheduler_cycle_count"] == 1


class TestTracer:
    def test_disabled_tracer_records_nothing(self):
        t = obs.Tracer()
        with t.span("work", tid="row"):
            pass
        t.complete("late", 0, 10)
        assert t.export()["traceEvents"] == []

    def test_span_records_complete_event_with_thread_name(self):
        t = obs.Tracer()
        t.start()
        with t.span("solve", tid="cycle", pods=3):
            pass
        t.stop()
        trace = t.export()
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        ms = [e for e in trace["traceEvents"] if e["ph"] == "M"]
        assert len(xs) == 1 and xs[0]["name"] == "solve"
        assert xs[0]["args"] == {"pods": 3}
        assert xs[0]["ts"] >= 0 and xs[0]["dur"] >= 0
        assert ms[0]["name"] == "thread_name"
        assert ms[0]["args"]["name"] == "cycle"
        assert ms[0]["tid"] == xs[0]["tid"]

    def test_start_clears_previous_run(self):
        t = obs.Tracer()
        t.start()
        with t.span("old"):
            pass
        t.start()
        t.stop()
        assert t.export()["traceEvents"] == []

    def test_export_is_perfetto_valid(self):
        t = obs.Tracer()
        t.start()
        with t.span("outer", tid="cycle"):
            with t.span("inner", tid="cycle"):
                pass
        with t.span("other-row", tid="pipeline/h2d/buf0"):
            pass
        t.stop()
        assert validate_trace(t.export()) == []


class TestCycleTrace:
    def _cluster(self):
        c = Cluster()
        c.add_node(Node(name="n0",
                        allocatable={CPU: 8000, MEMORY: 32 * gib, PODS: 110}))
        c.add_pod(Pod(name="ok", creation_ms=1,
                      containers=[Container(requests={CPU: 100})]))
        c.add_pod(Pod(name="huge", creation_ms=2,
                      containers=[Container(requests={CPU: 99_000})]))
        return c

    def test_traced_cycle_exports_loadable_timeline(self, tmp_path):
        obs.tracer.start()
        run_cycle(Scheduler(Profile(plugins=[NodeResourcesAllocatable()])),
                  self._cluster(), now=1000)
        obs.tracer.stop()
        out = tmp_path / "cycle.json"
        obs.tracer.write(str(out))
        trace = json.loads(out.read_text())
        assert validate_trace(trace) == []
        events = trace["traceEvents"]
        # only Perfetto-loadable chrome-trace phases
        assert {e["ph"] for e in events} <= {"X", "B", "E", "M"}
        names = {e["name"] for e in events if e["ph"] == "X"}
        # extension points QueueSort -> Bind appear as spans
        for expected in ("QueueSort/PrioritySort",
                         "Prepare/NodeResourcesAllocatable",
                         "Solve/tpu-scheduler", "Bind", "Attribution"):
            assert expected in names, (expected, sorted(names))
        # per-tid timestamps are monotonic in record order
        by_tid = {}
        for e in events:
            if e["ph"] == "X":
                by_tid.setdefault(e["tid"], []).append(e["ts"] + e["dur"])
        for ends in by_tid.values():
            assert all(b >= a for a, b in zip(ends, ends[1:]))

    def test_untraced_cycle_is_clean_and_silent(self):
        # tracing off (the default): the same cycle runs without touching
        # the tracer event buffer (stale events from earlier traced runs
        # stay untouched until the next start(clear=True))
        before = len(obs.tracer.export()["traceEvents"])
        run_cycle(Scheduler(Profile(plugins=[NodeResourcesAllocatable()])),
                  self._cluster(), now=1000)
        assert len(obs.tracer.export()["traceEvents"]) == before


class TestServedLoopNames:
    """ISSUE 24: the spans and registry names that account for the served
    loop's host time. The daemon-level spans (`Loop/sleep`, `TickTail/*`)
    are exercised end to end in tests/test_daemon.py."""

    _cluster = TestCycleTrace._cluster

    def _traced_cycle(self):
        obs.tracer.start()
        report = run_cycle(
            Scheduler(Profile(plugins=[NodeResourcesAllocatable()])),
            self._cluster(), now=1000,
        )
        obs.tracer.stop()
        trace = obs.tracer.export()
        rows = {e["tid"]: e["args"]["name"]
                for e in trace["traceEvents"] if e["ph"] == "M"}
        return report, trace, rows

    @pytest.mark.parametrize("name", [
        obs.FEED_EVENTS, obs.FEED_EVENT_NS, obs.HEALTHZ_HANDLER_MS,
        obs.TICKS, obs.TICK_WAKEUPS, obs.TICK_LOCKED,
        obs.FEED_QUIET_MS, obs.FEED_STALLS, obs.TICK_HOLD,
    ])
    def test_help_covers_the_new_names(self, name):
        assert name.startswith("scheduler_") and obs.HELP[name]
        m = obs.Metrics()
        if name in (obs.HEALTHZ_HANDLER_MS, obs.TICK_LOCKED,
                    obs.FEED_QUIET_MS, obs.TICK_HOLD):
            m.observe_ms(name, 1.5)
            kind = "histogram"
        else:
            m.inc(name, 3)
            kind = "counter"
        text = m.prometheus_text()
        assert f"# HELP {name} {obs.HELP[name]}" in text
        assert f"# TYPE {name} {kind}" in text

    @pytest.mark.parametrize("name", [
        "scheduler_flightrec_cycles_total", "scheduler_lane_commit_ms",
    ])
    def test_names_nobody_read_are_gone(self, name):
        # ISSUE 36: registered and incremented, named by no document,
        # test or benchmark entry; `LaneStats.fence_ms` and the recorder's
        # ring say the same
        assert name not in obs.HELP
        assert name not in {
            v for k, v in vars(obs).items()
            if k.isupper() and isinstance(v, str)
        }

    def test_a_paired_span_is_a_b_and_an_e_and_no_x(self):
        # what the feed's rows hold: readers of the X events take those for
        # one thread's, and a feed thread's spans overlap the tick's
        obs.tracer.start()
        obs.tracer.complete("Cycle", 1_000, 9_000, tid="cycle")
        obs.tracer.complete("Feed/segment", 2_000, 5_000, tid="feed/0",
                            args={"events": 3}, paired=True)
        obs.tracer.complete("Feed/segment", 7_000, -5, tid="feed/0",
                            paired=True)
        obs.tracer.stop()
        trace = obs.tracer.export()
        assert validate_trace(trace) == []
        assert [e["name"] for e in trace["traceEvents"] if e["ph"] == "X"] == [
            "Cycle"]
        pairs = [e for e in trace["traceEvents"] if e["ph"] in ("B", "E")]
        assert [(e["ph"], e["ts"]) for e in pairs] == [
            ("B", 2.0), ("E", 7.0), ("B", 7.0), ("E", 7.0)]
        assert pairs[0]["args"] == {"events": 3}
        assert all("args" not in e for e in pairs[1:])
        assert len({e["tid"] for e in pairs}) == 1
        assert all("dur" not in e for e in pairs)

    def test_a_paired_span_costs_nothing_while_the_tracer_is_off(self):
        obs.tracer.start()
        obs.tracer.stop()
        obs.tracer.complete("Feed/segment", 0, 10, tid="feed/0", paired=True)
        assert [e for e in obs.tracer.export()["traceEvents"]] == []

    def test_origin_ns_turns_a_callers_stamp_into_the_tracers(self):
        import time

        obs.tracer.start()
        stamp = time.perf_counter_ns()
        assert 0 <= stamp - obs.tracer.origin_ns <= obs.tracer.now_ns()
        obs.tracer.stop()

    @pytest.mark.parametrize("name", ["Cycle", "PendingScan", "Finalize"])
    def test_traced_cycle_records_the_span_on_the_cycle_row(self, name):
        _report, trace, rows = self._traced_cycle()
        assert validate_trace(trace) == []
        spans = [e for e in trace["traceEvents"]
                 if e["ph"] == "X" and e["name"] == name]
        assert len(spans) == 1 and rows[spans[0]["tid"]] == "cycle"

    def test_cycle_span_covers_the_cycle_and_says_what_it_did(self):
        before = obs.metrics.get(obs.SCHEDULING_CYCLES)
        report, trace, _rows = self._traced_cycle()
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        cycle = next(e for e in xs if e["name"] == "Cycle")
        assert cycle["args"] == {
            "cycle": before + 1, "pending": 2, "bound": len(report.bound),
        }
        # every other span of the cycle lies inside it
        for e in xs:
            assert cycle["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= cycle["ts"] + cycle["dur"]
        scan = next(e for e in xs if e["name"] == "PendingScan")
        assert scan["args"] == {"pods": 2}

    def test_cycle_span_is_recorded_when_the_cycle_raises(self):
        class Boom(Exception):
            pass

        class Exploding(NodeResourcesAllocatable):
            def configure_cluster(self, cluster):
                raise Boom()

        obs.tracer.start()
        with pytest.raises(Boom):
            run_cycle(Scheduler(Profile(plugins=[Exploding()])),
                      self._cluster(), now=1000)
        obs.tracer.stop()
        names = [e["name"] for e in obs.tracer.export()["traceEvents"]
                 if e["ph"] == "X"]
        assert names == ["Cycle"]

    def test_tracer_off_reads_no_clock_and_records_nothing(self, monkeypatch):
        def no_clock():
            raise AssertionError("a disabled tracer read its clock")

        t = obs.Tracer()
        monkeypatch.setattr(t, "now_ns", no_clock)
        with t.span("work", tid="daemon", pods=1):
            pass
        assert t.export()["traceEvents"] == []
        # the whole cycle, `Cycle` span included, with the tracer off
        monkeypatch.setattr(obs.tracer, "now_ns", no_clock)
        before = len(obs.tracer.export()["traceEvents"])
        report = run_cycle(
            Scheduler(Profile(plugins=[NodeResourcesAllocatable()])),
            self._cluster(), now=1000,
        )
        assert report.bound
        assert len(obs.tracer.export()["traceEvents"]) == before

    def test_export_carries_its_origin_on_the_monotonic_clock(self):
        import time

        t = obs.Tracer()
        lo = time.monotonic_ns()
        t.start()
        hi = time.monotonic_ns()
        with t.span("work"):
            pass
        t.stop()
        origin = t.export()["otherData"]["origin_monotonic_ns"]
        # `ts` 0 of the export: CLOCK_MONOTONIC at `start()`
        assert lo <= origin <= hi
        assert json.loads(json.dumps(t.export()))["otherData"] == {
            "origin_monotonic_ns": origin
        }


class TestServeTraceRows:
    """PR 6 gap closure: ServeEngine.refresh stages appear as spans on
    the "serve" row of a traced serve-mode cycle, and the trace stays
    Perfetto-valid with the new rows."""

    def _cluster(self):
        c = Cluster()
        for i in range(4):
            c.add_node(Node(
                name=f"n{i}",
                allocatable={CPU: 8000, MEMORY: 32 * gib, PODS: 110},
            ))
        for p in range(6):
            c.add_pod(Pod(name=f"p{p}", creation_ms=p,
                          containers=[Container(requests={CPU: 100})]))
        return c

    def test_serve_refresh_stage_spans(self):
        from scheduler_plugins_tpu.serving import ServeEngine

        cluster = self._cluster()
        engine = ServeEngine().attach(cluster)
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        obs.tracer.start()
        try:
            # first serve cycle re-bases; a churned second cycle applies
            # deltas and assembles from the resident columns
            run_cycle(sched, cluster, now=1000, serve=engine)
            cluster.add_pod(Pod(
                name="late", creation_ms=99,
                containers=[Container(requests={CPU: 100})],
            ))
            run_cycle(sched, cluster, now=2000, serve=engine)
        finally:
            obs.tracer.stop()
        trace = obs.tracer.export()
        assert validate_trace(trace) == []
        rows = {
            e["args"]["name"] for e in trace["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "thread_name"
        }
        assert "serve" in rows
        names = {e["name"] for e in trace["traceEvents"]
                 if e.get("ph") == "X"}
        for expected in ("ServeRefresh/drain", "ServeRefresh/classify",
                         "ServeRefresh/rebase", "ServeRefresh/apply",
                         "ServeRefresh/assemble"):
            assert expected in names, (expected, sorted(names))

    def test_untraced_serve_cycle_records_nothing(self):
        from scheduler_plugins_tpu.serving import ServeEngine

        cluster = self._cluster()
        engine = ServeEngine().attach(cluster)
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        before = len(obs.tracer.export()["traceEvents"])
        run_cycle(sched, cluster, now=1000, serve=engine)
        assert len(obs.tracer.export()["traceEvents"]) == before


class TestShardWaveTraceRows:
    """PR 7 gap closure: a traced sharded-wave solve emits per-chunk rows
    (waves + wave_occupancy) and the static collective census on the
    "shard_wave" row, and the merged trace stays Perfetto-valid."""

    def test_shard_wave_rows_and_census(self):
        import jax.numpy as jnp

        from scheduler_plugins_tpu.models import allocatable_scenario
        from scheduler_plugins_tpu.parallel.mesh import make_node_mesh
        from scheduler_plugins_tpu.parallel.solver import sharded_wave_solve

        cluster = allocatable_scenario(n_nodes=64, n_pods=256)
        pending = sorted(cluster.pending_pods(), key=lambda p: p.creation_ms)
        snap, meta = cluster.snapshot(pending, now_ms=0)
        weights = jnp.asarray(
            meta.index.encode({CPU: 1 << 20, MEMORY: 1}), jnp.int64
        )
        mesh = make_node_mesh(8)
        obs.tracer.start()
        try:
            sharded_wave_solve(
                snap, mesh, weights, chunk=128, collect_stats=True
            )
        finally:
            obs.tracer.stop()
        trace = obs.tracer.export()
        assert validate_trace(trace) == []
        rows = {
            e["args"]["name"] for e in trace["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "thread_name"
        }
        assert "shard_wave" in rows
        spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        chunks = [e for e in spans if e["name"].startswith("chunk[")]
        assert len(chunks) == 2  # 256 pods / 128 chunk
        for e in chunks:
            assert e["args"]["waves"] >= 1
            assert sum(e["args"]["wave_occupancy"]) > 0
        census = [e for e in spans if e["name"] == "census"]
        assert len(census) == 1
        args = census[0]["args"]
        assert args["shards"] == 8
        # the ring election never gathers the node axis (GL009's
        # trace-level twin)
        for prim in ("all_gather", "all_gather_invariant", "all_to_all"):
            assert args.get(prim, 0) == 0
        assert sum(
            v for k, v in args.items()
            if k in ("psum", "pmin", "pmax", "ppermute")
        ) > 0
