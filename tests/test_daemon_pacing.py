"""The daemon loop's pacing (ISSUE 25): a tick starts when a pod enters the
pending set, no sooner than `DEMAND_TICK_SPACING` tick durations after the
last one started, and on `--cycle-interval-s` otherwise. The daemon is built
in-process (no health server, no ledger) and its loop runs on a helper
thread; `signal.signal` is stubbed for the test, because a handler can
only be installed from the main thread."""

import json
import threading
import time
import types

import pytest

from scheduler_plugins_tpu import __main__ as daemon_main
from scheduler_plugins_tpu.__main__ import DEMAND_TICK_SPACING, Daemon
from scheduler_plugins_tpu.bridge.feed import apply_event
from scheduler_plugins_tpu.utils import observability as obs

PROFILE = {
    "plugins": ["NodeResourcesAllocatable"],
    "pluginConfig": [{"name": "NodeResourcesAllocatable",
                      "args": {"mode": "Least"}}],
}


def _counters() -> dict:
    return {
        "ticks": obs.metrics.get(obs.TICKS),
        "demand": obs.metrics.get(obs.TICK_WAKEUPS, reason="demand"),
        "interval": obs.metrics.get(obs.TICK_WAKEUPS, reason="interval"),
    }


def _since(before: dict) -> dict:
    return {k: v - before[k] for k, v in _counters().items()}


def _wait(predicate, timeout=30.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.002)
    return False


class Loop:
    """One daemon, its loop on a helper thread, and what the tests do to
    its store."""

    def __init__(self, tmp_path, interval_s: float, *flags):
        profile = tmp_path / "profile.json"
        profile.write_text(json.dumps(PROFILE))
        self.daemon = Daemon(daemon_main.parse_args([
            "--profile", str(profile), "--health-port", "-1", "--no-ledger",
            "--cycle-interval-s", str(interval_s), *flags,
        ]))
        self.thread = None
        self.serial = 0
        self.starts: list = []

    def apply(self, event: dict) -> None:
        with self.daemon.feed.locked():
            ack = apply_event(self.daemon.cluster, event)
        assert ack["ok"], ack

    def add_node(self, cpu: int = 8000) -> None:
        self.apply({"op": "upsert_node", "name": "n0",
                    "allocatable": {"cpu": cpu, "memory": 32 << 30,
                                    "pods": 110}})

    def add_pod(self, cpu: int = 100) -> str:
        self.serial += 1
        name = f"p{self.serial}"
        self.apply({"op": "upsert_pod", "name": name,
                    "requests": {"cpu": cpu, "memory": 1 << 20}})
        return f"default/{name}"

    def fake_tick(self, duration_s: float) -> None:
        """Replace the tick by one that takes `duration_s`, all of it
        counted as under the feed lock, and leaves the store alone;
        `starts` holds when each began."""
        def tick():
            self.starts.append(time.monotonic())
            time.sleep(duration_s)
            self.daemon.tick_locked_s = time.monotonic() - self.starts[-1]

        self.daemon.tick = tick  # `run` looks `tick` up on the instance

    def start(self) -> None:
        self.thread = threading.Thread(
            target=self.daemon.run, daemon=True, name="pacing-loop",
        )
        self.thread.start()

    def close(self) -> None:
        self.daemon.stop_event.set()
        if self.thread is None:
            self.daemon.feed.stop()
            return
        self.thread.join(timeout=30)
        assert not self.thread.is_alive()


@pytest.fixture
def loop(tmp_path, monkeypatch):
    monkeypatch.setattr(daemon_main.signal, "signal", lambda *_: None)
    made = []

    def make(interval_s: float, *flags) -> Loop:
        made.append(Loop(tmp_path, interval_s, *flags))
        return made[-1]

    yield make
    for one in made:
        one.close()


@pytest.fixture
def arrivals():
    """Call it with a `Loop`: a pod enters its store every 2 ms until the
    test ends."""
    over = threading.Event()
    threads = []

    def feed(loop: Loop) -> None:
        def run():
            while not over.wait(0.002):
                loop.add_pod()

        threads.append(threading.Thread(
            target=run, daemon=True, name="pacing-arrivals",
        ))
        threads[-1].start()

    yield feed
    over.set()
    for t in threads:
        t.join(timeout=10)


def test_serial_daemon_reads_the_pending_index(loop):
    d = loop(1.0).daemon
    assert d.cluster._pending_idx is not None
    assert d.cluster.on_pending_gain == d._pod_arrived
    assert not d._pod_waiting
    d._pod_arrived()
    assert d._pod_waiting


def test_a_pod_reaching_an_idle_daemon_is_bound_within_the_second(loop):
    lp = loop(5.0)
    d = lp.daemon
    lp.add_node()
    warm = lp.add_pod()
    d.tick()  # compiles the one-pod program outside the timed stretch
    assert d.cluster.pods[warm].node_name == "n0"
    before = _counters()
    lp.start()
    assert _wait(lambda: d.ticks >= 1)  # the loop's first tick, idle
    t0 = time.monotonic()
    uid = lp.add_pod()
    assert _wait(lambda: d.cluster.pods[uid].node_name is not None, 4.0)
    assert time.monotonic() - t0 < 1.0  # a fifth of the interval
    assert _since(before)["demand"] >= 1


@pytest.mark.parametrize("tick_s, interval_s, reason", [
    (0.02, 2.0, "demand"),     # 6 d = 0.12 s: ahead of the interval
    (0.05, 0.2, "interval"),   # d >= interval / 6: the interval's cadence
])
def test_tick_starts_keep_the_share_or_the_interval(
    loop, arrivals, tick_s, interval_s, reason
):
    ticks = 5
    lp = loop(interval_s, "--max-cycles", str(ticks))
    lp.fake_tick(tick_s)
    before = _counters()
    arrivals(lp)
    lp.start()
    lp.thread.join(timeout=30)
    assert len(lp.starts) == ticks
    gaps = [b - a for a, b in zip(lp.starts, lp.starts[1:])]
    took = _since(before)
    if reason == "demand":
        # never sooner than six nominal durations (the loop measures the
        # real one, which is no shorter), and well ahead of the interval
        assert min(gaps) >= DEMAND_TICK_SPACING * tick_s - 0.001, gaps
        assert max(gaps) < interval_s / 2, gaps
        assert took == {"ticks": ticks, "demand": ticks - 1, "interval": 1}
    else:
        # (a start is stamped inside the tick, a little after the loop's)
        assert min(gaps) >= 0.8 * interval_s, gaps
        assert took == {"ticks": ticks, "demand": 0, "interval": ticks}


def test_a_pod_left_unschedulable_does_not_rearm_the_loop(loop):
    interval_s, intervals = 0.2, 5
    lp = loop(interval_s)
    d = lp.daemon
    lp.add_node(cpu=1000)
    uid = lp.add_pod(cpu=4000)  # fits nowhere
    d.tick()  # compiles; the pod is left pending, in backoff
    assert d.cluster.pods[uid].node_name is None
    assert d.cluster.pending_count() == 1
    d._pod_waiting = False
    d._doorbell.reset()
    before, t0 = _counters(), time.monotonic()
    lp.start()
    time.sleep(intervals * interval_s)
    took, elapsed = _since(before), time.monotonic() - t0
    assert d.cluster.pods[uid].node_name is None
    assert took["demand"] == 0
    assert 2 <= took["ticks"] <= elapsed / interval_s + 1


def test_a_standby_ticks_on_the_interval_only(loop, arrivals):
    interval_s, ticks = 0.1, 4
    lp = loop(interval_s, "--max-cycles", str(ticks))
    d = lp.daemon
    d.elector = types.SimpleNamespace(
        is_leader=False, observed_holder="other", release=lambda: None
    )
    inner = d.tick

    def tick():
        lp.starts.append(time.monotonic())
        return inner()

    d.tick = tick
    before = _counters()
    arrivals(lp)
    lp.start()
    lp.thread.join(timeout=30)
    gaps = [b - a for a, b in zip(lp.starts, lp.starts[1:])]
    assert len(lp.starts) == ticks and min(gaps) >= 0.8 * interval_s, gaps
    assert _since(before) == {"ticks": ticks, "demand": 0, "interval": ticks}
    assert d.cycles == 0 and d.last_pending > 0  # it counted, not scheduled


@pytest.mark.parametrize("last_tick_s", [0.001, 10.0])
def test_stop_event_ends_the_wait_within_50_ms(loop, last_tick_s):
    # 0.001: stopped while waiting for a pod; 10.0: while waiting out the
    # share (6 d is past the interval, so the interval's end)
    d = loop(5.0).daemon
    done = []

    def wait():
        d._wait_for_tick(time.monotonic(), last_tick_s)
        done.append(time.monotonic())

    waiter = threading.Thread(
        target=wait, daemon=True, name="pacing-waiter",
    )
    waiter.start()
    time.sleep(0.1)
    assert not done
    t0 = time.monotonic()
    d.stop_event.set()
    waiter.join(timeout=5)
    assert done and done[0] - t0 < 0.05


def test_wakeup_counters_add_up_to_the_ticks(loop, arrivals):
    ticks = 12
    lp = loop(0.05, "--max-cycles", str(ticks))
    lp.fake_tick(0.002)
    before = _counters()
    arrivals(lp)
    lp.start()
    lp.thread.join(timeout=30)
    took = _since(before)
    assert took["ticks"] == lp.daemon.ticks == ticks
    assert took["demand"] + took["interval"] == ticks
    assert took["demand"] >= 1 and took["interval"] >= 1


def test_the_wait_is_one_span_that_says_what_ended_it(loop):
    d = loop(0.05).daemon
    obs.tracer.start()
    try:
        assert d._wait_for_tick(time.monotonic(), 0.001) == "interval"
        d._pod_arrived()
        assert d._wait_for_tick(time.monotonic(), 0.001) == "demand"
    finally:
        obs.tracer.stop()
    sleeps = [e for e in obs.tracer.export()["traceEvents"]
              if e.get("name") == "Loop/sleep"]
    assert [e["args"]["woke"] for e in sleeps] == ["interval", "demand"]
    # ISSUE 36: and what the decision rested on
    for e in sleeps:
        assert set(e["args"]) == {
            "woke", "locked_ms", "since_start_ms", "held_ms",
        }
        assert e["args"]["locked_ms"] == pytest.approx(1.0)
    idle, rung = (e["args"] for e in sleeps)
    assert idle["held_ms"] == 0.0 and idle["since_start_ms"] >= 50.0
    # the ring came before the wait began: the pod waited all of it
    assert rung["held_ms"] >= rung["since_start_ms"] >= 6 * 1.0 - 1e-6


# --- ISSUE 31: `Finalize` outside the feed lock and the spacing clock ------


def _tick_on_a_thread(daemon) -> threading.Thread:
    thread = threading.Thread(
        target=daemon.tick, daemon=True, name="pacing-tick",
    )
    thread.start()
    return thread


def test_the_feed_is_served_during_finalize_and_not_during_bind(
    loop, monkeypatch
):
    from scheduler_plugins_tpu.bridge.feed import FeedClient
    from scheduler_plugins_tpu.framework import cycle as cyc

    lp = loop(5.0, "--serve")
    d = lp.daemon
    lp.add_node()
    lp.add_pod()
    d.tick()  # compiles outside the held stretch
    held = {name: (threading.Event(), threading.Event())
            for name in ("bind", "finalize")}

    def holding(name, inner):
        def stage(*args, **kwargs):
            reached, go_on = held[name]
            reached.set()
            assert go_on.wait(60), f"the test never let {name} go on"
            return inner(*args, **kwargs)
        return stage

    monkeypatch.setattr(
        cyc, "_bind_decisions", holding("bind", cyc._bind_decisions)
    )
    monkeypatch.setattr(
        cyc, "_observe_quality", holding("finalize", cyc._observe_quality)
    )
    client = FeedClient(*d.feed.address)
    acks = []

    def send(name):
        acks.append(client.send({
            "op": "upsert_pod", "name": name,
            "requests": {"cpu": 100, "memory": 1 << 20},
        }))

    try:
        uid = lp.add_pod()
        tick = _tick_on_a_thread(d)
        assert held["bind"][0].wait(30), "the tick never reached Bind"
        during_bind = threading.Thread(
            target=send, args=("during-bind",), daemon=True,
            name="pacing-feed",
        )
        during_bind.start()
        during_bind.join(timeout=0.3)
        assert during_bind.is_alive() and not acks  # shut out: the lock
        held["bind"][1].set()
        assert held["finalize"][0].wait(30), "the tick never finalized"
        # inside Finalize the cycle's binds are in the store, its tail has
        # run, and the lock is free: the waiting event and a new one are
        # both acknowledged while the epilogue stands still
        during_bind.join(timeout=30)
        send("during-finalize")
        assert [a["ok"] for a in acks] == [True, True]
        assert tick.is_alive() and d.cluster.pods[uid].node_name == "n0"
        assert not d.feed.lock.locked()
        assert {"default/during-bind", "default/during-finalize"} <= set(
            d.cluster.pods
        )
    finally:
        for _reached, go_on in held.values():
            go_on.set()
        client.close()
    tick.join(timeout=30)
    assert not tick.is_alive() and d.last_quality is not None


class FakeClock:
    """`time` for `__main__`, moved by hand: `monotonic()` reads it, the
    doorbell's wait advances it by its whole timeout."""

    def __init__(self):
        self.now = 1000.0

    def monotonic(self) -> float:
        return self.now

    def time(self) -> float:
        return 1.7e9 + self.now


def test_demand_spacing_counts_the_locked_time_not_the_finalize(
    loop, monkeypatch
):
    lp = loop(1.0, "--max-cycles", "2")
    d = lp.daemon
    clock = FakeClock()
    monkeypatch.setattr(daemon_main, "time", clock)
    starts = []

    def cycle_store_stages(*_args, **_kwargs):
        starts.append(clock.now)
        d._pod_arrived()  # a pod is waiting whenever the loop looks
        clock.now += 0.010  # the lock is held 10 ms ...
        return types.SimpleNamespace(report=types.SimpleNamespace(
            bound={}, failed=[], quality=None,
        ))

    def cycle_report_stages(ctx, _tuner):
        clock.now += 0.010  # ... and Finalize takes 10 more, unlocked
        return ctx.report

    def wait(timeout):
        clock.now += max(timeout, 0.0)

    monkeypatch.setattr(d.feed, "cycle_store_stages", cycle_store_stages)
    monkeypatch.setattr(daemon_main, "cycle_report_stages", cycle_report_stages)
    monkeypatch.setattr(d._doorbell, "wait", wait)
    locked = obs.metrics.scoped()
    before = _counters()
    d.run()
    assert _since(before) == {"ticks": 2, "demand": 1, "interval": 1}
    # 6 x the 10 ms under the lock, not 6 x the 20 ms the tick took
    assert starts[1] - starts[0] == pytest.approx(
        DEMAND_TICK_SPACING * 0.010, abs=1e-9
    )
    assert d.tick_locked_s == pytest.approx(0.010, abs=1e-9)
    assert locked.hist_count(obs.TICK_LOCKED) == 2
    assert locked.hist_sum(obs.TICK_LOCKED) == pytest.approx(20.0, abs=1e-6)
    assert locked.hist_sum("scheduler_cycle") == pytest.approx(20.0, abs=1e-6)


def test_a_ticks_finalize_comes_before_the_next_ticks_refresh(
    loop, monkeypatch
):
    from scheduler_plugins_tpu.framework import cycle as cyc

    lp = loop(5.0, "--serve")
    d = lp.daemon
    lp.add_node()
    order, contexts = [], []
    refresh, finalize = d.engine.refresh, cyc._cycle_finalize

    def logged_refresh(*args, **kwargs):
        order.append(("refresh", d.engine.generation))
        return refresh(*args, **kwargs)

    def logged_finalize(ctx, **kwargs):
        order.append(("finalize", d.engine.generation))
        contexts.append(ctx)
        assert not d.feed.lock.locked()
        return finalize(ctx, **kwargs)

    monkeypatch.setattr(d.engine, "refresh", logged_refresh)
    monkeypatch.setattr(cyc, "_cycle_finalize", logged_finalize)
    for _ in range(2):
        uid = lp.add_pod()
        report = d.tick()
        assert report.bound == {uid: "n0"} and report.quality is not None
        assert d.last_quality == report.quality
    first, second = contexts
    assert first.served and second.served
    assert [what for what, _gen in order] == [
        "refresh", "finalize", "refresh", "finalize",
    ]
    # each Finalize found the engine where its own cycle's refresh left
    # it; the second refresh, which donates the columns the first cycle
    # solved on, came after the first Finalize
    assert order[1][1] == first.serve_generation
    assert order[3][1] == second.serve_generation > first.serve_generation
    # asked again now, the first cycle's Finalize will not read a donated
    # buffer: it leaves the quality out (and the daemon alive: PR 34)
    first.report.quality = None
    assert finalize(first) is None and first.report.quality is None
    assert finalize(second) is None


def test_a_traced_tick_finalizes_after_its_cycle_span_and_its_tail(loop):
    from tools.trace_smoke import validate_trace

    lp = loop(5.0, "--serve")
    d = lp.daemon
    lp.add_node()
    lp.add_pod()
    obs.tracer.start()
    try:
        d.tick()
    finally:
        obs.tracer.stop()
    trace = obs.tracer.export()
    assert validate_trace(trace) == []
    rows = {e["tid"]: e["args"]["name"]
            for e in trace["traceEvents"] if e["ph"] == "M"}
    spans = {}
    for e in trace["traceEvents"]:
        if e["ph"] == "X":
            assert e["name"] not in spans or e["name"] == "PendingScan"
            spans[e["name"]] = (e["ts"], e["ts"] + e["dur"], rows[e["tid"]])
    cycle, relock, reconcile, finalize, memory = (
        spans[name] for name in (
            "Cycle", "TickTail/relock", "TickTail/reconcile", "Finalize",
            "TickTail/memory",
        )
    )
    # a sibling after `Cycle`, not its last child: same row, no overlap
    assert cycle[2] == finalize[2] == "cycle"
    assert cycle[1] <= relock[0] <= relock[1] <= reconcile[0]
    assert reconcile[1] <= finalize[0] <= finalize[1] <= memory[0]
    assert spans["Bind"][1] <= cycle[1]


def test_arrivals_during_unlocked_finalizes_leave_resident_state_exact(
    loop, arrivals
):
    # stress: feed threads apply events while the loop's thread is inside
    # its unlocked `Finalize`, at a switch interval that interleaves them
    # often; a lost delta or a finalize on the wrong columns would show as
    # a divergence of resident state or an overcommitted node
    import sys

    ticks = 60
    lp = loop(0.05, "--serve", "--max-cycles", str(ticks))
    d = lp.daemon
    lp.add_node(cpu=10 ** 9)
    lp.add_pod()
    d.tick()  # compiles before the loop starts
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        arrivals(lp)
        lp.start()
        lp.thread.join(timeout=120)
    finally:
        sys.setswitchinterval(switch)
    assert not lp.thread.is_alive() and d.ticks == ticks
    assert d.cycles >= ticks // 2 and d.last_quality is not None
    with d.feed.locked():
        bound = [p for p in d.cluster.pods.values() if p.node_name]
        assert len(bound) == d.bound_total > ticks
        assert {p.node_name for p in bound} == {"n0"}
        assert d.engine.refresh(d.cluster, [], now_ms=0) is not None
        assert d.engine.verify(d.cluster) is None
        assert d.engine.rebases == 1
