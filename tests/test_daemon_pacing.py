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
        """Replace the tick by one that takes `duration_s` and leaves the
        store alone; `starts` holds when each began."""
        def tick():
            self.starts.append(time.monotonic())
            time.sleep(duration_s)

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
    assert [e["args"] for e in sleeps] == [
        {"woke": "interval"}, {"woke": "demand"},
    ]
