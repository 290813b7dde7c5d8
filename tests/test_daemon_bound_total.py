"""`/healthz`'s `bound_total` is the store's own count of its binds (ISSUE 28,
step 3).

The tick summed `len(report.bound)` in its tail, after the cycle had given
the feed lock up, so a client that saw the last pod of a batch bound on the
feed (a `sync`'s `pending`, a pod's `node_name` under the lock) and then
read `/healthz` could read a count a whole batch behind the store. The
benchmark's closed loop takes its base and its final count exactly so:
PR 27's runs ended `/healthz bound 46899 of 44847 arrivals` with `ledger
bound 43854` and `993 pending pods were deleted` (the base was read a warm
wave of 3,056 pods short, the loop paced its deletes on a count that far
ahead of the store), and `bound 20068 of 20132 arrivals` (the final read
came one gang of 64 short). Here a tick is held between its cycle and its
tail, with gangs in the batch: the daemon's count, the health surface and
the ledger's `pods_bound` have to be equal at that moment, and across a
gang that is rejected in one cycle and bound in the next.
"""

import json
import threading
import urllib.request

import pytest

from scheduler_plugins_tpu import __main__ as daemon_main
from scheduler_plugins_tpu.__main__ import Daemon
from scheduler_plugins_tpu.bridge.feed import apply_event
from scheduler_plugins_tpu.obs import ledger as podledger
from tests.test_daemon_pacing import _wait

PROFILE = {
    "plugins": ["NodeResourcesAllocatable", "Coscheduling",
                "CapacityScheduling"],
    "pluginConfig": [
        {"name": "NodeResourcesAllocatable", "args": {"mode": "Least"}},
        {"name": "Coscheduling", "args": {"permitWaitingTimeSeconds": 60,
                                          "podGroupBackoffSeconds": 0}},
    ],
}
GPU = "nvidia.com/gpu"
LABEL = "scheduling.x-k8s.io/pod-group"


class HeldDaemon:
    """A served daemon on a helper thread whose tick stops between its
    cycle (the binds are in the store, the feed lock is free) and its
    tail, until the test lets it go on."""

    def __init__(self, tmp_path):
        profile = tmp_path / "profile.json"
        profile.write_text(json.dumps(PROFILE))
        self.ledger = podledger.Ledger()
        self._ledger_before = podledger.LEDGER
        podledger.use(self.ledger)
        self.daemon = Daemon(daemon_main.parse_args([
            "--profile", str(profile), "--health-port", "0", "--serve",
            "--cycle-interval-s", "0.05",
        ]))
        self.cycle_done = threading.Event()
        self.go_on = threading.Event()
        cycle = self.daemon.feed.cycle_store_stages

        def held_cycle(*args, **kwargs):
            ctx = cycle(*args, **kwargs)
            if ctx.report.bound or ctx.report.failed:
                self.cycle_done.set()
                assert self.go_on.wait(60), "the test never let the tick go"
                self.go_on.clear()
            return ctx

        self.daemon.feed.cycle_store_stages = held_cycle
        self.thread = threading.Thread(
            target=self.daemon.run, daemon=True, name="pacing-loop",
        )

    def apply(self, *events) -> None:
        with self.daemon.feed.locked():
            for event in events:
                assert apply_event(self.daemon.cluster, event)["ok"], event

    def pod(self, name, gang=None) -> dict:
        event = {"op": "upsert_pod", "name": name, "namespace": "team",
                 "creation_ms": 1,
                 "requests": {"cpu": 1000, "memory": 1 << 30, GPU: 1}}
        if gang:
            event["labels"] = {LABEL: gang}
        return event

    def counts(self) -> dict:
        """What a client can see while the tick is held: the store under
        the feed lock, the daemon's count, `/healthz`, the ledger."""
        with self.daemon.feed.locked():
            store = sum(1 for p in self.daemon.cluster.pods.values()
                        if p.node_name is not None)
        url = "http://%s:%d/healthz" % self.daemon.health.address
        with urllib.request.urlopen(url, timeout=30) as reply:
            health = json.loads(reply.read())
        return {"store": store, "daemon": self.daemon.bound_total,
                "healthz": health["bound_total"],
                "ledger": self.ledger.pods_bound}

    def held_cycle_counts(self) -> dict:
        assert _wait(self.cycle_done.is_set), "no cycle bound or failed a pod"
        self.cycle_done.clear()
        counts = self.counts()
        self.go_on.set()
        return counts

    def close(self) -> str:
        self.daemon.stop_event.set()
        self.go_on.set()
        self.thread.join(timeout=30)
        podledger.use(self._ledger_before)
        assert not self.thread.is_alive()


@pytest.fixture
def held(tmp_path, monkeypatch):
    monkeypatch.setattr(daemon_main.signal, "signal", lambda *_: None)
    one = HeldDaemon(tmp_path)
    yield one
    one.close()


def test_bound_total_is_the_stores_count_while_a_tick_is_held(held, capsys):
    held.apply(
        {"op": "upsert_node", "name": "n0",
         "allocatable": {"cpu": 96000, "memory": 1 << 40, "pods": 110,
                         GPU: 8}},
        {"op": "upsert_namespace", "name": "team"},
        {"op": "upsert_quota", "name": "q", "namespace": "team",
         "min": {"cpu": 96000, "memory": 1 << 40, GPU: 8},
         "max": {"cpu": 96000, "memory": 1 << 40, GPU: 8}},
        {"op": "upsert_pod_group", "name": "whole", "namespace": "team",
         "min_member": 3, "creation_ms": 1},
        {"op": "upsert_pod_group", "name": "short", "namespace": "team",
         "min_member": 3, "creation_ms": 1},
        # a whole gang, two plain pods, and a gang one member short: the
        # first cycle binds five pods and rejects the short gang
        *(held.pod(f"whole-{m}", "whole") for m in range(3)),
        held.pod("solo-0"), held.pod("solo-1"),
        *(held.pod(f"short-{m}", "short") for m in range(2)),
    )
    held.thread.start()
    first = held.held_cycle_counts()
    assert first == {"store": 5, "daemon": 5, "healthz": 5, "ledger": 5}

    # the missing member arrives: the next cycle that finds work binds the
    # gang whole (a retried gang), and the counts agree again
    held.apply(held.pod("short-2", "short"))
    counts = held.held_cycle_counts()
    while counts["store"] < 8:  # its members' back-off may skip a cycle
        counts = held.held_cycle_counts()
    assert counts == {"store": 8, "daemon": 8, "healthz": 8, "ledger": 8}

    held.close()
    exit_line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert exit_line["daemon_exit"] and exit_line["bound_total"] == 8
    assert held.daemon.engine.rebases == 1
