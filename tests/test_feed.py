"""Event-feed bridge tests: a remote agent drives the cluster over TCP and a
scheduling cycle runs against the fed state."""

import json

import pytest

from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
from scheduler_plugins_tpu.bridge.feed import FeedClient, FeedServer, apply_event
from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable
from scheduler_plugins_tpu.state.cluster import Cluster

gib = 1 << 30


class TestFeed:
    def test_agent_feeds_then_cycle_schedules(self):
        cluster = Cluster()
        server = FeedServer(cluster).start()
        try:
            host, port = server.address
            client = FeedClient(host, port)
            assert client.send({
                "op": "upsert_node", "name": "n0",
                "allocatable": {CPU: 8000, MEMORY: 32 * gib, PODS: 110},
            })["ok"]
            assert client.send({
                "op": "upsert_quota", "name": "q", "namespace": "team",
                "min": {CPU: 4000, MEMORY: 16 * gib},
                "max": {CPU: 6000, MEMORY: 24 * gib},
            })["ok"]
            assert client.send({
                "op": "upsert_pod", "name": "web", "namespace": "team",
                "requests": {CPU: 500, MEMORY: gib},
            })["ok"]
            sync = client.send({"op": "sync"})
            assert sync == {"ok": True, "nodes": 1, "pods": 1, "pending": 1}
            report = server.run_cycle(
                Scheduler(Profile(plugins=[NodeResourcesAllocatable()])),
                now=1000,
            )
            assert report.bound == {"team/web": "n0"}
            # stale watch echo without the node must NOT demote the binding
            assert client.send({
                "op": "upsert_pod", "name": "web", "namespace": "team",
                "requests": {CPU: 500, MEMORY: gib},
            })["ok"]
            assert cluster.pods["team/web"].node_name == "n0"
            # delete by namespace+name (no uid); unknown deletes are errors
            assert client.send({
                "op": "delete_pod", "namespace": "team", "name": "web",
            })["ok"]
            assert not client.send({"op": "delete_pod", "uid": "team/ghost"})["ok"]
            assert client.send({"op": "sync"})["pods"] == 0
            # node lifecycle: delete_node removes it from scheduling
            assert client.send({"op": "delete_node", "name": "n0"})["ok"]
            assert client.send({"op": "sync"})["nodes"] == 0
            client.close()
        finally:
            server.stop()

    def test_malformed_and_unknown_events_reported(self):
        cluster = Cluster()
        server = FeedServer(cluster).start()
        try:
            client = FeedClient(*server.address)
            bad = client.send({"op": "explode"})
            assert not bad["ok"] and "unknown op" in bad["error"]
            # malformed JSON line
            client._file.write(b"{not json\n")
            client._file.flush()
            import json as _json

            ack = _json.loads(client._file.readline())
            assert not ack["ok"]
            # the connection stays usable afterwards
            assert client.send({"op": "sync"})["ok"]
            client.close()
        finally:
            server.stop()

    def test_metrics_event(self):
        cluster = Cluster()
        apply_event(cluster, {"op": "metrics",
                              "nodes": {"n0": {"cpu_avg": 42.0}}})
        assert cluster.node_metrics == {"n0": {"cpu_avg": 42.0}}


class TestFeedChurnFullSurface:
    """VERDICT round-1 #5 done-criterion: a multi-cycle churn driven ENTIRELY
    through the TCP feed, with every plugin family active — NRT, AppGroup,
    NetworkTopology, SeccompProfile, PriorityClass and PDB all cross the
    process boundary as protocol-v2 events (the reference watches each via
    informers: plugin.go:86-115, networkoverhead.go:136-171,
    sysched.go:305-396)."""

    def test_churn_through_feed_all_plugin_families(self):
        import numpy as np

        from scheduler_plugins_tpu.api.objects import (
            APP_GROUP_LABEL,
            POD_GROUP_LABEL,
            REGION_LABEL,
            WORKLOAD_SELECTOR_LABEL,
            ZONE_LABEL,
        )
        from scheduler_plugins_tpu.api.resources import PODS
        from scheduler_plugins_tpu.framework import Profile, Scheduler
        from scheduler_plugins_tpu.plugins import (
            CapacityScheduling,
            Coscheduling,
            NetworkOverhead,
            NodeResourcesAllocatable,
            NodeResourceTopologyMatch,
            PodState,
            SySched,
            TargetLoadPacking,
        )

        rng = np.random.default_rng(11)
        cluster = Cluster()
        server = FeedServer(cluster).start()
        try:
            client = FeedClient(*server.address)
            # --- cluster-scope CRs, all through the wire ---------------
            for i in range(6):
                zone = f"z{i % 4}"
                assert client.send({
                    "op": "upsert_node", "name": f"n{i}",
                    "allocatable": {CPU: 16_000, MEMORY: 64 * gib, PODS: 30},
                    "labels": {ZONE_LABEL: zone,
                               REGION_LABEL: f"r{(i % 4) // 2}"},
                })["ok"]
                assert client.send({
                    "op": "upsert_nrt", "node": f"n{i}",
                    "policy": 3, "scope": 0,  # single-numa-node, container
                    "zones": [
                        {"numa_id": z,
                         "available": {CPU: 8000, MEMORY: 32 * gib},
                         "costs": {str(o): 10 if o == z else 20
                                   for o in range(2)}}
                        for z in range(2)
                    ],
                })["ok"]
            assert client.send({
                "op": "upsert_quota", "name": "eq", "namespace": "team",
                "min": {CPU: 48_000, MEMORY: 192 * gib},
                "max": {CPU: 80_000, MEMORY: 320 * gib},
            })["ok"]
            assert client.send({
                "op": "upsert_app_group", "name": "mesh", "namespace": "team",
                "workloads": [
                    {"selector": "frontend"},
                    {"selector": "backend", "dependencies": [
                        {"workload_selector": "frontend",
                         "max_network_cost": 15},
                    ]},
                ],
                "topology_order": {"frontend": 0, "backend": 1},
            })["ok"]
            assert client.send({
                "op": "upsert_network_topology", "name": "nt-default",
                "namespace": "team",
                "weights": {"UserDefined": {
                    "zone": [[f"z{a}", f"z{b}", 5]
                             for a in range(4) for b in range(4) if a != b],
                    "region": [["r0", "r1", 40], ["r1", "r0", 40]],
                }},
            })["ok"]
            assert client.send({
                "op": "upsert_seccomp_profile", "name": "web",
                "namespace": "team",
                "syscalls": ["read", "write", "open", "close"],
            })["ok"]
            assert client.send({
                "op": "upsert_seccomp_profile", "name": "batch",
                "namespace": "team",
                "syscalls": ["read", "write", "mmap", "clone", "ptrace"],
            })["ok"]
            assert client.send({
                "op": "upsert_priority_class", "name": "tolerated",
                "value": 5, "annotations": {},
            })["ok"]
            assert client.send({
                "op": "upsert_pdb", "name": "web-pdb", "namespace": "team",
                "selector": {"app": "frontend"}, "disruptions_allowed": 1,
            })["ok"]

            sched = Scheduler(Profile(plugins=[
                NodeResourcesAllocatable(),
                Coscheduling(permit_waiting_seconds=5),
                CapacityScheduling(),
                NodeResourceTopologyMatch(),
                TargetLoadPacking(),
                NetworkOverhead(),
                SySched(),
                PodState(),
            ]))

            serial = 0
            total_bound = 0
            for cycle in range(10):
                now = 1000 * (cycle + 1)
                assert client.send({
                    "op": "metrics",
                    "nodes": {f"n{i}": {"cpu_avg": float(rng.uniform(5, 60)),
                                        "cpu_std": 4.0}
                              for i in range(6)},
                })["ok"]
                for _ in range(int(rng.integers(1, 5))):
                    serial += 1
                    wl = "frontend" if serial % 2 else "backend"
                    assert client.send({
                        "op": "upsert_pod", "name": f"p{serial:04d}",
                        "namespace": "team", "creation_ms": now,
                        "priority": int(rng.integers(0, 5)),
                        "priority_class_name": "tolerated",
                        "labels": {APP_GROUP_LABEL: "mesh",
                                   WORKLOAD_SELECTOR_LABEL: wl,
                                   "app": wl},
                        "containers": [
                            {"requests": {CPU: int(rng.integers(200, 2500)),
                                          MEMORY: 1 * gib},
                             "limits": {CPU: int(rng.integers(2500, 4000)),
                                        MEMORY: 2 * gib},
                             "seccomp_profile": "team/web"},
                            {"requests": {CPU: 200, MEMORY: gib},
                             "seccomp_profile": "team/batch"},
                        ],
                        "init_containers": [
                            {"requests": {CPU: 500, MEMORY: gib}},
                        ],
                        "overhead": {CPU: 50},
                    })["ok"]
                if cycle == 3:
                    assert client.send({
                        "op": "upsert_pod_group", "name": "gang",
                        "namespace": "team", "min_member": 3,
                        "creation_ms": now,
                    })["ok"]
                    for m in range(3):
                        serial += 1
                        assert client.send({
                            "op": "upsert_pod", "name": f"gm{m}",
                            "namespace": "team", "creation_ms": now + m,
                            "labels": {POD_GROUP_LABEL: "gang"},
                            "requests": {CPU: 1000, MEMORY: 2 * gib},
                        })["ok"]
                # completions through the wire
                with server.locked():
                    bound = [
                        p.uid for p in cluster.pods.values()
                        if p.node_name is not None and not p.pod_group()
                    ]
                for uid in bound:
                    if rng.random() < 0.2:
                        ns, name = uid.split("/", 1)
                        assert client.send({
                            "op": "delete_pod", "namespace": ns,
                            "name": name,
                        })["ok"]
                sync = client.send({"op": "sync"})
                assert sync["ok"]
                report = server.run_cycle(sched, now=now)
                total_bound += len(report.bound)
                with server.locked():
                    check_feed_invariants(cluster)

            # every tensor family must have been active in the solve
            with server.locked():
                pending = cluster.pending_pods() or [
                    next(iter(cluster.pods.values()))
                ]
                snap, _ = cluster.snapshot(pending, now_ms=99_000)
            assert snap.numa is not None
            assert snap.network is not None
            assert snap.syscalls is not None
            assert snap.metrics is not None
            assert snap.quota is not None
            assert total_bound > 10
            client.close()
        finally:
            server.stop()


def check_feed_invariants(cluster):
    from scheduler_plugins_tpu.api.resources import PODS

    used = {n: {} for n in cluster.nodes}
    for pod in cluster.pods.values():
        if pod.node_name is None:
            continue
        bucket = used[pod.node_name]
        for r, q in pod.effective_request().items():
            bucket[r] = bucket.get(r, 0) + q
        bucket[PODS] = bucket.get(PODS, 0) + 1
    for name, node in cluster.nodes.items():
        for r, q in used[name].items():
            assert q <= node.allocatable.get(r, 0), (name, r)
    for eq in cluster.quotas.values():
        total = {}
        for pod in cluster.pods.values():
            if pod.namespace == eq.namespace and pod.node_name is not None:
                for r, q in pod.effective_request().items():
                    total[r] = total.get(r, 0) + q
        for r, cap in eq.max.items():
            assert total.get(r, 0) <= cap, (eq.namespace, r)
    for pg in cluster.pod_groups.values():
        bound = sum(
            1 for p in cluster.gang_members(pg) if p.node_name is not None
        )
        assert bound == 0 or bound >= pg.min_member, (pg.full_name, bound)


class TestSpecFragments:
    def test_taints_affinity_spread_over_the_wire(self):
        from scheduler_plugins_tpu.framework import Profile, Scheduler
        from scheduler_plugins_tpu.plugins import (
            NodeAffinity,
            NodeResourcesAllocatable,
            PodTopologySpread,
            TaintToleration,
        )

        cluster = Cluster()
        server = FeedServer(cluster).start()
        try:
            client = FeedClient(*server.address)
            ZONE = "topology.kubernetes.io/zone"
            for i, (z, taints) in enumerate([
                ("z-a", []), ("z-a", [{"key": "dedicated", "value": "x"}]),
                ("z-b", []),
            ]):
                assert client.send({
                    "op": "upsert_node", "name": f"n{i}",
                    "allocatable": {"cpu": 8000, "memory": 32 << 30, "pods": 110},
                    "labels": {ZONE: z, "disk": "ssd"}, "taints": taints,
                })["ok"]
            for j in range(2):
                assert client.send({
                    "op": "upsert_pod", "name": f"p{j}", "creation_ms": j,
                    "labels": {"app": "web"},
                    "requests": {"cpu": 500, "memory": 1 << 30},
                    "node_selector": {"disk": "ssd"},
                    "tolerations": [],
                    "topology_spread": [{
                        "max_skew": 1, "topology_key": ZONE,
                        "when_unsatisfiable": "DoNotSchedule",
                        "label_selector": {"match_labels": {"app": "web"}},
                    }],
                    "node_affinity": {"required": [{"match_expressions": [
                        {"key": "disk", "operator": "In", "values": ["ssd"]}]}]},
                })["ok"]
            sched = Scheduler(Profile(plugins=[
                NodeResourcesAllocatable(), NodeAffinity(), TaintToleration(),
                PodTopologySpread()]))
            report = server.run_cycle(sched, now=1000)
            nodes = sorted(report.bound.values())
            # taint keeps p off n1; spread forces one per zone
            assert "n1" not in nodes
            assert nodes == ["n0", "n2"]
        finally:
            server.stop()


class TestResourceVersionFencing:
    def test_stale_rv_dropped(self):
        cluster = Cluster()
        server = FeedServer(cluster).start()
        try:
            client = FeedClient(*server.address)
            assert client.send({
                "op": "upsert_node", "name": "n0", "rv": 7,
                "allocatable": {"cpu": 4000, "memory": 1 << 30, "pods": 10},
            })["ok"]
            ack = client.send({
                "op": "upsert_node", "name": "n0", "rv": 5,  # replayed older
                "allocatable": {"cpu": 1, "memory": 1, "pods": 1},
            })
            assert ack["ok"] and ack.get("stale") and ack["last_rv"] == 7
            assert cluster.nodes["n0"].allocatable["cpu"] == 4000
            assert client.send({
                "op": "upsert_node", "name": "n0", "rv": 9,
                "allocatable": {"cpu": 8000, "memory": 1 << 30, "pods": 10},
            })["ok"]
            assert cluster.nodes["n0"].allocatable["cpu"] == 8000
        finally:
            server.stop()

    def test_stale_delete_fenced(self):
        cluster = Cluster()
        server = FeedServer(cluster).start()
        try:
            client = FeedClient(*server.address)
            client.send({"op": "upsert_pod", "name": "p", "rv": 10,
                         "requests": {"cpu": 100}})
            ack = client.send({"op": "delete_pod", "name": "p",
                               "namespace": "default", "rv": 4})
            assert ack.get("stale")
            assert "default/p" in cluster.pods
        finally:
            server.stop()

    def test_no_rv_is_last_writer_wins(self):
        cluster = Cluster()
        server = FeedServer(cluster).start()
        try:
            client = FeedClient(*server.address)
            client.send({"op": "upsert_node", "name": "n0",
                         "allocatable": {"cpu": 1000, "memory": 1, "pods": 1}})
            client.send({"op": "upsert_node", "name": "n0",
                         "allocatable": {"cpu": 2000, "memory": 1, "pods": 1}})
            assert cluster.nodes["n0"].allocatable["cpu"] == 2000
        finally:
            server.stop()


class TestFramedTransport:
    def test_framed_client_same_port(self):
        from scheduler_plugins_tpu.bridge.feed import FramedFeedClient

        cluster = Cluster()
        server = FeedServer(cluster).start()
        try:
            client = FramedFeedClient(*server.address)
            ack = client.send({
                "op": "upsert_node", "name": "n0",
                "allocatable": {"cpu": 4000, "memory": 1 << 30, "pods": 10},
            })
            assert ack["ok"]
            ack = client.send({"op": "sync"})
            assert ack["nodes"] == 1
            # line-mode clients still work on the same port
            line = FeedClient(*server.address)
            assert line.send({"op": "sync"})["nodes"] == 1
        finally:
            server.stop()


class TestGrpcTransport:
    def test_grpc_apply_and_stream(self):
        import pytest

        pytest.importorskip("grpc")
        from scheduler_plugins_tpu.bridge.grpc_feed import (
            GrpcFeedClient,
            GrpcFeedServer,
        )

        cluster = Cluster()
        server = GrpcFeedServer(cluster).start()
        try:
            client = GrpcFeedClient("127.0.0.1", server.port)
            assert client.send({
                "op": "upsert_node", "name": "n0",
                "allocatable": {"cpu": 4000, "memory": 1 << 30, "pods": 10},
            })["ok"]
            acks = client.send_batch([
                {"op": "upsert_pod", "name": f"p{j}", "rv": j,
                 "requests": {"cpu": 100}}
                for j in range(5)
            ] + [{"op": "sync"}])
            assert all(a["ok"] for a in acks)
            assert acks[-1]["pods"] == 5
            # fencing shared with the server's table
            assert client.send({"op": "upsert_pod", "name": "p3", "rv": 2,
                                "requests": {"cpu": 999}}).get("stale")
            client.close()
        finally:
            server.stop()


class TestFencingEdgeCases:
    def test_failed_event_does_not_burn_its_rv(self):
        cluster = Cluster()
        server = FeedServer(cluster).start()
        try:
            client = FeedClient(*server.address)
            # malformed (missing allocatable) -> error, rv NOT recorded
            ack = client.send({"op": "upsert_node", "name": "n0", "rv": 8})
            assert not ack["ok"]
            # corrected retry under the SAME rv must apply
            ack = client.send({"op": "upsert_node", "name": "n0", "rv": 8,
                               "allocatable": {"cpu": 4000, "memory": 1, "pods": 1}})
            assert ack["ok"] and not ack.get("stale")
            assert cluster.nodes["n0"].allocatable["cpu"] == 4000
        finally:
            server.stop()

    def test_rv_event_without_node_really_unbinds(self):
        cluster = Cluster()
        server = FeedServer(cluster).start()
        try:
            client = FeedClient(*server.address)
            client.send({"op": "upsert_pod", "name": "p", "rv": 1,
                         "requests": {"cpu": 100}, "node": "n0"})
            assert cluster.pods["default/p"].node_name == "n0"
            # fenced NEWER event without node: bind was rejected upstream
            client.send({"op": "upsert_pod", "name": "p", "rv": 2,
                         "requests": {"cpu": 100}})
            assert cluster.pods["default/p"].node_name is None
            # but an UN-fenced echo without node keeps the local bind
            client.send({"op": "upsert_pod", "name": "p", "node": "n0",
                         "rv": 3, "requests": {"cpu": 100}})
            client.send({"op": "upsert_pod", "name": "p",
                         "requests": {"cpu": 100}})
            assert cluster.pods["default/p"].node_name == "n0"
        finally:
            server.stop()

    def test_pod_fence_lane_shared_across_identifier_styles(self):
        cluster = Cluster()
        server = FeedServer(cluster).start()
        try:
            client = FeedClient(*server.address)
            client.send({"op": "upsert_pod", "uid": "default/p", "name": "p",
                         "rv": 9, "requests": {"cpu": 100}})
            # replay WITHOUT uid still lands in the same fence lane
            ack = client.send({"op": "upsert_pod", "name": "p", "rv": 4,
                               "requests": {"cpu": 999}})
            assert ack.get("stale")
            assert len(cluster.pods) == 1
        finally:
            server.stop()

    def test_null_spec_fields_tolerated(self):
        cluster = Cluster()
        server = FeedServer(cluster).start()
        try:
            client = FeedClient(*server.address)
            ack = client.send({
                "op": "upsert_pod", "name": "p", "requests": {"cpu": 100},
                "node_selector": None, "node_affinity": None,
                "tolerations": None, "topology_spread": None,
                "pod_affinity": None, "pod_anti_affinity": None,
            })
            assert ack["ok"], ack
        finally:
            server.stop()

    def test_oversized_frame_refused(self):
        import socket as _socket
        import struct as _struct

        cluster = Cluster()
        server = FeedServer(cluster).start()
        try:
            sock = _socket.create_connection(server.address)
            f = sock.makefile("rwb")
            f.write(_struct.pack(">BI", 0, 0xFFFFFFFF))
            f.flush()
            header = f.read(5)
            _flag, length = _struct.unpack(">BI", header)
            import json as _json
            ack = _json.loads(f.read(length))
            assert not ack["ok"] and "exceeds" in ack["error"]
        finally:
            server.stop()


class TestFeedStageCounters:
    """`scheduler_feed_events_total` / `scheduler_feed_event_ns_total{stage}`:
    each connection tallies its own events and flushes every 32, every
    100 ms and when it ends (`bridge.feed.FeedTally`). Deltas of the
    process-wide registry, no wall-clock thresholds beyond the one the
    test makes itself by holding the lock."""

    STAGES = ("codec", "lock_wait", "apply")

    @staticmethod
    def _counters():
        from scheduler_plugins_tpu.utils import observability as obs

        return {
            "events": obs.metrics.get(obs.FEED_EVENTS),
            **{
                stage: obs.metrics.get(obs.FEED_EVENT_NS, stage=stage)
                for stage in TestFeedStageCounters.STAGES
            },
        }

    @classmethod
    def _delta_after(cls, before, events, timeout_s=10.0):
        """The registry's rise since `before`, once `events` more events
        are in it (a closed connection's handler flushes on its own
        thread, a moment after the client's close returns)."""
        import time

        deadline = time.monotonic() + timeout_s
        while True:
            now = cls._counters()
            delta = {k: now[k] - before[k] for k in now}
            if delta["events"] >= events or time.monotonic() > deadline:
                return delta
            time.sleep(0.01)

    @pytest.mark.parametrize("n", [1, 31, 32, 33, 100])
    def test_every_event_is_counted_once_the_connection_ends(self, n):
        server = FeedServer(Cluster()).start()
        try:
            before = self._counters()
            client = FeedClient(*server.address)
            for j in range(n):
                assert client.send({
                    "op": "upsert_node", "name": f"n{j}",
                    "allocatable": {CPU: 1000, PODS: 10},
                })["ok"]
            client.close()
            delta = self._delta_after(before, n)
        finally:
            server.stop()
        assert delta["events"] == n
        for stage in self.STAGES:
            assert delta[stage] > 0, (stage, delta)

    def test_a_full_tally_is_flushed_before_the_connection_ends(self):
        from scheduler_plugins_tpu.bridge.feed import TALLY_FLUSH_EVENTS

        server = FeedServer(Cluster()).start()
        try:
            before = self._counters()
            client = FeedClient(*server.address)
            for _ in range(TALLY_FLUSH_EVENTS):
                client.send({"op": "sync"})
            # the 32nd event's flush ran before its ack was written
            assert self._counters()["events"] - before["events"] == (
                TALLY_FLUSH_EVENTS
            )
            client.close()
        finally:
            server.stop()

    def test_a_sender_blocked_on_the_lock_shows_as_lock_wait(self):
        import threading
        import time

        server = FeedServer(Cluster()).start()
        try:
            before = self._counters()
            client = FeedClient(*server.address)
            acked = threading.Event()

            def send():
                client.send({"op": "sync"})
                acked.set()

            with server.locked():
                sender = threading.Thread(
                    target=send, daemon=True, name="test-feed-sender"
                )
                sender.start()
                time.sleep(0.05)
                assert not acked.is_set()  # it waits for the lock we hold
            sender.join(timeout=10)
            assert acked.is_set()
            client.close()
            delta = self._delta_after(before, 1)
        finally:
            server.stop()
        assert delta["events"] == 1
        # the sender asked for the lock within the first few ms of the 50
        assert delta["lock_wait"] >= 40_000_000, delta
        assert delta["apply"] < delta["lock_wait"]

    def test_a_malformed_line_is_an_event_with_codec_time(self):
        import socket

        server = FeedServer(Cluster()).start()
        try:
            before = self._counters()
            with socket.create_connection(server.address) as sock:
                with sock.makefile("rwb") as f:
                    f.write(b"{not json\n")
                    f.flush()
                    assert json.loads(f.readline())["ok"] is False
            delta = self._delta_after(before, 1)
        finally:
            server.stop()
        assert delta["events"] == 1
        assert delta["codec"] > 0
        # it never asked for the lock
        assert delta["lock_wait"] == 0 and delta["apply"] == 0

    def test_two_connections_add_up(self):
        server = FeedServer(Cluster()).start()
        try:
            before = self._counters()
            a = FeedClient(*server.address)
            b = FeedClient(*server.address)
            for _ in range(5):
                a.send({"op": "sync"})
            for _ in range(7):
                b.send({"op": "sync"})
            a.close()
            b.close()
            delta = self._delta_after(before, 12)
        finally:
            server.stop()
        assert delta["events"] == 12

    @pytest.mark.parametrize("rpc", ["unary", "stream"])
    def test_grpc_front_end_counts_through_the_same_helper(self, rpc):
        pytest.importorskip("grpc")
        from scheduler_plugins_tpu.bridge.feed import TALLY_FLUSH_EVENTS
        from scheduler_plugins_tpu.bridge.grpc_feed import (
            GrpcFeedClient,
            GrpcFeedServer,
        )

        server = GrpcFeedServer(Cluster()).start()
        try:
            before = self._counters()
            client = GrpcFeedClient("127.0.0.1", server.port)
            if rpc == "stream":
                # a stream's end flushes its worker's tally
                n = 5
                client.send_batch([{"op": "sync"}] * n)
            else:
                # unary calls land on any worker of the pool: when 8
                # workers have seen 8 * 32 events between them, at least
                # one tally has filled and flushed
                n = 8 * TALLY_FLUSH_EVENTS
                for _ in range(n):
                    client.send({"op": "sync"})
            delta = self._delta_after(
                before, n if rpc == "stream" else TALLY_FLUSH_EVENTS
            )
            client.close()
        finally:
            server.stop()
        if rpc == "stream":
            assert delta["events"] == n
        else:
            assert TALLY_FLUSH_EVENTS <= delta["events"] <= n
        assert delta["codec"] > 0 and delta["apply"] > 0

    # -- ISSUE 36: a TCP connection's wall clock, from its first byte in
    # hand to its last ack flushed, is tiled by six parts: `codec`,
    # `lock_wait`, `apply`, `write`, `turnaround` and the quiet gaps (over
    # `FEED_QUIET_NS`). With the tracer on, each flush is one B/E pair on
    # the connection's `feed/<n>` row.

    @staticmethod
    def _feed_registry():
        from scheduler_plugins_tpu.utils import observability as obs

        hists = obs.metrics.histograms().get(
            obs.FEED_QUIET_MS, {"count": 0, "sum": 0.0})
        return {
            "events": obs.metrics.get(obs.FEED_EVENTS),
            "write": obs.metrics.get(obs.FEED_EVENT_NS, stage="write"),
            "turnaround": obs.metrics.get(
                obs.FEED_EVENT_NS, stage="turnaround"),
            "quiet_count": hists["count"],
            "quiet_sum_ms": hists["sum"],
            "stalls": obs.metrics.get(obs.FEED_STALLS),
        }

    @classmethod
    def _registry_after(cls, before, events, timeout_s=10.0):
        import time

        deadline = time.monotonic() + timeout_s
        while True:
            now = cls._feed_registry()
            delta = {k: now[k] - before[k] for k in now}
            if delta["events"] >= events or time.monotonic() > deadline:
                return delta
            time.sleep(0.01)

    @staticmethod
    def _segments(trace):
        """[(row, begin event, end event), ...] of the export's B/E pairs."""
        rows = {
            e["tid"]: e["args"]["name"]
            for e in trace["traceEvents"] if e["ph"] == "M"
        }
        out, began = [], {}
        for e in trace["traceEvents"]:
            if e["ph"] == "B":
                assert e["tid"] not in began, "a B inside a B"
                began[e["tid"]] = e
            elif e["ph"] == "E":
                out.append((rows[e["tid"]], began.pop(e["tid"]), e))
        assert not began, began
        return out

    def _drive(self, monkeypatch, client_class, sends, pauses=()):
        """One connection: `sends` acknowledged `sync` events, sleeping
        `pauses[i]` seconds before event i. Returns the connection's
        recording tally once its handler has closed it."""
        import time

        from scheduler_plugins_tpu.bridge import feed

        made: list = []
        monkeypatch.setattr(feed, "FeedTally", _recording_tally(made))
        pauses = dict(pauses)
        server = FeedServer(Cluster()).start()
        try:
            before = self._feed_registry()
            client = client_class(*server.address)
            for i in range(sends):
                if i in pauses:
                    time.sleep(pauses[i])
                assert client.send({"op": "sync"})["ok"]
            client.close()
            delta = self._registry_after(before, sends)
        finally:
            server.stop()
        assert delta["events"] == sends
        assert len(made) == 1
        return made[0], delta

    @pytest.mark.parametrize("transport", ["lines", "framed"])
    @pytest.mark.parametrize("sends", [1, 33, 100])
    def test_six_parts_tile_the_connections_wall_clock(
            self, monkeypatch, transport, sends):
        from scheduler_plugins_tpu.bridge.feed import FramedFeedClient

        client_class = FeedClient if transport == "lines" else FramedFeedClient
        tally, delta = self._drive(
            monkeypatch, client_class, sends, pauses={sends // 2: 0.02})
        wall = tally.mark_ns - tally.first_ns
        assert wall > 0
        # exactly: the stamps are contiguous, nothing is measured twice
        # and nothing falls between two parts (`turnaround` is what each
        # flushed stretch leaves: the registry's integers hold it)
        assert (sum(tally.parts.values()) + delta["turnaround"]
                + sum(tally.gaps)) == wall
        assert tally.parts["write"] > 0 and delta["turnaround"] > 0
        assert delta["write"] == tally.parts["write"]
        assert delta["quiet_count"] == len(tally.gaps)
        assert delta["quiet_sum_ms"] == pytest.approx(
            sum(tally.gaps) / 1e6, rel=1e-9, abs=1e-9)

    @pytest.mark.parametrize("pause_s, quiet, bursts", [
        (0.02, 1, 2),   # the client had nothing to send: a quiet gap
        (0.001, 0, 1),  # the client answered: turnaround
    ])
    def test_a_pause_is_quiet_or_turnaround_by_its_length(
            self, monkeypatch, pause_s, quiet, bursts):
        from scheduler_plugins_tpu.bridge.feed import FEED_QUIET_NS
        from scheduler_plugins_tpu.utils import observability as obs
        from tools.trace_smoke import validate_trace

        for _attempt in range(5):
            obs.tracer.start()  # clears the events
            try:
                tally, delta = self._drive(
                    monkeypatch, FeedClient, 2, pauses={1: pause_s})
                trace = obs.tracer.export()
            finally:
                obs.tracer.stop()
            # a loaded runner can oversleep 1 ms by 4: try again
            if quiet or not tally.gaps:
                break
        assert delta["quiet_count"] == quiet and len(tally.gaps) == quiet
        assert delta["stalls"] == 0
        if quiet:
            assert tally.gaps[0] >= 20_000_000 > FEED_QUIET_NS
        else:
            assert delta["turnaround"] >= 1_000_000
        assert validate_trace(trace) == []
        segments = self._segments(trace)
        assert segments and {row for row, _b, _e in segments} == {"feed/0"}
        assert all(b["name"] == "Feed/segment" for _r, b, _e in segments)
        # consecutive segments with no quiet gap before them are one burst
        assert 1 + sum(
            1 for _r, b, _e in segments if b["args"]["quiet_before_us"] > 0
        ) == bursts
        assert sum(b["args"]["events"] for _r, b, _e in segments) == 2
        for _row, b, e in segments:
            args = b["args"]
            # a segment's length is its busy time and its turnaround
            assert (e["ts"] - b["ts"]) == pytest.approx(
                args["busy_us"] + args["turnaround_us"], abs=0.01)
            assert 0 <= args["lock_wait_us"] <= args["busy_us"]
        if quiet:
            assert segments[-1][1]["args"]["quiet_before_us"] == (
                pytest.approx(tally.gaps[0] / 1000.0))

    @pytest.mark.parametrize("gap_ns, part", [
        (5_000_000, "turnaround"),      # FEED_QUIET_NS itself still answers
        (5_000_001, "quiet"),
        (999_999_999, "quiet"),
        (1_000_000_000, "stall"),
    ])
    def test_the_quiet_boundary_on_a_scripted_clock(
            self, monkeypatch, gap_ns, part):
        import threading
        import types

        from scheduler_plugins_tpu.bridge import feed

        assert feed.FEED_QUIET_NS == 5_000_000
        assert feed.FEED_STALL_NS == 1_000_000_000
        ticks = iter(range(1_000, 10_000_000, 1_000))
        clock = types.SimpleNamespace(perf_counter_ns=lambda: next(ticks))
        monkeypatch.setattr(feed, "time", clock)
        before = self._feed_registry()
        tally = feed.FeedTally(row="feed/t")  # stamps 1_000
        lock, cluster = threading.Lock(), Cluster()
        feed.apply_raw(tally, b'{"op": "sync"}', cluster, lock, None)
        tally.wrote()
        flushed = tally.mark_ns  # 1_000 after the first byte, 6 stamps on
        assert flushed == 7_000
        # the next line comes `gap_ns` after that flush
        ticks = iter(range(flushed + gap_ns, flushed + gap_ns + 10**6, 1_000))
        feed.apply_raw(tally, b'{"op": "sync"}', cluster, lock, None)
        tally.wrote()
        tally.close()
        delta = self._registry_after(before, 2)
        assert delta["events"] == 2
        if part == "turnaround":
            assert delta["turnaround"] == 1_000 + gap_ns
            assert delta["quiet_count"] == 0 and delta["stalls"] == 0
        else:
            assert delta["turnaround"] == 1_000
            assert delta["quiet_count"] == 1
            assert delta["quiet_sum_ms"] == pytest.approx(gap_ns / 1e6)
            assert delta["stalls"] == (1 if part == "stall" else 0)

    @pytest.mark.parametrize("gaps_ns", [
        (5_000_000, 5_000_001, 7_000_000, 1_000, 1_000_000_000),
        (1_000, 2_000, 3_000),
        (20_000_000,),
    ], ids=["mixed", "answering", "one-quiet"])
    def test_the_pulse_counts_the_quiet_the_tally_counts(
            self, monkeypatch, gaps_ns):
        # the daemon's loop reads the feed's quiet time off `FeedPulse`;
        # on one connection it is the tally's quiet gaps, to the
        # nanosecond, and the gap still open counts from its flush once
        # it is FEED_QUIET_NS long
        import threading
        import types

        from scheduler_plugins_tpu.bridge import feed

        ticks = iter(range(1_000, 10**7, 1_000))
        clock = types.SimpleNamespace(perf_counter_ns=lambda: next(ticks))
        monkeypatch.setattr(feed, "time", clock)
        made: list = []
        tally = _recording_tally(made)(row="feed/t")  # stamps 1_000
        pulse = feed.FeedPulse()  # stamps 2_000
        lock, cluster = threading.Lock(), Cluster()

        def line():
            feed.apply_raw(tally, b'{"op": "sync"}', cluster, lock, None,
                           pulse)
            assert pulse.in_ns > pulse.mark_ns  # in hand
            assert pulse.quiet_total(pulse.in_ns) == (pulse.quiet_ns, None)
            tally.wrote()
            pulse.mark_ns = tally.mark_ns

        line()
        assert pulse.quiet_ns == 0
        for gap in gaps_ns:
            at = tally.mark_ns + gap
            ticks = iter(range(at, at + 10**6, 1_000))
            line()
        tally.close()
        assert pulse.quiet_ns == sum(tally.gaps)
        assert len(tally.gaps) == sum(g > feed.FEED_QUIET_NS for g in gaps_ns)
        mark = pulse.mark_ns
        for open_ns, counted in ((feed.FEED_QUIET_NS - 1, 0),
                                 (feed.FEED_QUIET_NS, feed.FEED_QUIET_NS),
                                 (9_000_000, 9_000_000)):
            assert pulse.quiet_total(mark + open_ns) == (
                sum(tally.gaps) + counted, mark)

    @pytest.mark.parametrize("transport", ["lines", "framed"])
    def test_a_connections_pulse_agrees_with_its_tally(
            self, monkeypatch, transport):
        import time

        from scheduler_plugins_tpu.bridge import feed
        from scheduler_plugins_tpu.bridge.feed import FramedFeedClient

        client_class = FeedClient if transport == "lines" else FramedFeedClient
        made: list = []
        monkeypatch.setattr(feed, "FeedTally", _recording_tally(made))
        server = FeedServer(Cluster()).start()
        pulse = server.pulse
        try:
            client = client_class(*server.address)
            assert client.send({"op": "sync"})["ok"]
            assert pulse.connections == 1
            # from the first flush on, the two count from the same stamps
            quiet_before, gaps_before = pulse.quiet_ns, len(made[0].gaps)
            for pause in (0.0, 0.02, 0.001, 0.012, 0.0):
                time.sleep(pause)
                assert client.send({"op": "sync"})["ok"]
            client.close()
            deadline = time.monotonic() + 10
            while pulse.connections and time.monotonic() < deadline:
                time.sleep(0.01)
        finally:
            server.stop()
        assert pulse.connections == 0
        gaps = made[0].gaps[gaps_before:]
        assert len(gaps) >= 2  # the two pauses of 12 ms and more
        assert pulse.quiet_ns - quiet_before == sum(gaps)

    @pytest.mark.parametrize("rpc", ["unary", "stream"])
    def test_grpc_keeps_its_three_stages(self, monkeypatch, rpc):
        pytest.importorskip("grpc")
        from scheduler_plugins_tpu.bridge import feed
        from scheduler_plugins_tpu.bridge.grpc_feed import (
            GrpcFeedClient,
            GrpcFeedServer,
        )
        from scheduler_plugins_tpu.utils import observability as obs

        obs.tracer.start()
        server = GrpcFeedServer(Cluster()).start()
        try:
            before = self._feed_registry()
            client = GrpcFeedClient("127.0.0.1", server.port)
            if rpc == "stream":
                n = 5
                client.send_batch([{"op": "sync"}] * n)
            else:
                n = 8 * feed.TALLY_FLUSH_EVENTS
                for _ in range(n):
                    client.send({"op": "sync"})
            delta = self._registry_after(
                before, n if rpc == "stream" else feed.TALLY_FLUSH_EVENTS)
            client.close()
            trace = obs.tracer.export()
        finally:
            server.stop()
            obs.tracer.stop()
        assert delta["events"] >= min(n, feed.TALLY_FLUSH_EVENTS)
        # the library owns its reads and writes: no stamp around them
        assert delta["write"] == 0 and delta["turnaround"] == 0
        assert delta["quiet_count"] == 0 and delta["stalls"] == 0
        assert [e for e in trace["traceEvents"] if e["ph"] in "BE"] == []

    def test_tracer_off_a_flush_records_nothing(self, monkeypatch):
        from scheduler_plugins_tpu.utils import observability as obs

        obs.tracer.stop()

        def refuse(*_args, **_kwargs):
            raise AssertionError("the tracer is off: no record is made")

        monkeypatch.setattr(obs.tracer, "complete", refuse)
        from scheduler_plugins_tpu.bridge.feed import FeedTally

        slots = FeedTally.__slots__
        tally, _delta = self._drive(
            monkeypatch, FeedClient, 40, pauses={20: 0.01})
        assert len(tally.gaps) >= 1
        # and the tally holds numbers and its row's name, nothing that grows
        for slot in slots:
            assert isinstance(getattr(tally, slot), (int, str)), slot


def _recording_tally(made: list):
    """A `FeedTally` subclass that keeps, in integer nanoseconds,
    everything its instances hand to the registry: the stage sums of every
    flush but `turnaround` (which `flush` works out), every quiet gap,
    and the first stamp (`mark_ns` is the last).
    Each instance is appended to `made`."""
    from scheduler_plugins_tpu.bridge import feed

    class Recording(feed.FeedTally):
        __slots__ = ("first_ns", "parts", "gaps")

        def __init__(self, row=None):
            super().__init__(row)
            self.first_ns = self.mark_ns
            self.parts = {s: 0 for s in (
                "codec", "lock_wait", "apply", "write")}
            self.gaps = []
            made.append(self)

        def flush(self, now_ns):
            for stage in self.parts:
                self.parts[stage] += getattr(self, f"{stage}_ns")
            super().flush(now_ns)

        def quiet(self, gap_ns, now_ns):
            self.gaps.append(gap_ns)
            super().quiet(gap_ns, now_ns)

    return Recording
