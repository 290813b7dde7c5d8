"""The gang and quota axes land on `bucket_size` buckets (ISSUE 28, step 1).

`build_snapshot` and the serving engine sized a cycle's gang and quota
arrays to the object (`G = max(len(gang_pos), 1)`, `Q = max(len(namespaces),
1)`), so a store whose PodGroups and namespaces come and go compiled a
solve for every count it met. They now pad both to buckets, as nodes and
pods are. Held here: a padded row is inert (a solve of the padded arrays
equals a solve of the exact ones, output for output), and a store whose
objects come and go for 60 cycles compiles no more `solve` and
`serve_side_apply` shapes than the buckets it crossed.
"""

import numpy as np
import pytest

from scheduler_plugins_tpu.api.objects import (
    POD_GROUP_LABEL,
    Container,
    ElasticQuota,
    Pod,
    PodGroup,
)
from scheduler_plugins_tpu.api.resources import CPU, MEMORY
from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
from scheduler_plugins_tpu.models.scenarios import gang_quota_scenario
from scheduler_plugins_tpu.plugins import (
    CapacityScheduling,
    Coscheduling,
    NodeResourcesAllocatable,
)
from scheduler_plugins_tpu.serving import ServeEngine
from scheduler_plugins_tpu.utils import observability as obs
from scheduler_plugins_tpu.utils.intmath import bucket_size
from tests.test_serving import gib, make_cluster, trim_pads


def gang_scheduler():
    return Scheduler(Profile(plugins=[
        NodeResourcesAllocatable(),
        Coscheduling(permit_waiting_seconds=5),
        CapacityScheduling(),
    ]))


class TestPaddedRowsAreInert:
    # 3 gangs pad to 8, 8 sit on the bucket exactly, 9 pad to 16; the
    # scenario has a namespace per gang up to 16
    @pytest.mark.parametrize("n_gangs", [3, 8, 9])
    @pytest.mark.parametrize("seed", [0, 1])
    def test_padded_solve_equals_exact_solve(self, n_gangs, seed):
        cluster = gang_quota_scenario(
            n_gangs=n_gangs, gang_size=3, n_nodes=6, seed=seed
        )
        # a gang short of a member, and a namespace pressed against its
        # quota: refusals and waits are on the path, not only binds
        cluster.remove_pod("team-0/gang-0000-m002")
        cluster.quotas["team-1"].max = {CPU: 2500, MEMORY: 64 * gib}
        sched = gang_scheduler()
        pending = sched.sort_pending(cluster.pending_pods(), cluster)
        snap, meta = cluster.snapshot(pending, now_ms=1000)
        G = snap.gangs.min_member.shape[0]
        Q = snap.quota.min.shape[0]
        assert G == bucket_size(n_gangs) and Q == bucket_size(n_gangs)
        pad = np.asarray(snap.gangs.mask) == False  # noqa: E712
        assert pad.sum() == G - n_gangs
        assert not np.asarray(snap.gangs.min_member)[pad].any()
        assert not np.asarray(snap.gangs.total_members)[pad].any()
        assert not np.asarray(snap.quota.has_quota)[n_gangs:].any()
        sched.prepare(meta, cluster)
        padded = sched.solve(snap)
        exact = sched.solve(trim_pads(snap, meta))
        for name in ("assignment", "admitted", "wait", "failed_plugin"):
            np.testing.assert_array_equal(
                np.asarray(getattr(padded, name)),
                np.asarray(getattr(exact, name)), err_msg=name,
            )
        assert (np.asarray(padded.assignment) >= 0).any()
        assert not np.asarray(padded.admitted)[: len(pending)].all()


def _misses(program: str) -> int:
    return sum(
        value for key, value in obs.metrics.snapshot().items()
        if key.startswith(obs.JIT_CACHE_MISS) and f'"{program}"' in key
    )


class TestShapesFollowBuckets:
    def test_objects_that_come_and_go_compile_per_bucket_crossed(self):
        """60 served cycles over a store whose PodGroups go from 1 to 20
        and back and whose quota'd namespaces go from 1 to 12 and back:
        the batch stays in one pod bucket, so every `solve` shape is a
        (gang bucket, quota bucket) pair and every `serve_side_apply`
        shape a pair of table sizes."""
        cluster = make_cluster(6)
        engine = ServeEngine().attach(cluster)
        sched = gang_scheduler()
        solve0, side0 = _misses("solve"), _misses("serve_side_apply")
        pairs, table_pairs = set(), set()
        groups: list = []
        spaces: list = []
        serial = 0
        for cycle in range(60):
            now = 1000 * (cycle + 1)
            rising = cycle < 30
            # a namespace with its quota comes (or the newest goes) every
            # third cycle, a PodGroup with one bound member two cycles in
            # three
            if cycle % 3 == 0:
                if rising and len(spaces) < 12:
                    name = f"ns-{cycle:02d}"
                    cluster.add_quota(ElasticQuota(
                        name="eq", namespace=name,
                        min={CPU: 4000, MEMORY: 16 * gib},
                        max={CPU: 8000, MEMORY: 32 * gib},
                    ))
                    spaces.append(name)
                elif not rising and len(spaces) > 1:
                    gone = spaces.pop()
                    for uid in [u for u, p in cluster.pods.items()
                                if p.namespace == gone]:
                        cluster.remove_pod(uid)
                    groups[:] = [g for g in groups if g[0] != gone]
                    for key in [k for k in cluster.pod_groups
                                if k.startswith(gone + "/")]:
                        del cluster.pod_groups[key]
                    del cluster.quotas[gone]
            elif rising and len(groups) < 20:
                namespace = spaces[cycle % len(spaces)]
                name = f"g-{cycle:02d}"
                cluster.add_pod_group(PodGroup(
                    name=name, namespace=namespace, min_member=1,
                    creation_ms=now,
                ))
                groups.append((namespace, name))
            elif not rising and len(groups) > 1:
                namespace, name = groups.pop()
                for uid in [u for u, p in cluster.pods.items()
                            if p.namespace == namespace
                            and p.pod_group() == name]:
                    cluster.remove_pod(uid)
                del cluster.pod_groups[f"{namespace}/{name}"]
            # one or two pods a cycle: the pod bucket never moves
            for namespace, group in (groups[-1:] or [(spaces[0], None)]):
                serial += 1
                cluster.add_pod(Pod(
                    name=f"p{serial:04d}", namespace=namespace,
                    creation_ms=now + serial,
                    labels={POD_GROUP_LABEL: group} if group else {},
                    containers=[Container(
                        requests={CPU: 100, MEMORY: gib // 8}
                    )],
                ))
            report = run_cycle(sched, cluster, now=now, serve=engine)
            assert report.bound, cycle
            assert engine.gang_fallbacks == 0
            n_spaces = len({p.namespace for p in cluster.pods.values()
                            if p.node_name} | set(cluster.quotas))
            pairs.add((
                bucket_size(len(cluster.pod_groups)) if cluster.pod_groups
                else 0,
                bucket_size(n_spaces),
            ))
            table_pairs.add((engine._side_gpad, engine._side_qpad))
        assert max(g for g, _ in pairs) >= 32 and len(pairs) >= 4
        assert _misses("solve") - solve0 <= len(pairs)
        assert _misses("serve_side_apply") - side0 <= len(table_pairs)
        # the last cycle's binds are still in the delta sink
        assert engine.refresh(cluster, [], now_ms=99_000) is not None
        assert engine.verify(cluster) is None
