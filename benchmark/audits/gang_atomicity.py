"""A gang is bound whole or not at all: of every PodGroup, either no
member is bound or at least `min_member` are.

    audit(cluster) -> [problem, ...]

Reads the store's objects, under the feed lock, never the solver's tensors.
A member is a pod of the group's namespace that carries its name under the
pod-group label, as upstream's coscheduling finds them.
"""

from __future__ import annotations

POD_GROUP_LABEL = "scheduling.x-k8s.io/pod-group"


def audit(cluster) -> list:
    bound: dict = {}
    for pod in cluster.pods.values():
        group = pod.labels.get(POD_GROUP_LABEL)
        if group and pod.node_name is not None:
            key = f"{pod.namespace}/{group}"
            bound[key] = bound.get(key, 0) + 1
    broken = sorted(
        key for key, count in bound.items()
        if key in cluster.pod_groups
        and 0 < count < cluster.pod_groups[key].min_member
    )
    if not broken:
        return []
    return [
        f"{len(broken)} gangs bound below min_member, first {broken[0]}: "
        f"{bound[broken[0]]} of {cluster.pod_groups[broken[0]].min_member}"
    ]
