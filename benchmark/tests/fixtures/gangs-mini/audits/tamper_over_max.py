"""A negative of the fixture, not an audit: binds one more pod in the
namespace under quota, as large as the quota's whole `max`, so that
`quota_bounds`, listed after it, has something to find."""

import copy


def audit(cluster) -> list:
    namespace, quota = next(iter(cluster.quotas.items()))
    node = next(iter(cluster.nodes))
    pod = copy.deepcopy(next(
        p for p in cluster.pods.values()
        if p.namespace == namespace and p.node_name is not None
    ))
    pod.name, pod.uid = "intruder", f"{namespace}/intruder"
    pod.labels = {}
    pod.containers[0].requests.update(quota.max)
    pod.node_name = node
    cluster.pods[pod.uid] = pod
    return []
