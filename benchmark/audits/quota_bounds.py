"""No namespace is over its ElasticQuota `max`: of every resource the quota
bounds, the requests of the namespace's bound pods stay within it.

    audit(cluster) -> [problem, ...]

Reads the store's objects, under the feed lock, never the solver's tensors.
"""

from __future__ import annotations


def audit(cluster) -> list:
    used: dict = {}
    for pod in cluster.pods.values():
        if pod.node_name is None or pod.namespace not in cluster.quotas:
            continue
        row = used.setdefault(pod.namespace, {})
        for container in pod.containers:
            for resource, amount in container.requests.items():
                row[resource] = row.get(resource, 0) + amount
    problems = []
    for namespace, row in sorted(used.items()):
        bounds = cluster.quotas[namespace].max
        over = sorted(r for r, amount in row.items()
                      if r in bounds and amount > bounds[r])
        if over:
            problems.append(
                f"namespace {namespace} over its ElasticQuota max in "
                f"{', '.join(over)}: {row[over[0]]} of {bounds[over[0]]}"
            )
    return problems
