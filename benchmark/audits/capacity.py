"""No node holds more than it declares: of every resource in a node's
`allocatable`, the requests of the pods bound to it (and their count, as
`pods`) stay within it; every bound pod is on a known node.

    audit(cluster) -> [problem, ...]

Reads the store's objects, under the feed lock, never the solver's tensors.
"""

from __future__ import annotations


def audit(cluster) -> list:
    used: dict = {}
    problems = []
    for pod in cluster.pods.values():
        if pod.node_name is None:
            continue
        if pod.node_name not in cluster.nodes:
            problems.append(f"{pod.uid} bound to unknown node {pod.node_name}")
            continue
        row = used.setdefault(pod.node_name, {"pods": 0})
        row["pods"] += 1
        for container in pod.containers:
            for resource, amount in container.requests.items():
                row[resource] = row.get(resource, 0) + amount
    over = sum(
        1 for name, row in used.items()
        if any(amount > cluster.nodes[name].allocatable.get(resource, 0)
               for resource, amount in row.items() if amount)
    )
    if over:
        problems.append(f"{over} nodes hold more than their allocatable")
    return problems
