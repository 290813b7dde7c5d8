"""No node holds two pods of which one carries a required anti-affinity
term that matches the other under the term's topology key: for every bound
pod with such a term, no other bound pod that the term's selector matches,
in one of the term's namespaces, sits in the same topology domain (the same
value of the term's key on its node; a node without the key is in none).

    audit(cluster) -> [problem, ...]

Reads the store's objects, under the feed lock, never the solver's tensors.
Terms scoped by a `namespaceSelector` are not judged (the configuration
that names this audit has none); a term without `namespaces` is scoped to
its pod's own.
"""

from __future__ import annotations


def audit(cluster) -> list:
    by_node: dict = {}
    for pod in cluster.pods.values():
        if pod.node_name is not None:
            by_node.setdefault(pod.node_name, []).append(pod)
    carriers = [
        (pod, term)
        for pods in by_node.values() for pod in pods
        for term in pod.pod_anti_affinity_required
        if term.namespace_selector is None
    ]
    keys = {term.topology_key for _pod, term in carriers}
    by_domain: dict = {}  # (key, value) -> the bound pods in that domain
    for name, pods in by_node.items():
        node = cluster.nodes.get(name)
        if node is None:
            continue
        for key in keys:
            if key in node.labels:
                by_domain.setdefault(
                    (key, node.labels[key]), []
                ).extend(pods)
    pairs = 0
    for pod, term in carriers:
        node = cluster.nodes.get(pod.node_name)
        if node is None or term.topology_key not in node.labels:
            continue
        scope = term.namespaces or (pod.namespace,)
        selector = term.label_selector
        for other in by_domain[
            (term.topology_key, node.labels[term.topology_key])
        ]:
            if (
                other is not pod and other.namespace in scope
                and selector is not None and selector.matches(other.labels)
            ):
                pairs += 1
    if pairs:
        return [
            f"{pairs} (carrier, matching pod) pairs share a topology domain "
            "a required anti-affinity term forbids"
        ]
    return []
