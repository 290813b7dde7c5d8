"""Every bound pod's node satisfies the pod's `nodeSelector` and one of its
required node-affinity terms, by the node's labels (and, for `matchFields`,
its name) as the store has them now.

    audit(cluster) -> [problem, ...]

Reads the store's objects, under the feed lock, never the solver's tensors,
and matches with a matcher of its own: core/v1 NodeSelectorRequirement over
the six operators, as apimachinery's labels.Requirement defines them:

    In            the node has the key and its value is one of `values`
    NotIn         the node lacks the key, or its value is none of `values`
    Exists        the node has the key
    DoesNotExist  the node lacks the key
    Gt, Lt        the node has the key, `values` is one integer and the
                  node's value, read as an integer, is greater / less

A term is the AND of its `match_expressions` (over the labels) and its
`match_fields` (over {"metadata.name": the node's name}); a pod's required
terms are ORed; the `nodeSelector` (every key equal) is ANDed with them. A
pod bound to a node the store does not hold is the `capacity` audit's.
"""

from __future__ import annotations


def requirement_holds(key: str, operator: str, values, fields: dict) -> bool:
    present = key in fields
    value = fields.get(key)
    if operator == "In":
        return present and value in values
    if operator == "NotIn":
        return not present or value not in values
    if operator == "Exists":
        return present
    if operator == "DoesNotExist":
        return not present
    if operator in ("Gt", "Lt"):
        if not present or len(values) != 1:
            return False
        try:
            have, bound = int(value), int(values[0])
        except ValueError:
            return False
        return have > bound if operator == "Gt" else have < bound
    raise ValueError(f"unknown node selector operator {operator!r}")


def term_holds(term, node) -> bool:
    name = {"metadata.name": node.name}
    return all(
        requirement_holds(r.key, r.operator, tuple(r.values), node.labels)
        for r in term.match_expressions
    ) and all(
        requirement_holds(r.key, r.operator, tuple(r.values), name)
        for r in term.match_fields
    )


def admitted(pod, node) -> bool:
    if any(node.labels.get(k) != v for k, v in pod.node_selector.items()):
        return False
    terms = pod.node_affinity_required
    return not terms or any(term_holds(term, node) for term in terms)


def audit(cluster) -> list:
    refused = []
    for pod in cluster.pods.values():
        if pod.node_name is None or not (
            pod.node_selector or pod.node_affinity_required
        ):
            continue
        node = cluster.nodes.get(pod.node_name)
        if node is not None and not admitted(pod, node):
            refused.append(f"{pod.uid} on {node.name}")
    if not refused:
        return []
    shown = ", ".join(refused[:5])
    return [
        f"{len(refused)} bound pods sit on a node their nodeSelector or "
        f"required node affinity refuses: {shown}"
        + (" ..." if len(refused) > 5 else "")
    ]
