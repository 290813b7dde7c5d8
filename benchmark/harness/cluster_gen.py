"""Seeded cluster and pod data, as feed events. Stdlib only.

Everything is drawn from `random.Random` streams named by the run's seed
and a purpose ("nodes", "prefill", ...), so the same `--seed` gives the same
cluster, the same prefill and the same pods in every cell that shares a
configuration's `cluster` block, whatever else the cell draws.

The SKUs and request ranges are those of `chip_smoke._serve_events`; the
load-metric distributions those of `models.scenarios.trimaran_scenario`.
"""

from __future__ import annotations

import json
import random


def stream(seed: int, purpose: str) -> random.Random:
    return random.Random(f"{seed}/{purpose}")


def node_specs(cluster: dict, seed: int) -> list:
    """[(name, cpu_milli, memory_bytes, pods), ...] for the configuration's
    cluster: each node takes one of its SKUs, uniformly."""
    rng = stream(seed, "nodes")
    skus = cluster["skus"]
    out = []
    for i in range(cluster["nodes"]):
        sku = skus[rng.randrange(len(skus))]
        out.append((cluster["node_name"] % i, sku["cpu_milli"],
                    sku["memory_bytes"], sku["pods"]))
    return out


def node_line(spec) -> bytes:
    name, cpu, mem, pods = spec
    return (json.dumps({
        "op": "upsert_node", "name": name,
        "allocatable": {"cpu": cpu, "memory": mem, "pods": pods},
    }) + "\n").encode()


def draw_request(rng: random.Random, requests: dict) -> tuple:
    """(cpu milli, memory bytes), each uniform on [low, high)."""
    cpu_lo, cpu_hi = requests["cpu_milli"]
    mem_lo, mem_hi = requests["memory_bytes"]
    return rng.randrange(cpu_lo, cpu_hi), rng.randrange(mem_lo, mem_hi)


def pod_line(name: str, serial: int, cpu: int, mem: int, node=None) -> bytes:
    """One `upsert_pod` event line. `serial` is the pod's creation stamp:
    the queue sorts on it, so pods are solved in the order they were made.
    With `node` the pod arrives already bound, as a feed replay delivers
    the pods of a running cluster."""
    bound = b"" if node is None else b',"node":"%s"' % node.encode()
    return (
        b'{"op":"upsert_pod","name":"%s","creation_ms":%d,'
        b'"requests":{"cpu":%d,"memory":%d}%s}\n'
        % (name.encode(), serial, cpu, mem, bound)
    )


def delete_line(name: str) -> bytes:
    return b'{"op":"delete_pod","name":"%s"}\n' % name.encode()


def prefill(cluster: dict, nodes: list, count: int, seed: int) -> list:
    """[(pod name, cpu, mem, node name), ...]: `count` bound pods spread
    over the nodes in proportion to their cores (largest remainders), each
    checked against what its node still has. A pod its node cannot hold
    goes to the next node that can."""
    rng = stream(seed, "prefill")
    total_cpu = sum(n[1] for n in nodes)
    exact = [count * n[1] / total_cpu for n in nodes]
    share = [int(x) for x in exact]
    by_remainder = sorted(
        range(len(nodes)), key=lambda i: (share[i] - exact[i], i)
    )
    for i in by_remainder[:count - sum(share)]:
        share[i] += 1
    free = [[n[1], n[2], n[3]] for n in nodes]
    out, spilled = [], []
    serial = 0
    for i, n_pods in enumerate(share):
        for _ in range(n_pods):
            cpu, mem = draw_request(rng, cluster["pod_requests"])
            name = f"p-{serial:06d}"
            serial += 1
            if cpu <= free[i][0] and mem <= free[i][1] and free[i][2] >= 1:
                free[i][0] -= cpu
                free[i][1] -= mem
                free[i][2] -= 1
                out.append((name, cpu, mem, nodes[i][0]))
            else:
                spilled.append((name, cpu, mem))
    cursor = 0
    for name, cpu, mem in spilled:
        for step in range(len(nodes)):
            i = (cursor + step) % len(nodes)
            if cpu <= free[i][0] and mem <= free[i][1] and free[i][2] >= 1:
                free[i][0] -= cpu
                free[i][1] -= mem
                free[i][2] -= 1
                out.append((name, cpu, mem, nodes[i][0]))
                cursor = i
                break
        else:
            raise ValueError(f"the cluster cannot hold prefill pod {name}")
    return out


def node_metrics_line(nodes: list, spec: dict, seed: int, issue: int) -> bytes:
    """One `metrics` event for every node: the load watcher's report,
    percentages of capacity drawn uniformly from the ranges in `spec`.
    `issue` numbers the report, so each refresh draws new values."""
    rng = stream(seed, f"node_metrics/{issue}")
    ranges = spec["percent_ranges"]
    return (json.dumps({
        "op": "metrics",
        "nodes": {
            n[0]: {key: rng.uniform(lo, hi) for key, (lo, hi) in ranges.items()}
            for n in nodes
        },
    }) + "\n").encode()
