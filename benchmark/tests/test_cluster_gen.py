"""The same seed gives the same cluster; the prefill fits its nodes."""

from harness import cluster_gen as gen
from harness import spec


def test_seeded_nodes_and_prefill_repeat_and_fit():
    cell = spec.Cell("basic-5000n.steady")
    cluster = cell.config["cluster"]
    nodes = gen.node_specs(cluster, 7)
    assert nodes == gen.node_specs(cluster, 7)
    assert nodes != gen.node_specs(cluster, 8)
    assert len(nodes) == 5000
    pods = gen.prefill(cluster, nodes, cell.mix["prefill_bound_pods"], 7)
    assert pods == gen.prefill(cluster, nodes, cell.mix["prefill_bound_pods"], 7)
    assert len(pods) == 50000 and len({p[0] for p in pods}) == 50000
    alloc = {n[0]: n for n in nodes}
    used: dict = {}
    for _name, cpu, mem, node in pods:
        row = used.setdefault(node, [0, 0, 0])
        row[0] += cpu
        row[1] += mem
        row[2] += 1
    for node, (cpu, mem, count) in used.items():
        assert cpu <= alloc[node][1] and mem <= alloc[node][2]
        assert count <= alloc[node][3]
    # in proportion to cores: a 16-core node holds about four times a
    # 4-core node's pods
    by_size: dict = {}
    for node, (_c, _m, count) in used.items():
        by_size.setdefault(alloc[node][1], []).append(count)
    mean = {size: sum(v) / len(v) for size, v in by_size.items()}
    assert 3.5 < mean[16000] / mean[4000] < 4.5
    share = sum(r[0] for r in used.values()) / sum(n[1] for n in nodes)
    assert 0.5 < share < 0.7  # about 60 % of the cluster's CPU


def test_two_configurations_share_one_cluster_stream():
    a = spec.Cell("basic-5000n.steady").config["cluster"]
    b = spec.Cell("trimaran-5000n.steady").config["cluster"]
    assert gen.node_specs(a, 3) == gen.node_specs(b, 3)
