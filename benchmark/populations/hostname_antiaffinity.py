"""The plain population with one replica a node: scheduler_perf's
SchedulingPodAntiAffinity on the plain cluster. Stdlib only.

    Population(cluster, seed)   the configuration's `cluster` block, --seed

Nodes, requests, streams and names are `harness/cluster_gen.py`'s, through
`populations/plain.py`, so a seed gives this population the cluster and the
arrivals it gives the plain one. What is added:

- every node carries the hostname label (`cluster["hostname_label"]`) with
  its own name, as upstream's `uniqueNodeLabelStrategy` does: every node is
  a topology domain of its own;
- every pod, prefilled ones included, is the measured template
  (`cluster["pod_template"]`): its labels and its one required
  anti-affinity term, which matches every pod of the workload under the
  hostname key. Prefilled pods are in `cluster["namespaces"]["prefill"]`
  (upstream's init pods, `sched-0`), arrivals and the harness's waves in
  `cluster["namespaces"]["arrivals"]` (`sched-1`); the term names both;
- the prefill puts one pod on each of `count` distinct nodes, a seeded
  choice without replacement (stream `prefill/nodes`): dealt by cores, as
  `cluster_gen.prefill` deals, two could land on one node and the store
  would open in violation of the term. Their requests are the plain
  prefill's stream, and the smallest SKU holds any one pod.

No shape comes from the seed: the one selector group, the one topology key
and the domains (one a node) are the configuration's. The population has no
objects and no side events.
"""

from __future__ import annotations

import functools
import json

from harness import cluster_gen as gen
from harness.spec import Unit
from populations import plain


def _compact(value) -> bytes:
    return json.dumps(value, separators=(",", ":")).encode()


class Population:
    def __init__(self, cluster: dict, seed: int):
        self.cluster = cluster
        self.seed = seed
        self._plain = plain.Population(cluster, seed)
        template = cluster["pod_template"]
        spec = b',"labels":%s,"pod_anti_affinity":%s}\n' % (
            _compact(template["labels"]),
            _compact(template["pod_anti_affinity"]),
        )
        #: per role: the namespace's `uid` prefix, what the template adds to
        #: a plain pod's line before its brace, and to its delete line
        self._roles = {
            role: (
                namespace + "/",
                b',"namespace":"%s"' % namespace.encode() + spec,
                b',"namespace":"%s"}\n' % namespace.encode(),
            )
            for role, namespace in cluster["namespaces"].items()
        }

    @functools.cached_property
    def node_specs(self) -> list:
        return self._plain.node_specs

    def nodes(self):
        label = self.cluster["hostname_label"]
        for name, cpu, mem, pods in self.node_specs:
            yield (json.dumps({
                "op": "upsert_node", "name": name,
                "allocatable": {"cpu": cpu, "memory": mem, "pods": pods},
                "labels": {label: name},
            }) + "\n").encode()

    def objects(self):
        return ()

    def _templated(self, role: str, name: str, line: bytes) -> Unit:
        """A plain pod's line (it ends `}\\n`, as its delete line does) as
        the template, in the role's namespace."""
        prefix, spec, namespace = self._roles[role]
        return tuple.__new__(Unit, (
            (), (line[:-2] + spec,), (prefix + name,),
            (gen.delete_line(name)[:-2] + namespace,), True,
        ))

    def prefill(self, count: int) -> list:
        specs = self.node_specs
        if count > len(specs):
            raise ValueError(
                f"{count} prefilled pods, one a node, on {len(specs)} nodes"
            )
        chosen = gen.stream(self.seed, "prefill/nodes").sample(
            range(len(specs)), count
        )
        requests = gen.stream(self.seed, "prefill")
        units = []
        for serial, index in enumerate(chosen):
            cpu, mem = gen.draw_request(requests, self.cluster["pod_requests"])
            name = f"p-{serial:06d}"
            units.append(self._templated(
                "prefill", name,
                gen.pod_line(name, 0, cpu, mem, specs[index][0]),
            ))
        return units

    def unit(self, stream: str, index: int) -> Unit:
        """The plain population's `index`-th pod of `stream`, as the
        template."""
        _head, pods, uids, _removal, _binds = self._plain.unit(stream, index)
        name = uids[0][len(plain.NAMESPACE_SLASH):]
        return self._templated("arrivals", name, pods[0])

    def side(self, spec: dict, issue: int) -> bytes:
        raise ValueError(
            "the hostname_antiaffinity population has no side event "
            f"{spec['kind']!r}"
        )
