"""The plain population with every node in one zone and every pod under one
required node-affinity term: scheduler_perf's SchedulingNodeAffinity on the
plain cluster. Stdlib only.

    Population(cluster, seed)   the configuration's `cluster` block, --seed

Nodes, requests, streams, names and the prefill are `harness/cluster_gen.py`'s,
through `populations/plain.py`, so a seed gives this population the cluster,
the prefill and the arrivals it gives the plain one. What is added:

- every node carries `cluster["node_labels"]` (upstream's
  `labelNodePrepareStrategy`: `topology.kubernetes.io/zone: zone1` on all of
  them);
- every pod, prefilled ones included, is the measured template: its
  `upsert_pod` line gains `"node_affinity": cluster["pod_template"]
  ["node_affinity"]` (the wire shape `NodeSelectorTerm.from_wire` reads: one
  required term, zone In [zone1, zone2]) and nothing else. The term admits
  every node, as upstream's does.

No shape comes from the seed: one spec, one label key, one zone, in every
run. The population has no objects and no side events.
"""

from __future__ import annotations

import functools
import json

from harness import cluster_gen as gen
from harness.spec import Unit
from populations import plain


class Population:
    def __init__(self, cluster: dict, seed: int):
        self.cluster = cluster
        self.seed = seed
        self._plain = plain.Population(cluster, seed)
        #: what the template adds to a plain pod's line, before its brace
        self._suffix = b',"node_affinity":%s}\n' % json.dumps(
            cluster["pod_template"]["node_affinity"], separators=(",", ":")
        ).encode()

    @functools.cached_property
    def node_specs(self) -> list:
        return self._plain.node_specs

    def nodes(self):
        labels = self.cluster["node_labels"]
        for name, cpu, mem, pods in self.node_specs:
            yield (json.dumps({
                "op": "upsert_node", "name": name,
                "allocatable": {"cpu": cpu, "memory": mem, "pods": pods},
                "labels": labels,
            }) + "\n").encode()

    def objects(self):
        return ()

    def _templated(self, unit) -> Unit:
        # a plain pod's line ends `}\n`
        head, pods, uids, removal, binds = unit
        return tuple.__new__(Unit, (
            head, (pods[0][:-2] + self._suffix,), uids, removal, binds,
        ))

    def prefill(self, count: int) -> list:
        return [self._templated(unit) for unit in self._plain.prefill(count)]

    def unit(self, stream: str, index: int) -> Unit:
        """The plain population's `index`-th pod of `stream`, as the
        template."""
        return self._templated(self._plain.unit(stream, index))

    def side(self, spec: dict, issue: int) -> bytes:
        raise ValueError(
            "the zone_nodeaffinity population has no side event "
            f"{spec['kind']!r}"
        )
