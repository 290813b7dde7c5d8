"""The plain population spread over zones: scheduler_perf's
TopologySpreading on the plain cluster. Stdlib only.

    Population(cluster, seed)   the configuration's `cluster` block, --seed

Nodes, requests, streams and names are `harness/cluster_gen.py`'s, through
`populations/plain.py`, so a seed gives this population the cluster and the
arrivals it gives the plain one. What is added:

- every node carries the zone label (`cluster["zone_label"]`) with one of
  `cluster["zones"]`, taken in turn by node index, as upstream's
  `labelNodePrepareStrategy` does;
- every pod, prefilled ones included, is the measured template
  (`cluster["pod_template"]`): its labels and its one topology-spread
  constraint;
- the prefill is dealt to the zones in turn (pod j belongs to zone
  j mod zones), and within its zone to the zone's nodes in proportion to
  their cores, by `cluster_gen.prefill` on the zone's own nodes and stream.
  So the store opens with zone counts within one of each other: inside
  `maxSkew: 1`.

No shape comes from the seed: the zones, the one selector group and the one
topology key are the configuration's. The population has no side events.
"""

from __future__ import annotations

import functools
import json

from harness import cluster_gen as gen
from harness.spec import Unit
from populations import plain

NAMESPACE_SLASH = plain.NAMESPACE_SLASH


class Population:
    def __init__(self, cluster: dict, seed: int):
        self.cluster = cluster
        self.seed = seed
        self._plain = plain.Population(cluster, seed)
        template = cluster["pod_template"]
        #: what the template adds to a plain pod's line, before its brace
        self._suffix = (
            b',"labels":%s,"topology_spread":%s}\n' % (
                json.dumps(template["labels"],
                           separators=(",", ":")).encode(),
                json.dumps(template["topology_spread"],
                           separators=(",", ":")).encode(),
            )
        )

    @functools.cached_property
    def node_specs(self) -> list:
        return self._plain.node_specs

    def zone_of(self, index: int) -> str:
        zones = self.cluster["zones"]
        return zones[index % len(zones)]

    def nodes(self):
        label = self.cluster["zone_label"]
        for index, (name, cpu, mem, pods) in enumerate(self.node_specs):
            yield (json.dumps({
                "op": "upsert_node", "name": name,
                "allocatable": {"cpu": cpu, "memory": mem, "pods": pods},
                "labels": {label: self.zone_of(index)},
            }) + "\n").encode()

    def objects(self):
        return ()

    def _templated(self, line: bytes) -> bytes:
        # a plain pod's line ends `}\n`
        return line[:-2] + self._suffix

    def prefill(self, count: int) -> list:
        n_zones = len(self.cluster["zones"])
        units = [None] * count
        for zone in range(n_zones):
            share = len(range(zone, count, n_zones))
            placed = gen.prefill(
                self.cluster, self.node_specs[zone::n_zones], share,
                f"{self.seed}/zone-{zone}",
            )
            for j, (_name, cpu, mem, node) in enumerate(placed):
                serial = j * n_zones + zone
                name = f"p-{serial:06d}"
                units[serial] = Unit(
                    (), (self._templated(
                        gen.pod_line(name, 0, cpu, mem, node)
                    ),),
                    (NAMESPACE_SLASH + name,), (gen.delete_line(name),), True,
                )
        return units

    def unit(self, stream: str, index: int) -> Unit:
        """The plain population's `index`-th pod of `stream`, as the
        template."""
        head, pods, uids, removal, binds = self._plain.unit(stream, index)
        return tuple.__new__(Unit, (
            head, (pods[0][:-2] + self._suffix,), uids, removal, binds,
        ))

    def side(self, spec: dict, issue: int) -> bytes:
        raise ValueError(
            f"the zoned_spread population has no side event {spec['kind']!r}"
        )
