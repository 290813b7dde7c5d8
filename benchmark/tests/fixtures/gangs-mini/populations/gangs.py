"""Gangs of equal size over the configuration's namespaces, one of them
under an ElasticQuota. Stdlib only. The fixture's population: it shows what
a population other than `plain` has to bring, and nothing of it is measured.

The unit of arrival is a gang: its PodGroup, then its members, each with the
pod-group label, in the gang's namespace. Units alternate over the
namespaces. Every `over_quota_every`-th unit (at `over_quota_at`) is in the
quota's namespace and asks for more cpu than the quota's `max` in all: its
first members find room, are reserved and wait; the last is refused; the
gang is rejected whole and stays pending, whatever else the namespace holds.
Such a unit says `binds: False`. Nodes and the prefill are the plain ones.

The PodGroups are a fixed roster of `slots` (and `wave_slots` for the
harness's waves), all made in `objects()`: a unit takes the next slot in
turn, renews its PodGroup (the creation stamp orders the queue) and leaves
it behind empty when it goes. The program sizes a cycle's gang arrays by
the number of PodGroups in the store, to the object, and compiles a solve
for every size it meets (`state/snapshot.py`: `G = max(len(gang_pos), 1)`),
so PodGroups that come and go with their gangs compile in most cycles and
a warm-up never settles (PERF.md, Open questions). The roster has to be
longer than the units alive at once.
"""

from __future__ import annotations

import json

from harness import cluster_gen as gen
from harness.spec import Unit
from populations import plain

POD_GROUP_LABEL = "scheduling.x-k8s.io/pod-group"


def _line(event: dict) -> bytes:
    return (json.dumps(event) + "\n").encode()


class Population(plain.Population):
    """The plain nodes and prefill; its own objects and units."""

    def __init__(self, cluster: dict, seed: int):
        super().__init__(cluster, seed)
        self.shape = cluster["gangs"]

    def objects(self):
        quota = self.shape["quota"]
        for namespace in self.shape["namespaces"]:
            yield _line({"op": "upsert_namespace", "name": namespace})
        yield _line({"op": "upsert_quota", "name": "quota",
                     "namespace": quota["namespace"],
                     "min": quota["min"], "max": quota["max"]})
        for stream, count in (("arrivals", self.shape["slots"]),
                              ("wave", self.shape["wave_slots"])):
            for index in range(count):
                yield self._pod_group(stream, index, 0)

    def _slot(self, stream: str, index: int) -> tuple:
        """(PodGroup name, namespace, over quota) of a stream's unit."""
        shape = self.shape
        over = index % shape["over_quota_every"] == shape["over_quota_at"]
        if over:
            namespace = shape["quota"]["namespace"]
        else:
            namespace = shape["namespaces"][index % len(shape["namespaces"])]
        if stream == "arrivals":
            return "g-%03d" % (index % shape["slots"]), namespace, over
        return "w-%03d" % (index % shape["wave_slots"]), namespace, over

    def _pod_group(self, stream: str, index: int, serial: int) -> bytes:
        group, namespace, _over = self._slot(stream, index)
        return _line({"op": "upsert_pod_group", "name": group,
                      "namespace": namespace,
                      "min_member": self.shape["size"],
                      "creation_ms": serial})

    def unit(self, stream: str, index: int) -> Unit:
        rng = self._streams.get(stream)
        if rng is None:
            rng = self._streams[stream] = gen.stream(self.seed, stream)
        quota, size = self.shape["quota"], self.shape["size"]
        kind = "arrivals" if stream == "arrivals" else "wave"
        group, namespace, over = self._slot(kind, index)
        serial = index if kind == "arrivals" else plain.WAVE_SERIAL + index
        head = self._pod_group(kind, index, serial)
        tag = "%s-%07d" % (stream.replace("/", "-"), index)
        pods, uids, removal = [], [], []
        for member in range(size):
            cpu, mem = gen.draw_request(rng, self.cluster["pod_requests"])
            if over:
                cpu = quota["max"]["cpu"] // size + 1
            name = f"{tag}-{member}"
            pods.append(_line({
                "op": "upsert_pod", "name": name, "namespace": namespace,
                "creation_ms": serial, "labels": {POD_GROUP_LABEL: group},
                "requests": {"cpu": cpu, "memory": mem},
            }))
            uids.append(f"{namespace}/{name}")
            removal.append(_line({"op": "delete_pod", "name": name,
                                  "namespace": namespace}))
        return Unit((head,), tuple(pods), tuple(uids), tuple(removal),
                    not over)

    def side(self, spec: dict, issue: int) -> bytes:
        raise ValueError(
            f"the gangs population has no side event {spec['kind']!r}"
        )
