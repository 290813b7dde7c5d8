"""GPU training jobs under ElasticQuota: a job queue's arrivals on a cluster
of GPU nodes, one tenant a namespace. Stdlib only.

    Population(cluster, seed)   the configuration's `cluster` block, --seed

A unit of arrival is a job: `size` members drawn from the configuration's
`jobs.sizes` (mostly one GPU; most pods in gangs of 8 and more), each
member one `nvidia.com/gpu` and the job's one draw of cpu (whole cores) and
memory (whole GiB). A job of one is a plain pod, a job of two or more a
PodGroup with `minMember = size` and its members labelled. A tenant is
drawn Zipf over a seeded permutation of the namespaces; each has an
ElasticQuota on cpu, memory and the GPU, `min` its equal share of the
cluster, `max` far above what the closed loop keeps alive, so the borrowing
arithmetic runs on every pod and no unit that is to bind is ever refused.

Every `held_every`-th job of the window's stream (at `held_at`) is a held
unit: a gang of `held_size` in the namespace `capped`, whose quota allows
half of it. Its first members are reserved and wait, the next is refused,
the gang is rejected whole and stays pending for the rest of the run, tried
again as the queue's back-off allows. It says `binds: False`. The harness's
waves hold none, and a wave `<prefix>/<size>` is exactly `size` pods: its
last job is cut to the room that is left (a gang of that many), so the cycle
that solves a wave lands in the pod bucket of `size`, whatever the seed. Left
to whole jobs of up to 64, a wave of 64 stopped at the first job that did not
fit and warmed the bucket 8, 16, 32 or 64 as the seed chose.

**Nothing a program's shape depends on may come from the seed.** The
PodGroups are therefore a fixed roster, all made in `objects()`: `slots` for
the window's stream (a gang takes the next in turn, renews its PodGroup and
leaves it behind empty when it goes), `held_slots` (a held gang keeps its
own for good) and `wave_slots` for the harness's waves. A slot belongs to
one namespace for the whole run, drawn by the same Zipf when the roster is
made, and a gang is its slot's tenant's; a plain pod draws its own. So
every seed gives the same number of nodes, namespaces, quotas and
PodGroups, and the seed moves only who asks for what. The roster has to be
longer than the gangs alive at once (the configuration's file has the sum).
"""

from __future__ import annotations

import bisect
import functools
import json

from harness import cluster_gen as gen
from harness.spec import Unit
from populations import plain

POD_GROUP_LABEL = "scheduling.x-k8s.io/pod-group"
GIB = 1 << 30


def _line(event: dict) -> bytes:
    return (json.dumps(event) + "\n").encode()


def _cdf(weights) -> list:
    """Running shares of the weights' sum: the upper edges of each value's
    stretch of [0, 1)."""
    weights = list(weights)
    total, run, edges = sum(weights), 0.0, []
    for w in weights:
        run += w / total
        edges.append(run)
    return edges


class Population(plain.Population):
    def __init__(self, cluster: dict, seed: int):
        super().__init__(cluster, seed)
        jobs = self.jobs = cluster["jobs"]
        self.gpu = jobs["gpu"]
        self.tenants = [jobs["tenant_name"] % i for i in range(jobs["tenants"])]
        # Zipf over a seeded permutation: rank r (from 0) weighs 1/(r+1)^s
        self._by_rank = gen.stream(seed, "tenants").sample(
            self.tenants, len(self.tenants)
        )
        self._tenant_cdf = _cdf(
            1.0 / (r + 1) ** jobs["zipf_s"] for r in range(len(self.tenants))
        )
        self._size_cdf = _cdf(jobs["weights"])
        #: per stream: [its draws, the next job's index, gangs so far, the
        #: pods left to a wave (None for the window's stream)]
        self._state: dict = {}

    # -- draws ---------------------------------------------------------------
    @staticmethod
    def _pick(rng, values, cdf):
        """The value whose share of [0, 1) a uniform draw falls in."""
        return values[min(bisect.bisect_right(cdf, rng.random()),
                          len(values) - 1)]

    def _tenant(self, rng) -> str:
        return self._pick(rng, self._by_rank, self._tenant_cdf)

    def _size(self, rng) -> int:
        return self._pick(rng, self.jobs["sizes"], self._size_cdf)

    def _ask(self, rng) -> tuple:
        """(cpu milli, memory bytes) of a job's members."""
        cores_lo, cores_hi = self.jobs["cpu_cores"]
        gib_lo, gib_hi = self.jobs["memory_gib"]
        return (1000 * rng.randint(cores_lo, cores_hi),
                GIB * rng.randint(gib_lo, gib_hi))

    @functools.cached_property
    def roster(self) -> dict:
        """{"arrivals": [namespace of slot 0, ...], "wave": [...]}: a slot's
        tenant, drawn once from the seed, the same in every process that
        makes this population."""
        rng = gen.stream(self.seed, "roster")
        return {
            kind: [self._tenant(rng) for _ in range(self.jobs[key])]
            for kind, key in (("arrivals", "slots"), ("wave", "wave_slots"))
        }

    # -- what is there before the first pod ------------------------------------
    def nodes(self):
        sku = self.cluster["skus"][0]
        alloc = {"cpu": sku["cpu_milli"], "memory": sku["memory_bytes"],
                 "pods": sku["pods"], **sku["extended"]}
        for i in range(self.cluster["nodes"]):
            yield _line({"op": "upsert_node",
                         "name": self.cluster["node_name"] % i,
                         "allocatable": alloc})

    def objects(self):
        jobs, capped = self.jobs, self.jobs["capped"]
        for namespace in self.tenants + [capped["namespace"]]:
            yield _line({"op": "upsert_namespace", "name": namespace})
        for namespace in self.tenants:
            yield _line({"op": "upsert_quota", "name": "quota",
                         "namespace": namespace,
                         "min": jobs["quota"]["min"],
                         "max": jobs["quota"]["max"]})
        yield _line({"op": "upsert_quota", "name": "quota",
                     "namespace": capped["namespace"],
                     "min": capped["min"], "max": capped["max"]})
        for kind, prefix in (("arrivals", "g"), ("wave", "w")):
            for slot, namespace in enumerate(self.roster[kind]):
                yield self._pod_group(f"{prefix}-{slot:04d}", namespace, 1, 0)
        for slot in range(capped["held_slots"]):
            yield self._pod_group(f"h-{slot:04d}", capped["namespace"], 1, 0)

    @staticmethod
    def _pod_group(name: str, namespace: str, size: int, serial: int) -> bytes:
        return _line({"op": "upsert_pod_group", "name": name,
                      "namespace": namespace, "min_member": size,
                      "creation_ms": serial})

    def _pod(self, name, namespace, serial, cpu, mem, group=None, node=None):
        event = {"op": "upsert_pod", "name": name, "namespace": namespace,
                 "creation_ms": serial,
                 "requests": {"cpu": cpu, "memory": mem, self.gpu: 1}}
        if group is not None:
            event["labels"] = {POD_GROUP_LABEL: group}
        if node is not None:
            event["node"] = node
        return _line(event)

    def prefill(self, count: int) -> list:
        """`count` plain one-GPU pods that arrive bound, tenants by the same
        Zipf, spread over the nodes in proportion to their cores (largest
        remainders, as `plain` spreads them): the nodes are one SKU, so one
        pod each from the first node on, then a second each."""
        rng = gen.stream(self.seed, "prefill")
        n_nodes = self.cluster["nodes"]
        per_node = self.cluster["skus"][0]["extended"][self.gpu]
        if count > n_nodes * per_node:
            raise ValueError(f"the cluster cannot hold {count} prefill pods")
        share = [count // n_nodes + (i < count % n_nodes)
                 for i in range(n_nodes)]
        units, serial = [], 0
        for i, n_pods in enumerate(share):
            node = self.cluster["node_name"] % i
            for _ in range(n_pods):
                cpu, mem = self._ask(rng)
                namespace = self._tenant(rng)
                name = f"p-{serial:06d}"
                serial += 1
                units.append(Unit(
                    (), (self._pod(name, namespace, 0, cpu, mem, node=node),),
                    (f"{namespace}/{name}",),
                    (_line({"op": "delete_pod", "name": name,
                            "namespace": namespace}),),
                    True,
                ))
        return units

    # -- a unit of arrival: a job ------------------------------------------------
    def unit(self, stream: str, index: int) -> Unit:
        """The stream's `index`-th job. A stream's jobs are drawn in order,
        from 0: a gang's slot is its number among the stream's gangs."""
        arrivals = stream == "arrivals"
        state = self._state.get(stream)
        if state is None:
            room = None if arrivals else int(stream.rsplit("/", 1)[1])
            state = self._state[stream] = [
                gen.stream(self.seed, stream), 0, 0, room,
            ]
        rng, expected, gangs, room = state
        if index != expected:
            raise ValueError(
                f"stream {stream!r}: job {index} asked for, {expected} is next"
            )
        state[1] = index + 1
        jobs, capped = self.jobs, self.jobs["capped"]
        serial = index if arrivals else plain.WAVE_SERIAL + index
        tag = "%s-%07d" % (stream.replace("/", "-"), index)
        cpu, mem = self._ask(rng)
        size = self._size(rng)
        if room:  # a wave's last job fills it exactly
            size = min(size, room)
            state[3] = room - size
        plain_tenant = self._tenant(rng)  # drawn for every job: one stream
        number = index // capped["held_every"]
        # past the last held slot a job binds like any other
        held = (arrivals and number < capped["held_slots"]
                and index % capped["held_every"] == capped["held_at"])
        if held:
            size, namespace = capped["held_size"], capped["namespace"]
            group = f"h-{number:04d}"
        elif size == 1:
            size, namespace, group = 1, plain_tenant, None
        else:
            kind, prefix = ("arrivals", "g") if arrivals else ("wave", "w")
            slot = gangs % len(self.roster[kind])
            state[2] = gangs + 1
            namespace = self.roster[kind][slot]
            group = f"{prefix}-{slot:04d}"
        head = ()
        if group is not None:
            head = (self._pod_group(group, namespace, size, serial),)
        pods, uids, removal = [], [], []
        for member in range(size):
            name = f"{tag}-{member}"
            pods.append(self._pod(name, namespace, serial, cpu, mem, group))
            uids.append(f"{namespace}/{name}")
            removal.append(_line({"op": "delete_pod", "name": name,
                                  "namespace": namespace}))
        return Unit(head, tuple(pods), tuple(uids), tuple(removal), not held)

    def side(self, spec: dict, issue: int) -> bytes:
        raise ValueError(
            f"the gpu_gangs population has no side event {spec['kind']!r}"
        )
