"""The plain population: nodes of cpu, memory and a pod count, and pods of
cpu and memory in the namespace `default`. Stdlib only.

It is `harness/cluster_gen.py` behind the interface every population has;
the streams, their names and the order they are drawn in are that module's,
so a seed gives the cluster, the prefill, the arrivals, the reports and the
waves it always gave (`tests/test_populations.py` holds the digests).

    Population(cluster, seed)   the configuration's `cluster` block, --seed
    nodes()           the `upsert_node` lines
    objects()         what must exist before the first pod (nothing here)
    prefill(count)    [Unit, ...]: `count` pods that arrive bound
    unit(stream, i)   the i-th unit of arrival of a named stream: one pod
    side(spec, issue) the line of a `feed_side_events` entry, by its kind
"""

from __future__ import annotations

import functools

from harness import cluster_gen as gen
from harness.spec import Unit

NAMESPACE_SLASH = "default/"
#: a wave's pods sort behind every arrival (`creation_ms` orders the queue)
WAVE_SERIAL = 1_000_000_000


class Population:
    def __init__(self, cluster: dict, seed: int):
        self.cluster = cluster
        self.seed = seed
        self._streams: dict = {}

    @functools.cached_property
    def node_specs(self) -> list:
        """Drawn when first needed: a wave's population never asks."""
        return gen.node_specs(self.cluster, self.seed)

    def nodes(self):
        return (gen.node_line(n) for n in self.node_specs)

    def objects(self):
        return ()

    def prefill(self, count: int) -> list:
        return [
            _pod(gen.pod_line(name, 0, cpu, mem, node), name)
            for name, cpu, mem, node in gen.prefill(
                self.cluster, self.node_specs, count, self.seed
            )
        ]

    def _open(self, stream: str) -> tuple:
        """A stream's state: its draws and their ranges, its pods' name
        format and first serial. Arrival `index` of the run is `a-<index>`,
        made at `index`; pod `index` of a wave `<prefix>/<size>` is
        `<prefix>-<size>-<index>`."""
        requests = self.cluster["pod_requests"]
        if stream == "arrivals":
            name_format, first_serial = "a-%07d", 0
        else:
            name_format = stream.replace("/", "-") + "-%06d"
            first_serial = WAVE_SERIAL
        state = self._streams[stream] = (
            gen.stream(self.seed, stream).randrange,
            *requests["cpu_milli"], *requests["memory_bytes"],
            name_format, first_serial,
        )
        return state

    def unit(self, stream: str, index: int) -> Unit:
        """The stream's `index`-th pod. Each stream's requests are drawn in
        the order they are asked for. The window's client calls this once an
        arrival, between an ack and the next line, so it is kept short: the
        two draws are `gen.draw_request`'s, in its order, without the call."""
        (randrange, cpu_lo, cpu_hi, mem_lo, mem_hi, name_format,
         first_serial) = self._streams.get(stream) or self._open(stream)
        cpu = randrange(cpu_lo, cpu_hi)
        mem = randrange(mem_lo, mem_hi)
        name = name_format % index
        return tuple.__new__(Unit, (
            (), (gen.pod_line(name, first_serial + index, cpu, mem),),
            (NAMESPACE_SLASH + name,), (gen.delete_line(name),), True,
        ))

    def side(self, spec: dict, issue: int) -> bytes:
        if spec["kind"] != "node_metrics":
            raise ValueError(
                f"the plain population has no side event {spec['kind']!r}"
            )
        return gen.node_metrics_line(self.node_specs, spec, self.seed, issue)


def _pod(line: bytes, name: str) -> Unit:
    return Unit((), (line,), (NAMESPACE_SLASH + name,),
                (gen.delete_line(name),), True)
