"""Resource vocabulary and integer quantity encoding.

The reference stores quantities as k8s `resource.Quantity` int64 values — CPU in
millicores, memory/ephemeral-storage in bytes, extended ("scalar") resources as
raw counts (see /root/reference/pkg/noderesources/resource_allocation.go:84-96
and /root/reference/pkg/capacityscheduling/elasticquota.go:189-221). We pin the
same integer units so decisions are bit-identical; the tensor layout fixes an
ordered resource axis R shared by every array in a snapshot.
"""

from __future__ import annotations

from collections.abc import Iterable, Mapping

import numpy as np

# Canonical names (match k8s v1.ResourceName strings).
CPU = "cpu"
MEMORY = "memory"
EPHEMERAL_STORAGE = "ephemeral-storage"
PODS = "pods"

#: The first four slots of every resource axis, in fixed order. Extended
#: resources (nvidia.com/gpu, hugepages-2Mi, ...) are appended per snapshot.
CANONICAL = (CPU, MEMORY, EPHEMERAL_STORAGE, PODS)

# Defaults used by the upstream "NonZeroRequested" accounting that the
# Allocatable scorer reads (upstream k/k pkg/scheduler/util/nonzero):
# pods with no cpu/mem request are charged these amounts for *scoring* only.
DEFAULT_MILLI_CPU_REQUEST = 100  # 0.1 core
DEFAULT_MEMORY_REQUEST = 200 * 1024 * 1024  # 200 MiB


class ResourceIndex:
    """Ordered resource-name <-> axis-position mapping for one snapshot.

    Immutable once built. `encode` turns a {name: int} mapping into a dense
    int64 vector on the fixed axis; unknown names raise (callers must build the
    index from the union of names up front — silent drops would corrupt quota
    sums).
    """

    def __init__(self, extended: Iterable[str] = ()):
        names = list(CANONICAL)
        for name in extended:
            if name not in names:
                names.append(name)
        self._names: tuple[str, ...] = tuple(names)
        self._pos = {name: i for i, name in enumerate(self._names)}

    @classmethod
    def union(cls, *mappings: Mapping[str, int]) -> "ResourceIndex":
        """Build an index covering every resource named in `mappings`."""
        extended = []
        for m in mappings:
            for name in m:
                if name not in CANONICAL and name not in extended:
                    extended.append(name)
        return cls(extended)

    @property
    def names(self) -> tuple[str, ...]:
        return self._names

    def __len__(self) -> int:
        return len(self._names)

    def __contains__(self, name: str) -> bool:
        return name in self._pos

    def position(self, name: str) -> int:
        return self._pos[name]

    def slots(self, quantities: Mapping[str, int], default: int = 0) -> list[int]:
        """`encode` as a plain list: for a caller that lowers several
        mappings and makes one array of them. Unknown names raise."""
        vals = [default] * len(self._names)
        pos = self._pos
        for name, qty in quantities.items():
            vals[pos[name]] = int(qty)
        return vals

    def encode(self, quantities: Mapping[str, int], default: int = 0) -> np.ndarray:
        return np.array(self.slots(quantities, default), dtype=np.int64)

    def decode(self, vec: np.ndarray) -> dict[str, int]:
        return {name: int(vec[i]) for i, name in enumerate(self._names) if vec[i]}

    def is_extended(self, name: str) -> bool:
        return name not in CANONICAL


def add_quantities(a: Mapping[str, int], b: Mapping[str, int]) -> dict[str, int]:
    out = dict(a)
    for k, v in b.items():
        out[k] = out.get(k, 0) + v
    return out


def max_quantities(a: Mapping[str, int], b: Mapping[str, int]) -> dict[str, int]:
    out = dict(a)
    for k, v in b.items():
        out[k] = max(out.get(k, 0), v)
    return out
