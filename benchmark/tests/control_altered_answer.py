"""The control of `correct`: the benchmark's own command, with one answer
altered where the program produces it. It has to end `correct: false`.

    python3 benchmark/tests/control_altered_answer.py --workload <cell> ...

takes `run.py`'s arguments. Every configuration states that placements are
bit-equal to the sequential solve in queue order. The control breaks that
guarantee the way a careless optimisation would: in every solve, the last
pod placed goes to another node that still has room for it, the one with
the most cpu left. The placement is legal (no node is overfilled, so the
audits and the hard-violation replay stay silent); it is not the
reference's, and the probe has to count it.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402  (puts the repo on sys.path)


def _alter(snap, result):
    assignment = np.array(result.assignment)
    placed = np.flatnonzero(assignment >= 0)
    if not placed.size:
        return result
    last = placed[-1]
    demand = np.array(snap.pods.req)
    demand[:, 3] = 1  # the pods slot: one a pod
    free = np.array(snap.nodes.alloc) - np.array(snap.nodes.requested)
    np.subtract.at(free, assignment[placed], demand[placed])
    room = (free >= demand[last]).all(axis=1) & np.array(snap.nodes.mask)
    room[assignment[last]] = False
    if not room.any():
        return result
    assignment[last] = int(np.argmax(np.where(room, free[:, 0], -1)))
    return result.replace(assignment=assignment)


def main() -> int:
    from scheduler_plugins_tpu.framework import runtime

    solve = runtime.Scheduler.solve

    def altered(self, snap, *args, **kwargs):
        return _alter(snap, solve(self, snap, *args, **kwargs))

    runtime.Scheduler.solve = altered
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
