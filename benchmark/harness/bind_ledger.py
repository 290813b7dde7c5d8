"""The end of a pod's delay: the instant the store binds it.

A client of the feed cannot see a bind (the protocol has no bind egress),
so the benchmark takes its own stamp at the layer boundary where the
decision lands: `obs.ledger.Ledger.on_bind`, which `Cluster.bind` calls for
every pod. The stamp is `time.monotonic_ns()`, the clock of the client's
due stamps. The ring keeps its default size: `/healthz` walks it on every
call, and a deployment pays for 4,096 records, not for the window's pods.
"""

from __future__ import annotations

import time

from scheduler_plugins_tpu.obs.ledger import Ledger


class BindStampLedger(Ledger):
    def __init__(self):
        super().__init__()
        #: (pod uid, monotonic ns) in bind order
        self.bind_stamps: list = []
        #: monotonic ns at which each scheduling cycle opened
        self.cycle_stamps: list = []

    def cycle_open(self, now_ms: int):
        self.cycle_stamps.append(time.monotonic_ns())
        return super().cycle_open(now_ms)

    def on_bind(self, uid: str, node: str) -> None:
        self.bind_stamps.append((uid, time.monotonic_ns()))
        super().on_bind(uid, node)
