"""Closed loop: keep the cell's `outstanding` pods waiting for a bind.

A Job or queue controller that refills as its pods land, or a burst after
a restart. The client sends as fast as acks allow while fewer than K pods
are outstanding (sent minus the polled bound count), and deletes bound
arrivals at the same pace, oldest first, once they are more than K behind
the bound count, so occupancy is stationary. An arrival is due the moment
the loop is free to send it. A pending pod is never deleted. Arrivals come
and go by the population's unit (a pod; a gang); K and the counts are pods.
"""

from __future__ import annotations

import time


def run(ctx) -> None:
    outstanding = ctx.cell["outstanding"]
    while True:
        now = ctx.now()
        if ctx.over(now):
            return
        ctx.side_events()
        bound = ctx.bound_total()
        if len(ctx.due) - bound < outstanding:
            ctx.arrive(now)
            ctx.depart_oldest(behind=outstanding)
        else:
            time.sleep(0.002)
