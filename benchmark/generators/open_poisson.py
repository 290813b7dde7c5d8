"""Open loop: Poisson arrivals at the cell's fixed rate, whatever the
daemon does with them. Independent pod creators on a busy cluster.

Each arrival is due at the previous one's due time plus an exponential gap
drawn from the seed, and is timed from when it was due: a send that waits
for the feed lock makes the generator late, and that wait belongs to the
pod's delay. One departure follows each arrival, so occupancy is
stationary: first the prefilled pods in a seeded order, then arrivals,
oldest first, once the polled bound count has passed them. A pending pod is
never deleted.
"""

from __future__ import annotations

from harness import cluster_gen as gen

#: warm-up only: an ack this many intervals late is a compile, not traffic
FORGIVE_INTERVALS = 3


def run(ctx) -> None:
    gaps = gen.stream(ctx.seed, "gaps")
    victims = list(ctx.prefilled)
    gen.stream(ctx.seed, "departures").shuffle(victims)
    victims.reverse()  # pop() takes them in the shuffled order
    due = ctx.now()
    while True:
        due += int(gaps.expovariate(ctx.rate()) * 1e9)
        if ctx.over(due):
            return
        ctx.sleep_until(due)
        late = ctx.arrive(due)
        if ctx.warming_up() and late > FORGIVE_INTERVALS * ctx.interval_ns:
            ctx.forgive(late)
            due += late
        if victims:
            ctx.remove(victims.pop())
        else:
            ctx.depart_oldest()
