"""The load generator: a process of its own, stdlib only.

Started by `run.py` with one JSON plan on its standard input. It never
imports JAX or the program: it speaks the feed's wire protocol over TCP and
reads `/healthz` over HTTP, as an agent and an operator outside the daemon's
process would, and keeps the generator off the daemon's interpreter lock.

Two threads: the generator (this file's main thread, which runs the mix's
generator from `generators/<kind>.py`) and a poller that reads `/healthz` at
most ten times a second. A third, idle one reads the parent's messages.

Every time stamp is `time.monotonic_ns()`: CLOCK_MONOTONIC is one clock for
every process of a Linux machine, and the parent checks at the handshake
that its own reading brackets this process's.

Protocol with the parent, one JSON object per line:
    parent -> child   the plan; {"clock": ns}; {"go": true};
                      {"window_start_ns", "window_ns"};
                      {"stop": true}; sweeps only: {"rate": pods_per_s},
                      {"mark": path} (write the stamps of the arrivals
                      since the last mark to `path`)
    child -> parent   {"event": "hello"|"clock"|"loaded"|"generating"|
                       "marked"|"done"|"error", ...}
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import spec, wire  # noqa: E402


def say(**message) -> None:
    sys.stdout.write(json.dumps(message) + "\n")
    sys.stdout.flush()


class Context:
    """What a generator sees: the feed, the clock, the daemon's bound count
    as the poller last read it, the cell's and the mix's parameters, and
    the recorders every sample goes through."""

    def __init__(self, plan: dict):
        self.plan = plan
        self.seed = plan["seed"]
        self.cell = plan["cell"]
        self.mix = plan["mix"]
        self.config = plan["config"]
        self.cluster = plan["config"]["cluster"]
        self.interval_ns = int(plan["cycle_interval_s"] * 1e9)
        self.cooldown_ns = int(plan["cooldown_s"] * 1e9)
        self.feed = wire.Feed(*plan["feed"])
        self.population = spec.population(self.config, self.seed)
        # parent's announcements
        self.window_start_ns = None
        self.window_end_ns = None
        self.stopped = False
        self.go = threading.Event()
        self._rate = self.cell.get("rate_pods_per_s")
        # poller's view
        self._bound_base = 0
        self._bound = 0
        self.polls = []  # (t_ns, bound_total, pending, cycles, rtt_ns)
        # samples, one entry per arriving pod that is to bind (the pods of
        # one unit share its due time)
        self.due = []     # when it was due
        self.sent = []    # ... when its line was written
        self.acked = []   # ... when its ack was read
        self.uids = []    # ... the uid the store knows it by
        #: (sent_ns, ack latency ns) of every other line: a delete, a unit's
        #: head, the pods of a unit that is not to bind
        self.other_ack_ns = []
        self.refused = 0
        self.deletes = 0  # pods taken away again
        self.held = 0     # pods of units that are not to bind: they stay
        self.units_sent = 0
        self.forgiven_ns = 0
        self.prefilled = []  # the prefill's units
        #: (pods, removal lines) of the units sent, to bind, not departed
        self._live = collections.deque()
        self._departed = 0   # pods of the arrivals that have departed
        self._side = []  # [next due ns, spec, issue number]
        self._marked = 0

    # -- time -------------------------------------------------------------
    @staticmethod
    def now() -> int:
        return time.monotonic_ns()

    def warming_up(self) -> bool:
        return self.window_start_ns is None or self.now() < self.window_start_ns

    def over(self, t_ns: int) -> bool:
        """True once the generator has no more arrivals to make: a few
        intervals after the window (the plan's `cooldown_s`), so that the
        cycles which bind the window's last pods see the traffic the others
        saw, not its end."""
        return self.stopped or (
            self.window_end_ns is not None
            and t_ns >= self.window_end_ns + self.cooldown_ns
        )

    def rate(self) -> float:
        return self._rate

    def sleep_until(self, t_ns: int) -> None:
        self.side_events()
        wait = t_ns - self.now()
        if wait > 0:
            time.sleep(wait / 1e9)

    # -- the daemon, as the poller last saw it -------------------------------
    def bound_total(self) -> int:
        """Pods the daemon has bound since the generator started."""
        return self._bound - self._bound_base

    def poll_forever(self) -> None:
        url = self.plan["health"]
        period_s = max(0.1, self.mix["healthz_poll_period_s"])
        while not self.stopped:
            t0 = self.now()
            try:
                health = wire.healthz(url)
            except OSError:
                return  # the daemon is gone: the parent reports why
            t1 = self.now()
            self._bound = health["bound_total"]
            self.polls.append((t1, health["bound_total"], health["pending"],
                               health["cycles"], t1 - t0))
            time.sleep(max(0.0, period_s - (t1 - t0) / 1e9))

    # -- traffic ------------------------------------------------------------
    def arrive(self, due_ns: int) -> int:
        """Send the population's next unit of arrival, due at `due_ns`, one
        acknowledged line at a time; returns how late its last ack came
        back. Every pod of the unit is an arrival due at `due_ns`."""
        head, pods, uids, removal, binds = self.population.unit(
            "arrivals", self.units_sent
        )
        self.units_sent += 1
        if not binds:
            self._send_other(head + pods)
            self.held += len(pods)
            return self.now() - due_ns
        if head:
            self._send_other(head)
        self.uids.extend(uids)  # first: `listen` slices by `acked`
        for line in pods:
            sent = self.now()
            ack = self.feed.send_line(line)
            acked = self.now()
            self.refused += wire.refused(ack)
            self.due.append(due_ns)
            self.sent.append(sent)
            self.acked.append(acked)
        self._live.append((len(pods), removal))
        return acked - due_ns

    def _send_other(self, lines) -> None:
        for line in lines:
            sent = self.now()
            ack = self.feed.send_line(line)
            self.other_ack_ns.append((sent, self.now() - sent))
            self.refused += wire.refused(ack)

    def remove(self, unit) -> None:
        """Take a unit away again, through its own removal lines."""
        self._send_other(unit.removal)
        self.deletes += len(unit.pods)

    def depart_oldest(self, behind: int = 0) -> bool:
        """Take away the oldest arrival still there, if the polled bound
        count is `behind` pods or more past its last pod: it has bound, by
        the order pods are solved in. A pending pod is never deleted."""
        if not self._live:
            return False
        size, removal = self._live[0]
        if self._departed + size > self._bound - self._bound_base - behind:
            return False
        self._live.popleft()
        self._send_other(removal)
        self.deletes += size
        self._departed += size
        return True

    def forgive(self, late_ns: int) -> None:
        """Warm-up only: a stall of several intervals is a program being
        compiled, not traffic. The generator moves its schedule by it, so
        that no burst follows the stall and compiles shapes the window
        will never use."""
        self.forgiven_ns += late_ns

    # -- side events of the configuration (load-metric reports) --------------
    def plan_side_events(self) -> None:
        now = self.now()
        for spec in self.config.get("feed_side_events", []):
            if spec.get("at_setup"):
                self.feed.send_line(self._side_line(spec, 0))
            self._side.append([now + int(spec["period_s"] * 1e9), spec, 1])

    def _side_line(self, spec: dict, issue: int) -> bytes:
        return self.population.side(spec, issue)

    def phase_side_events(self) -> None:
        """The window is known now: put one report of each kind at its
        phase inside it, and the following ones a period apart."""
        for entry in self._side:
            at = self.window_start_ns + int(
                entry[1]["window_phase"]
                * (self.window_end_ns - self.window_start_ns)
            )
            if at > self.now():
                entry[0] = at

    def side_events(self) -> None:
        now = self.now()
        for entry in self._side:
            if now >= entry[0]:
                due, spec, issue = entry
                self.feed.send_line(self._side_line(spec, issue))
                entry[0] = due + int(spec["period_s"] * 1e9)
                entry[2] = issue + 1


def listen(ctx: Context) -> None:
    for raw in sys.stdin:
        message = json.loads(raw)
        if "clock" in message:
            say(event="clock", ns=ctx.now())
        if "window_start_ns" in message:
            ctx.window_end_ns = message["window_start_ns"] + message["window_ns"]
            ctx.window_start_ns = message["window_start_ns"]
            ctx.phase_side_events()
        if message.get("go"):
            ctx.go.set()
        if "rate" in message:
            ctx._rate = message["rate"]
        if "mark" in message:
            first, ctx._marked = ctx._marked, len(ctx.acked)
            with open(message["mark"], "w") as f:
                json.dump({"uids": ctx.uids[first:ctx._marked],
                           "due_ns": ctx.due[first:ctx._marked],
                           "sent_ns": ctx.sent[first:ctx._marked]}, f)
            say(event="marked", path=message["mark"])
        if message.get("stop"):
            ctx.stopped = True
    # end of input: the parent is gone (it never closes this pipe while it
    # lives), so there is nobody to report to and nothing may be left behind
    os._exit(1)


def load(ctx: Context) -> dict:
    """Set-up traffic: the population's nodes, its objects, the mix's
    prefill of bound pods, the configuration's first side events, then a
    `sync` fence."""
    t0 = ctx.now()
    refused = ctx.feed.send_all(ctx.population.nodes())
    refused += ctx.feed.send_all(ctx.population.objects())
    t1 = ctx.now()
    ctx.prefilled = ctx.population.prefill(ctx.mix["prefill_bound_pods"])
    refused += ctx.feed.send_all(
        line for unit in ctx.prefilled for line in unit.head + unit.pods
    )
    t2 = ctx.now()
    ctx.plan_side_events()
    ack = ctx.feed.send({"op": "sync"})
    if refused or not ack.get("ok"):
        raise RuntimeError(f"set-up traffic refused: {refused} events, {ack}")
    return {"nodes": ack["nodes"], "pods": ack["pods"],
            "pending": ack["pending"], "nodes_s": (t1 - t0) / 1e9,
            "prefill_s": (t2 - t1) / 1e9}


def drain(ctx: Context) -> dict:
    """After the last arrival: fence until nothing is pending but the pods
    that are not to bind. A pod counts as failed by when it bound (the
    parent holds it to the grace), not by how long this takes."""
    deadline = ctx.now() + int(ctx.plan["drain_limit_s"] * 1e9)
    ack = ctx.feed.send({"op": "sync"})
    while ack.get("pending", 0) > ctx.held and ctx.now() < deadline:
        time.sleep(0.05)
        ack = ctx.feed.send({"op": "sync"})
    return ack


def report(ctx: Context, sync_ack: dict) -> dict:
    t0, t1 = ctx.window_start_ns or 0, ctx.window_end_ns or 0
    window = [i for i, due in enumerate(ctx.due) if t0 <= due < t1]
    sent_in = [i for i, sent in enumerate(ctx.sent) if t0 <= sent < t1]
    return {
        # pods: sent to bind, sent to stay pending, taken away, prefilled
        "arrivals": len(ctx.due), "held": ctx.held, "deletes": ctx.deletes,
        "prefilled": sum(len(unit.pods) for unit in ctx.prefilled),
        "units": ctx.units_sent, "refused": ctx.refused,
        "forgiven_s": ctx.forgiven_ns / 1e9,
        # arrivals due inside the window: uid and due stamp
        "window_uids": [ctx.uids[i] for i in window],
        "window_due_ns": [ctx.due[i] for i in window],
        "window_late_ns": [ctx.sent[i] - ctx.due[i] for i in window],
        # every send whose line was written inside the window
        "window_ack_ns": [ctx.acked[i] - ctx.sent[i] for i in sent_in]
        + [lat for sent, lat in ctx.other_ack_ns if t0 <= sent < t1],
        "polls": ctx.polls,
        "bound_base": ctx._bound_base,
        "sync": sync_ack,
        "healthz": wire.healthz(ctx.plan["health"]),
    }


def main() -> int:
    plan = json.loads(sys.stdin.readline())
    spec.use_index(plan["index"])
    ctx = Context(plan)
    say(event="hello", ns=ctx.now(), pid=os.getpid())
    threading.Thread(target=listen, args=(ctx,), daemon=True,
                     name="client-listen").start()
    say(event="loaded", **load(ctx))
    ctx.go.wait()  # the parent warms the cell's listed pod buckets first

    health = wire.healthz(plan["health"])
    ctx._bound_base = ctx._bound = health["bound_total"]
    poller = threading.Thread(target=ctx.poll_forever, daemon=True,
                              name="client-poll")
    poller.start()
    generator = spec.load_module("generators", ctx.mix["generator"])
    say(event="generating", ns=ctx.now())
    generator.run(ctx)
    sync_ack = drain(ctx)
    ctx.stopped = True
    poller.join(timeout=35)
    with open(plan["report_path"], "w") as f:
        json.dump(report(ctx, sync_ack), f)
    ctx.feed.close()
    say(event="done", report=plan["report_path"])
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as exc:  # the parent turns this into its own failure
        say(event="error", error=f"{type(exc).__name__}: {exc}")
        raise
