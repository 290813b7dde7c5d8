"""Find a steady cell's knee: the highest arrival rate the daemon sustains.

    python3 benchmark/sweep.py --workload <steady cell> --rates 500,1000,2000 \
        [--seed 0] [--seconds 15]

Run by hand, once, on the chip, when a cell is defined; the driver never
runs it. One daemon, one client, as in `run.py`; the client's rate is set to
each of `--rates` in turn (ascending; the sweep ends at the first rate that
is not sustained). At each rate the mix runs until it is warm (the
cell's `warmup_s`, and as long again after the last compile), then for
`--seconds`, and one JSON line says what happened: the pods each cycle left
pending and how late the generator ran (each as a mean over the first third
and over the last third of the stretch), the decision latency from due to
bind and the cycle time. Ingest is synchronous and shut out while a cycle
holds the feed lock, so past the knee the queue grows in the generator, as
lateness, more than in the daemon. The knee is the highest rate at which
neither the daemon's queue nor the generator's lateness is larger in the
last third than in the first, and the generator's p99 lateness stays under
one cycle interval. The cell's file then takes 0.8 x that rate as a number.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import run as harness_run
from harness import spec, stats


class Sweep(harness_run.Run):
    def _control(self) -> None:
        daemon = self.daemon
        interval_ns = int(daemon.args.cycle_interval_s * 1e9)
        if self.args.warm_pod_counts is not None:
            self.cell.params["warm_pod_counts"] = self.args.warm_pod_counts
        since = self.start_client()
        for rate in self.args.rates:
            self._say(rate=rate)
            self.wait_warm(max(since, time.monotonic_ns()))
            self._mark()  # drop the arrivals of the warm-up
            t0 = time.monotonic_ns()
            t1 = t0 + int(self.args.seconds * 1e9)
            before = self.registry_now()
            pending = []  # (t_ns, pods left pending by the last cycle)
            while time.monotonic_ns() < t1:
                pending.append((time.monotonic_ns(), daemon.last_pending))
                time.sleep(0.05)
            after = self.registry_now()
            marked = self._mark()
            time.sleep(2 * interval_ns / 1e9)  # let the last arrivals bind
            bound_at = dict(self.binds)
            delays = [
                (bound_at.get(uid, float("inf")) - due) / 1e6
                for uid, due in zip(marked["uids"], marked["due_ns"])
            ]
            late = [s - d for s, d in zip(marked["sent_ns"], marked["due_ns"])]
            third = (t1 - t0) // 3
            s0, n0 = before["histograms"].get("scheduler_cycle", (0.0, 0))
            s1, n1 = after["histograms"].get("scheduler_cycle", (0.0, 0))
            late_p99 = stats.percentile(late, 99)
            first = stats.mean([p for t, p in pending if t < t0 + third])
            last = stats.mean([p for t, p in pending if t >= t1 - third])
            dues = marked["due_ns"]
            late_first = stats.mean(
                [x for x, due in zip(late, dues) if due < t0 + third]
            )
            late_last = stats.mean(
                [x for x, due in zip(late, dues) if due >= t1 - third]
            )
            sustained = bool(
                last <= max(first, 1.0) * 1.25
                and late_last <= max(late_first, 1e6) * 1.25
                and late_p99 < interval_ns
            )
            self.info(
                "rate", rate_pods_per_s=rate, arrivals=len(delays),
                pending_first_third=first, pending_last_third=last,
                late_ms_first_third=late_first / 1e6,
                late_ms_last_third=late_last / 1e6,
                late_ms_p99=late_p99 / 1e6,
                decision_ms_p50=stats.percentile(delays, 50),
                decision_ms_p99=stats.percentile(delays, 99),
                cycle_ms_mean=stats.window_delta_mean(s0, n0, s1, n1),
                cycles=n1 - n0,
                compile_events=self.compile_events(),
                sustained=sustained,
            )
            if not sustained:
                break  # the generator's lateness carries into higher rates
        self._say(stop=True)
        self._hear("done", harness_run.CLIENT_REPLY_S + harness_run.DRAIN_LIMIT_S)
        self.result = {"swept": self.args.rates}

    def _mark(self) -> dict:
        path = os.path.join(self.out_dir, "sweep_mark.json")
        self._say(mark=path)
        self._hear("marked", harness_run.CLIENT_REPLY_S)
        return spec.load_json(path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(r) for r in s.split(",")])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--warm-pod-counts", default=None,
                    type=lambda s: [int(n) for n in s.split(",")],
                    help="pod buckets to warm before the first rate, in "
                         "place of the cell's: a sweep crosses more buckets "
                         "than the cell's own rate does")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--index", default=None)
    args = ap.parse_args(argv)
    args.trace = 0
    return harness_run.execute(args, Sweep)


if __name__ == "__main__":
    sys.exit(main())
