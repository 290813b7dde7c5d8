"""Reductions of the host spans of a traced run (`obs.tracer`'s, and the
harness's `Tick`), all on CLOCK_MONOTONIC and cut to the window. Untraced
run: nothing to read.

A cycle is counted by its `Snapshot` span, which only a cycle with pending
pods opens (it covers whichever path assembled the snapshot: the resident
engine's refresh or `Cluster.snapshot`).

selector: {"spans": [<name or prefix*>], "stat": <one of>, "scale": factor}
  mean           mean duration of the matching spans
  per_cycle      their summed duration over the number of cycles
  per_bound_pod  their summed duration over the pods bound in the window
  missing_share  1 - (matching spans / cycles): the share of cycles
                 in which no such span was opened
  lead_in        mean time from a matching span's start to the start of the
                 first other span inside it (for `Tick`: the wait for the
                 feed lock, which the cycle takes before its first span)
"""

import bisect


def _matching(run, names):
    t0, t1 = run.window
    exact = {n for n in names if not n.endswith("*")}
    prefixes = tuple(n[:-1] for n in names if n.endswith("*"))
    return [
        s for s in run.spans
        if t0 <= s[1] < t1
        and (s[0] in exact or (prefixes and s[0].startswith(prefixes)))
    ]


def read(selector: dict, run):
    if run.spans is None:
        return None
    t0, t1 = run.window
    spans = _matching(run, selector["spans"])
    total_ns = sum(end - start for _, start, end, _ in spans)
    cycles = len(_matching(run, ["Snapshot"]))
    stat = selector["stat"]
    if stat == "mean":
        value = total_ns / len(spans) if spans else None
    elif stat == "per_cycle":
        value = total_ns / cycles if cycles else None
    elif stat == "per_bound_pod":
        bound = sum(1 for _uid, t in run.binds if t0 <= t < t1)
        value = total_ns / bound if bound else None
    elif stat == "missing_share":
        return 1.0 - len(spans) / cycles if cycles else None
    elif stat == "lead_in":
        names = {s[0] for s in spans}
        starts = sorted(s[1] for s in run.spans if s[0] not in names)
        leads = []
        for _name, start, end, _args in spans:
            i = bisect.bisect_left(starts, start)
            if i < len(starts) and starts[i] < end:
                leads.append(starts[i] - start)
        value = sum(leads) / len(leads) if leads else None
    else:
        raise ValueError(f"unknown stat {stat!r}")
    return None if value is None else value * selector.get("scale", 1.0)
