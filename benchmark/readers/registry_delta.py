"""What the program's own metrics registry (`obs.metrics`) took during the
window, or during set-up: exact sums and counts read at the window's two
edges, never bucketed percentiles.

selector, one of:
  {"histogram": <rendered key, e.g. name{label="v"}, or a prefix ending
   in *, whose histograms are added up>, "stat": "mean"|"sum",
   "scope": "window"|"setup", "scale": factor}
  {"counters": [<key prefix>, ...], "scope": "window"|"setup"}
      the delta of each prefix's counters summed, then the largest of them
"""

from harness import stats


def _edges(run, scope: str):
    empty = {"counters": {}, "histograms": {}}
    if scope == "setup":
        return empty, run.registry["setup"]
    return run.registry["setup"], run.registry["window"]


def _histogram(edge: dict, key: str) -> tuple:
    if not key.endswith("*"):
        return edge["histograms"].get(key, (0.0, 0))
    matching = [
        v for k, v in edge["histograms"].items() if k.startswith(key[:-1])
    ]
    return sum(v[0] for v in matching), sum(v[1] for v in matching)


def read(selector: dict, run):
    before, after = _edges(run, selector.get("scope", "window"))
    if "histogram" in selector:
        s0, n0 = _histogram(before, selector["histogram"])
        s1, n1 = _histogram(after, selector["histogram"])
        if selector.get("stat", "mean") == "sum":
            value = s1 - s0
        else:
            value = stats.window_delta_mean(s0, n0, s1, n1)
        return None if value is None else value * selector.get("scale", 1.0)
    deltas = []
    for prefix in selector["counters"]:
        deltas.append(sum(
            value - before["counters"].get(key, 0)
            for key, value in after["counters"].items()
            if key.startswith(prefix)
        ))
    return float(max(deltas))
