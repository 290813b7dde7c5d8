"""A percentile of one of the series the client kept, scaled.

selector: {"series": "window_ack_ns" | "window_late_ns" | "poll_rtt_ns",
           "percentile": 0..100, "scale": factor applied to the result}

window_ack_ns   send -> ack of every event line written inside the window
window_late_ns  sent - due of every arrival due inside the window
poll_rtt_ns     round trip of every `/healthz` poll made inside the window
"""

from harness import stats


def read(selector: dict, run):
    name = selector["series"]
    if name == "poll_rtt_ns":
        t0, t1 = run.window
        series = [p[4] for p in run.client["polls"] if t0 <= p[0] < t1]
    else:
        series = run.client[name]
    value = stats.percentile(series, selector["percentile"])
    return None if value is None else value * selector.get("scale", 1.0)
