"""Pods bound per second, as the client's polls of `/healthz.bound_total`
saw them: binds land in lumps of one cycle each, and the rate is taken
between the first and the last lump seen inside the window
(`harness.stats.lump_rate`). Fewer than two lumps: nothing to read.
"""

from harness import stats


def read(selector: dict, run):
    polls = [(t_ns, bound) for t_ns, bound, *_ in run.client["polls"]]
    rate, _lumps = stats.lump_rate(polls, *run.window)
    return rate
