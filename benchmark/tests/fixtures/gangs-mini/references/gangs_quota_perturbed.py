"""A negative of the fixture: the reference with one placement moved. The
probe has to report the slot that differs."""

from harness import spec

gangs_quota = spec.load_module("references", "gangs_quota")


def solve(x: dict, profile: dict) -> dict:
    out = gangs_quota.solve(x, profile)
    placed = (out["assignment"] >= 0).nonzero()[0]
    if placed.size:
        first = placed[0]
        nodes = x["nodes.mask"].nonzero()[0]
        out["assignment"][first] = nodes[nodes != out["assignment"][first]][0]
    return out
