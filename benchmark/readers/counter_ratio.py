"""The ratio of what two of the program's counters (`obs.metrics`) took
during the window: delta of one over delta of the other, read at the
window's two edges as `registry_delta` reads them. Nanoseconds summed per
stage over events counted gives the mean cost of an event in that stage.

selector: {"numerator": <rendered counter key, e.g. name{label="v"}>,
           "denominator": <rendered counter key>, "scale": factor}

A counter the program does not have counts as one that did not move; a
denominator that did not move leaves nothing to read.
"""


def read(selector: dict, run):
    before = run.registry["setup"]["counters"]
    after = run.registry["window"]["counters"]

    def delta(key: str):
        return after.get(key, 0) - before.get(key, 0)

    denominator = delta(selector["denominator"])
    if denominator <= 0:
        return None
    return (
        delta(selector["numerator"]) / denominator
        * selector.get("scale", 1.0)
    )
