"""Seconds between two instants of the run, on the harness's own clock.

selector: {"from": <instant>, "to": <instant>}; instants: process_start,
window_open, window_close.
"""


def read(selector: dict, run):
    instants = {
        "process_start": run.t_process_start_ns,
        "window_open": run.window[0],
        "window_close": run.window[1],
    }
    return (instants[selector["to"]] - instants[selector["from"]]) / 1e9
