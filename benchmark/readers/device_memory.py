"""Peak bytes in use on the fullest device, from the backend's allocator
statistics at the end of the window. None on a backend that keeps none.
"""


def read(selector: dict, run):
    peak = run.memory_peak_bytes
    return None if peak is None else float(peak)
