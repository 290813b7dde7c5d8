"""Numbers that only the device's own trace can give
(`harness.trace_reduce`, over the profiled stretch of a traced run). No
device plane in the trace (the CPU backend): nothing to read.

selector: {"stat": <one of>, ...}
  idle_share                1 - busy / profiled stretch
  module_us_per_pod         device time of the runs of {"module"} whose
                            host span {"span"} lies inside the stretch,
                            over the pods those spans solved (their
                            `pending` argument)
  module_roofline_percent   the least time those runs could take, bytes
                            over the device's HBM bandwidth, over their
                            device time. The bytes per pod come from the
                            configuration's reference (`min_bytes_per_pod`),
                            from the padded node count and the resource
                            axis; the scan is memory-bound by construction
                            (no matrix product in a step)
"""

import importlib

#: the trace's device and host planes are aligned to a millisecond or two
#: only (on a v5e the device's events sit about 1.2 ms early): a program
#: run belongs to the host span it starts in, give or take this much
SKEW_NS = 5_000_000


def _solved(run, selector):
    """(device ns, pods) of the module's runs that a host span brackets."""
    trace = run.device_trace
    offset = run.trace_offset_ns
    w0, w1 = trace["window_ns"]
    prefix = selector["span"].rstrip("*")
    spans = [
        (s - offset, e - offset, args.get("pending", 0))
        for name, s, e, args in run.spans
        if name.startswith(prefix) and s - offset >= w0 and e - offset <= w1
    ]
    device_ns = pods = 0
    for name, start, end in trace["module_events"]:
        if name != selector["module"]:
            continue
        for s, e, pending in spans:
            if s - SKEW_NS <= start < e:
                device_ns += end - start
                pods += pending
                break
    return device_ns, pods


def read(selector: dict, run):
    trace = run.device_trace
    if trace is None:
        return None
    stat = selector["stat"]
    if stat == "idle_share":
        w0, w1 = trace["window_ns"]
        return 1.0 - trace["busy_ns"] / (w1 - w0)
    device_ns, pods = _solved(run, selector)
    if not pods or not device_ns:
        return None
    if stat == "module_us_per_pod":
        return device_ns / pods / 1e3
    if stat == "module_roofline_percent":
        from scheduler_plugins_tpu.utils.intmath import bucket_size

        reference = importlib.import_module(
            f"references.{run.cell.config['reference']}"
        )
        n_nodes = bucket_size(run.cell.config["cluster"]["nodes"])
        least_s = (
            pods * reference.min_bytes_per_pod(n_nodes, 4)
            / run.peaks["hbm_bytes_per_s"]
        )
        return 100.0 * least_s / (device_ns / 1e9)
    raise ValueError(f"unknown stat {stat!r}")
