"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals) / window."""
from bench import trace as trace_lib


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    w = trace_lib.window_s(run.trace)
    return 100.0 * (1.0 - trace_lib.busy_s(run.trace) / w)
