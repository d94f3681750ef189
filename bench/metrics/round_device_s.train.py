"""Device seconds of one MapReduce round of batch training: the traced
time of the ``_round_jit`` program over its executions in the window."""
from bench import trace as trace_lib


def read(run):
    if run.trace is None:
        return None
    secs = sum(v for k, v in trace_lib.program_seconds(run.trace).items()
               if k.endswith("_round_jit") and "sweep" not in k)
    runs = sum(v for k, v in trace_lib.program_runs(run.trace).items()
               if k.endswith("_round_jit") and "sweep" not in k)
    return secs / runs if runs else None
