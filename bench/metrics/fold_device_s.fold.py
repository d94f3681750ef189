"""Device seconds of the batched fold per wave: the traced time of the
``_sweep_round_jit`` and ``_sweep_final_jit`` programs in the window,
over the waves traced, one ``_sweep_final_jit`` execution each."""
from bench import trace as trace_lib

PROGRAMS = ("_sweep_round_jit", "_sweep_final_jit")


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    secs = sum(v for k, v in trace_lib.program_seconds(run.trace).items()
               if any(p in k for p in PROGRAMS))
    waves = sum(v for k, v in trace_lib.program_runs(run.trace).items()
                if "_sweep_final_jit" in k)
    return secs / waves if waves and secs else None
