"""Device seconds of the dual-CD solver per wave: the union of the
intervals of the device ops traced under the program's ``svm.solve``
scope (every round's local solves and the final solve; a ``while`` op
holds its body's ops, so they are not summed), clipped to the traced
window, over the waves traced, one ``_sweep_final_jit``
execution each (as ``fold_device_s.fold``)."""
from pathlib import Path

from bench import trace as trace_lib
from bench.metrics import _scopes

CHECKOUT = Path(__file__).resolve().parents[2]


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    ops = _scopes.device_ops(run, CHECKOUT)
    secs = _scopes.scope_seconds(ops, "svm.solve", run.trace.window)
    runs = sum(v for k, v in trace_lib.program_runs(run.trace).items()
               if "_sweep_final_jit" in k)
    return secs / runs if runs and secs else None
