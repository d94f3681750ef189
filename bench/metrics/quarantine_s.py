"""Host seconds of the service's submit-side NaN quarantine per wave:
the summed duration of the program's ``svc.quarantine`` spans inside
the traced window, over the ``svc.wave`` spans that began in it."""


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    secs = sum(max(min(e.start + e.dur, hi) - max(e.start, lo), 0.0)
               for e in run.trace.host if e.name == "svc.quarantine")
    waves = sum(1 for e in run.trace.host
                if e.name == "svc.wave" and lo <= e.start < hi)
    return secs / waves if waves and secs else None
