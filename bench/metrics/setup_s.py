"""Seconds from the start of the process to the opening of the window:
loading, making the inputs, the first fits, warm-up and compiling."""


def read(run):
    return run.setup["setup_s"]
