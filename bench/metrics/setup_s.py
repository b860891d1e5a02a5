"""Seconds from the process's start to the window's opening: imports,
weights, library, engine construction (AOT compiles or cache loads), the
warm-up of every program and the lead-in traffic."""


def read(run):
    return run.setup_s
