"""Set-up: from the process's start to the producer's start, with the
table load, the daemon's start and the warm-up (and, on a first run,
the compiles)."""


def read(run):
    return run.setup_s
