"""Device: share of the profiled stretch in which no operation ran on
the chip."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
