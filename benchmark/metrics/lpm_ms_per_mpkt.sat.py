"""Kernels: device milliseconds in the resident step's lpm stage (its
named scope) per million packets whose verdicts were delivered in the
profiled stretch."""
from benchmark import stages


def read(run):
    return stages.stage_ms_per_mpkt(run, ("lpm",))
