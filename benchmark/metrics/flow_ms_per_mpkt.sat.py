"""Kernels: device milliseconds in the resident step's flow_probe and
flow_insert stages (its named scopes) per million packets whose
verdicts were delivered in the profiled stretch."""
from benchmark import stages


def read(run):
    return stages.stage_ms_per_mpkt(run, ("flow_probe", "flow_insert"))
