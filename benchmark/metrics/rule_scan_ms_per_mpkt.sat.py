"""Kernels: device milliseconds in the resident step's rule_scan stage
(its named scope: row gather, scan, finalize) per million packets whose
verdicts were delivered in the profiled stretch."""
from benchmark import stages


def read(run):
    return stages.stage_ms_per_mpkt(run, ("rule_scan",))
