"""Kernels: device milliseconds in the resident step's trie walk (its
lpm stage and the lpm_root, lpm_level and lpm_resolve stages inside it)
per million packets whose verdicts were delivered in the profiled
stretch."""
from benchmark import stages, trie_floor


def read(run):
    return stages.stage_ms_per_mpkt(run, trie_floor.WALK_STAGES)
