"""Dispatch: microseconds per ring record of the host's work on the
record's outputs after the readback (the program's infw.ring.finalize
spans: split, verdict finalize, statistics), over the records whose
readback the trace holds."""
from benchmark import stages


def read(run):
    return stages.span_us_per_record(run, stages.FINALIZE_SPAN)
