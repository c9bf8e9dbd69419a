"""Dispatch: microseconds per ring record that the daemon's loop waited
on the device for the record's outputs (the program's
infw.ring.readback spans, over the records whose readback the trace
holds)."""
from benchmark import stages


def read(run):
    return stages.span_us_per_record(run, stages.READBACK_SPAN)
