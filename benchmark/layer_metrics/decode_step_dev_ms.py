"""Median device time of one execution of the decode program, from the
trace's program line (first chip)."""

from _common import median, trace_reduce

PROGRAM = r"decode"


def read(run):
    ds = trace_reduce.program_durations(run["reduced"], PROGRAM)
    return 1e3 * median(ds) if ds else None
