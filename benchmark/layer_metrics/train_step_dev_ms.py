"""Median device time of one execution of the train-step program, from
the trace's program line."""

from _common import median, trace_reduce

PROGRAM = r"step"


def read(run):
    ds = trace_reduce.program_durations(run["reduced"], PROGRAM)
    # the step program is the long one; helper programs are microseconds
    ds = [d for d in ds if d > 0.25 * max(ds)] if ds else ds
    return 1e3 * median(ds) if ds else None
