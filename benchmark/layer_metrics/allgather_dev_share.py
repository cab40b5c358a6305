"""Device time of all-gather ops over the traced slice, first chip: total
(not exposed) collective time as a share of the slice."""

from _common import trace_reduce

OPS = r"all-gather|all_gather|AllGather"


def read(run):
    red = run["reduced"]
    if not red["window_s"]:
        return None
    return 100.0 * trace_reduce.time_of(red, OPS) / red["window_s"]
