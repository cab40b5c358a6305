"""95th percentile of the gaps between successive token lines at the
client; every gap whose later line arrived inside the window, all
requests pooled."""

from benchmark.harness import percentile
from benchmark.serving import window_gaps


def read(run):
    if run["kind"] != "serving":
        return None
    gaps = window_gaps(run["records"], run["window"])
    return 1e3 * percentile(gaps, 95) if gaps else None
