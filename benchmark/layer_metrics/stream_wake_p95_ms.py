"""95th percentile of how long a token lay in its request's queue before the
replica's handler thread ran (``req.stream.put`` to ``get``'s return, one
process, one clock), over the window (``stats()["stream"]["wake"]``;
``benchmark/stream_stations.py``): the hand-over of the interpreter lock from
the step loop to a stream's thread."""

import _common  # noqa: F401  (puts the repository on the path)
from benchmark.stream_stations import p95_ms


def read(run):
    return p95_ms(run, "wake")
