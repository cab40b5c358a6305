"""95th percentile of the gap between one stream's chunks where the HTTP
proxy has WRITTEN and drained them, on the proxy's own clock, reported
back to the replica on the next ask and its ack, over the window
(``stats()["stream"]["written"]``; ``benchmark/stream_stations.py``): the
last station before the client's socket, to lay beside ``itl_p95_ms``."""

import _common  # noqa: F401  (puts the repository on the path)
from benchmark.stream_stations import p95_ms


def read(run):
    return p95_ms(run, "written")
