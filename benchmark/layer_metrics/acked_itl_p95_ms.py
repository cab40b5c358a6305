"""95th percentile of the gap between one stream's tokens where their ACKS
come back to the replica (the consumer took the item from the head, plus
the hop back), over the window (``stats()["stream"]["acked"]``;
``benchmark/stream_stations.py``).  What it stands above ``sent_itl_p95_ms``
is made in the head and by a consumer late to ask."""

import _common  # noqa: F401  (puts the repository on the path)
from benchmark.stream_stations import p95_ms


def read(run):
    return p95_ms(run, "acked")
