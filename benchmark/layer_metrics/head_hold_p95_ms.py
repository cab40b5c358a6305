"""95th percentile of how long a stream item lay in the head before its
consumer had it (``_on_stream_item`` to the hand-out in ``rpc_stream_next``,
the head's clock, carried back as ``hold_s`` on the ack), over the window
(``stats()["stream"]["head_hold"]``; ``benchmark/stream_stations.py``): the
wake-up under the head's one lock, or a proxy late to ask."""

import _common  # noqa: F401  (puts the repository on the path)
from benchmark.stream_stations import p95_ms


def read(run):
    return p95_ms(run, "head_hold")
