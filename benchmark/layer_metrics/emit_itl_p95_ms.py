"""95th percentile of the gap between one stream's tokens where the engine's
loop EMITS them (``req.stream.put``; the replica's ``llm_inter_token_latency_s``),
pooled over streams, first token excluded, over the window: what the device
and the step loop made of the gap, before any hand-over to the client
(``stats()["stream"]["emit"]``; ``benchmark/stream_stations.py``)."""

import _common  # noqa: F401  (puts the repository on the path)
from benchmark.stream_stations import p95_ms


def read(run):
    return p95_ms(run, "emit")
