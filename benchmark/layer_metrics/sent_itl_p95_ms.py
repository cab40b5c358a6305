"""95th percentile of the gap between one stream's tokens where the replica's
handler thread has SENT them (``send_raw`` of the ``stream_item`` returned:
woken, serialised, stored, past the ack window), over the window
(``stats()["stream"]["sent"]``; ``benchmark/stream_stations.py``).  What it
stands above ``emit_itl_p95_ms`` is made inside the replica's process."""

import _common  # noqa: F401  (puts the repository on the path)
from benchmark.stream_stations import p95_ms


def read(run):
    return p95_ms(run, "sent")
