"""Device milliseconds per prefill-chunk execution in the leaf ops of the
routed expert layer's two scopes, ``moe_router`` and ``moe_experts`` (first
chip): the router over the chunk's rows and the tile loop over its pairs, each
on a ``program_spans`` line beside what the device counted of the slice's
chunks (touched experts, pairs and the tiles' rows a chunk) and what the
chunks stood at (``chunk_occupancy``: valid tokens, context reached).  None
where the program does not count a chunk's touched experts (a program from
before PR 61), has a shared expert, or the slice holds no chunk."""

from _inner_scope import chunk_occupancy
from moe_routed_decode_dev_ms import scopes_ms


def read(run):
    stood = dict(chunk_occupancy(run) or {})
    stood.pop("between", None)  # the device's counts name their pair of readings
    return scopes_ms(run, "chunk", **stood)
