"""Prompt tokens served from the radix tree: ``hit_tokens`` over
(``hit_tokens`` + ``prefill_tokens_computed``), deltas across the window."""

from _common import delta


def read(run):
    hit, computed = delta(run, "prefix_cache", "hit_tokens"), delta(run, "prefill_tokens_computed")
    if hit is None or computed is None or hit + computed == 0:
        return None
    return 100.0 * hit / (hit + computed)
