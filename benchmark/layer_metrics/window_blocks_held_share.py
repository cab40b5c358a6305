"""Blocks the window layers' sub-pool holds for the live sequences over the
blocks the SAME sequences hold in a full layer, which hands nothing back
(``stats()["kv_pool"]``: ``window_blocks_held`` over ``full_blocks_held``,
summed over the run's readings of the engine at which a sequence was live: the
window's two ends and, in a traced run, the slice's).  A ratio, lower is
better: what releasing the blocks behind the window leaves of a window
layer's K/V; 1 would be a window layer that keeps every block.  The full
layers' blocks of a whole prompt are claimed at admission and a window layer's
chunk by chunk, so a sequence still prefilling reads a little low.  None where
the pool counts no such blocks (a program from before PR 69, a pool of one
layer kind)."""


def read(run):
    held = full = 0
    for reading in (run.get("counters") or {}).values():
        pool = (reading or {}).get("kv_pool") or {}
        if "window_blocks_held" in pool:
            held += pool["window_blocks_held"]
            full += pool["full_blocks_held"]
    return held / full if full else None
