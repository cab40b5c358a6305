"""The share of the rows the expert layer's tile loop computed in prefill
chunks that were (row, held expert) pairs: ``stats()["moe"]``'s ``chunk_pairs``
over ``chunk_tile_rows`` across the window, both counted on the device.  A
tile is whole whatever it holds (``ops.moe.expert_layer``): 512 rows x 4 of 64
experts are 32 pairs an expert, ONE tile of 64 rows half full, so about 50%
where every chunk is full.  None where the program does not count the chunks'
tile rows (a program from before PR 61)."""

from _common import delta


def read(run):
    pairs, rows = delta(run, "moe", "chunk_pairs"), delta(run, "moe", "chunk_tile_rows")
    if pairs is None or not rows:
        return None
    return 100.0 * pairs / rows
