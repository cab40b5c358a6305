"""The share of the rows the expert layer's tile loop computed in decodes
that were (row, held expert) pairs: ``stats()["moe"]``'s ``decode_pairs`` over
``decode_tile_rows`` across the window, both counted on the device.  A tile is
whole whatever it holds (``ops.moe.expert_layer``): at 16 rows a touched
expert is one tile of 16 rows for its 2-3 pairs, so about 15% here; the rest
is what the loop multiplies beside the weights it had to read anyway.  None
where the program does not count the tiles' rows."""

from _common import delta


def read(run):
    pairs, rows = delta(run, "moe", "decode_pairs"), delta(run, "moe", "decode_tile_rows")
    if pairs is None or not rows:
        return None
    return 100.0 * pairs / rows
