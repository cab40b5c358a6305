"""The share of the experts a decode touched that went through the expert
layer's BATCH FORM (``ops.moe.expert_layer`` at no more rows than a tile: a
touched expert sees the whole batch in one kernel step, no tile is made):
``stats()["moe"]``'s ``decode_expert_steps`` over ``decode_touched`` across the
window, both counted on the device.  100 where every decode is 16 rows; the
tile loop adds nothing to the count.  None where the program does not count
the form's steps (a program from before PR 60)."""

from _common import delta


def read(run):
    steps, touched = delta(run, "moe", "decode_expert_steps"), delta(run, "moe", "decode_touched")
    if steps is None or not touched:
        return None
    return 100.0 * steps / touched
