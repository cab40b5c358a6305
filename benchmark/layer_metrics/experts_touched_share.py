"""Held experts with at least one row, as a share of the held experts of
every expert layer, per decode over the window: ``stats()["moe"]``'s
``decode_touched`` over ``decodes`` times (expert layers x experts held),
counted on the device.  What a decode's expert layers read follows it: at a
deployment's 16 rows a chip's WORTH of pairs every expert is touched; at this
cell's 4 pairs a layer about 3.5 of 12 are."""

from _common import delta


def read(run):
    touched, decodes = delta(run, "moe", "decode_touched"), delta(run, "moe", "decodes")
    if touched is None or not decodes:
        return None
    model = run["model"]
    slots = (model["n_layers"] - model["n_dense_layers"]) * model["experts_held"]
    return 100.0 * touched / (decodes * slots)
