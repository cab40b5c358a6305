"""Device milliseconds per decode execution in the leaf ops whose
``op_name`` lies in the ``sample`` scope (``_sample_rows`` /
``_verify_rows``): survives any recompile that renumbers ``fusion.N``."""

from _program_spans import load


def read(run):
    spans = load(run)
    if spans is None or not spans["decodes"] or not spans["sample_s"]:
        return None
    return 1e3 * spans["sample_s"] / spans["decodes"]
