"""Device milliseconds per decode execution in the leaf ops whose
``op_name`` lies in the ``retention`` scope: the feature maps of q and k,
the decode kernel that updates and reads every live row's state, and the
normalisation (first chip).  None where the program has no such scope."""

from _program_spans import load


def read(run):
    spans = load(run)
    if spans is None or not spans["decodes"]:
        return None
    ops = (spans.get("decode_by_scope") or {}).get("retention")
    return 1e3 * sum(ops.values()) / spans["decodes"] if ops else None
