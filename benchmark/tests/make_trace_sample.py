"""Cut a recorded ``.xplane.pb`` down to a small sample for the test of
the reduction: the device planes' op and program lines and the
benchmark's own host spans, between two instants.

    python benchmark/tests/make_trace_sample.py <trace_dir> <out.json.gz> [seconds]
"""

import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import trace_reduce as tr  # noqa: E402


def main(argv):
    trace_dir, out = argv[0], argv[1]
    seconds = float(argv[2]) if len(argv) > 2 else 0.5
    planes = tr.read_planes(tr.find_xplane(trace_dir))
    starts = [
        e[1] for p, lines in planes.items() if p.startswith("/device:TPU")
        for e in lines.get(tr.OPS_LINE, [])
    ]
    lo = min(starts) + 1e9  # skip the first second: the profiler settles
    hi = lo + seconds * 1e9
    sample = {}
    for p, lines in planes.items():
        for ln, evs in lines.items():
            if p.startswith("/device:TPU") and ln in (tr.OPS_LINE, tr.MODULES_LINE):
                keep = [e for e in evs if lo <= e[1] and e[1] + e[2] <= hi]
            else:
                keep = [e for e in evs if e[0].startswith(tr.SPAN_PREFIX)
                        and lo <= e[1] and e[1] + e[2] <= hi]
            if keep:
                sample.setdefault(p, {})[ln] = keep
    with gzip.open(out, "wt") as f:
        json.dump(sample, f)
    print({p: {ln: len(e) for ln, e in lines.items()} for p, lines in sample.items()})


if __name__ == "__main__":
    main(sys.argv[1:])
