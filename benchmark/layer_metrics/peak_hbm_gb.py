"""Peak device memory on the fullest chip: ``peak_bytes_in_use`` +
``peak_bytes_reserved`` (the steps' temporaries show only in the second:
PERF.md, Findings of PR 21)."""


def read(run):
    peak = run["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
