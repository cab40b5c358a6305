"""Share of the first chip's idle seconds in the traced slice that lie
under one of the engine loop's phase spans (``llm.step.*``, ``llm.loop.*``
on the profiler's clock): how much of the idle time has a name."""

from _program_spans import load


def read(run):
    spans = load(run)
    if spans is None or not spans["idle_s"]:
        return None
    return 100.0 * spans["idle_covered_s"] / spans["idle_s"]
