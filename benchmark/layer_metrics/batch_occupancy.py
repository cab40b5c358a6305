"""Decode slots filled: tokens generated over (engine steps x max_slots),
both as deltas of ``stats()`` across the window.  Every counted step
launches a decode while anything runs (``LLMEngine.step`` returns before
counting when the scheduler has no work); first tokens come from the
prefill's sample and count too, so a step can exceed its slots by one."""

from _common import delta


def read(run):
    steps, toks = delta(run, "steps"), delta(run, "tokens_generated")
    if not steps:
        return None
    return 100.0 * toks / (steps * run["engine"]["max_slots"])
