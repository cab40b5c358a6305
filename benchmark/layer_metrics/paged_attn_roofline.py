"""The paged attention kernel's share of its roofline in decode.  It is
memory-bound: the least time is the live K and V bytes one chip must read
(the family's ``paged_decode_kv_bytes`` at the mean live length over the traced
slice, from the pool ledger's ``seq_bytes`` sampled at the slice's two
ends) over the chip's HBM bandwidth; the time taken is the kernel's summed
device time over the slice divided by the decode programs executed.

The two ledger readings are taken just OUTSIDE the slice: ``trace_start``
is asked for before the profiler starts and ``trace_stop`` before it is
stopped, and each is answered when the engine's lock lets the reader in
(``t_read``), up to seconds later, while the profiler starts or writes
out.  Waiting for them inside the slice is what let it slide
(``serving.take_slice``)."""

from _common import family_piece, trace_reduce

#: the decode program holds one Pallas kernel, the paged attention kernel;
#: the trace names it by its call target only (PERF.md: named scopes)
KERNEL = r"(decode|verify)[^/]*/.*tpu_custom_call"
PROGRAM = r"decode|verify"


def read(run):
    if not run.get("peaks"):
        return None  # a rehearsal has no chip to compare with
    red, c = run["reduced"], run.get("counters") or {}
    calls = len(trace_reduce.program_durations(red, PROGRAM))
    kernel_s = trace_reduce.time_of(red, KERNEL)
    if not calls or not kernel_s or "trace_start" not in c:
        return None
    block_tokens = run["engine"]["block_size"]
    live = [
        c[k]["hbm"]["seq_bytes"] / c[k]["hbm"]["block_bytes"] * block_tokens
        for k in ("trace_start", "trace_stop")
    ]
    need = family_piece(run["config"], "paged_decode_kv_bytes")(
        sum(live) / 2, run["model"], run["engine"]["tp"])
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / (kernel_s / calls)
