"""The flash attention kernels' share of their roofline in training.
Compute-bound: the least time is the causal forward + backward FLOPs of
the steps in the traced slice (the family's ``flash_train_flops``) over the
chip's peak bf16 FLOP/s; the time taken is the summed device time of the
forward, dQ and dK/dV kernels over the slice."""

from _common import family_piece, trace_reduce

#: the step program's Pallas kernels are the three flash kernels (forward,
#: dQ, dK/dV); the trace names them by their call target only
KERNELS = r"step[^/]*/.*tpu_custom_call"
PROGRAM = r"step"


def read(run):
    if not run.get("peaks"):
        return None  # a rehearsal has no chip to compare with
    red = run["reduced"]
    ds = trace_reduce.program_durations(red, PROGRAM)
    steps = len([d for d in ds if d > 0.25 * max(ds)]) if ds else 0
    kernel_s = trace_reduce.time_of(red, KERNELS)
    if not steps or not kernel_s:
        return None
    need = steps * family_piece(run["config"], "flash_train_flops")(run["batch"], run["model"])
    return 100.0 * (need / run["peaks"]["flops_bf16"]) / kernel_s
