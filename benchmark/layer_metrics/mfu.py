"""Model FLOP/s utilisation of the window: tokens/s x the FLOPs a trained
token requires (the family's ``train_flops_per_token``; for gpt2,
``costs.gpt2_train_flops_per_token``: matmul parameters x6 plus causal
attention, no embedding rows, no recomputation) over the chip's peak bf16
FLOP/s."""

from _common import family_piece


def read(run):
    if not run.get("peaks"):
        return None  # a rehearsal has no chip to compare with
    m = run.get("train")
    if not m:
        return None
    rate = m["steps"] * m["tokens_per_step"] / (m["t_close"] - m["t_open"])
    flops = family_piece(run["config"], "train_flops_per_token")(run["model"])
    return 100.0 * rate * flops / (run["peaks"]["flops_bf16"] * run["device"]["count"])
