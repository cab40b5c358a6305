"""Operations and bytes a kernel or a step NEEDS, from shapes alone.

These are the algorithm's counts, not what a particular kernel executes:
recomputation, padding and masked-out work do not count.  Shares of the
roofline divide them by the chip's published peaks (``peaks.json``) and
by device time from the trace.
"""

from __future__ import annotations


def gpt2_train_flops_per_token(model: dict) -> float:
    """Forward + backward FLOPs one trained token requires in a dense
    GPT-2-style decoder: 6 x the parameters that sit in matrix
    multiplications (attention and MLP projections and the output head;
    embedding rows are looked up, not multiplied) plus causal attention
    (QK^T and PV, half of the square, x3 for forward + backward)."""
    d, L, s, V = model["d_model"], model["n_layers"], model["seq_len"], model["vocab_size"]
    matmul_params = L * 12 * d * d + d * V
    attn = L * 3 * (2 * 2 * s * d * 0.5)
    return 6.0 * matmul_params + attn


def flash_train_flops(batch: int, model: dict) -> float:
    """Causal flash attention, forward + backward, one train step: the
    forward is 2 matrix products over half the (s x s) square, the backward
    5 (S is recomputed once; dV, dP, dQ, dK), each 2*s*s*head_dim/2 FLOPs
    per head — the usual 3.5x-forward accounting.  A kernel that recomputes
    more (the dq and dkv kernels here each recompute S and dP) is not
    credited for it."""
    s, d, L = model["seq_len"], model["d_model"], model["n_layers"]
    per_matmul = 2.0 * s * s * d * 0.5  # all heads together: h * head_dim = d
    return batch * L * 7.0 * per_matmul


def paged_decode_kv_bytes(live_tokens: float, model: dict, tp: int = 1) -> float:
    """Bytes of K and V one decode step must read, on ONE chip, over all
    layers: every live token's key and value row of every layer, once, in
    the pool's dtype (2 bytes).  ``live_tokens`` is summed over the running
    sequences.  Under ``tp`` each chip holds 1/tp of the heads."""
    d, L = model["d_model"], model["n_layers"]
    return live_tokens * L * 2 * (d / tp) * 2.0
