"""Causal multi-head attention — impl dispatcher.

Three interchangeable paths behind one signature (the reference framework has
no attention op of its own — compute is user torch code; this is part of the
"long-context first-class" mandate, SURVEY.md §5.7):

* ``xla``   — einsum + masked softmax; fine for short sequences, O(seq²)
  memory (the mask/score matrix materializes).
* ``flash`` — Pallas blockwise online-softmax kernel with custom-VJP
  backward (``ops/flash_attention.py``); O(seq) memory, MXU-dense.
* ``ring``  — sequence-parallel flash over the ``sp`` mesh axis
  (``ops/ring_attention.py``), selected by the model layer when the mesh
  shards sequence.

``auto`` follows ONE rule, ``auto_impl``: flash on a TPU backend when the
sequence tiles the kernel's 128-lane blocks, else XLA.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _xla_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    *_, seq, head_dim = q.shape
    scale = 1.0 / (head_dim**0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    mask = jnp.tril(jnp.ones((seq, seq), dtype=bool))
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def auto_impl(seq: int) -> str:
    """THE ``impl="auto"`` rule, by platform and shape (``models.gpt``
    applies the same one before it picks the shard_map'd kernel):
    ``"flash"`` on a TPU backend when ``seq`` tiles the kernel's
    128-lane blocks, else ``"xla"``.  Off a TPU the Pallas kernel could
    only run interpreted — orders of magnitude slower than compiled XLA
    — so ``auto`` never picks it there."""
    if jax.default_backend() == "tpu" and seq >= 128 and seq % 128 == 0:
        return "flash"
    return "xla"


def causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, impl: str = "auto"
) -> jax.Array:
    """q,k,v: (batch, heads, seq, head_dim) → (batch, heads, seq, head_dim).

    bf16-friendly with fp32 softmax accumulation on every path.
    """
    if impl not in ("auto", "xla", "flash"):
        raise ValueError(
            f"unknown attention impl {impl!r}; expected 'auto', 'xla' or 'flash' "
            "(sequence-parallel ring attention is ops.ring_attention, selected "
            "by the model layer when the mesh shards sequence)"
        )
    if impl == "xla":
        return _xla_attention(q, k, v)
    if impl == "auto" and auto_impl(q.shape[2]) == "xla":
        return _xla_attention(q, k, v)
    from ray_tpu.ops.flash_attention import flash_attention

    return flash_attention(q, k, v)
