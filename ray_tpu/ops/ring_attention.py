"""Ring attention: causal attention with the sequence axis sharded over the
``sp`` mesh axis, KV blocks rotating around the ring via ``ppermute``.

Sequence/context parallelism is absent from the reference (SURVEY.md §5.7 —
"no ring attention, Ulysses, context-parallel, or blockwise attention
anywhere"); the TPU build makes it first-class: each device holds a
``seq/sp`` slice of Q/K/V, computes blockwise online-softmax partials of its
Q slice against the KV slice currently resident, then passes KV to its ring
neighbor over ICI. After ``sp`` hops every Q row has seen every allowed K —
O(seq/sp) memory per chip, compute overlapped with the ICI transfer by XLA's
latency-hiding scheduler.

The per-hop partial merge is the same online-softmax algebra as the flash
kernel (``ops/flash_attention.py``); fully-masked hops (KV chunk strictly in
the causal future) contribute zero weight. Differentiable end-to-end —
``ppermute`` transposes to the reverse rotation in the backward pass;
``jax.checkpoint`` on the hop body keeps backward memory at one hop's
activations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _chunk_partials(q_scaled, k, v, q_off, k_off):
    """Blockwise softmax partials of one Q slice vs one KV chunk.

    q_scaled (B,H,Sq,D) fp32 already scaled; returns (m (B,H,Sq),
    l (B,H,Sq), acc (B,H,Sq,D)) with zero weight on causally-masked keys.
    """
    sq, sk = q_scaled.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q_scaled, k.astype(jnp.float32))
    rows = q_off + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    cols = k_off + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    allowed = cols <= rows
    s = jnp.where(allowed, s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.where(allowed, jnp.exp(s - m[..., None]), 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return m, l, acc


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, axis_name: str = "sp") -> jax.Array:
    """Causal attention over ring-sharded sequences. MUST run inside a
    ``shard_map`` (or equivalent SPMD region) where ``axis_name`` is a mesh
    axis and q,k,v are the LOCAL (batch, heads, seq/sp, head_dim) slices,
    sharded contiguously in sequence order.
    """
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    scale = 1.0 / (d**0.5)
    q32 = q.astype(jnp.float32) * scale
    q_off = idx * s_local

    m = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s_local), jnp.float32)
    acc = jnp.zeros((b, h, s_local, d), jnp.float32)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def hop(carry, kv, k_chunk_idx):
        m, l, acc = carry
        k_cur, v_cur = kv
        cm, cl, cacc = _chunk_partials(q32, k_cur, v_cur, q_off, k_chunk_idx * s_local)
        m_new = jnp.maximum(m, cm)
        corr = jnp.exp(m - m_new)
        ccorr = jnp.exp(cm - m_new)
        l = l * corr + cl * ccorr
        acc = acc * corr[..., None] + cacc * ccorr[..., None]
        return (m_new, l, acc)

    kv = (k, v)
    # static python loop: sp is a mesh constant, so this unrolls into sp
    # compute+ppermute stages XLA can pipeline.
    for r in range(sp):
        k_chunk_idx = (idx - r) % sp
        carry = hop((m, l, acc), kv, k_chunk_idx)
        m, l, acc = carry
        if r != sp - 1:
            kv = jax.tree_util.tree_map(
                lambda t: jax.lax.ppermute(t, axis_name, perm), kv
            )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array, mesh) -> jax.Array:
    """shard_map wrapper: q,k,v global (batch, heads, seq, head_dim) arrays
    with batch over (dp,fsdp), heads over tp, seq over sp. Usable inside jit
    (e.g. from the GPT block under pjit)."""
    spec = P(("dp", "fsdp"), "tp", "sp", None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name="sp"),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
