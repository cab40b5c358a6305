"""Grouped-query attention over a paged K/V cache: ``H`` query heads, ``K``
key-value heads, query head ``i`` reads key-value head ``i // (H / K)``.

* ``gqa_paged_attention`` — one query token a row (a decode).  The ``H / K``
  query heads that share a key-value head ride the paged kernel's WINDOW
  axis, all at one position, as ``ops.diff_attention`` does it:
  ``paged_verify_attention`` with ``w = H / K`` is grouped-query attention
  with no kernel of its own, and a cached head is read once for its whole
  group.
* ``gqa_chunk_attention`` — a prefill chunk's queries over ONE sequence's
  cache, the chunk's own keys already written.  It walks the block table in
  RUNS of ``run_blocks`` blocks with a running maximum and sum (the online
  softmax), as far as the chunk's last valid position and no further: a
  ``(H, chunk, context)`` float32 score array (520 MB at 20 heads x 512 x
  12,800) is never made, only ``(H, chunk, run)`` of it.  Plain
  ``jax.numpy``: the scores of a run go through HBM (PERF.md section 5 has
  what that costs on the chip).
* ``rotary_half`` — rotary over the whole head in the half-split form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.paged_attention import NEG_INF, paged_verify_attention

#: blocks a step of the chunk walk gathers: 512 tokens at a block of 128
_RUN_TOKENS = 512


def rotary_half(x, positions, theta: float):
    """x: (n, heads, e) at ``positions`` (n,): lanes ``[0:e/2]`` rotate with
    ``[e/2:e]`` at ``theta ** (-2i / e)``.  float32 out."""
    e = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, e, 2, dtype=jnp.float32) / e)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :e // 2], x[..., e // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def gqa_paged_attention(q, k_pool, v_pool, tables, positions, impl: str = "auto"):
    """q: (rows, H, e) in the pools' dtype; pools: (blocks, K, block, e);
    tables: (rows, tmax) int32; positions: (rows,) int32, the query's position
    (its own k/v already written).  Returns (rows, H, e) in q's dtype."""
    rows, h, e = q.shape
    kv = k_pool.shape[1]
    grouped = q.reshape(rows, kv, h // kv, e).transpose(0, 2, 1, 3)   # (rows, w, K, e)
    pos = jnp.broadcast_to(positions[:, None], grouped.shape[:2])
    att = paged_verify_attention(grouped, k_pool, v_pool, tables, pos, impl=impl)
    return att.transpose(0, 2, 1, 3).reshape(rows, h, e)


def gqa_chunk_attention(q, k_pool, v_pool, table, positions, n_ctx):
    """q: (C, H, e) in the pools' dtype, at ``positions`` (C,) of ONE
    sequence; pools: (blocks, K, block, e); table: (tmax,) int32; ``n_ctx``:
    how many positions of the sequence are written (the chunk's last valid
    one, plus one).  Query ``c`` attends every position ``<= positions[c]``.
    Returns (C, H, e) float32."""
    c, h, e = q.shape
    kv, bs = k_pool.shape[1], k_pool.shape[2]
    run = max(1, min(table.shape[0], _RUN_TOKENS // bs))
    span = run * bs
    # the table padded to whole runs (with the trash block, never attended)
    tab = jnp.pad(table, (0, -table.shape[0] % run))
    # (K, G * C, e): a key-value head's queries are one matrix
    qm = q.reshape(c, kv, h // kv, e).transpose(1, 2, 0, 3).reshape(kv, -1, e)
    reach = jnp.tile(positions, h // kv)[None, :, None]               # (1, G * C, 1)

    def tokens_of(pool, ids):
        return pool[ids].transpose(1, 0, 2, 3).reshape(kv, span, e)

    def over_runs(r, carry):
        m_prev, l_prev, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(tab, r * run, run)
        k, v = tokens_of(k_pool, ids), tokens_of(v_pool, ids)
        scores = jnp.einsum("kme,kte->kmt", qm, k,
                            preferred_element_type=jnp.float32) * e**-0.5
        seen = (r * span + jnp.arange(span))[None, None, :] <= reach
        scores = jnp.where(seen, scores, NEG_INF)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(scores - m_new), 0.0)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        pv = jnp.einsum("kmt,kte->kme", p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    rows = qm.shape[1]
    _, l, acc = jax.lax.fori_loop(
        0, (n_ctx + span - 1) // span, over_runs,
        (jnp.full((kv, rows, 1), NEG_INF, jnp.float32),
         jnp.zeros((kv, rows, 1), jnp.float32),
         jnp.zeros((kv, rows, e), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(kv, h // kv, c, e).transpose(2, 0, 1, 3).reshape(c, h, e)
