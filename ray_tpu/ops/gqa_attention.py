"""Grouped-query attention over a paged K/V cache: ``H`` query heads, ``K``
key-value heads, query head ``i`` reads key-value head ``i // (H / K)``.

* ``gqa_paged_attention`` — one query token a row (a decode).  The ``H / K``
  query heads that share a key-value head ride the paged kernel's WINDOW
  axis, all at one position, as ``ops.diff_attention`` does it:
  ``paged_verify_attention`` with ``w = H / K`` is grouped-query attention
  with no kernel of its own, and a cached head is read once for its whole
  group.  Heads NARROWER than a lane row (64-wide: LFM2) lie ``pack`` side by
  side in a row of the pool, ``(blocks, K / pack, block, pack * e)``: a
  token's K is the same bytes in the same order, unpadded, where a pool
  padded to the kernel's 128-lane rows would hold twice the bytes.  Both
  functions read the packing off the pool's shape.
* ``gqa_chunk_attention`` — a prefill chunk's queries over ONE sequence's
  cache, the chunk's own keys already written.  It walks the block table in
  RUNS of ``run_blocks`` blocks with a running maximum and sum (the online
  softmax), as far as the chunk's last valid position and no further: a
  ``(H, chunk, context)`` float32 score array (520 MB at 20 heads x 512 x
  12,800) is never made, only ``(H, chunk, run)`` of it.  Plain
  ``jax.numpy``: the scores of a run go through HBM (PERF.md section 5 has
  what that costs on the chip).
* ``rotary_half`` — rotary over the whole head in the half-split form.

Both attentions take a ``window``: a query at position ``p`` then sees the
keys ``> p - window`` alone, the walk STARTS at the first block the step's
first query still sees (``ops.paged_attention.first_window_block``) and no
table entry before that block is read, so a window layer's work and bytes
follow ``min(context, window)`` and its pool may take those blocks back
(``llm.cache.LayerTypedPool``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.paged_attention import (
    NEG_INF, first_window_block, paged_verify_attention)

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


def gqa_paged_attention(q, k_pool, v_pool, tables, positions, impl: str = "auto",
                        window: int | None = None):
    """q: (rows, H, e) in the pools' dtype; pools: (blocks, K, block, e);
    tables: (rows, tmax) int32; positions: (rows,) int32, the query's position
    (its own k/v already written).  Returns (rows, H, e) in q's dtype.

    Pools of ``pack`` heads a row, (blocks, K / pack, block, pack * e): ``q``
    may be any float dtype (it is rounded HERE, once) and the result is in
    the pools'.  A query head is laid into ITS head's ``e`` lanes of a ``pack * e`` row with
    zeros in the others, so ``q . [k_a | k_b] = q . k_a`` exactly; the kernel
    has the ``pack * H / K`` query heads of a packed row on its window axis and
    scales by ``(pack * e) ** -0.5``, so ``q`` is multiplied by ``sqrt(pack)``
    as it is rounded; ``p . [v_a | v_b]`` has the head's result in its own
    lanes and the neighbour's in the others, which are dropped.  The cached
    bytes are read once, as before; the kernel multiplies ``pack`` times the
    lanes."""
    rows, h, e = q.shape
    kv, pack = k_pool.shape[1], k_pool.shape[3] // e
    group = h // (kv * pack)
    if pack == 1:
        grouped = q.reshape(rows, kv, h // kv, e).transpose(0, 2, 1, 3)   # (rows, w, K, e)
    else:
        # (rows, K / pack, head of the row, query of the head, lanes' head, e)
        own = jnp.eye(pack, dtype=jnp.float32)[None, None, :, None, :, None]
        laid = (q.astype(jnp.float32) * pack**0.5).reshape(rows, kv, pack, group, 1, e) * own
        grouped = laid.astype(k_pool.dtype).reshape(
            rows, kv, pack * group, pack * e).transpose(0, 2, 1, 3)
    pos = jnp.broadcast_to(positions[:, None], grouped.shape[:2])
    att = paged_verify_attention(grouped, k_pool, v_pool, tables, pos, impl=impl, window=window)
    if pack == 1:
        return att.transpose(0, 2, 1, 3).reshape(rows, h, e)
    att = att.transpose(0, 2, 1, 3).reshape(rows, kv, pack, group, pack, e)
    return jnp.stack([att[:, :, j, :, j] for j in range(pack)], axis=2).reshape(rows, h, e)


def gqa_chunk_attention(q, k_pool, v_pool, table, positions, n_ctx, window: int | None = None):
    """q: (C, H, e) in the pools' dtype, at ``positions`` (C,) of ONE
    sequence; pools: (blocks, K, block, e), or ``pack`` heads a row (above);
    table: (tmax,) int32; ``n_ctx``:
    how many positions of the sequence are written (the chunk's last valid
    one, plus one).  Query ``c`` attends every position ``<= positions[c]``
    (and ``> positions[c] - window``: the walk then starts at the run that
    holds query 0's first visible block).  Returns (C, H, e) float32."""
    c, h, e = q.shape
    pack = k_pool.shape[3] // e
    kv, bs = k_pool.shape[1] * pack, k_pool.shape[2]
    run = max(1, min(table.shape[0], _RUN_TOKENS // bs))
    span = run * bs
    # the table padded to whole runs (with the trash block, never attended)
    tab = jnp.pad(table, (0, -table.shape[0] % run))
    # (K, G * C, e): a key-value head's queries are one matrix
    qm = q.reshape(c, kv, h // kv, e).transpose(1, 2, 0, 3).reshape(kv, -1, e)
    reach = jnp.tile(positions, h // kv)[None, :, None]               # (1, G * C, 1)

    def tokens_of(pool, ids):
        if pack == 1:
            return pool[ids].transpose(1, 0, 2, 3).reshape(kv, span, e)
        return pool[ids].reshape(run, kv // pack, bs, pack, e).transpose(
            1, 3, 0, 2, 4).reshape(kv, span, e)

    def over_runs(r, carry):
        m_prev, l_prev, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(tab, r * run, run)
        k, v = tokens_of(k_pool, ids), tokens_of(v_pool, ids)
        scores = jnp.einsum("kme,kte->kmt", qm, k,
                            preferred_element_type=jnp.float32) * e**-0.5
        at = (r * span + jnp.arange(span))[None, None, :]
        seen = at <= reach
        if window is not None:
            seen &= at > reach - window
        scores = jnp.where(seen, scores, NEG_INF)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(scores - m_new), 0.0)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        pv = jnp.einsum("kmt,kte->kme", p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    rows = qm.shape[1]
    first_run = 0 if window is None else first_window_block(positions[0], window, bs) // run
    _, l, acc = jax.lax.fori_loop(
        first_run, (n_ctx + span - 1) // span, over_runs,
        (jnp.full((kv, rows, 1), NEG_INF, jnp.float32),
         jnp.zeros((kv, rows, 1), jnp.float32),
         jnp.zeros((kv, rows, e), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(kv, h // kv, c, e).transpose(2, 0, 1, 3).reshape(c, h, e)
