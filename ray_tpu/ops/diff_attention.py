"""Differential attention over grouped key-value heads, in the pair form.

Heads come in pairs (Differential Transformer, arXiv:2410.05258): query
pair ``i`` of ``H/2`` holds two ``e``-wide heads ``q_1, q_2``; key-value
pair ``g = i // (H/K)`` of ``K/2`` holds ``k_1, k_2`` and ONE value ``2e``
wide.  Each half is an ordinary softmax attention over the pair's value,

    A_j = softmax(q_j k_j^T / sqrt(e) + mask) v        (j = 1, 2),

and the layer's output is ``RMSNorm_2e(A_1 - lambda A_2) (1 - lambda_0)``
(``diff_combine``).

The caches hold a PAIR as one head: keys ``[k_1, k_2]`` and the value, both
``2e`` wide, so at ``e = 64`` a cached head is one 128-lane tile and the
pools are the shape ``ops.paged_attention`` tiles (``(blocks, K/2, block,
2e)``).  A half meets its own keys through a query that is zero on the
other half's lanes: ``[q_1, 0] . [k_1, k_2] = q_1 . k_1``.  The four
queries that share a key-value pair (two query pairs, two halves each: ``H/K``
times 2) ride the paged kernel's WINDOW axis, all at one position:
``paged_verify_attention`` with ``w = 2 H/K`` is grouped-head differential
attention with no kernel of its own.  Its scale is ``(2e) ** -0.5``, so the
padded queries carry the missing ``sqrt 2``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.paged_attention import NEG_INF, paged_verify_attention


def pair_heads(x, heads: int):
    """(n, heads * e) keys or values -> (n, heads / 2, 2e): a pair a head."""
    return x.reshape(x.shape[0], heads // 2, -1)


def _padded_queries(q, kv_heads: int):
    """q: (n, H, e) -> (n, 2 H/K, K/2, 2e): per key-value pair its H/K
    query pairs' halves, each zero on the other half's lanes and scaled by
    sqrt 2 (see module doc)."""
    n, h, e = q.shape
    group = h // kv_heads
    q = (q.astype(jnp.float32) * 2.0**0.5).astype(q.dtype)
    q = q.reshape(n, kv_heads // 2, group, 2, 1, e)
    eye = jnp.eye(2, dtype=q.dtype)[:, :, None]          # (half, lane half, 1)
    padded = (q * eye).reshape(n, kv_heads // 2, group * 2, 2 * e)
    return padded.transpose(0, 2, 1, 3)


def _halves(att, kv_heads: int):
    """(n, 2 H/K, K/2, 2e) attention of the padded queries -> (A_1, A_2),
    each (n, H/2, 2e) in query-pair order."""
    n, w, pairs, d = att.shape
    att = att.transpose(0, 2, 1, 3).reshape(n, pairs, w // 2, 2, d)
    return (att[:, :, :, 0].reshape(n, -1, d), att[:, :, :, 1].reshape(n, -1, d))


def diff_paged_attention(q, k_pool, v_pool, tables, positions, kv_heads: int,
                         impl: str = "auto"):
    """One query token a row over a paged cache of pairs.  q: (rows, H, e);
    pools: (blocks, K/2, block, 2e); tables: (rows, tmax) int32; positions:
    (rows,) int32, the query's position (its own k/v already written; -1: a
    row with nothing to attend).  Returns (A_1, A_2), each (rows, H/2, 2e)
    in q's dtype."""
    padded = _padded_queries(q, kv_heads)
    pos = jnp.broadcast_to(positions[:, None], padded.shape[:2])
    att = paged_verify_attention(padded, k_pool, v_pool, tables, pos, impl=impl)
    return _halves(att, kv_heads)


def diff_dense_attention(q, k, v, mask):
    """A chunk's queries over gathered keys.  q: (c, H, e); k, v: (t, K/2,
    2e) pairs; mask: (c, t) bool.  Returns (A_1, A_2), each (c, H/2, 2e),
    float32 softmax."""
    c, h, e = q.shape
    t, pairs, _ = k.shape
    group = h // (2 * pairs)
    q = q.astype(jnp.float32).reshape(c, pairs, group, 2, e)
    k = k.astype(jnp.float32).reshape(t, pairs, 2, e)
    scores = jnp.einsum("cgpje,tgje->cgpjt", q, k) * e**-0.5
    scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("cgpjt,tgd->cgpjd", probs, v.astype(jnp.float32))
    att = att.reshape(c, pairs * group, 2, 2 * e)
    return att[:, :, 0], att[:, :, 1]


def diff_combine(a1, a2, lam, lam0: float, scale, eps: float):
    """``RMSNorm(A_1 - lambda A_2) * scale * (1 - lambda_0)``, float32.  a1,
    a2: (n, H/2, 2e); lam: a scalar; scale: (2e,)."""
    x = a1.astype(jnp.float32) - lam * a2.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32) * (1.0 - lam0)
