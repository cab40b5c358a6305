"""Paged attention over a block-table KV cache: decode, prefill, verify.

Generalizes ``models.gptj._attend_cached`` (one query row against a dense
per-sequence cache) to the paged layout the ``ray_tpu.llm`` engine uses:
the cluster-wide KV cache is a fixed pool of physical blocks

    k_pool, v_pool : (num_blocks, heads, block_size, head_dim)

and each decode slot owns a *block table* mapping its logical block index
to a physical block id.  The functions here know nothing of layers: the
jitted steps (``llm.model_runner._layer_loop``) hand every layer the
WHOLE engine pool as its free ``(layers * blocks_per_layer, heads,
block_size, head_dim)`` view, with block tables offset by ``layer *
blocks_per_layer`` — a slice ``pool[layer]`` as the operand would be
materialized, a pool-sized copy per layer.  Static shapes throughout —
the pool size, block size, and table width are compile-time constants;
only the table CONTENTS and per-slot lengths are data — so the engine
jits one decode step and reuses it for every admission/eviction pattern.

Three entry points:

* ``paged_attention`` — one query per slot (the decode step).
* ``paged_prefill_attention_xla`` — chunked prefill for ONE sequence.
* ``paged_verify_attention`` — ``w = k+1`` consecutive queries per slot
  (speculative-decode verification): query ``i`` of a slot sits at
  ``positions[s, i]`` and attends causally over the slot's paged cache
  INCLUDING the window's own earlier positions (their k/v are scattered
  in before the attention runs).  The causal intra-window mask is just
  ``cache_pos <= positions[s, i]`` — window k/v live at those positions.

``paged_attention`` and ``paged_verify_attention`` each have two
interchangeable paths behind one signature (same contract as
``ops.attention``):

* ``xla``    — gather the table's blocks into a dense (slots, heads,
  table*block, d) view, masked softmax.  The reference path; also what
  multi-chip pjit partitions cleanly.
* ``pallas`` — ONE scalar-prefetch Pallas kernel (decode is the verify
  kernel at window width 1): grid (slot, logical block), the block
  table is prefetched so each step DMAs exactly its physical KV block
  from HBM, online-softmax accumulation across the minor (block) grid
  dimension.  No (slots, table*block) score matrix and no gathered
  cache copy ever materializes.  Compiled by Mosaic on a TPU backend
  (parity against the XLA path at the served shapes: ``chip_smoke.py``'s
  kernel phase); interpreted everywhere else, which only tests that ask
  for ``impl="pallas"`` reach (``tests/test_llm_engine.py``,
  ``tests/test_llm_spec.py``).

``auto`` follows ONE rule, ``auto_impl``: the Pallas kernel on a TPU
backend when the pool tiles (block_size a multiple of 8, head_dim of
128), else XLA.

Convention: table entries past a sequence's allocation MUST point at a
valid physical block (the engine pads with block 0, its reserved trash
block — in the whole-pool view, the layer's own block 0); masking by
``lengths``/``positions`` makes their values irrelevant.  Slots with
``length == 0`` produce finite garbage (big-negative masking, never NaN)
— callers discard inactive slots.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def auto_impl(block_size: int, head_dim: int) -> str:
    """THE ``impl="auto"`` rule, by platform and shape, for decode and
    verify alike: ``"pallas"`` on a TPU backend when the per-head KV
    block ``(block_size, head_dim)`` tiles (sublanes by 8, lanes by
    128), else ``"xla"``.  Off a TPU the kernel could only run
    interpreted — orders of magnitude slower than compiled XLA — so
    ``auto`` never picks it there.  The rule is per head: under
    ``EngineConfig(tp=N)`` the kernel runs inside a shard_map body on
    ``n_heads // tp`` local heads with every tile shape unchanged, and
    the tp psum happens in the caller after the output projection, so
    the kernel needs no collective."""
    if _on_tpu() and block_size % 8 == 0 and head_dim % 128 == 0:
        return "pallas"
    return "xla"


# ---------------------------------------------------------------------------
# XLA reference path
# ---------------------------------------------------------------------------


def paged_attention_xla(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
) -> jax.Array:
    """q: (slots, heads, d); pools: (num_blocks, heads, block, d);
    block_tables: (slots, tmax) int32; lengths: (slots,) int32 — valid
    cache positions per slot (new token's k/v already written).
    Returns (slots, heads, d) in q.dtype, fp32 softmax accumulation."""
    s, h, d = q.shape
    scale = d**-0.5
    k = k_pool[block_tables]  # (slots, tmax, heads, block, d)
    v = v_pool[block_tables]
    k = k.transpose(0, 2, 1, 3, 4).reshape(s, h, -1, d)
    v = v.transpose(0, 2, 1, 3, 4).reshape(s, h, -1, d)
    logits = jnp.einsum(
        "shd,shkd->shk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    mask = jnp.arange(k.shape[2])[None, None, :] < lengths[:, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("shk,shkd->shd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_prefill_attention_xla(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    positions: jax.Array,
) -> jax.Array:
    """Chunked-prefill attention for ONE sequence: each chunk query at
    ``positions[i]`` attends causally over the sequence's paged cache
    (chunk k/v already scattered in).  q: (chunk, heads, d);
    block_table: (tmax,) int32; positions: (chunk,) int32.  Returns
    (chunk, heads, d)."""
    c, h, d = q.shape
    scale = d**-0.5
    with jax.named_scope("paged_attention"):
        k = k_pool[block_table]  # (tmax, heads, block, d)
        v = v_pool[block_table]
        k = k.transpose(1, 0, 2, 3).reshape(h, -1, d)
        v = v.transpose(1, 0, 2, 3).reshape(h, -1, d)
        logits = jnp.einsum(
            "chd,hkd->chk", q.astype(jnp.float32), k.astype(jnp.float32)
        ) * scale
        mask = jnp.arange(k.shape[1])[None, None, :] <= positions[:, None, None]
        logits = jnp.where(mask, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("chk,hkd->chd", probs, v.astype(jnp.float32))
        return out.astype(q.dtype)


def paged_verify_attention_xla(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
) -> jax.Array:
    """Multi-query verification attention (see module doc).  q: (slots, w,
    heads, d); pools: (num_blocks, heads, block, d); block_tables:
    (slots, tmax) int32; positions: (slots, w) int32 — the absolute cache
    position of each query (its own k/v already written).  Query (s, i)
    attends every cache position ``<= positions[s, i]`` — causal across
    the window because the window's positions are consecutive.  Returns
    (slots, w, heads, d) in q.dtype, fp32 softmax accumulation."""
    s, w, h, d = q.shape
    scale = d**-0.5
    k = k_pool[block_tables]  # (slots, tmax, heads, block, d)
    v = v_pool[block_tables]
    k = k.transpose(0, 2, 1, 3, 4).reshape(s, h, -1, d)
    v = v.transpose(0, 2, 1, 3, 4).reshape(s, h, -1, d)
    logits = jnp.einsum(
        "swhd,shkd->swhk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    mask = (
        jnp.arange(k.shape[2])[None, None, None, :]
        <= positions[:, :, None, None]
    )
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("swhk,shkd->swhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _paged_verify_kernel(
    # scalar prefetch
    tables_ref,   # (slots * tmax,) int32 — flattened block tables
    pos_ref,      # (slots * w,) int32 — flattened query positions
    # blocked inputs
    q_ref,        # (1, w, heads, d)
    k_ref,        # (1, heads, block, d) — THE slot's j-th physical block
    v_ref,
    # blocked output
    o_ref,        # (1, w, heads, d)
    # scratch (carried across the minor grid dim)
    acc_ref,      # (heads, w, d) f32
    m_ref,        # (heads, w, 1) f32
    l_ref,        # (heads, w, 1) f32
    *,
    block_size: int,
    w: int,
    scale: float,
):
    s = pl.program_id(0)
    j = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # per-query positions; the window is consecutive, so the LAST query's
    # position bounds the valid cache
    qpos = jnp.stack([pos_ref[s * w + i] for i in range(w)])  # (w,)
    length = qpos[w - 1] + 1

    @pl.when(j * block_size < length)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32).transpose(1, 0, 2)  # (heads, w, d)
        k = k_ref[0].astype(jnp.float32)                     # (heads, block, d)
        v = v_ref[0].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, k,
            (((2,), (2,)), ((0,), (0,))),    # contract d, batch heads
            preferred_element_type=jnp.float32,
        ) * scale                             # (heads, w, block)
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1
        )                                     # (1, block)
        causal = pos[None, :, :] <= qpos[None, :, None]  # (1, w, block)
        scores = jnp.where(causal, scores, NEG_INF)

        m_prev = m_ref[...]                   # (heads, w, 1)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)           # (heads, w, block)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v,
            (((2,), (1,)), ((0,), (0,))),     # contract block, batch heads
            preferred_element_type=jnp.float32,
        )                                     # (heads, w, d)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).transpose(1, 0, 2).astype(o_ref.dtype)


def _paged_verify_pallas(q, k_pool, v_pool, block_tables, positions):
    slots, w, heads, d = q.shape
    _, _, block_size, _ = k_pool.shape
    tmax = block_tables.shape[1]
    scale = d**-0.5
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # minor (block) dimension executes sequentially on TPU, so the
        # online-softmax scratch carries across a slot's kv blocks
        grid=(slots, tmax),
        in_specs=[
            pl.BlockSpec((1, w, heads, d), lambda s, j, tbl, pos: (s, 0, 0, 0)),
            pl.BlockSpec(
                (1, heads, block_size, d),
                lambda s, j, tbl, pos: (tbl[s * tmax + j], 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, heads, block_size, d),
                lambda s, j, tbl, pos: (tbl[s * tmax + j], 0, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, w, heads, d), lambda s, j, tbl, pos: (s, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((heads, w, d), jnp.float32),
            pltpu.VMEM((heads, w, 1), jnp.float32),
            pltpu.VMEM((heads, w, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_verify_kernel, block_size=block_size, w=w, scale=scale
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, w, heads, d), q.dtype),
        interpret=not _on_tpu(),
        # the name the kernel has in a lowered program and a device trace
        name="paged_attention_decode" if w == 1 else "paged_attention_verify",
    )(block_tables.reshape(-1).astype(jnp.int32),
      positions.reshape(-1).astype(jnp.int32),
      q, k_pool, v_pool)


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------


def _resolve_impl(impl: str, k_pool: jax.Array) -> str:
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(
            f"unknown paged attention impl {impl!r}; expected 'auto', 'xla' "
            "or 'pallas'"
        )
    if impl == "auto":
        _, _, block_size, d = k_pool.shape
        return auto_impl(block_size, d)
    return impl


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    impl: str = "auto",
) -> jax.Array:
    """Single-position attention over a paged KV cache (see module doc).

    q: (slots, heads, head_dim); k_pool/v_pool: (num_blocks, heads,
    block_size, head_dim); block_tables: (slots, tmax) int32; lengths:
    (slots,) int32.  ``impl``: auto | xla | pallas.
    """
    with jax.named_scope("paged_attention"):
        if _resolve_impl(impl, k_pool) == "xla":
            return paged_attention_xla(q, k_pool, v_pool, block_tables, lengths)
        # decode is a verify window of width 1 whose query sits at length - 1
        return _paged_verify_pallas(
            q[:, None], k_pool, v_pool, block_tables, (lengths - 1)[:, None]
        )[:, 0]


def paged_verify_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    impl: str = "auto",
) -> jax.Array:
    """Multi-query verification attention over a paged KV cache (see
    module doc): ``w`` consecutive queries per slot for speculative-decode
    verification, causal intra-window masking by absolute position.

    q: (slots, w, heads, head_dim); k_pool/v_pool: (num_blocks, heads,
    block_size, head_dim); block_tables: (slots, tmax) int32; positions:
    (slots, w) int32.  ``impl``: auto | xla | pallas.
    """
    with jax.named_scope("paged_attention"):
        if _resolve_impl(impl, k_pool) == "xla":
            return paged_verify_attention_xla(
                q, k_pool, v_pool, block_tables, positions
            )
        return _paged_verify_pallas(q, k_pool, v_pool, block_tables, positions)
